"""RL003 sync-confinement: `block_until_ready` lives ONLY in
`serving/devbridge.py`.

devbridge is the single sanctioned module binding the device sync into
the observability layer as an injected capability (invoked only in
bench mode; tests/test_devtime.py proves serving never calls
it). Any other identifier-level use of `block_until_ready` anywhere in
the scanned tree is a finding — a sync smuggled into serving would
serialize the XGrammar-style host/device overlap, and one hidden in a
library path is a latency cliff waiting for load.

Within `src/repro/serving/` the rule additionally bans the quieter
sync spellings `.item()` and `device_get` (the pre-reprolint
source-scan in tests/test_obs.py, mechanized).

AST/identifier matching, not regex: docstrings and comments may say
"block_until_ready" freely.
"""
from __future__ import annotations

import ast

from ..findings import Finding
from ..registry import rule

ALLOWED_FILE = "src/repro/serving/devbridge.py"
SERVING_PREFIX = "src/repro/serving/"


def _ident_uses(tree, ident: str):
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id == ident:
            yield node.lineno
        elif isinstance(node, ast.Attribute) and node.attr == ident:
            yield node.lineno


@rule("RL003", "sync-confinement")
def check(project):
    """block_until_ready only in serving/devbridge.py; no .item() /
    device_get syncs inside the serving package"""
    findings = []
    for sf in project.files:
        if sf.rel == ALLOWED_FILE:
            continue
        for line in _ident_uses(sf.tree, "block_until_ready"):
            findings.append(Finding(
                rule="RL003", name="sync-confinement", path=sf.rel,
                line=line,
                message="block_until_ready outside "
                        "serving/devbridge.py: the device sync is an "
                        "injected capability confined to the bridge so "
                        "no serving or library path can silently "
                        "serialize the host/device overlap",
                hint="route the sync through the obs devtime bridge, "
                     "or justify a deliberate timing bracket with a "
                     "suppression"))
        if sf.rel.startswith(SERVING_PREFIX):
            for line in _ident_uses(sf.tree, "device_get"):
                findings.append(Finding(
                    rule="RL003", name="sync-confinement", path=sf.rel,
                    line=line,
                    message="device_get in the serving package: a "
                            "host transfer is a device sync",
                    hint="only [B]-sized resolved ids may cross to "
                         "the host, via the step loop's resolve phase"))
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and not node.args:
                    findings.append(Finding(
                        rule="RL003", name="sync-confinement",
                        path=sf.rel, line=node.lineno,
                        message=".item() in the serving package "
                                "blocks on the device value — a "
                                "hidden per-token sync",
                        hint="batch the transfer (np.asarray at the "
                             "resolve phase) instead of scalarizing"))
    return findings
