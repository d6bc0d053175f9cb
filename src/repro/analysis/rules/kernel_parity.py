"""RL005 kernel-parity: every Pallas kernel package ships its contract.

A `src/repro/kernels/<pkg>/` that dispatches `pallas_call` must carry:

  * `ops.py`  — the dispatch wrapper serving code imports (and the
    interpret-mode / sharding routing point);
  * `ref.py`  — the jnp reference implementation the kernel is held
    bit-exact against;
  * a parity test: some `tests/test_*.py` references
    `kernels.<pkg>` / `kernels/<pkg>` (the repo's convention since the
    masked_logits kernel — parity fuzz is what caught the S=1 gemv
    rounding split and the fused-select edge cases).

A kernel without a ref and a test is an unfalsifiable kernel; this
rule makes that state unrepresentable at HEAD.
"""
from __future__ import annotations

import ast
import re

from ..findings import Finding
from ..registry import rule

KERNELS_PREFIX = "src/repro/kernels/"


def _uses_pallas_call(tree) -> int:
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id == "pallas_call":
            return node.lineno
        if isinstance(node, ast.Attribute) and \
                node.attr == "pallas_call":
            return node.lineno
    return 0


@rule("RL005", "kernel-parity")
def check(project):
    """every pallas_call kernel package ships ops.py + ref.py and is
    referenced by a parity test"""
    findings = []
    pkgs: dict = {}          # pkg -> {rel: (sf, pallas_line)}
    for sf in project.files:
        if not sf.rel.startswith(KERNELS_PREFIX):
            continue
        parts = sf.rel[len(KERNELS_PREFIX):].split("/")
        if len(parts) != 2:
            continue         # kernels/__init__.py: not a package
        pkgs.setdefault(parts[0], {})[parts[1]] = (
            sf, _uses_pallas_call(sf.tree))
    test_texts = None
    for pkg, files in sorted(pkgs.items()):
        dispatching = [(rel, sf, ln) for rel, (sf, ln) in files.items()
                       if ln]
        if not dispatching:
            continue
        anchor_rel, _, anchor_line = dispatching[0]
        anchor = f"{KERNELS_PREFIX}{pkg}/{anchor_rel}"
        for required in ("ops.py", "ref.py"):
            if required not in files and \
                    project.read_text(
                        f"{KERNELS_PREFIX}{pkg}/{required}") is None:
                findings.append(Finding(
                    rule="RL005", name="kernel-parity", path=anchor,
                    line=anchor_line,
                    message=f"kernel package '{pkg}' dispatches "
                            f"pallas_call but ships no {required} — "
                            f"every kernel needs a dispatch wrapper "
                            f"(ops.py) and a jnp reference (ref.py) "
                            f"to be held bit-exact against",
                    hint="see kernels/fused_select for the package "
                         "shape"))
        if test_texts is None:
            test_texts = [(rel, project.read_text(rel) or "")
                          for rel in project.glob("tests/test_*.py")]
        pat = re.compile(rf"kernels[./]{re.escape(pkg)}\b")
        if not any(pat.search(text) for _, text in test_texts):
            findings.append(Finding(
                rule="RL005", name="kernel-parity", path=anchor,
                line=anchor_line,
                message=f"kernel package '{pkg}' is referenced by no "
                        f"tests/test_*.py — an untested Pallas kernel "
                        f"has no parity guarantee",
                hint="add a bit-exactness fuzz vs ref.py (the "
                     "fused_select/paged_attention test pattern)"))
    return findings
