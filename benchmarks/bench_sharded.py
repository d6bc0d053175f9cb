"""Sharded (tensor-parallel) engine benchmark rows.

Emits `engine_sharded_m{1,2,4}` CSV rows — the vocab-parallel serving
engine (docs/sharding.md) at mesh sizes 1, 2 and 4 — next to an
unsharded baseline row, and asserts token-for-token identity with the
baseline on every run (the bit-exactness contract is part of the
benchmark, not just the test suite).

Mesh rows need devices to span. On the CPU (`JAX_PLATFORMS=cpu`) this
module gives XLA four host devices as it is imported, which must happen
before jax loads: `benchmarks/run.py` imports it first, and the rows then
run in the bench's own process. On an accelerator they run on the real
devices, and a mesh larger than the device count is a SKIPPED row.
`--smoke` is the seconds-scale CI gate wired into `make bench-smoke`.
"""
from __future__ import annotations

import os
import sys


def force_cpu_host_devices(n: int = 4) -> None:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


force_cpu_host_devices()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import time

from benchmarks.common import build_demo, emit

MESHES = (1, 2, 4)


def _reqs(n, max_new):
    from repro.core.decoding import DecodeConfig
    from repro.serving.engine import Request
    return [Request(rid=i, prompt=b"Q: generate. A:", grammar="json",
                    max_new_tokens=max_new,
                    decode=DecodeConfig(method="sample", temperature=0.9),
                    seed=i) for i in range(n)]


def main(smoke: bool = False) -> int:
    import jax
    n, max_new, slots = (6, 8, 4) if smoke else (16, 20, 8)
    meshes = (2,) if smoke else MESHES

    base, _, _ = build_demo(("json",), slots=slots)
    base.generate(_reqs(n, max_new))                     # warm jit
    bstates, bstats = base.generate(_reqs(n, max_new))
    want = [s.token_ids for s in bstates]
    emit("engine_sharded_base", bstats.wall / max(bstats.tokens, 1) * 1e6,
         f"tok_s={bstats.tokens_per_sec:.1f};mesh=none;n={n}",
         stats=bstats)

    ok = bstats.tokens > 0
    for m in meshes:
        if jax.device_count() < m:
            # e.g. an inherited XLA_FLAGS pinned a smaller device count:
            # an unreachable mesh size is a skip, not a failure — only
            # identity violations fail the run
            emit(f"engine_sharded_m{m}", 0,
                 f"SKIPPED;devices={jax.device_count()}")
            continue
        eng, _, _ = build_demo(("json",), slots=slots, mesh=m)
        eng.generate(_reqs(n, max_new))                  # warm jit
        states, stats = eng.generate(_reqs(n, max_new))
        identical = [s.token_ids for s in states] == want
        ok = ok and identical and stats.tokens == bstats.tokens
        emit(f"engine_sharded_m{m}",
             stats.wall / max(stats.tokens, 1) * 1e6,
             f"tok_s={stats.tokens_per_sec:.1f};"
             f"mesh_devices={stats.mesh_devices};"
             f"identical_to_base={identical};"
             f"speedup_vs_base="
             f"{stats.tokens_per_sec / max(bstats.tokens_per_sec, 1e-9):.2f}x;"
             f"n={n}", stats=stats)
    if smoke:
        print(f"bench-sharded-smoke: {'OK' if ok else 'FAILED'} "
              f"({bstats.tokens} tokens, identity "
              f"{'held' if ok else 'VIOLATED'})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(smoke="--smoke" in sys.argv[1:]))
