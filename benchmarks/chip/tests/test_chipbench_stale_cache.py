"""A decode step that hands back a stale KV cache comes out not correct by
the logit gap, on the tiny cell on the CPU.

The engine's dense decode consumes (donates) the caches it is given. The
fault here keeps a copy made before the call and returns it in place of
the updated caches, so every request is still served, from a cache that
never learns its decoded tokens: the comparison that decides `correct`
has to see that in the logits, with no request failing."""
import time

import jax
import jax.numpy as jnp

import tiny
from harness import cell

jax.config.update("jax_enable_compilation_cache", False)
SEED = 2**31 + 78


def _stale_copy(engine):
    real = engine._decode

    def decode(params, caches, tok, pos):
        kept = jax.tree.map(jnp.copy, caches)
        logits, _ = real(params, caches, tok, pos)
        return logits, kept
    engine._decode = decode


def test_stale_cache_fails_the_logit_gap():
    run = cell.Run(tiny.spec(grammar="json"), SEED, 3.0, False,
                   time.perf_counter())
    out = run.go(engine_hook=_stale_copy)
    checks = out["checks"]
    assert out["attempted"] > 5 and out["failed"] == 0
    assert not out["correct"], checks
    assert any(not checks[gap]["value"] <= checks[gap]["limit"]
               for gap in ("max_logit_gap", "mean_logit_gap")), checks
