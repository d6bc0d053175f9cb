"""Pure-jnp oracle for the fused mask+filter+sample kernel.

One call takes a decode step from raw logits to selected token ids:
packed mask-row union (CI row gather + CD residue overlay) → EOS /
unconstrained handling → temperature scaling → `topk_topp_filter` →
greedy argmax or categorical sample. The reference is the COMPOSITION
of the legacy pieces (`masked_logits_ref` + `select_batch`), so its
outputs are bit-identical to the pre-fusion pipeline by construction —
the Pallas kernel is fuzzed against it (tests/test_fused_select.py).
`sample_masked` is the sampling tail both paths run after the mask.

Two sampling inputs are supported:

  * `keys` [B, 2] uint32 — the legacy path: per-slot
    `jax.random.categorical` streams (vmapped).
  * `noise` [B, V] f32 — precomputed standard-Gumbel noise (see
    `gumbel_noise`). `categorical(key, logits)` IS
    `argmax(logits + gumbel(key))`, so `argmax(filtered + noise)` with
    `noise = gumbel(key, (V,))` selects the *bit-identical* token while
    moving the PRNG work off the mask-time critical path (the engine
    dispatches noise generation speculatively at the end of the
    previous step's resolve).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..masked_logits.ref import masked_logits_ref
from ...core.decoding import select_batch, topk_topp_filter


def gumbel_noise(keys, vocab_size: int) -> jnp.ndarray:
    """[B, V] f32 standard-Gumbel noise, one stream per slot — exactly
    the noise `jax.vmap(jax.random.categorical)(keys, ...)` would draw,
    so argmax(filtered + noise) reproduces the sampled ids bitwise."""
    return jax.vmap(
        lambda k: jax.random.gumbel(k, (vocab_size,), jnp.float32))(keys)


def fused_select_ref(logits, store, rows, cd, eos_allowed, constrained,
                     greedy_flags, temperature, top_k, top_p, *,
                     keys=None, noise=None, eos_id: int = 1):
    """Reference fused step: -> (ids [B] int32, masked [B, V]).

    Exactly one of `keys` / `noise` must be given unless every row is
    greedy (both None). Returns the masked logits too: the engine's
    opportunistic accept test and resample ban-list path both need
    them."""
    masked = masked_logits_ref(logits, store, rows, eos_allowed,
                               eos_id=eos_id, constrained=constrained,
                               cd=cd)
    if keys is not None:
        return select_batch(masked, keys, greedy_flags, temperature,
                            top_k, top_p), masked
    from repro.distributed.api import shard_hint
    hinted = shard_hint(masked, "sample_logits")
    arg = jnp.argmax(hinted, axis=-1).astype(jnp.int32)
    if noise is None:
        # all-greedy host-static variant: no filter, no PRNG
        return arg, masked
    return sample_masked(hinted, arg, greedy_flags, temperature, top_k,
                         top_p, noise), masked


def sample_masked(masked, arg, greedy_flags, temperature, top_k, top_p,
                  noise):
    """Sampling tail shared by the reference and the kernel op: filter
    the temperature-scaled masked logits, argmax them plus the Gumbel
    noise, and keep `arg` (the masked argmax) for greedy rows."""
    scaled = masked / jnp.maximum(temperature, 1e-6)[:, None]
    scaled = topk_topp_filter(scaled, top_k, top_p)
    sampled = jnp.argmax(scaled + noise, axis=-1)
    return jnp.where(greedy_flags, arg, sampled).astype(jnp.int32)
