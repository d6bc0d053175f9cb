"""Public op: fused grammar-mask + filter + sample on device.

`fused_mask_select` turns a decode step's (logits, precomputed row ids,
residue words, per-slot decode configs) into selected token ids — and
the masked logits, which the engine's opportunistic accept/resample
paths reuse — in ONE jitted device program.

Dispatch follows `kernels.use_kernel`: the compiled kernel on a TPU,
the jnp reference on the CPU and under an active sharding context,
where the reference partitions cleanly and keeps the "sample_logits"
combine hint (docs/sharding.md).

The kernel computes the mask union, the masked logits and their argmax;
sampling (`ref.sample_masked`) runs on its masked logits in XLA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import fused_select
from .ref import fused_select_ref, gumbel_noise, sample_masked  # noqa: F401
from .. import use_kernel


def fused_mask_select(logits, store, rows, cd, eos_allowed, constrained,
                      greedy_flags=None, temperature=None, top_k=None,
                      top_p=None, *, noise=None, eos_id: int = 1,
                      backend: str = "auto"):
    """-> (ids [B] int32, masked [B, V]).

    `noise` [B, V] is precomputed Gumbel noise (`gumbel_noise`); with
    it the per-slot configs pick greedy or sampled ids. Without it every
    row is greedy and the configs may be omitted. Both forms select the
    ids of the reference's keys variant bit for bit
    (tests/test_fused_select.py)."""
    if not use_kernel(backend):
        return fused_select_ref(logits, store, rows, cd, eos_allowed,
                                constrained, greedy_flags, temperature,
                                top_k, top_p, noise=noise, eos_id=eos_id)
    if cd is None:
        cd = jnp.zeros((logits.shape[0], store.shape[1]), jnp.uint32)
    arg, masked = fused_select(logits, store, rows, cd, eos_allowed,
                               constrained, eos_id=eos_id,
                               interpret=jax.default_backend() != "tpu")
    if noise is None:
        return arg, masked
    return sample_masked(masked, arg, greedy_flags, temperature, top_k,
                         top_p, noise), masked


def fused_mask_select_span(logits, store, rows, cd, eos_allowed,
                           constrained, greedy_flags=None, temperature=None,
                           top_k=None, top_p=None, *, noise=None,
                           eos_id: int = 1, backend: str = "auto"):
    """Span ([B, S, V]) form for speculative verification: every draft
    position carries its own row set / residue / eos / constrained
    flag; the per-slot decode configs broadcast across the span.
    Flattens (b, s) and delegates — numerically identical to the batch
    form by construction. Returns (ids [B, S], masked [B, S, V])."""
    B, S, V = logits.shape
    rep = lambda a: None if a is None else jnp.repeat(a, S, axis=0)
    ids, masked = fused_mask_select(
        logits.reshape(B * S, V), store, rows.reshape(B * S, -1),
        None if cd is None else cd.reshape(B * S, -1),
        eos_allowed.reshape(B * S), constrained.reshape(B * S),
        rep(greedy_flags), rep(temperature), rep(top_k), rep(top_p),
        noise=None if noise is None else noise.reshape(B * S, V),
        eos_id=eos_id, backend=backend)
    return ids.reshape(B, S), masked.reshape(B, S, V)
