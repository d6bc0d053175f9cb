"""Pure-jnp grammar-mask application: the reference half of the fused
mask+select kernel (`kernels/fused_select`).

Semantics: for each batch row b, union the packed mask-store rows
`rows[b, :]` (int32 row ids, -1 = padding), unpack the resulting bitmask,
and replace logits outside the mask with NEG_INF. `eos_allowed[b]`
additionally opens the EOS position (paper: EOS is legal iff C_k ∈ L(G),
decided host-side by the parser).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def masked_logits_ref(logits, store, rows, eos_allowed, eos_id: int = 1,
                      constrained=None, cd=None):
    """logits [B,V], store [R,W] uint32, rows [B,A] int32,
    eos_allowed [B] bool -> masked logits [B,V].

    `constrained` [B] bool (optional): rows where it is False pass through
    unmasked — the batched engine mixes constrained and unconstrained
    requests in one fused call.

    `cd` [B,W] uint32 (optional): the context-split residue overlay —
    per-slot packed words ORed into the row union (the host computed
    only these few context-dependent bits; everything else comes from
    the precomputed rows)."""
    B, V = logits.shape
    safe = jnp.maximum(rows, 0)
    gathered = store[safe]                                   # [B,A,W]
    gathered = jnp.where((rows >= 0)[..., None], gathered, jnp.uint32(0))
    words = jax.lax.reduce(gathered, jnp.uint32(0), jnp.bitwise_or,
                           dimensions=(1,))                  # [B,W]
    if cd is not None:
        # a residue overlay may be narrower than a lane-padded store
        extra = words.shape[1] - cd.shape[1]
        words = words | jnp.pad(cd, ((0, 0), (0, extra)))
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & \
        jnp.uint32(1)
    mask = bits.reshape(B, -1)[:, :V].astype(bool)
    mask = mask.at[:, eos_id].set(mask[:, eos_id] | eos_allowed)
    if constrained is not None:
        mask = mask | ~constrained[:, None]
    return jnp.where(mask, logits, jnp.asarray(NEG_INF, logits.dtype))


def masked_logits_span_ref(logits, store, rows, eos_allowed, eos_id: int = 1,
                           constrained=None, cd=None):
    """[B,K,V] span form (draft-verify speculation): position k of slot b
    has its own row set / eos flag / constrained flag / cd overlay.
    Delegates to the [B,V] reference on the flattened (b, k) axis so the
    two paths stay numerically identical by construction."""
    B, K, V = logits.shape
    out = masked_logits_ref(
        logits.reshape(B * K, V), store, rows.reshape(B * K, -1),
        eos_allowed.reshape(B * K), eos_id=eos_id,
        constrained=None if constrained is None
        else constrained.reshape(B * K),
        cd=None if cd is None else cd.reshape(B * K, -1))
    return out.reshape(B, K, V)
