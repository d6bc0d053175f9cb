"""Pure-jnp reference for paged attention: gather-then-attend.

The reference reconstructs the dense cache view of a slot from its page
table (`pool[page_table[b]]` → `[B, L, K, Dh]` with L = n_pages·ps) and
then runs `attend`, the attention math the dense decode path
(`layers._self_attention_decode`) calls too, so paged attention is
bit-identical to the dense engine's attention, and the Pallas kernel has
an executable oracle.

`attend` lays the math out head-major, as the kernel does: the queries
of kv head k are one `[G·S, Dh]` block (group-major rows) and both
contractions are batched matmuls with the head as a batch dim, which
Mosaic puts on the MXU. The kernel runs the same einsums per slot, so
in interpret mode it reproduces the reference bit for bit.

Position convention: the token stored at (page_table[b, j], o) sits at
absolute position j·ps + o of slot b's logical sequence; validity needs
no kv_pos array — entry l is attendable iff its page is mapped
(page_table ≥ 0) and l ≤ q_pos (positions beyond the frontier hold
stale/unwritten data and are masked, which is also what rolls back
rejected speculative writes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attend(q, kc, vc, valid):
    """q [B,S,H,Dh] (roped, unscaled); kc/vc [B,L,K,Dh]; valid [B,S,L]
    bool -> [B,S,H,Dh] float32. GQA: query head h reads kv head h // G.
    bf16 operands with f32 accumulation: no f32 image of the KV cache is
    ever materialized."""
    B, S, H, Dh = q.shape
    K = kc.shape[2]
    G = H // K
    qh = (q * (1.0 / (Dh ** 0.5))).reshape(B, S, K, G, Dh)
    qh = qh.transpose(0, 2, 3, 1, 4).reshape(B, K, G * S, Dh)
    # the explicit head-major transposes are the kernel's operand
    # layouts; XLA:CPU rounds a dot by its operand layout, so without
    # them the interpreted kernel would differ in the last bit
    s = jnp.einsum("bkqd,bksd->bkqs", qh, kc.transpose(0, 2, 1, 3),
                   preferred_element_type=jnp.float32)
    s = jnp.where(jnp.tile(valid, (1, G, 1))[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    o = jnp.einsum("bkqs,bksd->bkqd", p, vc.transpose(0, 2, 1, 3),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, K, G, S, Dh).transpose(0, 3, 1, 2, 4).reshape(
        B, S, H, Dh)


def paged_attention_ref(q, k_pool, v_pool, page_table, pos):
    """q [B,S,H,Dh] (roped, unscaled); k_pool/v_pool [P,ps,K,Dh];
    page_table [B,nP] int32 (-1 = unmapped); pos [B] int32 absolute start
    positions (span query i of slot b sits at pos[b] + i).
    Returns [B,S,H,Dh] in q.dtype. Full causal attention (no sliding
    window — the paged engine is gated to window-free archs)."""
    P, ps, K, Dh = k_pool.shape
    B, S, H, _ = q.shape
    nP = page_table.shape[1]
    L = nP * ps

    safe = jnp.maximum(page_table, 0)                        # [B, nP]
    kc = k_pool[safe].reshape(B, L, K, Dh)
    vc = v_pool[safe].reshape(B, L, K, Dh)

    qpos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B,S]
    idx = jnp.arange(L, dtype=jnp.int32)                     # absolute pos
    mapped = jnp.repeat(page_table >= 0, ps, axis=1)         # [B, L]
    valid = mapped[:, None, :] & \
        (idx[None, None, :] <= qpos[:, :, None])             # [B, S, L]
    return attend(q, kc, vc, valid).astype(q.dtype)


def paged_attention_decode_ref(q, k_pool, v_pool, page_table, pos):
    """Decode ([B,1]) convenience wrapper: q [B,H,Dh] -> [B,H,Dh]."""
    return paged_attention_ref(q[:, None], k_pool, v_pool, page_table,
                               pos)[:, 0]
