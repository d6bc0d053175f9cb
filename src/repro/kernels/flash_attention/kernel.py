"""Pallas TPU flash attention (blocked online-softmax, causal + sliding
window + GQA).

Grid (B, H, nQ, nK) with nK innermost ("arbitrary"); q/k/v tiles live in
VMEM via BlockSpecs, MXU-aligned (block sizes multiples of 128 on the
lane dim). Running max/denominator/accumulator persist in VMEM scratch
across the nK steps of one (b, h, iq) cell; output is written on the last
step. K/V BlockSpecs map head h -> kv head h//G (GQA without
materializing repeated KV).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, num_k: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # [BQ, Dh]
    k = k_ref[0, 0].astype(jnp.float32)                # [BK, Dh]
    v = v_ref[0, 0].astype(jnp.float32)                # [BK, Dh]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [BQ, BK]

    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[:, 0] = m_new

    @pl.when(ik == num_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True):
    """q [B,Sq,H,Dh]; k,v [B,Sk,K,Dh] -> [B,Sq,H,Dh].
    q positions right-aligned to k (q_offset = Sk - Sq)."""
    B, Sq, H, Dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    scale = 1.0 / (Dh ** 0.5)

    # layout to [B, H, S, Dh] for clean blocking
    qh = q.swapaxes(1, 2)
    kh = k.swapaxes(1, 2)
    vh = v.swapaxes(1, 2)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k=nk, q_offset=Sk - Sq)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qh, kh, vh)
    return out.swapaxes(1, 2)
