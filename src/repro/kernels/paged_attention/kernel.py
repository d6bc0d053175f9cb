"""Pallas TPU kernel: page-table attention for the paged KV-cache engine.

A slot's KV lives scattered across a global page pool
`[num_pages, page_size, K, Dh]`; its page table `[nP]` (int32, -1 =
unmapped) names the pages that make up its logical sequence. The kernel
streams the slot's pages into VMEM via scalar-prefetched BlockSpec
index_maps (`pool` block for grid step (b, j) is page
`page_table[b, j]` — the TPU-idiomatic dynamic gather), then runs ONE
exact softmax over the assembled `[K, L, Dh]` KV buffer on the last
page step. Page validity comes from the scalar-prefetched page table.

Doing the softmax once over the gathered buffer (instead of an online
softmax per page) buys bit-exactness with the jnp reference and the
dense decode path in interpret mode, and costs 2·L·K·Dh words of VMEM
scratch.

The buffers are head-major `[K, L, Dh]` and the queries `[K, G·S, Dh]`,
so both contractions are batched matmuls with the head as the leading
batch dim, which Mosaic puts on the MXU; the reference (`ref.attend`)
runs the same einsums. With the head batch dim in the middle, Mosaic
lowers them as a broadcast-multiply-reduce: at S = 32, L = 512 that is
refused under a 48 MiB VMEM limit and takes 207 s to compile under
120 MiB.

The scratch bounds the context. At K=16, Dh=64, bf16, page 16 and the
default scoped VMEM of a v5e, the compiled kernel fits L = 1536 and not
L = 2048, at S = 1 and at S = 32 (tests/test_tpu_compile.py compiles
L = 512).

Grid: (B, nP) with nP innermost ("arbitrary"); q/out blocks revisit b
across the page steps; compute fires on the last one. Two entry points
share the body: `paged_attention_decode` ([B, 1] queries, the plain
engine step) and `paged_attention_span` ([B, S], the speculative /
chunked-prefill span step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pt_ref,              # scalar-prefetch [B, nP] int32 page table
            pos_ref,             # scalar-prefetch [B] int32 start positions
            q_ref,               # [1, K, G*S, Dh]
            k_ref,               # [1, ps, K, Dh]  (page pt[b, j])
            v_ref,               # [1, ps, K, Dh]
            o_ref,               # [1, K, G*S, Dh]
            kbuf, vbuf,          # VMEM [K, L, Dh] gathered KV
            *, page_size: int, num_pages: int, span: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    ps = page_size
    L = num_pages * ps

    # ---- gather phase: copy page j into its slice of the KV buffer ----
    off = pl.multiple_of(j * ps, ps)
    for h in range(kbuf.shape[0]):
        kbuf[h, pl.ds(off, ps), :] = k_ref[0, :, h, :]
        vbuf[h, pl.ds(off, ps), :] = v_ref[0, :, h, :]

    # ---- compute phase: one exact softmax over the whole buffer ----
    @pl.when(j == num_pages - 1)
    def _compute():
        q = q_ref[0] * (1.0 / (q_ref.shape[-1] ** 0.5))
        rows = q.shape[1]                                  # G*S, q-minor
        lpos = jax.lax.broadcasted_iota(jnp.int32, (rows, L), 1)
        qpos = pos_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (rows, L), 0) % span
        mapped = jnp.zeros((rows, L), jnp.bool_)
        for jj in range(num_pages):
            mapped |= (lpos // ps == jj) & (pt_ref[b, jj] >= 0)
        valid = mapped & (lpos <= qpos)
        s = jnp.einsum("kqd,ksd->kqs", q, kbuf[...],
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
        o = jnp.einsum("kqs,ksd->kqd", p.astype(vbuf.dtype), vbuf[...],
                       preferred_element_type=jnp.float32)
        o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_span(q, k_pool, v_pool, page_table, pos, *,
                         interpret: bool = False):
    """q [B,S,H,Dh] (roped, unscaled); k_pool/v_pool [P,ps,K,Dh];
    page_table [B,nP] int32 (-1 = unmapped); pos [B] int32 absolute start
    positions -> [B,S,H,Dh]. Full causal attention; GQA via the group
    dim (kv head = h // G)."""
    B, S, H, Dh = q.shape
    P, ps, K, _ = k_pool.shape
    nP = page_table.shape[1]
    L = nP * ps
    G = H // K
    kernel = functools.partial(_kernel, page_size=ps, num_pages=nP, span=S)
    q = q.reshape(B, S, K, G, Dh).transpose(0, 2, 3, 1, 4).reshape(
        B, K, G * S, Dh)
    per_slot = lambda b, j, pt, pos: (b, 0, 0, 0)
    page = lambda b, j, pt, pos: (jnp.maximum(pt[b, j], 0), 0, 0, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nP),
            in_specs=[
                pl.BlockSpec((1, K, G * S, Dh), per_slot),
                pl.BlockSpec((1, ps, K, Dh), page),
                pl.BlockSpec((1, ps, K, Dh), page),
            ],
            out_specs=pl.BlockSpec((1, K, G * S, Dh), per_slot),
            scratch_shapes=[pltpu.VMEM((K, L, Dh), k_pool.dtype),
                            pltpu.VMEM((K, L, Dh), v_pool.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32), q, k_pool,
      v_pool)
    return out.reshape(B, K, G, S, Dh).transpose(0, 3, 1, 2, 4).reshape(
        B, S, H, Dh)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_decode(q, k_pool, v_pool, page_table, pos, *,
                           interpret: bool = False):
    """Decode ([B, 1]) variant: q [B,H,Dh] -> [B,H,Dh]."""
    return paged_attention_span(q[:, None], k_pool, v_pool, page_table,
                                pos, interpret=interpret)[:, 0]
