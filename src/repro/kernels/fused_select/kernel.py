"""Pallas TPU kernel: fused mask union + masked logits + argmax — ONE
device call takes a decode step from raw logits and precomputed store
row ids to the masked logits and their greedy ids.

Grid: (cdiv(B, 8), cdiv(V, 4096)) with the vocab-chunk axis innermost.
8 batch rows share a grid step (B is padded up to a multiple of 8), so
every logits block is a whole sublane tile; Mosaic refuses the lane
gather below on a single-row block.

  * Union (first vocab chunk of a batch block): for each accept row a,
    the bb selected store rows are DMA'd from HBM (the store stays in
    `pl.ANY`; row ids are scalar-prefetched) and ORed into a VMEM
    accumulator seeded with the slot's context-dependent residue words.
    HBM rows are (8, 128)-tiled, so each DMA moves the aligned 8-row
    group that holds the row and the row is read out of VMEM. That is
    why the store needs R % 8 == 0 and W % 128 == 0 (the engine pads it
    once; `fused_select` pads anything else).
  * Mask (every chunk): 4096 vocab ids = 128 packed words. Lane l of
    the t-th 128-lane tile needs bit l % 32 of word 4t + l // 32, which
    is one in-vreg lane gather of the chunk's words. Allowed logits
    pass, the rest become NEG_INF; EOS and unconstrained rows open up.
  * Argmax: a running (max, first index) pair per row across chunks,
    with positions past V excluded — jnp.argmax's first-max tie rule.

Sampling is not in the body: the top-k/top-p filter sorts the whole
vocab, which Mosaic cannot lower. The op (`ops.py`) runs it on the
kernel's masked logits in the same jit (`ref.sample_masked`, shared
with the reference).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
CHUNK_WORDS = 128                 # packed words per vocab chunk
CHUNK = 32 * CHUNK_WORDS          # vocab ids per grid step
ROW_GROUP = 8                     # HBM sublane tile of the uint32 store


def _kernel(rows_ref,            # scalar-prefetch [B, A] int32 (-1 pad)
            eos_ref,             # scalar-prefetch [B] int32
            cons_ref,            # scalar-prefetch [B] int32
            logits_ref,          # [bb, CHUNK]
            store_hbm,           # [R, Wp] uint32, left in HBM
            cd_ref,              # [bb, Wp] uint32 residue overlay
            ids_ref,             # out [bb, 1] int32
            masked_ref,          # out [bb, CHUNK]
            acc_ref,             # scratch [bb, nv * CHUNK_WORDS] uint32
            rowbuf,              # scratch [bb, 8, Wp] uint32
            sem,                 # DMA semaphores [bb]
            best_ref,            # scratch [bb, 1] f32 running max
            arg_ref,             # scratch [bb, 1] int32 running argmax
            *, eos_id: int, num_accept: int, vocab: int, block_b: int):
    i = pl.program_id(0)
    v = pl.program_id(1)
    b0 = i * block_b
    words = cd_ref.shape[1]

    @pl.when(v == 0)
    def _union():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.uint32)
        acc_ref[:, :words] = cd_ref[...]

        def add_accept_row(a, carry):
            copies = []
            for r in range(block_b):
                rid = jnp.maximum(rows_ref[b0 + r, a], 0)
                start = pl.multiple_of(rid - rid % ROW_GROUP, ROW_GROUP)
                cp = pltpu.make_async_copy(
                    store_hbm.at[pl.ds(start, ROW_GROUP)], rowbuf.at[r],
                    sem.at[r])
                cp.start()
                copies.append(cp)
            for r, cp in enumerate(copies):
                cp.wait()
                rid = rows_ref[b0 + r, a]

                @pl.when(rid >= 0)
                def _or():
                    acc_ref[pl.ds(r, 1), :words] |= \
                        rowbuf[r, pl.ds(rid % ROW_GROUP, 1), :]
            return carry

        jax.lax.fori_loop(0, num_accept, add_accept_row, 0)
        best_ref[...] = jnp.full(best_ref.shape, -jnp.inf, jnp.float32)
        arg_ref[...] = jnp.zeros(arg_ref.shape, jnp.int32)

    row = b0 + jax.lax.broadcasted_iota(jnp.int32, (block_b, 1), 0)
    eos_ok = jnp.zeros((block_b, 1), jnp.bool_)
    free = jnp.zeros((block_b, 1), jnp.bool_)
    for r in range(block_b):
        eos_ok |= (row == b0 + r) & (eos_ref[b0 + r] > 0)
        free |= (row == b0 + r) & (cons_ref[b0 + r] == 0)

    wchunk = acc_ref[:, pl.ds(pl.multiple_of(v * CHUNK_WORDS, CHUNK_WORDS),
                              CHUNK_WORDS)]                 # [bb, 128]
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_b, LANES), 1)
    shift = (lane % 32).astype(jnp.uint32)
    cmax = jnp.full((block_b, 1), -jnp.inf, jnp.float32)
    carg = jnp.zeros((block_b, 1), jnp.int32)
    for t in range(CHUNK // LANES):
        wsel = jnp.take_along_axis(wchunk, 4 * t + lane // 32, axis=1)
        allow = ((wsel >> shift) & jnp.uint32(1)) == jnp.uint32(1)
        gpos = v * CHUNK + t * LANES + lane
        allow |= ((gpos == eos_id) & eos_ok) | free
        lg = logits_ref[:, t * LANES:(t + 1) * LANES]
        m = jnp.where(allow, lg, jnp.asarray(NEG_INF, lg.dtype))
        masked_ref[:, t * LANES:(t + 1) * LANES] = m
        val = jnp.where(gpos < vocab, m.astype(jnp.float32), -jnp.inf)
        tmax = jnp.max(val, axis=1, keepdims=True)
        targ = jnp.min(jnp.where(val == tmax, gpos, jnp.int32(2 ** 31 - 1)),
                       axis=1, keepdims=True)
        carg = jnp.where(tmax > cmax, targ, carg)
        cmax = jnp.maximum(tmax, cmax)
    better = cmax > best_ref[...]
    arg_ref[...] = jnp.where(better, carg, arg_ref[...])
    best_ref[...] = jnp.where(better, cmax, best_ref[...])

    @pl.when(v == pl.num_programs(1) - 1)
    def _emit():
        ids_ref[...] = arg_ref[...]


def _pad_to(x, axis: int, multiple: int):
    n = x.shape[axis]
    extra = -n % multiple
    if not extra:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, extra)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("eos_id", "interpret"))
def fused_select(logits, store, rows, cd, eos_allowed, constrained, *,
                 eos_id: int = 1, interpret: bool = False):
    """logits [B,V], store [R,W] uint32, rows [B,A] int32 (-1 pad),
    cd [B,W'] uint32 (W' <= W), eos/constrained [B] bool ->
    (greedy ids [B] int32, masked [B,V])."""
    B, V = logits.shape
    A = rows.shape[1]
    # the engine hands an aligned store, so these pads are no-ops there
    store = _pad_to(_pad_to(store, 0, ROW_GROUP), 1, CHUNK_WORDS)
    cd = jnp.pad(cd, ((0, 0), (0, store.shape[1] - cd.shape[1])))
    bb = ROW_GROUP
    if B % bb:
        logits, rows, cd, eos_allowed, constrained = (
            _pad_to(x, 0, bb)
            for x in (logits, rows, cd, eos_allowed, constrained))
    Bp = logits.shape[0]
    nv = pl.cdiv(V, CHUNK)
    kernel = functools.partial(_kernel, eos_id=eos_id, num_accept=A,
                               vocab=V, block_b=bb)
    ids, masked = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Bp // bb, nv),
            in_specs=[
                pl.BlockSpec((bb, CHUNK), lambda i, v, *pf: (i, v)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((bb, store.shape[1]),
                             lambda i, v, *pf: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bb, 1), lambda i, v, *pf: (i, 0)),
                pl.BlockSpec((bb, CHUNK), lambda i, v, *pf: (i, v)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bb, nv * CHUNK_WORDS), jnp.uint32),
                pltpu.VMEM((bb, ROW_GROUP, store.shape[1]), jnp.uint32),
                pltpu.SemaphoreType.DMA((bb,)),
                pltpu.VMEM((bb, 1), jnp.float32),
                pltpu.VMEM((bb, 1), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, V), logits.dtype),
        ],
        interpret=interpret,
        name="fused_select",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(rows.astype(jnp.int32), eos_allowed.astype(jnp.int32),
      constrained.astype(jnp.int32), logits, store, cd)
    return ids[:B, 0], masked[:B]
