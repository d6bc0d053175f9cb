"""Compile the serving path's Pallas kernels for a TPU v5e that is
described, not attached, at qwen1.5-0.5b widths: V = 151936, 8 decode
slots, the JSON mask store's 1430 rows, 16 heads of 64, pages of 16 and
a 512-token context. Then the dense decode step and the cache insert at
the benchmark's 16 slots x 1536 positions, with the layer count cut to
2: their caches are updated in place (donated, aliased, no cache-sized
copy).

Interpret mode cannot see what the chip's compiler refuses: blocks that
break the (8, 128) tiling, slices it cannot prove aligned, more VMEM
than a kernel may take. These compiles can. Nothing runs, so they say
nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_select.kernel import fused_select
from repro.kernels.fused_select.ref import sample_masked
from repro.kernels.paged_attention.kernel import paged_attention_span

V = 151936                      # qwen1.5-0.5b vocabulary
SLOTS = 8
ACCEPT = 8                      # accept-width bucket
STORE_ROWS = 1432               # JSON store's 1430 rows, padded to 8
WORDS = -(-V // 32)             # 4748 packed words per row
STORE_WORDS = -(-WORDS // 128) * 128
HEADS, HEAD_DIM, PAGE, CONTEXT = 16, 64, 16, 512


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this environment
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _select_shapes(chip, rows):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    return (spec((rows, V), jnp.bfloat16),       # the engine's logits
            spec((STORE_ROWS, STORE_WORDS), jnp.uint32),
            spec((rows, ACCEPT), jnp.int32),
            spec((rows, WORDS), jnp.uint32),
            spec((rows,), jnp.bool_), spec((rows,), jnp.bool_))


# 1 row: the opportunistic single-slot mask; 8: a dense decode step;
# 128: a speculative span of 16 positions on 8 slots
@pytest.mark.parametrize("rows", [1, SLOTS, SLOTS * 16])
def test_fused_select_greedy_compiles(one_chip, rows):
    _compile(lambda *a: fused_select(*a, interpret=False),
             *_select_shapes(one_chip, rows))


def test_fused_select_sample_compiles(one_chip):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)

    def step(logits, store, rows, cd, eos, cons, greedy, temp, top_k,
             top_p, noise):
        arg, masked = fused_select(logits, store, rows, cd, eos, cons,
                                   interpret=False)
        return sample_masked(masked, arg, greedy, temp, top_k, top_p,
                             noise)

    _compile(step, *_select_shapes(one_chip, SLOTS),
             spec((SLOTS,), jnp.bool_), spec((SLOTS,), jnp.float32),
             spec((SLOTS,), jnp.int32), spec((SLOTS,), jnp.float32),
             spec((SLOTS, V), jnp.float32))


# 1: a decode step; 32: a prefill chunk (the engine's prefill_chunk)
@pytest.mark.parametrize("span", [1, 32])
def test_paged_attention_compiles(one_chip, span):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    pages = CONTEXT // PAGE
    pool = spec((SLOTS * pages, PAGE, HEADS, HEAD_DIM), jnp.bfloat16)
    _compile(lambda *a: paged_attention_span(*a, interpret=False),
             spec((SLOTS, span, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
             spec((SLOTS, pages), jnp.int32), spec((SLOTS,), jnp.int32))


# ------------------------------------------------ the dense decode step --

DECODE_SLOTS, DECODE_LEN = 16, 1536


def _qwen_2_layers():
    from repro.models.config import ModelConfig
    from repro.models.model import build_model
    return build_model(ModelConfig(
        name="qwen1.5-0.5b-2l", arch_type="dense", num_layers=2,
        d_model=1024, vocab_size=V, num_heads=HEADS, num_kv_heads=HEADS,
        head_dim=HEAD_DIM, d_ff=2816, qkv_bias=True, rope_theta=1e6,
        norm_eps=1e-6, tie_embeddings=True, dtype="bfloat16"))


def _on(chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def _cache_sized(text: str, elements: int) -> list:
    """Copies, dynamic slices and dynamic-update-slices (or fusions named
    for them), and f32 buffers, with at least `elements` elements,
    outside fusion bodies: the ops that move a cache-sized buffer on
    their own, or write it widened to f32."""
    prefetch = set(re.findall(r"%(copy-start[\w.-]*) = .*"
                              r"cross_program_prefetch", text))
    out, fused = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            fused = "fused" in head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%((?:copy|[a-z_]*dynamic[-_]"
                     r"(?:update[-_])?slice)[\w.-]*) = \w+\[([\d,]+)\]", line) \
            or re.match(r"\s*(?:ROOT )?%([\w.-]+) = f32\[([\d,]+)\]", line)
        done = re.search(r"copy-done\(%([\w.-]+)\)", line)
        if done and done.group(1) in prefetch:
            continue            # a weight fetched ahead of the program
        if m and not fused:
            n = 1
            for d in m.group(2).split(","):
                n *= int(d)
            if n >= elements:
                out.append(m.group(1))
    return out


@pytest.mark.parametrize("program", ["decode_step", "insert",
                                     "decode_step_trunk_shard"])
def test_dense_cache_updated_in_place(v5e, one_chip, program):
    """The engine's jit of each program (caches donated, default device
    layouts): the caches alias input to output, and the decode step keeps
    them in the layout it was given, with no cache-sized copy and little
    scratch memory. `decode_step_trunk_shard` compiles the step under
    the four chips' serving mesh with the trunk and the kv heads sharded:
    the scatter stays shard-local, with no f32 rewrite of the cache."""
    from repro.serving.engine import insert
    model = _qwen_2_layers()
    shapes = jax.eval_shape(
        lambda: model.init_decode_caches(DECODE_SLOTS, DECODE_LEN))
    caches = _on(one_chip, shapes)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    shards = 1
    if program == "decode_step":
        params = _on(one_chip, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
        slots = spec((DECODE_SLOTS,), jnp.int32)
        compiled = jax.jit(model.decode_step, donate_argnums=1).lower(
            params, caches, slots, slots).compile()
    elif program == "insert":
        one = _on(one_chip, jax.eval_shape(
            lambda: model.init_decode_caches(1, DECODE_LEN)))
        compiled = jax.jit(insert, donate_argnums=0).lower(
            caches, one, spec((), jnp.int32)).compile()
    else:
        compiled = _compile_trunk_shard(v5e, model, shapes)
        shards = len(v5e.devices)
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(shapes))
    assert mem.alias_size_in_bytes >= cache_bytes // shards
    if program != "insert":
        assert mem.temp_size_in_bytes < 64 << 20
        one_layer = DECODE_SLOTS * DECODE_LEN * HEADS * HEAD_DIM // shards
        assert _cache_sized(compiled.as_text(), one_layer) == []


def _compile_trunk_shard(v5e, model, cache_shapes):
    """The dense decode step as a four-chip serving engine with
    `trunk_shard` jits it: parameters and caches placed by the serving
    rules, traced inside them, caches donated."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.distributed.api import use_sharding
    from repro.distributed.sharding import (serving_cache_shardings,
                                            serving_param_shardings,
                                            serving_rules)
    mesh = Mesh(np.array(v5e.devices).reshape(1, -1), ("data", "model"))
    cfg = model.cfg

    def placed(shapes, shardings):
        return jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sh), shapes, shardings)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = placed(params, serving_param_shardings(params, mesh, cfg,
                                                    trunk_shard=True))
    caches = placed(cache_shapes, serving_cache_shardings(
        cache_shapes, mesh, cfg, trunk_shard=True))
    slots = jax.ShapeDtypeStruct((DECODE_SLOTS,), jnp.int32,
                                 sharding=NamedSharding(mesh, PartitionSpec()))
    with use_sharding(mesh, serving_rules(mesh, cfg, trunk_shard=True)):
        return jax.jit(model.decode_step, donate_argnums=1).lower(
            params, caches, slots, slots).compile()
