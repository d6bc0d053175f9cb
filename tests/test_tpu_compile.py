"""Compile the serving path's Pallas kernels for a TPU v5e that is
described, not attached, at qwen1.5-0.5b widths: V = 151936, 8 decode
slots, the JSON mask store's 1430 rows, 16 heads of 64, pages of 16 and
a 512-token context.

Interpret mode cannot see what the chip's compiler refuses: blocks that
break the (8, 128) tiling, slices it cannot prove aligned, more VMEM
than a kernel may take. These compiles can. Nothing runs, so they say
nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
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
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


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
