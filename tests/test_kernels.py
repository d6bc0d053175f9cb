"""Per-kernel validation: pallas_call (interpret=True on CPU) vs pure-jnp
ref.py oracles, swept over shapes/dtypes + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade gracefully: only @given tests skip
    from tests._hypothesis_stub import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_select.kernel import fused_select
from repro.kernels.masked_logits.ref import masked_logits_ref


def masked_logits(logits, store, rows, eos, cd):
    """The fused kernel's masked-logits output (every row constrained),
    in interpret mode."""
    ones = jnp.ones(eos.shape, bool)
    return fused_select(logits, store, rows, cd, eos, ones,
                        interpret=True)[1]


# ------------------------------ masked_logits ------------------------------

# V = 4480 and 9 rows leave a partial last vocab chunk and a partial
# last batch block, the shapes of deployment vocabularies (V = 151936)
@pytest.mark.parametrize("B,V,R,A", [
    (1, 512, 32, 4),
    (4, 2048, 300, 12),
    (3, 1024, 64, 48),
    (2, 4096, 128, 8),
    (9, 4480, 128, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_logits_matches_ref(B, V, R, A, dtype):
    rng = np.random.default_rng(B * V + A)
    store = rng.integers(0, 2 ** 32, size=(R, V // 32), dtype=np.uint32)
    rows = rng.integers(-1, R, size=(B, A)).astype(np.int32)
    logits = rng.normal(size=(B, V)).astype(np.float32)
    eos = rng.integers(0, 2, size=(B,)).astype(bool)
    cd = rng.integers(0, 2 ** 32, size=(B, V // 32), dtype=np.uint32)
    args = (jnp.asarray(logits, dtype), jnp.asarray(store),
            jnp.asarray(rows), jnp.asarray(eos))
    ref = masked_logits_ref(*args, cd=jnp.asarray(cd))
    out = masked_logits(*args, jnp.asarray(cd))
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(out, np.float32))


@settings(max_examples=20, deadline=None)
@given(
    B=st.integers(1, 4),
    A=st.integers(1, 16),
    seed=st.integers(0, 2 ** 16),
)
def test_masked_logits_property(B, A, seed):
    V, R = 512, 40
    rng = np.random.default_rng(seed)
    store = rng.integers(0, 2 ** 32, size=(R, V // 32), dtype=np.uint32)
    rows = rng.integers(-1, R, size=(B, A)).astype(np.int32)
    logits = rng.normal(size=(B, V)).astype(np.float32)
    eos = rng.integers(0, 2, size=(B,)).astype(bool)
    cd = rng.integers(0, 2 ** 32, size=(B, V // 32), dtype=np.uint32)
    args = (jnp.asarray(logits), jnp.asarray(store), jnp.asarray(rows),
            jnp.asarray(eos))
    out = np.asarray(masked_logits(*args, jnp.asarray(cd)))
    ref = np.asarray(masked_logits_ref(*args, cd=jnp.asarray(cd)))
    np.testing.assert_array_equal(out, ref)
    # property: every unmasked position was allowed by some row, the
    # context-dependent residue overlay, or EOS
    keep = out > -1e29
    union = np.zeros(V, dtype=bool)
    for b in range(B):
        union[:] = np.unpackbits(cd[b].view(np.uint8),
                                 bitorder="little")[:V].astype(bool)
        for r in rows[b]:
            if r >= 0:
                bits = np.unpackbits(store[r].view(np.uint8),
                                     bitorder="little")[:V].astype(bool)
                union |= bits
        if eos[b]:
            union[1] = True
        assert np.array_equal(keep[b], union)


# ------------------------------ flash_attention ----------------------------

@pytest.mark.parametrize("B,Sq,Sk,H,K,Dh,bq,bk", [
    (1, 128, 128, 4, 4, 64, 64, 64),       # MHA square
    (2, 128, 128, 8, 2, 64, 32, 64),       # GQA
    (1, 64, 256, 4, 1, 32, 64, 64),        # MQA, Sk > Sq (decode-ish)
    (2, 256, 256, 6, 3, 128, 128, 128),    # MXU-aligned tiles
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, Sq, Sk, H, K, Dh, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(Sq + Sk), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, Dh), dtype=dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, Dh), dtype=dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, Dh), dtype=dtype)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, K, Dh = 2, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, Dh), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, Dh), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, Dh), dtype=jnp.float32)
    ref = attention_ref(q, k, v, causal=True, window=window)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, Sq, Sk, H, K, Dh = 1, 64, 128, 2, 2, 64
    q = jax.random.normal(ks[0], (B, Sq, H, Dh), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, K, Dh), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, K, Dh), dtype=jnp.float32)
    ref = attention_ref(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    Sq=st.sampled_from([32, 64, 96]),
    H=st.sampled_from([2, 4]),
    K=st.sampled_from([1, 2]),
    seed=st.integers(0, 1000),
)
def test_flash_attention_property(Sq, H, K, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, Dh = 1, 32
    q = jax.random.normal(ks[0], (B, Sq, H, Dh), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (B, Sq, K, Dh), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (B, Sq, K, Dh), dtype=jnp.float32)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
