"""Fused mask+filter+sample parity: the Pallas kernel, the jnp
reference, the precomputed-Gumbel-noise variant, and the legacy
two-call pipeline (apply mask, then select_batch) must all pick the
BIT-IDENTICAL token for identical inputs — that identity is what lets
the engine swap the fused call in without changing a single generated
token (ISSUE 9 acceptance: token-for-token identity in every mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade gracefully: only @given tests skip
    from tests._hypothesis_stub import given, settings, st

from repro.core.decoding import select_batch
from repro.kernels.fused_select.kernel import fused_select
from repro.kernels.fused_select.ops import fused_mask_select
from repro.kernels.fused_select.ref import fused_select_ref, gumbel_noise
from repro.kernels.masked_logits.ref import masked_logits_ref


def _step_inputs(rng, B, V, R, A):
    """One random fused-select problem: store rows, row ids with -1 pad,
    residue overlay, per-slot flags and decode configs."""
    store = rng.integers(0, 2 ** 32, size=(R, V // 32), dtype=np.uint32)
    rows = rng.integers(-1, R, size=(B, A)).astype(np.int32)
    cd = rng.integers(0, 2 ** 32, size=(B, V // 32), dtype=np.uint32)
    # zero some residue rows: the common no-residue case must be covered
    cd[rng.random(B) < 0.5] = 0
    logits = rng.normal(size=(B, V)).astype(np.float32)
    eos = rng.integers(0, 2, size=(B,)).astype(bool)
    constrained = rng.integers(0, 2, size=(B,)).astype(bool)
    greedy = rng.integers(0, 2, size=(B,)).astype(bool)
    temp = rng.uniform(0.4, 1.6, size=(B,)).astype(np.float32)
    top_k = rng.integers(0, 12, size=(B,)).astype(np.int32)
    top_p = rng.uniform(0.5, 1.2, size=(B,)).astype(np.float32)
    keys = rng.integers(0, 2 ** 32, size=(B, 2), dtype=np.uint32)
    return (jnp.asarray(logits), jnp.asarray(store), jnp.asarray(rows),
            jnp.asarray(cd), jnp.asarray(eos), jnp.asarray(constrained),
            jnp.asarray(greedy), jnp.asarray(temp), jnp.asarray(top_k),
            jnp.asarray(top_p), jnp.asarray(keys))


@pytest.mark.parametrize("B,V,R,A", [
    (1, 512, 32, 4),
    (4, 2048, 300, 12),
    (3, 1024, 64, 48),
])
def test_all_variants_bit_identical(B, V, R, A):
    (logits, store, rows, cd, eos, cons, greedy, temp, top_k, top_p,
     keys) = _step_inputs(np.random.default_rng(B * V + A), B, V, R, A)
    # legacy two-call pipeline: mask, then the pre-fusion selector
    masked_legacy = masked_logits_ref(logits, store, rows, eos,
                                     constrained=cons, cd=cd)
    ids_legacy = select_batch(masked_legacy, keys, greedy, temp, top_k,
                              top_p)
    # fused reference, keys variant (same categorical streams)
    ids_rk, masked_rk = fused_select_ref(logits, store, rows, cd, eos,
                                         cons, greedy, temp, top_k, top_p,
                                         keys=keys)
    # fused reference, precomputed-noise variant
    noise = gumbel_noise(keys, V)
    ids_rn, masked_rn = fused_select_ref(logits, store, rows, cd, eos,
                                         cons, greedy, temp, top_k, top_p,
                                         noise=noise)
    # Pallas kernel + sampling tail, noise variant (interpret on CPU)
    ids_k, masked_k = fused_mask_select(logits, store, rows, cd, eos, cons,
                                        greedy, temp, top_k, top_p,
                                        noise=noise, backend="pallas")
    np.testing.assert_array_equal(np.asarray(ids_legacy),
                                  np.asarray(ids_rk))
    np.testing.assert_array_equal(np.asarray(ids_rk), np.asarray(ids_rn))
    np.testing.assert_array_equal(np.asarray(ids_rn), np.asarray(ids_k))
    for m in (masked_rk, masked_rn, masked_k):
        np.testing.assert_array_equal(np.asarray(masked_legacy),
                                      np.asarray(m))


def test_greedy_variant_matches_argmax():
    """The all-greedy host-static variant (no filter, no PRNG) must
    equal argmax over the masked logits — and the sample variant with
    greedy flags all-True must agree with it."""
    (logits, store, rows, cd, eos, cons, _, temp, top_k, top_p,
     keys) = _step_inputs(np.random.default_rng(3), 4, 1024, 80, 8)
    ones = jnp.ones((4,), bool)
    ids_g, masked_g = fused_select(logits, store, rows, cd, eos, cons,
                                   interpret=True)
    ref = masked_logits_ref(logits, store, rows, eos, constrained=cons,
                            cd=cd)
    np.testing.assert_array_equal(np.asarray(ids_g),
                                  np.argmax(np.asarray(ref), axis=-1))
    np.testing.assert_array_equal(np.asarray(masked_g), np.asarray(ref))
    ids_s, _ = fused_select_ref(logits, store, rows, cd, eos, cons, ones,
                                temp, top_k, top_p,
                                noise=gumbel_noise(keys, 1024))
    np.testing.assert_array_equal(np.asarray(ids_g), np.asarray(ids_s))


def test_none_cd_means_no_overlay():
    """cd=None through the ref equals an explicit all-zero overlay."""
    (logits, store, rows, _, eos, cons, greedy, temp, top_k, top_p,
     keys) = _step_inputs(np.random.default_rng(9), 3, 512, 40, 6)
    zeros = jnp.zeros((3, 512 // 32), jnp.uint32)
    a = fused_select_ref(logits, store, rows, None, eos, cons, greedy,
                         temp, top_k, top_p, keys=keys)
    b = fused_select_ref(logits, store, rows, zeros, eos, cons, greedy,
                         temp, top_k, top_p, keys=keys)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_span_form_matches_batch():
    from repro.kernels.fused_select.ops import fused_mask_select_span
    B, S, V, R, A = 2, 3, 512, 40, 6
    (logits, store, rows, cd, eos, cons, greedy, temp, top_k, top_p,
     keys) = _step_inputs(np.random.default_rng(17), B * S, V, R, A)
    noise = gumbel_noise(keys, V)
    ids_flat, masked_flat = fused_mask_select(
        logits, store, rows, cd, eos, cons,
        jnp.repeat(greedy[:B], S), jnp.repeat(temp[:B], S),
        jnp.repeat(top_k[:B], S), jnp.repeat(top_p[:B], S), noise=noise)
    ids_span, masked_span = fused_mask_select_span(
        logits.reshape(B, S, V), store, rows.reshape(B, S, A),
        cd.reshape(B, S, -1), eos.reshape(B, S), cons.reshape(B, S),
        greedy[:B], temp[:B], top_k[:B], top_p[:B],
        noise=noise.reshape(B, S, V))
    np.testing.assert_array_equal(np.asarray(ids_flat).reshape(B, S),
                                  np.asarray(ids_span))
    np.testing.assert_array_equal(
        np.asarray(masked_flat).reshape(B, S, V), np.asarray(masked_span))


@settings(max_examples=15, deadline=None)
@given(B=st.integers(1, 4), A=st.integers(1, 16),
       seed=st.integers(0, 2 ** 16))
def test_fused_select_property(B, A, seed):
    """Kernel vs keys-reference under random shapes/configs — the
    strongest form: two different samplers (categorical vs
    argmax+noise), two different executors (XLA vs Pallas interpret),
    one answer."""
    V, R = 512, 40
    (logits, store, rows, cd, eos, cons, greedy, temp, top_k, top_p,
     keys) = _step_inputs(np.random.default_rng(seed), B, V, R, A)
    ids_ref, masked_ref = fused_select_ref(logits, store, rows, cd, eos,
                                           cons, greedy, temp, top_k,
                                           top_p, keys=keys)
    ids_k, masked_k = fused_mask_select(logits, store, rows, cd, eos, cons,
                                        greedy, temp, top_k, top_p,
                                        noise=gumbel_noise(keys, V),
                                        backend="pallas")
    np.testing.assert_array_equal(np.asarray(ids_ref), np.asarray(ids_k))
    np.testing.assert_array_equal(np.asarray(masked_ref),
                                  np.asarray(masked_k))
