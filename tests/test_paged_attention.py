"""Paged attention: the Pallas kernel, the jnp gather reference and the
dense decode path must agree BIT-EXACTLY (the engine's token-for-token
equivalence claim rests on it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade gracefully: only @given tests skip
    from tests._hypothesis_stub import given, settings, st

from repro.kernels.paged_attention.kernel import (paged_attention_decode,
                                                  paged_attention_span)
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import attend, paged_attention_ref

NEG_INF = -1e30


def _setup(rng, B, S, H, K, Dh, ps, nP, P, dtype=np.float32, min_pos=None):
    """Random pools + disjoint per-slot page tables + start positions
    with pos + S - 1 inside the mapped region."""
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)).astype(dtype))
    kp = jnp.asarray(rng.normal(size=(P, ps, K, Dh)).astype(dtype))
    vp = jnp.asarray(rng.normal(size=(P, ps, K, Dh)).astype(dtype))
    perm = rng.permutation(P)
    pt = np.full((B, nP), -1, np.int32)
    pos = np.zeros(B, np.int32)
    off = 0
    for b in range(B):
        n = int(rng.integers(1, nP + 1))
        n = max(n, -(-S // ps))          # mapped region must cover the span
        pt[b, :n] = perm[off:off + n]
        off += n
        hi = n * ps - S
        lo = 0 if min_pos is None else min(min_pos, hi)
        pos[b] = int(rng.integers(lo, hi + 1))
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(pos)


def _dense_twin(q, kp, vp, pt, pos):
    """The dense decode path (layers._self_attention_decode): kv_pos
    validity and its attention math (`ref.attend`) on a densely
    materialized copy of the paged cache."""
    P, ps, K, Dh = kp.shape
    B, S, H, _ = q.shape
    nP = pt.shape[1]
    L = nP * ps
    ptn = np.asarray(pt)
    kc = np.zeros((B, L, K, Dh), np.asarray(kp).dtype)
    vc = np.zeros((B, L, K, Dh), np.asarray(vp).dtype)
    kv_pos = np.full((B, L), -1, np.int32)
    for b in range(B):
        for j in range(nP):
            if ptn[b, j] >= 0:
                kc[b, j * ps:(j + 1) * ps] = np.asarray(kp)[ptn[b, j]]
                vc[b, j * ps:(j + 1) * ps] = np.asarray(vp)[ptn[b, j]]
                kv_pos[b, j * ps:(j + 1) * ps] = np.arange(j * ps,
                                                           (j + 1) * ps)
    qpos = np.asarray(pos)[:, None] + np.arange(S)[None, :]
    valid = (kv_pos[:, None, :] >= 0) & \
        (kv_pos[:, None, :] <= qpos[:, :, None])
    return attend(q, jnp.asarray(kc), jnp.asarray(vc),
                  jnp.asarray(valid)).astype(q.dtype)


def _bits(x):
    return np.asarray(jnp.asarray(x).astype(jnp.float32))


def test_span_kernel_bit_exact_vs_ref_and_dense():
    rng = np.random.default_rng(0)
    args = _setup(rng, B=3, S=4, H=8, K=4, Dh=32, ps=8, nP=5, P=16)
    ref = paged_attention_ref(*args)
    ker = paged_attention_span(*args, interpret=True)
    dense = _dense_twin(*args)
    np.testing.assert_array_equal(_bits(ref), _bits(ker))
    np.testing.assert_array_equal(_bits(ref), _bits(dense))


def test_decode_variant_bit_exact():
    rng = np.random.default_rng(1)
    q, kp, vp, pt, pos = _setup(rng, B=4, S=1, H=4, K=2, Dh=16,
                                ps=4, nP=6, P=32)
    ref = paged_attention_ref(q, kp, vp, pt, pos)
    ker = paged_attention_decode(q[:, 0], kp, vp, pt, pos, interpret=True)
    np.testing.assert_array_equal(_bits(ref[:, 0]), _bits(ker))


def test_bfloat16_bit_exact():
    rng = np.random.default_rng(2)
    q, kp, vp, pt, pos = _setup(rng, B=2, S=2, H=4, K=2, Dh=16,
                                ps=4, nP=4, P=12)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    ref = paged_attention_ref(q, kp, vp, pt, pos)
    ker = paged_attention_span(q, kp, vp, pt, pos, interpret=True)
    dense = _dense_twin(q, kp, vp, pt, pos)
    np.testing.assert_array_equal(_bits(ref), _bits(ker))
    np.testing.assert_array_equal(_bits(ref), _bits(dense))


@pytest.mark.parametrize("B,S,H,K", [(3, 4, 8, 4), (4, 1, 4, 2),
                                     (2, 5, 6, 3), (2, 1, 16, 16)])
def test_head_major_layout_matches_ref(B, S, H, K):
    """The kernel's head-major layout (batched matmuls over the head
    dim), the one the chip compiles, run through the interpreter across
    GQA groupings, MHA at S = 1 included: bit-exact with the reference."""
    rng = np.random.default_rng(B * S + H)
    args = _setup(rng, B=B, S=S, H=H, K=K, Dh=16, ps=4, nP=5,
                  P=B * 5 + 2)
    ref = paged_attention_ref(*args)
    ker = paged_attention_span(*args, interpret=True)
    np.testing.assert_array_equal(_bits(ker), _bits(ref))


def test_unmapped_pages_never_contribute():
    """Entries behind -1 page-table slots must be invisible even when
    the pool rows they'd alias hold huge values."""
    rng = np.random.default_rng(3)
    q, kp, vp, pt, pos = _setup(rng, B=2, S=2, H=4, K=2, Dh=16,
                                ps=4, nP=4, P=12)
    ref = paged_attention_ref(q, kp, vp, pt, pos)
    poisoned = kp.at[0].set(1e4)   # page 0 = the clamp target of -1 slots
    pt2 = np.asarray(pt).copy()
    assert (pt2 == 0).sum() <= 1   # page 0 mapped at most once
    mask0 = ~(pt2 == 0).any(axis=1)
    ref2 = paged_attention_ref(q, poisoned, vp, jnp.asarray(pt2), pos)
    # slots that never map page 0 are unchanged by the poison
    np.testing.assert_array_equal(_bits(ref)[mask0], _bits(ref2)[mask0])


def test_ops_dispatcher_backends_agree():
    rng = np.random.default_rng(4)
    args = _setup(rng, B=2, S=3, H=4, K=4, Dh=16, ps=4, nP=4, P=12)
    a = paged_attention(*args, backend="jnp")
    b = paged_attention(*args, backend="pallas")
    c = paged_attention(*args, backend="auto")
    np.testing.assert_array_equal(_bits(a), _bits(b))
    np.testing.assert_array_equal(_bits(a), _bits(c))


@settings(max_examples=10, deadline=None)
@given(
    B=st.sampled_from([1, 2, 3]),
    S=st.sampled_from([1, 2, 4]),
    HK=st.sampled_from([(4, 2), (4, 4), (8, 2)]),
    ps=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 2 ** 16),
)
def test_fuzz_kernel_parity(B, S, HK, ps, seed):
    H, K = HK
    rng = np.random.default_rng(seed)
    nP = int(rng.integers(max(1, -(-(S + 1) // ps)), 6))
    P = B * nP + 2
    args = _setup(rng, B=B, S=S, H=H, K=K, Dh=8, ps=ps, nP=nP, P=P)
    ref = paged_attention_ref(*args)
    ker = paged_attention_span(*args, interpret=True)
    dense = _dense_twin(*args)
    np.testing.assert_array_equal(_bits(ref), _bits(ker))
    np.testing.assert_array_equal(_bits(ref), _bits(dense))
