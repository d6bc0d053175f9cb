"""The dense decode cache is updated in place (models/model.py::
_decode_trunk, serving/engine.py).

Each layer scatters its new rows into the stacked caches carried
through the layer scan, with or without a serving mesh. Against its
twins:

  * **steps / span** — from empty caches, the dense path and the paged
    path (the documented bit-exact twin, here with one page table a
    slot laid end to end) give bit-identical logits and K/V lines over
    several steps: one token a step, and a ragged span gated by
    `feed_mask`; padding lanes stay zero and gated positions write
    nothing;
  * **ring** — a sliding-window ring cache that wraps gives the logits
    of a linear cache under the same window, and holds its last lines;
  * **donate / mesh1** — the engine's dense decode, span decode and
    insert consume their cache argument (the old arrays are deleted),
    without a mesh and through a one-device serving mesh;
  * **overlap** — dense greedy streams with the host/device overlap on
    equal those with it off.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.decoding import DecodeConfig
from repro.launch.mesh import make_serving_mesh
from repro.models.model import build_model
from repro.serving.engine import Engine, Request

B, STEPS, MAX_LEN = 3, 6, 64
PAGE, PAGES = 8, 4               # a slot's pages: PAGE * PAGES positions


def _model(window=0):
    cfg = replace(get_config("syncode-demo"), vocab_size=1024, num_layers=2,
                  d_model=64, d_ff=128, num_heads=4, num_kv_heads=2,
                  head_dim=16, sliding_window=window)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _span_fn(model, ctx=None):
    return jax.jit(lambda p, c, t, q, fm: model.decode_span(
        p, c, t, q, feed_mask=fm, batch_ctx=ctx))


def _feed(rng, span, ragged):
    toks = jnp.asarray(rng.integers(3, 1024, (B, span)), jnp.int32)
    fed = rng.integers(1, span + 1, B) if ragged else np.full(B, span)
    return toks, fed, jnp.asarray(np.arange(span)[None, :] < fed[:, None])


def _paged_twin(span, ragged):
    """Dense and paged caches fed the same tokens: bit-identical logits
    at every fed position, and the dense lines are the paged pool's."""
    model, params = _model()
    rng = np.random.default_rng(7)
    table = jnp.arange(B * PAGES, dtype=jnp.int32).reshape(B, PAGES)
    dense_fn = _span_fn(model)
    paged_fn = _span_fn(model, {"page_table": table})
    dense = model.init_decode_caches(B, PAGE * PAGES)
    paged = model.init_paged_caches(B * PAGES, PAGE)
    pos = np.zeros(B, np.int32)
    for _ in range(STEPS):
        toks, fed, fm = _feed(rng, span, ragged)
        got, dense = dense_fn(params, dense, toks, jnp.asarray(pos), fm)
        want, paged = paged_fn(params, paged, toks, jnp.asarray(pos), fm)
        live = np.asarray(fm)
        np.testing.assert_array_equal(np.asarray(got)[live],
                                      np.asarray(want)[live])
        pos = pos + fed.astype(np.int32)
    for d, pg in zip(dense[0], paged[0]):     # group 0, each layer kind
        n, _, L, K, W = d["k"].shape
        Dh = pg["k"].shape[-1]
        for name in ("k", "v"):
            lines = np.asarray(pg[name]).reshape(n, B, L, K, Dh)
            np.testing.assert_array_equal(np.asarray(d[name])[..., :Dh],
                                          lines)
            assert not np.asarray(d[name])[..., Dh:].any()
        line = np.asarray(d["kv_pos"])                      # [n, B, L]
        want = np.where(np.arange(L)[None, :] < pos[:, None],
                        np.arange(L)[None, :], -1)
        np.testing.assert_array_equal(line, np.broadcast_to(want, line.shape))


def _ring():
    """A ring cache of the window's length, wrapped, against a linear
    cache under the same window: the same logits at every step, and the
    ring holds the linear cache's last `window` lines."""
    window = 8
    ring_model, params = _model(window)
    linear_model, _ = _model()
    ring_fn = _span_fn(ring_model)
    linear_fn = _span_fn(linear_model, {"window": window})
    ring = ring_model.init_decode_caches(B, MAX_LEN)
    linear = linear_model.init_decode_caches(B, MAX_LEN)
    assert ring[0][0]["k"].shape[2] == window
    rng = np.random.default_rng(7)
    pos = np.array([0, 3, 5], np.int32)
    for _ in range(3 * window):
        toks, _, fm = _feed(rng, 1, False)
        got, ring = ring_fn(params, ring, toks, jnp.asarray(pos), fm)
        want, linear = linear_fn(params, linear, toks, jnp.asarray(pos), fm)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        pos = pos + 1
    r, lin = ring[0][0], linear[0][0]
    assert int(np.asarray(r["kv_pos"]).max()) >= window      # it wrapped
    for b in range(B):
        last = np.arange(pos[b] - window, pos[b])
        for name in ("k", "v", "kv_pos"):
            np.testing.assert_array_equal(
                np.asarray(r[name])[:, b, last % window],
                np.asarray(lin[name])[:, b, last])


def _engine(model, params, tokenizer, bundle, **kw):
    g, tab, store, _ = bundle
    return Engine(model, params, tokenizer, {"json": (g, tab, store)},
                  max_len=MAX_LEN, slots=2, **kw)


def _reqs():
    return [Request(rid=i, prompt=p, grammar="json", max_new_tokens=n,
                    decode=DecodeConfig(method="greedy"), seed=i)
            for i, (p, n) in enumerate([(b"Q: generate. A:", 8),
                                        (b"Q: a list. A:", 12),
                                        (b"Q: nested. A:", 6)])]


def _streams(eng):
    states, stats = eng.generate(_reqs())
    return [st.token_ids for st in states], stats


def _deleted(tree) -> bool:
    return all(a.is_deleted() for a in jax.tree.leaves(tree))


def _donate(model, params, tokenizer, bundle, **kw):
    eng = _engine(model, params, tokenizer, bundle, **kw)
    caches = eng._place_caches(model.init_decode_caches(eng.slots, MAX_LEN))
    tok = jnp.zeros((eng.slots,), jnp.int32)
    _, new = eng._decode(eng.params, caches, tok, tok)
    assert _deleted(caches) and not _deleted(new)
    prompt, n = eng._bucketed_prompt([5, 6, 7])
    _, pc = eng._prefill(eng.params, {"tokens": prompt}, jnp.int32(n))
    ins = eng._insert_caches(new, pc, jnp.int32(1))
    assert _deleted(new) and not _deleted(pc)
    toks = jnp.zeros((eng.slots, 4), jnp.int32)
    _, out = eng._span_decode(eng.params, ins, toks, tok,
                              jnp.ones((eng.slots, 4), bool))
    assert _deleted(ins) and not _deleted(out)


def _overlap(model, params, tokenizer, bundle):
    on, s_on = _streams(_engine(model, params, tokenizer, bundle,
                                overlap=True))
    off, s_off = _streams(_engine(model, params, tokenizer, bundle,
                                  overlap=False))
    assert on == off
    assert s_on.overlap_dispatched > 0 and s_off.overlap_dispatched == 0


def _mesh1(model, params, tokenizer, bundle):
    _donate(model, params, tokenizer, bundle, mesh=make_serving_mesh(1))


@pytest.mark.parametrize("case", ["steps", "span", "ring", "donate",
                                  "overlap", "mesh1"])
def test_dense_cache_in_place(case, tokenizer, grammar_bundle):
    if case == "steps":
        return _paged_twin(span=1, ragged=False)
    if case == "span":
        return _paged_twin(span=4, ragged=True)
    if case == "ring":
        return _ring()
    model, params = _model()
    bundle = grammar_bundle("json")
    {"donate": _donate, "overlap": _overlap, "mesh1": _mesh1}[case](
        model, params, tokenizer, bundle)
