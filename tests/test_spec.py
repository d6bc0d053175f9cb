"""Grammar-aware speculative decoding: soundness + exact equivalence.

The two system-level guarantees:
  * every speculative mechanism (jump-forward forced tokens, draft-verify
    accepted tokens) emits only tokens the exact parser oracle admits —
    partial outputs stay in L_p(G) at every step;
  * greedy speculative decoding is token-for-token IDENTICAL to the
    plain batched engine on every builtin grammar (forced tokens are the
    masked distribution's single support point; accepted drafts equal the
    selection the plain engine would have made).
"""
import jax
import numpy as np
import pytest

from repro.core.constrain import GrammarConstraint
from repro.core.decoding import DecodeConfig
from repro.core.grammars import BUILTIN, load_grammar
from repro.core.mask_store import build_mask_store
from repro.core.parser import IncrementalParser
from repro.core.sampling import GrammarSampler
from repro.serving.engine import Engine, Request
from repro.spec import (NGramProposer, SpecConfig, SuffixAutomatonProposer,
                        forced_literal, jump_forward, retokenize_aligned)


# --------------------------- proposers ---------------------------------

def test_suffix_automaton_proposes_repeated_continuation():
    p = SuffixAutomatonProposer()
    p.extend([5, 6, 7, 8, 9, 5, 6, 7])
    # longest earlier suffix is (5, 6, 7) ending at index 2 -> continue 8, 9
    assert p.match_len() == 3
    assert p.propose(2) == [8, 9]
    assert p.propose(4) == [8, 9, 5, 6]


def test_suffix_automaton_no_match_proposes_nothing():
    p = SuffixAutomatonProposer()
    p.extend([1, 2, 3, 4])
    assert p.propose(3) == []


def test_suffix_automaton_min_match_gates():
    p = SuffixAutomatonProposer(min_match=3)
    p.extend([1, 2, 9, 3, 2, 9])     # longest repeated suffix (2, 9): len 2
    assert p.propose(2) == []
    q = SuffixAutomatonProposer(min_match=2)
    q.extend([1, 2, 9, 3, 2, 9])
    assert q.propose(1) == [3]


def test_ngram_proposer_matches_sam_on_simple_loop():
    sam, ng = SuffixAutomatonProposer(), NGramProposer(max_n=4)
    seq = [3, 1, 4, 1, 5, 3, 1, 4]
    sam.extend(seq)
    ng.extend(seq)
    assert sam.propose(2) == ng.propose(2) == [1, 5]


# ----------------------- mask-store spec queries ------------------------

def test_popcount_and_sole_survivor_match_unpack(grammar_bundle):
    g, tab, store, gc = grammar_bundle("json")
    rng = np.random.default_rng(0)
    for _ in range(50):
        rows = rng.integers(-1, store.num_rows,
                            size=rng.integers(1, 8)).astype(np.int64)
        ref = np.zeros(store.tokenizer.vocab_size, bool)
        for r in rows:
            if r >= 0:
                ref |= store.unpack(store.packed[r])
        assert store.union_popcount(rows) == int(ref.sum())
        sole = store.sole_survivor(rows)
        if ref.sum() == 1:
            assert sole == int(np.argmax(ref))
        else:
            assert sole is None


def test_row_popcounts_lazy_table(grammar_bundle):
    g, tab, store, gc = grammar_bundle("calc")
    pc = store.row_popcounts()
    assert pc.shape == (store.num_rows,)
    for r in (0, store.num_rows // 2, store.num_rows - 1):
        assert pc[r] == int(store.unpack(store.packed[r]).sum())


def test_allowed_first_bytes_matches_token_scan(grammar_bundle):
    g, tab, store, gc = grammar_bundle("json")
    sm = gc.step_rows(b'{"a": ')
    union = store.union_rows(sm.rows)
    fb = store.allowed_first_bytes(union)
    mask = store.unpack(union)
    ref = np.zeros(256, bool)
    for tid in np.where(mask)[0]:
        tb = store.tokenizer.id_to_bytes[tid]
        if tb:
            ref[tb[0]] = True
    np.testing.assert_array_equal(fb, ref)


# ------------------------- jump-forward soundness -----------------------

@pytest.mark.parametrize("name", BUILTIN)
def test_jump_emits_only_oracle_valid_tokens(name, grammar_bundle, tokenizer):
    """Fuzz: from random valid-prefix texts, every token emitted by the
    jump analyzer (both modes) must pass a FRESH oracle's
    is_valid_extension at its emission point."""
    g, tab, store, gc = grammar_bundle(name)
    gs = GrammarSampler(g, seed=11)
    rng = np.random.default_rng(11)
    checked = 0
    for s in gs.sample_batch(8, budget=14, max_bytes=160):
        cut = int(rng.integers(0, len(s) + 1))
        prefix = s[:cut]
        try:
            gc.parser.partial_parse(prefix)
        except Exception:
            continue                      # cut landed outside L_p(G)
        for literal in (False, True):
            jr = jump_forward(gc, prefix, 12, literal=literal)
            oracle = GrammarConstraint(g, tab, store, tokenizer)
            cur = prefix
            for t in jr.tokens:
                assert oracle.is_valid_extension(cur, t), \
                    (name, literal, cur, t)
                cur += tokenizer.id_to_bytes[t]
                checked += 1
        # byte-level: every forced-literal prefix must stay in L_p(G)
        lit = forced_literal(gc, prefix, max_bytes=16)
        p2 = IncrementalParser(g, tab)
        for i in range(1, len(lit) + 1):
            p2.partial_parse(prefix + lit[:i])    # raises if outside L_p
            checked += 1
    if name == "jsonmsg":
        # whitespace-ignored grammars rarely force anything (a space is
        # always an alternative next byte) — the compact schema grammar
        # must actually exercise the property
        assert checked > 0


def test_forced_step_classifies_jsonmsg(grammar_bundle):
    g, tab, store, gc = grammar_bundle("jsonmsg")
    kind, tok, sm = gc.forced_step(b'[{"id":3,"op":"get","args":["x"')
    assert kind in ("free", "token")      # '"' may close or extend the arg
    # after a complete record list, ']' closes: popcount small but >1 is
    # fine; the interesting case is byte-forcing below


def test_forced_literal_jsonmsg_keys(grammar_bundle):
    """The compact schema grammar forces whole key literals at byte
    level even though several tokenizations survive in the mask."""
    g, tab, store, gc = grammar_bundle("jsonmsg")
    assert forced_literal(gc, b"[") == b'{"id":'
    assert forced_literal(gc, b'[{"id":3,') == b'"op":"'
    assert forced_literal(gc, b'[{"id":3,"op":"get",') == b'"args":['


def test_retokenize_aligned(tokenizer):
    # stable boundary: '=' cannot merge with '"'
    prefix = tokenizer.encode(b"x=")
    ids = retokenize_aligned(tokenizer, prefix, b'"name"')
    assert ids is not None
    assert b"".join(tokenizer.id_to_bytes[t] for t in ids) == b'"name"'
    # unstable boundary: the vocab holds a fused ' "' token, so canonical
    # encoding merges the prefix's trailing space with the literal's
    # opening quote -> the check must reject
    prefix2 = tokenizer.encode(b"x = ")
    assert retokenize_aligned(tokenizer, prefix2, b'"name"') is None


# ----------------------- engine-level equivalence -----------------------

@pytest.fixture(scope="module")
def spec_engine(tokenizer):
    from dataclasses import replace
    from repro.configs import get_config
    from repro.models.model import build_model
    bundles = {}
    for name in BUILTIN:
        g, tab = load_grammar(name)
        bundles[name] = (g, tab, build_mask_store(g, tokenizer))
    cfg = replace(get_config("syncode-demo"), vocab_size=tokenizer.vocab_size,
                  num_layers=2, d_model=128, d_ff=256, num_heads=4,
                  num_kv_heads=2, head_dim=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return Engine(model, params, tokenizer, bundles, max_len=200,
                  slots=4), bundles


def _reqs(gname, method="greedy", n=4, max_new=24, temp=1.0, seed0=0):
    return [Request(rid=i, prompt=b"say:", grammar=gname,
                    max_new_tokens=max_new,
                    decode=DecodeConfig(method=method, temperature=temp),
                    seed=seed0 + i) for i in range(n)]


@pytest.mark.parametrize("name", BUILTIN)
def test_greedy_spec_identical_to_plain_engine(name, spec_engine):
    """Acceptance criterion: greedy speculative decoding (default config)
    is token-for-token identical to the plain batched engine."""
    engine, bundles = spec_engine
    plain, _ = engine.generate(_reqs(name))
    spec, stats = engine.generate_speculative(_reqs(name))
    for a, b in zip(plain, spec):
        assert a.token_ids == b.token_ids, (name, a.generated, b.generated)
        assert a.finish_reason == b.finish_reason
        assert a.generated == b.generated
    assert stats.tokens == sum(s.steps for s in spec)


def test_greedy_spec_identical_with_more_requests_than_slots(spec_engine):
    engine, bundles = spec_engine
    n = 2 * engine.slots + 1
    plain, _ = engine.generate(_reqs("jsonmsg", n=n, max_new=16))
    spec, _ = engine.generate_speculative(_reqs("jsonmsg", n=n, max_new=16))
    for a, b in zip(plain, spec):
        assert a.token_ids == b.token_ids


def test_spec_sampling_outputs_stay_valid(spec_engine):
    """Sampling carries no token-equivalence claim, but the grammar
    guarantee must hold: completed outputs parse, partials stay in
    L_p(G)."""
    engine, bundles = spec_engine
    for name in ("json", "jsonmsg"):
        states, stats = engine.generate_speculative(
            _reqs(name, method="sample", temp=1.0, seed0=40))
        g, tab, _ = bundles[name]
        for st in states:
            assert st.finish_reason in ("eos", "length", "max_len")
            if st.finish_reason == "eos":
                assert IncrementalParser(g, tab).recognize(st.generated)
            else:
                IncrementalParser(g, tab).partial_parse(st.generated)


def test_literal_jump_outputs_valid_and_jump_heavy(spec_engine):
    """literal_jump=True trades exact token equivalence for longer jumps;
    byte-level grammar soundness must survive, and on the schema grammar
    a large fraction of tokens must come from jumps."""
    engine, bundles = spec_engine
    spec = SpecConfig(literal_jump=True)
    states, stats = engine.generate_speculative(
        _reqs("jsonmsg", n=4, max_new=40), spec=spec)
    g, tab, _ = bundles["jsonmsg"]
    for st in states:
        if st.finish_reason == "eos":
            assert IncrementalParser(g, tab).recognize(st.generated)
        else:
            IncrementalParser(g, tab).partial_parse(st.generated)
    assert stats.jump_tokens > 0
    assert stats.jump_fraction > 0.3
    # jumped tokens commit without a per-token decode: fewer device steps
    # than committed tokens
    assert stats.decode_steps < stats.tokens


def test_spec_mixed_pool_grammars_and_unconstrained(spec_engine):
    engine, bundles = spec_engine
    specs = [("json", "greedy"), ("calc", "sample"), (None, "greedy"),
             ("jsonmsg", "sample")]
    reqs = [Request(rid=i, prompt=b"say:", grammar=gname, max_new_tokens=14,
                    decode=DecodeConfig(method=m, temperature=1.0),
                    seed=70 + i)
            for i, (gname, m) in enumerate(specs)]
    states, _ = engine.generate_speculative(reqs)
    assert sorted(s.req.rid for s in states) == list(range(len(specs)))
    for st in states:
        if st.req.grammar is None:
            continue
        g, tab, _ = bundles[st.req.grammar]
        if st.finish_reason == "eos":
            assert IncrementalParser(g, tab).recognize(st.generated)
        else:
            IncrementalParser(g, tab).partial_parse(st.generated)


def test_spec_rejects_recurrent_arch(tokenizer):
    from dataclasses import replace
    from repro.configs import get_config
    from repro.models.model import build_model
    cfg = replace(get_config("syncode-demo"), arch_type="ssm",
                  vocab_size=tokenizer.vocab_size, num_layers=2,
                  d_model=64, d_ff=128)
    model = build_model(cfg)
    assert not model.supports_span_decode
    params = model.init(jax.random.PRNGKey(0))
    g, tab = load_grammar("calc")
    eng = Engine(model, params, tokenizer,
                 {"calc": (g, tab, build_mask_store(g, tokenizer))},
                 max_len=64, slots=2)
    with pytest.raises(ValueError, match="position-addressed"):
        eng.generate_speculative(_reqs("calc", n=1, max_new=4))


# ------------------------ span decode / kernel parity -------------------

def test_decode_span_matches_sequential_decode_steps(tokenizer):
    from dataclasses import replace
    from repro.configs import get_config
    from repro.models.model import build_model
    import jax.numpy as jnp
    cfg = replace(get_config("syncode-demo"), vocab_size=512, num_layers=2,
                  d_model=64, d_ff=128, num_heads=4, num_kv_heads=2,
                  head_dim=16)
    m = build_model(cfg)
    assert m.supports_span_decode
    params = m.init(jax.random.PRNGKey(1))
    B, L, S = 2, 32, 4
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(3, 500, (B, 5)), jnp.int32)
    _, pc = m.prefill(params, {"tokens": prompt}, cache_len=L)
    toks = rng.integers(3, 500, (B, S)).astype(np.int32)
    c_seq = pc
    outs = []
    for i in range(S):
        o, c_seq = m.decode_step(params, c_seq, jnp.asarray(toks[:, i]),
                                 jnp.asarray(np.full(B, 5 + i, np.int32)))
        outs.append(np.asarray(o))
    o_span, c_span = m.decode_span(params, pc, jnp.asarray(toks),
                                   jnp.asarray(np.full(B, 5, np.int32)))
    np.testing.assert_array_equal(np.stack(outs, 1), np.asarray(o_span))
    for a, b in zip(jax.tree.leaves(c_seq), jax.tree.leaves(c_span)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_span_feed_mask_gates_cache_writes(tokenizer):
    from dataclasses import replace
    from repro.configs import get_config
    from repro.models.model import build_model
    import jax.numpy as jnp
    cfg = replace(get_config("syncode-demo"), vocab_size=512, num_layers=1,
                  d_model=64, d_ff=128, num_heads=4, num_kv_heads=2,
                  head_dim=16)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    B, L, S = 2, 16, 4
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(3, 500, (B, 3)), jnp.int32)
    _, pc = m.prefill(params, {"tokens": prompt}, cache_len=L)
    toks = jnp.asarray(rng.integers(3, 500, (B, S)), jnp.int32)
    pos = jnp.asarray(np.full(B, 3, np.int32))
    fm = jnp.asarray(np.array([[True, True, False, False]] * B))
    _, c_masked = m.decode_span(params, pc, toks, pos, feed_mask=fm)
    c_two = pc
    for i in range(2):
        _, c_two = m.decode_step(params, c_two, toks[:, i],
                                 jnp.asarray(np.full(B, 3 + i, np.int32)))
    for a, b in zip(jax.tree.leaves(c_masked), jax.tree.leaves(c_two)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_masked_logits_span_kernel_matches_ref():
    import jax.numpy as jnp
    from repro.kernels.fused_select.ops import fused_mask_select_span
    from repro.kernels.masked_logits.ref import masked_logits_span_ref
    rng = np.random.default_rng(0)
    B, K, V, R, A = 3, 4, 256, 64, 6
    store = jnp.asarray(rng.integers(0, 2 ** 32, (R, V // 32),
                                     dtype=np.uint32))
    rows = jnp.asarray(rng.integers(-1, R, (B, K, A)).astype(np.int32))
    logits = jnp.asarray(rng.normal(size=(B, K, V)).astype(np.float32))
    eos = jnp.asarray(rng.integers(0, 2, (B, K)).astype(bool))
    cd = jnp.asarray(rng.integers(0, 2 ** 32, (B, K, V // 32),
                                  dtype=np.uint32))
    _, out = fused_mask_select_span(logits, store, rows, cd, eos,
                                    jnp.ones((B, K), bool),
                                    backend="pallas")
    ref = masked_logits_span_ref(logits, store, rows, eos, cd=cd)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_apply_grammar_mask_span_constrained_passthrough():
    import jax.numpy as jnp
    from repro.kernels.fused_select.ops import fused_mask_select_span
    rng = np.random.default_rng(1)
    B, K, V, R, A = 2, 3, 128, 16, 4
    store = jnp.asarray(rng.integers(0, 2 ** 32, (R, V // 32),
                                     dtype=np.uint32))
    rows = jnp.asarray(rng.integers(0, R, (B, K, A)).astype(np.int32))
    logits = jnp.asarray(rng.normal(size=(B, K, V)).astype(np.float32))
    eos = jnp.asarray(np.zeros((B, K), bool))
    cons = jnp.asarray(np.array([[True, False, True],
                                 [False, False, True]]))
    _, out = fused_mask_select_span(logits, store, rows, None, eos, cons,
                                    backend="jnp")
    out = np.asarray(out)
    np.testing.assert_array_equal(out[0, 1], np.asarray(logits)[0, 1])
    np.testing.assert_array_equal(out[1, 0], np.asarray(logits)[1, 0])
    assert (out[0, 0] != np.asarray(logits)[0, 0]).any()
