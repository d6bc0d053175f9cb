"""Benchmarks mirroring the paper's tables (scaled to the CPU demo
substrate; trends and invariants, not absolute numbers — DESIGN.md §5).

Table 1 (JSON): syntax errors + generation time, SynCode vs standard.
Table 2 (SQL): validity/"executability" proxy + tokens + time.
Table 3 (GPL): syntax-error reduction on the GPL stand-in (minilang).
Table 5: mask-store creation time/memory vs vocabulary size.
Fig. 10: per-step overhead, incremental parsing vs from scratch.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .common import build_demo, collect_line, emit, timeit


def _run_requests(engine, grammar, n, max_new, constrained=True, seed0=0,
                  temperature=0.9):
    from repro.core.decoding import DecodeConfig
    from repro.serving.engine import Request
    reqs = [
        Request(rid=i, prompt=b"Q: generate. A:",
                grammar=grammar if constrained else None,
                max_new_tokens=max_new,
                decode=DecodeConfig(method="sample",
                                    temperature=temperature),
                seed=seed0 + i)
        for i in range(n)
    ]
    return engine.generate(reqs)


def _error_counts(states, parser, grammar=None, table=None):
    complete = [s for s in states if s.finish_reason == "eos"]
    syntax_errors = sum(
        1 for s in states
        if not parser.recognize(s.generated))
    # like the paper (§6.3): length-truncated outputs count as compiler
    # errors even though SynCode keeps them valid PARTIAL programs —
    # report that invariant separately
    valid_partial = 0
    if grammar is not None:
        from repro.core.parser import IncrementalParser
        for s in states:
            try:
                IncrementalParser(grammar, table).partial_parse(s.generated)
                valid_partial += 1
            except Exception:
                pass
    return syntax_errors, len(complete), valid_partial


def table1_json(n=6, max_new=60):
    from repro.core.parser import IncrementalParser
    engine, bundles, tok = build_demo(("json",))
    g, tab, _ = bundles["json"]
    parser = IncrementalParser(g, tab)

    t0 = time.time()
    sync_states, sync_stats = _run_requests(engine, "json", n, max_new)
    sync_time = time.time() - t0
    t0 = time.time()
    std_states, std_stats = _run_requests(engine, "json", n, max_new,
                                          constrained=False)
    std_time = time.time() - t0

    sync_err, sync_done, _ = _error_counts(sync_states, parser)
    std_err, std_done, _ = _error_counts(std_states, parser)
    sync_complete_valid = sum(
        parser.recognize(s.generated) for s in sync_states
        if s.finish_reason == "eos")
    emit("table1_json_syncode", sync_time / n * 1e6,
         f"syntax_errors={sync_err}/{n};complete={sync_done};"
         f"valid_complete={sync_complete_valid}/{sync_done};"
         f"tok_s={sync_stats.tokens_per_sec:.1f}", stats=sync_stats)
    emit("table1_json_standard", std_time / n * 1e6,
         f"syntax_errors={std_err}/{n};"
         f"tok_s={std_stats.tokens_per_sec:.1f}", stats=std_stats)


def table1_python():
    """Table 1 carried to a real indentation-sensitive language:
    python_mini off/mask/strict with CPython ast.parse as the judge
    (benchmarks/bench_table1.py; masked rows must show 0 errors)."""
    from benchmarks import bench_table1
    if bench_table1.main() != 0:
        raise RuntimeError("bench_table1 reported syntax errors in a "
                           "masked mode")


def table2_sql(n=6, max_new=140):
    from repro.core.parser import IncrementalParser
    engine, bundles, tok = build_demo(("sql",))
    g, tab, _ = bundles["sql"]
    parser = IncrementalParser(g, tab)
    t0 = time.time()
    st, stats = _run_requests(engine, "sql", n, max_new)
    dt = time.time() - t0
    err, done, vp = _error_counts(st, parser, g, tab)
    toks = stats.tokens / max(1, n)
    t0 = time.time()
    st2, stats2 = _run_requests(engine, "sql", n, max_new,
                                constrained=False)
    dt2 = time.time() - t0
    err2, _, vp2 = _error_counts(st2, parser, g, tab)
    emit("table2_sql_syncode", dt / n * 1e6,
         f"syntax_errors={err}/{n};complete={done};"
         f"valid_partial={vp}/{n};avg_tokens={toks:.0f}", stats=stats)
    emit("table2_sql_standard", dt2 / n * 1e6,
         f"syntax_errors={err2}/{n};valid_partial={vp2}/{n}",
         stats=stats2)


def table3_gpl(n=6, max_new=140):
    from repro.core.parser import IncrementalParser
    for gname in ("minilang", "calc"):
        engine, bundles, tok = build_demo((gname,))
        g, tab, _ = bundles[gname]
        parser = IncrementalParser(g, tab)
        st, stats = _run_requests(engine, gname, n, max_new)
        err, done, vp = _error_counts(st, parser, g, tab)
        st2, _ = _run_requests(engine, gname, n, max_new,
                               constrained=False)
        err2, _, vp2 = _error_counts(st2, parser, g, tab)
        red = (1 - err / max(err2, 1)) * 100 if err2 else 100.0
        emit(f"table3_{gname}", stats.wall / max(stats.tokens, 1) * 1e6,
             f"syncode_errors={err}/{n};standard_errors={err2}/{n};"
             f"reduction={red:.0f}%;valid_partial={vp}vs{vp2}",
             stats=stats)


def table5_mask_store():
    from repro.core.grammars import load_grammar
    from repro.core.mask_store import build_mask_store
    from repro.core.tokenizer import ByteTokenizer
    for vocab in (512, 2048, 8192):
        tok = ByteTokenizer(vocab)
        for gname in ("json", "minilang"):
            g, tab = load_grammar(gname)
            t0 = time.time()
            store = build_mask_store(g, tok)
            dt = time.time() - t0
            emit(f"table5_store_{gname}_v{vocab}", dt * 1e6,
                 f"rows={store.num_rows};MB={store.nbytes()/1e6:.2f}")


def fig10_incremental():
    """Per-step parser cost, incremental vs from scratch, growing output."""
    from repro.core.grammars import load_grammar
    from repro.core.parser import IncrementalParser
    from repro.core.sampling import GrammarSampler
    g, tab = load_grammar("minilang")
    gs = GrammarSampler(g, seed=5)
    text = b" ".join(gs.sample_batch(12, budget=16, max_bytes=400))
    for mode, inc in (("incremental", True), ("scratch", False)):
        p = IncrementalParser(g, tab)
        t0 = time.time()
        steps = 0
        i = 8
        while i < min(len(text), 1200):
            p.partial_parse(text[:i], incremental=inc)
            i += 4
            steps += 1
        dt = (time.time() - t0) / steps
        emit(f"fig10_parse_{mode}", dt * 1e6, f"steps={steps}")


def mask_union_micro():
    """The paper's accelerator offload: fused mask gather+union+apply."""
    import jax.numpy as jnp
    from repro.kernels.fused_select.kernel import fused_select
    from repro.kernels.masked_logits.ref import masked_logits_ref
    rng = np.random.default_rng(0)
    B, V, R, A = 8, 2048, 2000, 32
    # jnp.asarray of fresh rng temporaries: nothing mutates the host
    # arrays afterwards, so CPU zero-copy aliasing is harmless here
    store = jnp.asarray(rng.integers(0, 2 ** 32, (R, V // 32),
                                     dtype=np.uint32))
    rows = jnp.asarray(rng.integers(-1, R, (B, A)).astype(np.int32))
    logits = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32))
    eos = jnp.asarray(np.ones(B, bool))
    ref = jax.jit(masked_logits_ref)
    # reprolint: disable=RL003 deliberate timing bracket: this benchmark measures device wall time
    dt = timeit(lambda: jax.block_until_ready(
        ref(logits, store, rows, eos)), n=20)
    emit("mask_union_jnp_ref", dt * 1e6, f"B={B};V={V};A={A}")
    cd = jnp.zeros((B, V // 32), jnp.uint32)
    interpret = jax.default_backend() != "tpu"
    # reprolint: disable=RL003 deliberate timing bracket: this benchmark measures device wall time
    dt2 = timeit(lambda: jax.block_until_ready(
        fused_select(logits, store, rows, cd, eos, eos,
                     interpret=interpret)), n=3)
    if interpret:
        emit("mask_union_pallas_interpret", dt2 * 1e6,
             "interpret-mode (CPU correctness path; TPU is the target)")
    else:
        emit("mask_union_pallas", dt2 * 1e6, f"B={B};V={V};A={A}")


def batched_engine_throughput(n=16, max_new=20):
    """Continuous batching vs the sequential round-robin baseline.

    Same n requests, same grammar, decode pool B in {1, 4, 16}. The
    sequential engine pays one [1, V] decode + one mask call + a host
    sync per request per token; the batched engine pays one [B, V]
    decode + one fused mask call per step for the whole pool, so
    tokens/sec must grow with B (the acceptance bar is B=16 beating
    sequential)."""
    from repro.core.decoding import DecodeConfig
    from repro.serving.engine import Request

    def reqs():
        return [Request(rid=i, prompt=b"Q: generate. A:", grammar="json",
                        max_new_tokens=max_new,
                        decode=DecodeConfig(method="sample",
                                            temperature=0.9),
                        seed=i) for i in range(n)]

    engine, bundles, tok = build_demo(("json",), slots=1)
    _, seq = engine.generate_sequential(reqs())     # warm jit via run 1
    _, seq = engine.generate_sequential(reqs())
    emit("engine_seq", seq.wall / max(seq.tokens, 1) * 1e6,
         f"tok_s={seq.tokens_per_sec:.1f};n={n}", stats=seq)
    for B in (1, 4, 16):
        engine, bundles, tok = build_demo(("json",), slots=B)
        engine.generate(reqs())                     # warm jit
        _, stats = engine.generate(reqs())
        emit(f"engine_batched_b{B}",
             stats.wall / max(stats.tokens, 1) * 1e6,
             f"tok_s={stats.tokens_per_sec:.1f};"
             f"decode_steps={stats.decode_steps};n={n}", stats=stats)


def opportunistic_ablation(n=4, max_new=50):
    for opp in (False, True):
        engine, bundles, tok = build_demo(("json",), opportunistic=opp)
        st, stats = _run_requests(engine, "json", n, max_new)
        emit(f"opportunistic_{'on' if opp else 'off'}",
             stats.wall / max(stats.tokens, 1) * 1e6,
             f"mask_computations={stats.mask_computations};"
             f"hits={stats.opportunistic_hits};tokens={stats.tokens}",
             stats=stats)


def speculative_engine_throughput(n=16, max_new=48):
    """Grammar-aware speculation vs the plain batched engine on JSON
    generation (ISSUE 2 acceptance: >= 1.3x tokens/s over
    engine_batched_b16, with jump-token fraction and draft acceptance
    rate in the CSV).

    Two workloads, both JSON and both through the same B=16 pool:
      * json      — generic RFC-8259 grammar, generations dominated by
                    free-text string/number regions (speculation's hard
                    case; drafts only).
      * jsonmsg   — compact schema-constrained records, where the grammar
                    determines braces/quotes/keys (speculation's home
                    turf; literal jump-forward + drafts).
    Each emits a matched plain-engine baseline row so the speedup is
    apples-to-apples (same grammar, same greedy decode, same requests)."""
    from repro.core.decoding import DecodeConfig
    from repro.serving.engine import Request
    from repro.spec import SpecConfig

    def reqs(gname):
        return [Request(rid=i, prompt=b"Q: generate. A:", grammar=gname,
                        max_new_tokens=max_new,
                        decode=DecodeConfig(method="greedy"), seed=i)
                for i in range(n)]

    for gname, spec in (("json", SpecConfig()),
                        ("jsonmsg", SpecConfig(literal_jump=True))):
        engine, bundles, tok = build_demo((gname,), slots=16)
        engine.generate(reqs(gname))                        # warm jit
        _, base = engine.generate(reqs(gname))
        engine.generate_speculative(reqs(gname), spec=spec)  # warm jit
        _, st = engine.generate_speculative(reqs(gname), spec=spec)
        emit(f"engine_spec_baseline_{gname}_b16",
             base.wall / max(base.tokens, 1) * 1e6,
             f"tok_s={base.tokens_per_sec:.1f};"
             f"decode_steps={base.decode_steps};n={n}", stats=base)
        emit(f"engine_spec_{gname}_b16",
             st.wall / max(st.tokens, 1) * 1e6,
             f"tok_s={st.tokens_per_sec:.1f};"
             f"decode_steps={st.decode_steps};"
             f"jump_frac={st.jump_fraction:.2f};"
             f"accept_rate={st.acceptance_rate:.2f};"
             f"speedup_vs_plain={st.tokens_per_sec / base.tokens_per_sec:.2f}x;"
             f"n={n}", stats=st)


def paged_engine_sharedprefix(n=32, max_new=24):
    """Paged KV engine vs the dense batched engine on a SHARED-PREFIX
    workload at equal KV memory budget (ISSUE 3 acceptance: >= 1.3x
    tokens/sec over the dense b16 engine at equal memory, equivalently
    >= 2x pool size at equal memory, prefix_hit_rate > 0 in the CSV).

    All n requests carry the same long jsonmsg schema prompt — the
    constrained-serving common case (one schema/system prompt, short
    per-request tails). Three rows:

      engine_batched_b16_sharedprefix  dense pool, 16 slots — its
          [16, max_len] caches ARE the memory budget (6400 KV slots);
          prefills and stores the prefix once per request.
      engine_paged_b16_sharedprefix    paged, same 16 slots, same
          budget (400 pages x 16): prefix prefilled/stored once,
          chunked-prefill admission; shows hit rate + pages/request.
      engine_paged_b32_eqmem_sharedprefix  the payoff row: prefix
          sharing means 32 slots fit the SAME 6400-slot budget (peak
          utilization stays well under 1), and doubling the pool width
          at fixed memory is where paging turns into tokens/sec."""
    from repro.core.decoding import DecodeConfig
    from repro.serving.engine import Request

    prompt = (b'sys: emit compact msg records like '
              b'{"type": "x", "seq": 1, "body": "abc"} '
              b'with single-digit seq and short lowercase body. ' * 2
              )[:192]

    def reqs():
        return [Request(rid=i, prompt=prompt, grammar="jsonmsg",
                        max_new_tokens=max_new,
                        decode=DecodeConfig(method="greedy"), seed=i)
                for i in range(n)]

    dense, _, _ = build_demo(("jsonmsg",), slots=16)
    dense.generate(reqs())                          # warm jit
    _, base = dense.generate(reqs())
    emit("engine_batched_b16_sharedprefix",
         base.wall / max(base.tokens, 1) * 1e6,
         f"tok_s={base.tokens_per_sec:.1f};"
         f"decode_steps={base.decode_steps};"
         f"prompt_len={len(prompt)};n={n}", stats=base)

    def kv_cols(st):
        return (f"prefix_hit_rate={st.prefix_hit_rate:.2f};"
                f"kv_pages_in_use={st.kv_pages_in_use};"
                f"kv_peak_utilization={st.kv_peak_utilization:.3f};"
                f"pages_per_req="
                f"{st.kv_page_allocs / max(st.requests, 1):.1f}")

    for slots, name in ((16, "engine_paged_b16_sharedprefix"),
                        (32, "engine_paged_b32_eqmem_sharedprefix")):
        paged, _, _ = build_demo(("jsonmsg",), slots=slots, paged=True,
                                 page_size=16, num_pages=400)
        paged.generate(reqs())                      # warm jit
        _, st = paged.generate(reqs())
        emit(name, st.wall / max(st.tokens, 1) * 1e6,
             f"tok_s={st.tokens_per_sec:.1f};"
             f"decode_steps={st.decode_steps};"
             f"speedup_vs_dense="
             f"{st.tokens_per_sec / base.tokens_per_sec:.2f}x;"
             f"{kv_cols(st)};n={n}", stats=st)


def async_engine_throughput():
    """Async/streaming engine rows: engine_async_b16_{sampled,greedy}_
    overlap_{off,on} + sync twins (benchmarks/bench_async.py) — the
    persistent step loop vs the sync engine, host/device overlap off
    and on, identity asserted per run."""
    from benchmarks import bench_async
    if bench_async.main(smoke=False) != 0:
        raise RuntimeError("bench_async reported identity violation")


def sharded_engine_throughput():
    """Tensor-parallel (vocab-sharded) engine rows: engine_sharded_m1 /
    _m2 / _m4 + an unsharded baseline (docs/sharding.md), each asserting
    token-for-token identity with the baseline. They run in this
    process, on its devices (benchmarks/bench_sharded.py)."""
    from benchmarks import bench_sharded
    if bench_sharded.main(smoke=False) != 0:
        raise RuntimeError("bench_sharded reported identity violation")


def assert_rows_complete(rows) -> None:
    """Every artifact row must carry the full attribution column set and
    a resolvable run identity — the regression observatory refuses to
    persist rows it can't later diff or attribute."""
    from .common import ATTRIBUTION_COLS, run_meta_dict
    meta = run_meta_dict()
    assert meta.get("git_sha"), "run_meta missing git_sha"
    assert meta.get("jax_version"), "run_meta missing jax_version"
    for row in rows:
        missing = [c for c in ATTRIBUTION_COLS
                   if c not in row.get("attribution", {})]
        assert not missing, \
            f"row {row.get('name')!r} missing attribution cols {missing}"
        assert "name" in row and "us_per_call" in row, f"malformed row {row}"


ALL = [table1_json, table1_python, table2_sql, table3_gpl,
       table5_mask_store,
       fig10_incremental, mask_union_micro, opportunistic_ablation,
       batched_engine_throughput, speculative_engine_throughput,
       paged_engine_sharedprefix, async_engine_throughput,
       sharded_engine_throughput]
