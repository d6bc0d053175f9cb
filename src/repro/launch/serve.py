"""Serving launcher: grammar-constrained generation with the Engine.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --grammar json -n 4 \
      --max-new 80 --temperature 0.8 --slots 4 \
      [--grammar-mode grammar_mask|grammar_strict] \
      [--sequential] [--opportunistic] [--checkpoint ckpt] \
      [--speculative] [--literal-jump] [--draft-k K] [--max-jump J]

`--slots B` sets the width of the continuous-batching decode pool (one
[B, V] decode + one fused mask call per step); `--sequential` uses the
round-robin one-request-per-device-call baseline instead.

`--speculative` enables grammar-aware speculative decoding (jump-forward
forced continuations + draft-verify spans; see docs/speculation.md);
`--literal-jump` additionally jumps grammar-forced byte literals,
re-tokenized canonically (longer jumps, byte-identical grammar
guarantees, token stream may differ from the plain engine's).

`--serve` starts the persistent streaming HTTP endpoint instead of a
batch run (docs/serving.md): one background step loop with live
admission, per-token NDJSON streaming, cancellation on disconnect and
per-request deadlines:

  python -m repro.launch.serve --serve --port 8400 --grammar json
  curl -N -d '{"prompt": "say:", "grammar": "json"}' \
      http://127.0.0.1:8400/generate

`--no-overlap` disables the host/device overlap in the dense decode
loop (on by default; serving/loop.py).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.core.decoding import DecodeConfig
from repro.core.grammars import BUILTIN, load_grammar
from repro.core.mask_store import build_mask_store
from repro.core.parser import IncrementalParser
from repro.core.tokenizer import ByteTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.serving.engine import Engine, Request


def build_engine(arch="syncode-demo", grammars=BUILTIN, vocab=None,
                 max_len=512, opportunistic=False, checkpoint=None,
                 seed=0, slots=4, paged=False, page_size=16,
                 num_pages=None, prefill_chunk=32, mesh=None,
                 trunk_shard=False, overlap=True,
                 grammar_mode="grammar_mask", telemetry=True,
                 devtime=False):
    """mesh: None | int (model-parallel degree; 1 = single device) | a
    prebuilt jax Mesh with a "model" axis. See docs/sharding.md."""
    cfg = get_config(arch)
    if vocab:
        from dataclasses import replace
        cfg = replace(cfg, vocab_size=vocab)
    tok = ByteTokenizer(cfg.vocab_size)
    bundles = {}
    for name in grammars:
        g, tab = load_grammar(name)
        bundles[name] = (g, tab, build_mask_store(g, tok))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    if checkpoint:
        from repro.training.checkpoint import load_checkpoint
        params, step, _ = load_checkpoint(checkpoint, params)
        print(f"loaded checkpoint at step {step}")
    if isinstance(mesh, int):
        # mesh=1 builds a real single-device mesh (exercises the whole
        # sharded code path; benchmarks use it to price the machinery)
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(mesh)
    return Engine(model, params, tok, bundles, max_len=max_len,
                  opportunistic=opportunistic, slots=slots, paged=paged,
                  page_size=page_size, num_pages=num_pages,
                  prefill_chunk=prefill_chunk, mesh=mesh,
                  trunk_shard=trunk_shard, overlap=overlap,
                  grammar_mode=grammar_mode, telemetry=telemetry,
                  devtime=devtime), bundles, tok


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="syncode-demo")
    ap.add_argument("--grammar", default="json", choices=list(BUILTIN))
    ap.add_argument("--grammar-mode", default="grammar_mask",
                    choices=("grammar_mask", "grammar_strict"),
                    help="mask approximation family (docs/grammars.md): "
                         "grammar_mask over-approximates (never bans a "
                         "valid token); grammar_strict under-approximates "
                         "(only tokens ending exactly on terminal "
                         "boundaries or inside one terminal)")
    ap.add_argument("-n", "--num-requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=80)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--opportunistic", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--prompt", default="Q: produce output. A:")
    ap.add_argument("-B", "--slots", type=int, default=4,
                    help="continuous-batching decode pool width")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: page-table attention, "
                         "refcounted prefix sharing, chunked prefill "
                         "(docs/kv_paging.md)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV pool size in pages (default: the dense "
                         "engine's memory budget, slots*max_len/page)")
    ap.add_argument("--mesh", type=int, default=None,
                    help="tensor-parallel mesh size: shard embed/lm_head,"
                         " logits, the packed mask store and the mask/"
                         "sample hot path across N devices (vocab "
                         "parallelism, token-for-token identical to "
                         "single-device; docs/sharding.md). CPU runs "
                         "need XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--trunk-shard", action="store_true",
                    help="with --mesh: additionally shard the model "
                         "trunk megatron-style (memory relief at TPU "
                         "scale; gives up bit-exact equivalence)")
    ap.add_argument("--sequential", action="store_true",
                    help="round-robin baseline (one request per call)")
    ap.add_argument("--speculative", action="store_true",
                    help="grammar-aware speculative decoding "
                         "(jump-forward + draft-verify)")
    ap.add_argument("--literal-jump", action="store_true",
                    help="jump grammar-forced byte literals, canonically "
                         "re-tokenized (longer jumps)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens per slot per speculative step")
    ap.add_argument("--max-jump", type=int, default=16,
                    help="max forced tokens committed per jump")
    ap.add_argument("--proposer", default="sam", choices=("sam", "ngram"),
                    help="draft proposer (suffix automaton | n-gram)")
    ap.add_argument("--serve", action="store_true",
                    help="start the persistent streaming HTTP endpoint "
                         "(POST /generate NDJSON stream, GET /healthz; "
                         "docs/serving.md) instead of a batch run")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8400)
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable host/device overlap in the dense "
                         "decode loop (serving/loop.py)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the observability layer (phase spans, "
                         "latency histograms, trace capture; "
                         "docs/observability.md) — count stats stay "
                         "exact, timing stats read 0")
    ap.add_argument("--devtime", action="store_true",
                    help="bench mode: device-span brackets sync "
                         "on exit so stats carry true device intervals "
                         "(adds per-step syncs — not for serving; "
                         "docs/observability.md)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    engine, bundles, tok = build_engine(
        args.arch, grammars=(args.grammar,),
        opportunistic=args.opportunistic, checkpoint=args.checkpoint,
        slots=args.slots, paged=args.paged, page_size=args.page_size,
        num_pages=args.num_pages, mesh=args.mesh,
        trunk_shard=args.trunk_shard, overlap=not args.no_overlap,
        grammar_mode=args.grammar_mode, telemetry=not args.no_telemetry,
        devtime=args.devtime)

    if args.serve:
        import asyncio

        from repro.serving.async_engine import AsyncEngine
        from repro.serving.server import run_server
        spec = None
        if args.speculative:
            from repro.spec import SpecConfig
            spec = SpecConfig(literal_jump=args.literal_jump,
                              draft_k=args.draft_k, max_jump=args.max_jump,
                              proposer=args.proposer)
        aeng = AsyncEngine(engine, spec=spec, verbose=True)
        try:
            asyncio.run(run_server(aeng, host=args.host, port=args.port))
        except KeyboardInterrupt:
            pass
        return

    dc = DecodeConfig(method="greedy" if args.greedy else "sample",
                      temperature=args.temperature)
    reqs = [Request(rid=i, prompt=args.prompt.encode(),
                    grammar=args.grammar, max_new_tokens=args.max_new,
                    decode=dc, seed=i) for i in range(args.num_requests)]
    if args.speculative:
        from repro.spec import SpecConfig
        spec = SpecConfig(literal_jump=args.literal_jump,
                          draft_k=args.draft_k, max_jump=args.max_jump,
                          proposer=args.proposer)
        states, stats = engine.generate_speculative(reqs, spec=spec,
                                                    verbose=True)
    else:
        run = (engine.generate_sequential if args.sequential
               else engine.generate)
        states, stats = run(reqs, verbose=True)

    g, tab, _ = bundles[args.grammar]
    p = IncrementalParser(g, tab)
    complete = [s for s in states if s.finish_reason == "eos"]
    valid = sum(p.recognize(s.generated) for s in complete)
    print(f"\n{stats.tokens} tokens @ {stats.tokens_per_sec:.1f} tok/s "
          f"({stats.decode_steps} decode steps x {stats.batch_slots} slots)"
          f" | mask {stats.mask_time:.2f}s/{stats.mask_computations} | "
          f"opportunistic hits {stats.opportunistic_hits}")
    if stats.mesh_devices > 1:
        print(f"tensor-parallel: {stats.mesh_devices}-device mesh "
              f"(vocab-sharded mask path"
              f"{', trunk sharded' if args.trunk_shard else ''})")
    if args.speculative:
        print(f"speculation: jump {stats.jump_tokens} tokens "
              f"({stats.jump_fraction:.0%} of output), drafts "
              f"{stats.draft_accepted}/{stats.draft_proposed} accepted "
              f"({stats.acceptance_rate:.0%}), plan {stats.plan_time:.2f}s")
    if args.paged:
        print(f"kv paging: {stats.kv_pages_in_use} pages in use, peak "
              f"util {stats.kv_peak_utilization:.0%}, prefix hit rate "
              f"{stats.prefix_hit_rate:.0%}, {stats.kv_evictions} "
              f"evictions, {stats.kv_cow_copies} COW copies")
    print(f"complete: {len(complete)}/{len(states)}, "
          f"valid among complete: {valid}/{len(complete)}")
    if valid != len(complete):
        raise SystemExit("a completed output does not parse")


if __name__ == "__main__":
    main()
