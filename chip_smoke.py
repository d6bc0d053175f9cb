"""Chip smoke test: serve qwen1.5-0.5b on one TPU through the engine.

    python chip_smoke.py              # one chip, phases (a)-(e)
    python chip_smoke.py --mesh 4     # four chips, the vocab-parallel mesh

It builds the server the way a user does (`repro.launch.serve.
build_engine`) at the model's published width, with random weights made
from `--seed`, 8 decode slots and a 512-token context, and checks what
comes out by the repo's own means:

  (a) dense: greedy and sampled JSON, 8 requests x 32 new tokens, each
      request with its own prompt. Every output parses as a prefix of
      the grammar's language, every output that ended with EOS parses
      whole, and the greedy outputs are not all equal.
  (b) kernel: at served steps of (a) the engine's compiled fused
      mask+select, on the bf16 logits it was served, returned the same
      masked logits and ids as the jnp reference on the same inputs.
  (c) paged: the paged engine (compiled attention kernel) against dense
      greedy, teacher-forced: fed dense greedy's tokens, its logits stay
      within LOGIT_TOL of dense's at every step, and where its own pick
      differs, dense's gap between the two picks is within what that
      bound allows. Its free-running outputs parse, and how far they
      agree with dense greedy is printed: on a TPU the two modes are
      separate XLA programs that round differently (PERF.md).
  (d) speculative: speculative greedy on `jsonmsg` against plain greedy,
      by the bounded teacher-forced comparison of (c) at every token the
      speculative loop picks with the model; its free-running outputs
      parse, and their agreement with plain greedy is printed.
  (e) async: requests submitted through the AsyncEngine all finish.

`--mesh 4` runs only the vocab-parallel engine on a 4-device serving
mesh and the single-device engine it is compared with, by the bounded
teacher-forced comparison of (c), and checks that the embedding,
lm_head and packed mask store are split over 4 distinct devices. That
single-device engine is a 1-device mesh: under any mesh the engine takes
the jnp references of the kernels (docs/sharding.md), so this is the
comparison that isolates vocab parallelism.

Each phase prints the jitted programs it compiled with the Pallas
kernels each contains, compile seconds, decode steps, tokens/s on the
host clock (a smoke figure, not a benchmark) and the device's peak
memory. The last line is one JSON object; it is printed only when every
phase passed. Without a TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import re
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
SLOTS = 8
MAX_LEN = 512
NEW_TOKENS = 32
# Teacher-forced logit bound: 8 bf16 ulps of the largest logits (|l| in
# [4, 8), ulp 1/32). On a v5e, dense vs paged qwen1.5-0.5b differ by at
# most 0.1016 over 256 steps; an off-by-one causal frontier in paged
# attention moves them by 2.34 at the median step and a wrong page read
# by 4.97 (PERF.md).
LOGIT_TOL = 0.25


class Phases:
    """Runs named phases, keeps going after a failure, and attributes
    compile time and compiled programs (from the IR dump) to each."""

    def __init__(self, dump_dir: str):
        import jax
        self.jax = jax
        self.dump_dir = dump_dir
        self.compile_s = 0.0
        self.seen: set[str] = set()
        self.failed: list[str] = []

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def programs(self) -> dict[str, set[str]]:
        """Jitted programs lowered since the last call -> the Pallas
        kernels (tpu_custom_call kernel_name) each contains."""
        out = {}
        for path in sorted(glob.glob(os.path.join(self.dump_dir, "*"))):
            if path in self.seen:
                continue
            self.seen.add(path)
            m = re.match(r"jax_ir\d+_(.+?)(_compile)?\.mlir$",
                         os.path.basename(path))
            if not m:
                continue
            with open(path) as f:
                text = f.read()
            kernels = set(re.findall(r'kernel_name = "(\w+)"', text))
            out.setdefault(m.group(1), set()).update(kernels)
        return out

    def run(self, name: str, fn):
        print(f"== phase {name}", flush=True)
        c0, t0 = self.compile_s, time.perf_counter()
        try:
            info = fn() or {}
        except Exception:
            traceback.print_exc()
            print(f"   FAILED: {name}", flush=True)
            self.failed.append(name)
            self.programs()
            return None
        progs = self.programs()
        missing = [(want, kernel) for want, kernel
                   in info.pop("needs_kernel", {}).items()
                   if not any(kernel in ks for p, ks in progs.items()
                              if want in p)]
        for want, kernel in missing:
            print(f"   FAILED: no compiled '{want}' program holds the "
                  f"'{kernel}' kernel", flush=True)
        if missing:
            self.failed.append(name)
        backends = ", ".join(
            f"{p}={'+'.join(sorted(ks)) if ks else 'xla'}"
            for p, ks in sorted(progs.items()))
        stats = self.jax.devices()[0].memory_stats() or {}
        print(f"   programs compiled: {backends or 'none (cached)'}")
        print(f"   compile s: {self.compile_s - c0:.2f}   "
              f"phase wall s: {time.perf_counter() - t0:.2f}   "
              f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
        for k, v in info.items():
            print(f"   {k}: {v}")
        if not missing:
            print(f"   ok: {name}", flush=True)
        return info


def check(ok, message) -> None:
    """A smoke check that stays under `python -O`."""
    if not ok:
        raise RuntimeError(message)


def prompt(rid: int) -> bytes:
    """Distinct prompts of distinct lengths (32 to 95 bytes over three
    prefill buckets), so the slots hold different states."""
    return b"Q: request %d, " % rid + b"in JSON, " * rid + \
        b"produce output. A:"


def requests(grammar: str, method: str, n: int = SLOTS):
    from repro.core.decoding import DecodeConfig
    from repro.serving.engine import Request
    return [Request(rid=i, prompt=prompt(i), grammar=grammar,
                    max_new_tokens=NEW_TOKENS, seed=i,
                    decode=DecodeConfig(method=method, temperature=0.8))
            for i in range(n)]


def distinct(states, what: str) -> None:
    """A comparison of runs whose outputs are all equal checks one
    sequence, not the slots."""
    outs = {tuple(s.token_ids[s.prompt_len:]) for s in states}
    check(len(outs) > 1, f"{what}: all {len(states)} outputs are equal")


def check_language(engine, grammar: str, states) -> str:
    """Every output is in the grammar's prefix language; every output
    that ended with EOS is in the language itself."""
    from repro.core.parser import IncrementalParser
    g, tab, _ = engine.bundles[grammar]
    done = [s for s in states if s.finish_reason == "eos"]
    for s in states:
        IncrementalParser(g, tab).partial_parse(s.generated,
                                                incremental=False)
        check(s.finish_reason, f"request {s.req.rid} did not finish")
    for s in done:
        check(IncrementalParser(g, tab).recognize(s.generated),
              f"request {s.req.rid} ended with EOS outside the language")
    return f"{len(states)} in the prefix language, {len(done)}/{len(done)}" \
           f" complete outputs parse"


def run_stats(stats) -> str:
    return (f"{stats.tokens} tokens, {stats.decode_steps} decode steps, "
            f"{stats.tokens_per_sec:.1f} tok/s (host clock, smoke figure, "
            f"not a benchmark)")


def same_tokens(a, b, what: str) -> str:
    check(len(a) == len(b), f"{what}: {len(a)} vs {len(b)} requests")
    distinct(a, what)
    for x, y in zip(a, b):
        check(x.token_ids == y.token_ids,
              f"{what}: request {x.req.rid} differs:\n{x.generated!r}\n"
              f"{y.generated!r}")
    return f"{len(a)} distinct requests token-for-token identical"


def agreement(a, b) -> str:
    """How far each pair of runs agrees, in new tokens."""
    same, apart = 0, []
    for x, y in zip(a, b):
        if x.token_ids == y.token_ids:
            same += 1
            continue
        n = 0
        while x.token_ids[n] == y.token_ids[n]:
            n += 1
        apart.append(n - x.prompt_len)
    return f"{same} of {len(a)} requests identical" + (
        f"; the others part at new token {sorted(apart)}" if apart else "")


class Recorder:
    """Keeps, for every token a greedy run selects with the model, that
    step's logits row of its request, keyed by (rid, index of the new
    token), beside the token. With `forced` (such a key -> token) the run
    commits the forced token in place of its own pick (teacher forcing),
    and the row keeps its own pick. Hooks the step loops' selection:
    per-step for the dense and paged modes, per span position for the
    speculative mode (whose jump-forward tokens need no model pick)."""

    def __init__(self, engine, forced=None):
        import numpy as np
        self.engine = engine
        self.rows: dict = {}
        forced = forced or {}
        last = {}

        def key(st):
            return st.req.rid, len(st.token_ids) - st.prompt_len

        def stash(name):
            fn = getattr(engine, name)

            def call(logits, *args, **kwargs):
                last["logits"] = logits
                return fn(logits, *args, **kwargs)
            return call

        resolve = engine._select_resolve
        span_resolve = engine._resolve_span_selection

        def rec_resolve(ctx, slot_state, *args, **kwargs):
            committed, ctr = resolve(ctx, slot_state, *args, **kwargs)
            rows = np.asarray(last["logits"]).astype(np.float32)
            for b, own in list(committed.items()):
                k = key(slot_state[b])
                self.rows[k] = (rows[b], own)
                if forced.get(k, own) != own:
                    committed[b] = forced[k]
                    ctx.clean = False       # no speculative forward
            return committed, ctr

        def rec_span_resolve(st, masked, b, idx, *args):
            own = span_resolve(st, masked, b, idx, *args)
            if own is not None:
                k = key(st)
                self.rows[k] = (np.asarray(last["logits"][b, idx])
                                .astype(np.float32), own)
                own = forced.get(k, own)
            return own

        hooks = {"_select_dispatch": stash("_select_dispatch"),
                 "_select_resolve": rec_resolve,
                 "_span_mask_select": stash("_span_mask_select"),
                 "_resolve_span_selection": rec_span_resolve}
        # instance attributes (the jitted fns) to restore; None = method
        self.saved = {name: vars(engine).get(name) for name in hooks}
        for name, fn in hooks.items():
            setattr(engine, name, fn)

    def detach(self) -> dict:
        for name, fn in self.saved.items():
            if fn is None:
                delattr(self.engine, name)
            else:
                setattr(self.engine, name, fn)
        return self.rows

    @staticmethod
    def forced_by(rows) -> dict:
        return {key: token for key, (_, token) in rows.items()}


def bounded(ref: dict, got: dict, tol: float) -> str:
    """Teacher-forced agreement of two runs (Recorder rows; `got` was
    forced onto `ref`'s tokens and picks at some or all of its steps):
    at every step `got` picked, the logits differ by at most `tol`, and
    where `got`'s own pick differs, `ref`'s gap between the two picks is
    within the 2 * tol that bound allows, a near-tie. A cache, page or
    position fault moves the logits far past `tol`."""
    import numpy as np
    outside = sorted(got.keys() - ref.keys())
    check(got and not outside, f"steps outside the reference: {outside[:8]}")
    worst, flips = 0.0, []
    for key in sorted(got):
        (lr, token), (lg, own) = ref[key], got[key]
        d = float(np.max(np.abs(lg - lr)))
        worst = max(worst, d)
        check(d <= tol, f"request {key[0]} new token {key[1]}: logits "
                        f"differ by {d} > {tol}")
        if own != token:
            gap = float(lr[token] - lr[own])
            check(gap <= 2 * tol, f"request {key[0]} new token {key[1]}: "
                                  f"pick {own} != {token}, gap {gap}")
            flips.append(f"r{key[0]}@{key[1]} gap {gap:.6g}")
    return (f"{len(got)} of {len(ref)} steps compared, max |logit "
            f"difference| {worst:.6g} (bound {tol}); own picks that "
            f"differ: {len(flips)} {flips[:8]}")


def record_calls(engine, name: str, every: int, calls: list):
    """Wraps the engine's jitted `name` to keep the inputs and outputs of
    every `every`-th call; returns the undo."""
    fn, seen = getattr(engine, name), [0]

    def rec(*args):
        out = fn(*args)
        if seen[0] % every == 0:
            calls.append((name, args, out))
        seen[0] += 1
        return out
    setattr(engine, name, rec)
    return lambda: setattr(engine, name, fn)


def single_chip(ph: Phases, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.fused_select.ref import fused_select_ref
    from repro.launch.serve import build_engine
    from repro.serving.async_engine import AsyncEngine
    from repro.serving.engine import Engine
    from repro.spec import SpecConfig

    built = {}

    def dense():
        t0 = time.perf_counter()
        engine, _, _ = build_engine(ARCH, grammars=("json", "jsonmsg"),
                                    slots=SLOTS, max_len=MAX_LEN, seed=seed)
        built["engine"] = engine
        setup = time.perf_counter() - t0
        calls = built["calls"] = []
        undo = [record_calls(engine, name, 4, calls)
                for name in ("_fused_greedy", "_fused_sample")]
        rec = Recorder(engine)
        greedy, gstats = engine.generate(requests("json", "greedy"))
        built["greedy"], built["rows"] = greedy, rec.detach()
        distinct(greedy, "dense greedy")
        sampled, sstats = engine.generate(requests("json", "sample"))
        for u in undo:
            u()
        return {"setup s (weights + mask stores + compile)": f"{setup:.2f}",
                "greedy": run_stats(gstats),
                "greedy outputs": check_language(engine, "json", greedy),
                "sampled": run_stats(sstats),
                "sampled outputs": check_language(engine, "json", sampled),
                "needs_kernel": {"fused_greedy": "fused_select",
                                 "fused_sample": "fused_select"}}

    def kernel():
        ref = jax.jit(fused_select_ref)
        n, ties, dtypes = 0, 0, set()
        for name, args, (masked, ids, _) in built["calls"]:
            if name == "_fused_greedy":
                ref_ids, ref_masked = ref(*args, None, None, None, None)
            else:
                ref_ids, ref_masked = ref(*args[:-1], noise=args[-1])
            check(jnp.array_equal(masked, ref_masked),
                  f"{name} call {n}: masked logits differ")
            check(jnp.array_equal(ids, ref_ids),
                  f"{name} call {n}: ids {ids} != {ref_ids}")
            m = np.asarray(masked).astype(np.float32)
            ties += int(((m == m.max(-1, keepdims=True)).sum(-1) > 1).sum())
            dtypes.add(str(args[0].dtype))
            n += 1
        check(n and dtypes == {"bfloat16"}, f"{n} calls, logits {dtypes}")
        return {"engine's fused select vs the jnp reference":
                f"{n} served steps of (a) (greedy and sampled, bf16 "
                f"logits): ids and masked logits equal; {ties} of "
                f"{n * SLOTS} rows had a tied masked maximum"}

    def paged():
        engine = built["engine"]
        eng = Engine(engine.model, engine.params, engine.tok,
                     engine.bundles, max_len=MAX_LEN, slots=SLOTS,
                     paged=True)
        states, stats = eng.generate(requests("json", "greedy"))
        rec = Recorder(eng, forced=Recorder.forced_by(built["rows"]))
        forced, _ = eng.generate(requests("json", "greedy"))
        rows = rec.detach()
        same_tokens(built["greedy"], forced, "teacher forcing")
        check(rows.keys() == built["rows"].keys(), "paged skipped steps")
        return {"paged greedy": run_stats(stats),
                "outputs": check_language(engine, "json", states),
                "vs dense greedy, free-running": agreement(built["greedy"],
                                                           states),
                "vs dense greedy, teacher-forced": bounded(
                    built["rows"], rows, LOGIT_TOL),
                "needs_kernel": {"paged": "paged_attention"}}

    def speculative():
        engine = built["engine"]
        rec = Recorder(engine)
        plain, _ = engine.generate(requests("jsonmsg", "greedy"))
        plain_rows = rec.detach()
        spec, stats = engine.generate_speculative(
            requests("jsonmsg", "greedy"), spec=SpecConfig())
        # drafts are accepted on the model's own picks, which forcing
        # does not reach: the forced run speculates by jump-forward only
        rec = Recorder(engine, forced=Recorder.forced_by(plain_rows))
        forced, _ = engine.generate_speculative(
            requests("jsonmsg", "greedy"), spec=SpecConfig(draft=False))
        rows = rec.detach()
        same_tokens(plain, forced, "teacher forcing")
        return {"speculative greedy": run_stats(stats),
                "jumped / drafted tokens accepted":
                f"{stats.jump_tokens} / {stats.draft_accepted} of "
                f"{stats.draft_proposed}",
                "outputs": check_language(engine, "jsonmsg", spec),
                "vs plain greedy, free-running": agreement(plain, spec),
                "vs plain greedy, teacher-forced (jump-forward)": bounded(
                    plain_rows, rows, LOGIT_TOL)}

    def async_engine():
        engine = built["engine"]

        async def go():
            aeng = AsyncEngine(engine)
            try:
                return await aeng.generate(requests("json", "sample", 4))
            finally:
                await aeng.drain()
        states, stats = asyncio.run(go())
        return {"async": run_stats(stats),
                "outputs": check_language(engine, "json", states)}

    ph.run("a dense", dense)
    if "engine" not in built:
        return
    ph.run("b kernel check", kernel)
    ph.run("c paged", paged)
    ph.run("d speculative", speculative)
    ph.run("e async", async_engine)


def mesh(ph: Phases, seed: int, size: int) -> None:
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import build_engine
    from repro.serving.engine import Engine

    def vocab_parallel():
        built, _, _ = build_engine(ARCH, grammars=("json",), slots=SLOTS,
                                   max_len=MAX_LEN, seed=seed)
        base, sharded = (
            Engine(built.model, built.params, built.tok, built.bundles,
                   max_len=MAX_LEN, slots=SLOTS, mesh=make_serving_mesh(n))
            for n in (1, size))
        emb = sharded.params["embed_block"]
        placed = {"embed": emb["embed"], "packed store": sharded._store_cat}
        if "lm_head" in emb:
            placed["lm_head"] = emb["lm_head"]
        spread = {}
        for name, arr in placed.items():
            devs = {s.device.id for s in arr.addressable_shards}
            shapes = {s.data.shape for s in arr.addressable_shards}
            check(len(devs) == size and shapes != {arr.shape},
                  f"{name} is not split over {size} devices: {devs} {shapes}")
            spread[name] = f"devices {sorted(devs)}, shard {shapes.pop()}" \
                           f" of {arr.shape}"
        rec = Recorder(base)
        want, bstats = base.generate(requests("json", "greedy"))
        want_rows = rec.detach()
        distinct(want, "1-device mesh greedy")
        got, stats = sharded.generate(requests("json", "greedy"))
        check(stats.mesh_devices == size, f"{stats.mesh_devices} devices")
        rec = Recorder(sharded, forced=Recorder.forced_by(want_rows))
        forced, _ = sharded.generate(requests("json", "greedy"))
        rows = rec.detach()
        same_tokens(want, forced, "teacher forcing")
        check(rows.keys() == want_rows.keys(), "the mesh skipped steps")
        out = {"1-device mesh": run_stats(bstats),
               f"{size}-device mesh": run_stats(stats),
               "outputs": check_language(base, "json", got),
               "vs 1-device mesh, free-running": agreement(want, got),
               "vs 1-device mesh, teacher-forced": bounded(want_rows, rows,
                                                           LOGIT_TOL)}
        out.update({f"{k} shards": v for k, v in spread.items()})
        return out

    ph.run(f"vocab-parallel mesh {size}", vocab_parallel)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", type=int, default=None,
                    help="run only the vocab-parallel engine on a mesh of "
                         "this many devices, against one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform} devices", file=sys.stderr)
        return 2
    if args.mesh and len(devices) < args.mesh:
        print(f"chip_smoke: --mesh {args.mesh} needs {args.mesh} devices; "
              f"{len(devices)} found", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(f"device: {devices[0].device_kind} x {len(devices)}, "
          f"jax {jax.__version__}", flush=True)

    with tempfile.TemporaryDirectory() as dump_dir:
        jax.config.update("jax_dump_ir_to", dump_dir)
        ph = Phases(dump_dir)
        if args.mesh:
            mesh(ph, args.seed, args.mesh)
        else:
            single_chip(ph, args.seed)
        jax.config.update("jax_dump_ir_to", "")
    if ph.failed:
        print(f"chip_smoke: failed phases: {', '.join(ph.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
