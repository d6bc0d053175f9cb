"""One run of one cell: set-up, the measured window, the comparison.

The cell names a configuration (configs/<config>.json) and a traffic
mix (traffic/<mix>.json). The run builds the program's engine from the
configuration the way `launch/serve.py::build_engine` does (its
`build_model`, `ByteTokenizer`, grammar bundles and `Engine`), with
weights made here from the seed, serves through
`serving/async_engine.py::AsyncEngine.submit` into the persistent dense
step loop, and times everything from the client side.

Order of a run:
  set-up    weights, mask stores (cached in the checkout), engine, and a
            warm-up through the same front end that compiles every
            shape the mix uses (its prefill buckets, the decode step,
            the greedy and sampling selects, the resample)
  window    `--seconds` of the mix's traffic; a traced run (--trace 1)
            records the profiler over the window's last seconds
  drain     an open mix waits (at most a minute) for every request due
            in the window to show its first token; then all is aborted
  check     device peak memory is read, the engine is dropped, and the
            served tokens of a seeded sample of finished greedy
            requests are compared with the float32 reference
"""
from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import check, counts, loadgen, peaks, xplane
from .vocab import Vocab

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout
CACHE = HERE / ".cache"
TRACE_SECONDS = 4.0
DRAIN_SECONDS = 60.0
CLOCK_MARK = "bench_clock_mark"


def profile_options():
    """Device ops and the host's own annotations; no Python call tracing
    (it would slow the host work the trace is meant to see) and no HLO
    protos (size)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------- the spec --

def load_spec(workload: str, root: Path = ROOT) -> dict:
    """BENCHMARK.json's entries for one cell, with its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": json.loads((root / cfg["file"]).read_text()),
        "mix": json.loads((HERE / "traffic" /
                           f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def reader(name: str):
    """metrics/<name>.py's `read(run)`."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def prefill_buckets(mix: dict) -> list:
    """The engine prefills a prompt of n tokens as n - 1 tokens padded to
    a power of two, capped at max_len: the buckets this mix reaches."""
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    out, b = [], 1
    while b < hi - 1:
        b *= 2
        if b >= lo - 1:
            out.append(min(b, mix["max_len"]))
    return sorted(set(out))


# ------------------------------------------------------------ the engine --

class Served:
    """The program under test, built from a configuration and a mix."""

    def __init__(self, config: dict, mix: dict, seed: int):
        import jax
        from repro.core.grammars import load_grammar
        from repro.core.mask_store import build_mask_store
        from repro.core.tokenizer import ByteTokenizer
        from repro.models.config import ModelConfig
        from repro.models.model import build_model
        from repro.serving.async_engine import AsyncEngine
        from repro.serving.engine import Engine
        from . import weights

        cfg = ModelConfig(**config["serve"])
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.make_weights = weights.maker(shapes, cfg.d_model)
        self.words = weights.seed_words(seed)
        params = self.make_weights(self.words)
        tok = ByteTokenizer(cfg.vocab_size)
        self.vocab = Vocab(cfg.vocab_size)
        if tok.id_to_bytes != self.vocab.token_bytes:
            raise RuntimeError("the program's vocabulary differs from the "
                               "one the benchmark rebuilds")
        bundles = {}
        if mix["grammar"]:
            g, tab = load_grammar(mix["grammar"])
            bundles[mix["grammar"]] = (g, tab, build_mask_store(
                g, tok, cache_dir=str(CACHE / "masks")))
        self.engine = Engine(model, params, tok, bundles,
                             max_len=mix["max_len"], slots=mix["slots"])
        self.aeng = AsyncEngine(self.engine)
        # reprolint: disable=RL003 set-up ends with the weights on the chip
        jax.block_until_ready(params)


# -------------------------------------------------------------- the run --

class Run:
    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 t_process: float):
        self.spec, self.seed, self.seconds = spec, int(seed), float(seconds)
        self.trace, self.t_process = bool(trace), t_process
        self.config, self.mix = spec["config"], spec["mix"]
        self.compiles = {"trace": 0, "backend": 0}
        self.reqs: list = []
        self.handles: dict = {}
        self.tasks: list = []

    # ---- compile counting (the chip_smoke.py listener idea) ----------

    def _listen(self) -> None:
        import jax

        def on_event(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles["backend"] += 1
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.compiles["trace"] += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)

    # ---- what else stops the host in the window ----------------------

    def _watch_gc(self) -> None:
        """Every garbage collection's pause, by generation, on the
        client's clock (printed with the samples, not a metric)."""
        self.gc_pauses: list = []
        started = {}

        def on_gc(phase, info):
            if phase == "start":
                started["t"] = time.perf_counter()
            elif "t" in started:
                t = started.pop("t")
                self.gc_pauses.append((t, info["generation"],
                                       (time.perf_counter() - t) * 1e3))
        gc.callbacks.append(on_gc)
        self._gc_cb = on_gc

    async def _watch_queue(self, src, until: float) -> None:
        """The front end's waiting queue, sampled every 20 ms."""
        self.depth: list = []
        while time.perf_counter() < until:
            self.depth.append(len(src))
            await asyncio.sleep(0.02)

    # ---- requests through the front end -------------------------------

    def _request(self, r, rid: int):
        from repro.core.decoding import DecodeConfig
        from repro.serving.engine import Request
        return Request(
            rid=rid, prompt=r.prompt, grammar=self.mix["grammar"],
            max_new_tokens=r.max_new,
            decode=DecodeConfig(method="greedy" if r.greedy else "sample",
                                temperature=self.mix["temperature"]),
            seed=(self.seed * 1000003 + rid) & 0xFFFFFFFF)

    def _submit(self, r, rid=None) -> None:
        try:
            h = self.served.aeng.submit(self._request(r, r.i if rid is None
                                                      else rid))
        except RuntimeError as e:       # the step loop has died
            log(f"request {r.i} not submitted: {e}")
            r.failed = True
            return
        self.handles[r.i] = h
        self.tasks.append(asyncio.ensure_future(self._consume(r, h)))

    @staticmethod
    async def _consume(r, h) -> None:
        async for _ in h.tokens():
            r.token_t.append(time.perf_counter())
        st = await h.result()
        r.state = st
        if st is not None and st.finish_reason == "eos" and not r.token_t:
            r.token_t.append(time.perf_counter())   # EOS first: no bytes
        if st is None or st.finish_reason not in ("eos", "length",
                                                  "max_len", "cancelled"):
            r.failed = True
        elif st.admit_t is not None:
            r.admit_t = st.admit_t

    async def _warm(self) -> None:
        """Compile every shape the window will use, through the front
        end: each prefill bucket, greedy-only batches (the host-static
        argmax select) and, where the mix samples, mixed batches; then
        the resample program the oracle's rejection path calls."""
        eng = self.served.engine
        masked = []
        real = {n: getattr(eng, n) for n in ("_fused_greedy",
                                              "_fused_sample")}

        def keep(fn):
            def call(*a):
                out = fn(*a)
                masked.append(out[0])
                return out
            return call
        for n, fn in real.items():
            setattr(eng, n, keep(fn))
        try:
            phases = [True] if self.mix["greedy_share"] >= 1 else \
                [True, False]
            rid = 1 << 30
            for all_greedy in phases:
                batch = []
                for k, b in enumerate(prefill_buckets(self.mix)):
                    text, ids = self.traffic.prompt(rid, b)
                    r = loadgen.Req(i=rid, prompt=text, prompt_ids=ids,
                                    max_new=4,
                                    greedy=all_greedy or k % 2 == 0)
                    rid += 1
                    batch.append(r)
                    self._submit(r)
                await asyncio.gather(*self.tasks)
                self.tasks.clear()
                bad = [r.state and r.state.finish_reason for r in batch
                       if r.failed]
                if bad:
                    log(f"warm-up requests failed: {bad}")
        finally:
            for n, fn in real.items():
                setattr(eng, n, fn)
        import jax.numpy as jnp
        B = eng.slots
        if masked:
            eng._resample(masked[-1], jnp.asarray(np.zeros(B, np.int32)),
                          jnp.asarray(np.zeros(B, bool)),
                          jnp.asarray(np.ones(B, bool)),
                          jnp.asarray(np.ones(B, np.float32)),
                          jnp.asarray(np.zeros(B, np.int32)),
                          jnp.asarray(np.ones(B, np.float32)),
                          jnp.asarray(np.zeros((B, 2), np.uint32)))
            self.logit_bytes = masked[-1].dtype.itemsize
        else:
            self.logit_bytes = 4
        self.handles.clear()

    # ---- the window ------------------------------------------------------

    async def _window(self) -> None:
        import jax
        mix, T = self.mix, self.seconds
        kind = mix["arrivals"]["kind"]
        if kind == "poisson":
            n = self.traffic.due_before(T)
            self.reqs = [self.traffic.request(i) for i in range(n)]
        else:
            self.reqs = [self.traffic.request(i)
                         for i in range(int(mix["arrivals"]["prepare"]))]
        src = self.served.aeng._source      # the front end's wait queue
        stop = asyncio.Event()
        self.stats0 = self.served.aeng.stats()
        self.compiles = {"trace": 0, "backend": 0}
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - self.t_process
        self.t1 = self.t0 + T
        if kind == "poisson":
            gen = loadgen.open_loop(self.reqs, self.t0, self._submit)
        else:
            extra = iter(range(len(self.reqs), 1 << 30))

            def make(i):
                if i >= len(self.reqs):
                    self.reqs.append(self.traffic.request(next(extra)))
                return self.reqs[i]
            gen = loadgen.backlog_loop(
                make, self._submit, lambda: len(src),
                int(mix["arrivals"]["waiting"]), stop)
        gen_task = asyncio.ensure_future(gen)
        watch = asyncio.ensure_future(self._watch_queue(src, self.t1))
        tdir = None
        if self.trace:
            await loadgen._sleep_until(self.t1 - min(TRACE_SECONDS, T / 2))
            tdir = tempfile.mkdtemp(prefix="chip_trace_")
            jax.profiler.start_trace(tdir, profiler_options=profile_options())
            self.served.aeng.telemetry.tracer.start()
            self.mark_t = time.perf_counter()
            with jax.profiler.TraceAnnotation(CLOCK_MARK):
                pass
            self.trace_t0 = time.perf_counter()
        await loadgen._sleep_until(self.t1)
        self.stats1 = self.served.aeng.stats()
        self.compiles_in_window = dict(self.compiles)
        if self.trace:
            self.served.aeng.telemetry.tracer.stop()
            jax.profiler.stop_trace()
        stop.set()          # a backlog stops; an open loop still sends
        await gen_task      # what fell due in the window, late or not
        await watch
        self.trace_dir = tdir
        if kind != "poisson":   # the backlog's queue was never due
            for r in self.reqs:
                h = self.handles.get(r.i)
                if h is not None and r.admit_t is None and \
                        h._state is None:
                    h.cancel()
        deadline = time.perf_counter() + DRAIN_SECONDS
        while time.perf_counter() < deadline and any(
                r.state is None and not r.failed for r in self.reqs
                if r.i in self.handles):
            await asyncio.sleep(0.01)
        try:
            await self.served.aeng.abort()
        except RuntimeError as e:       # the step loop died: every request
            log(f"serving stopped: {e!r}")  # it held reads as failed
        await asyncio.gather(*self.tasks)

    async def _serve(self) -> None:
        await self._warm()
        await self._window()

    # ---- metrics -----------------------------------------------------------

    def _in_window(self) -> list:
        """An open mix's requests due in the window; a backlog's requests
        that the server took (its waiting queue is cancelled at the
        close: those were never due)."""
        if self.mix["arrivals"]["kind"] == "poisson":
            return [r for r in self.reqs if r.due_t is not None
                    and self.t0 <= r.due_t < self.t1]
        return [r for r in self.reqs if r.sent_t is not None and
                not (r.state is not None and r.state.finish_reason ==
                     "cancelled" and not r.token_t)]

    def end_to_end(self) -> dict:
        reqs = self._in_window()
        toks = [t for r in self.reqs for t in r.token_t
                if self.t0 <= t < self.t1]
        gaps = [(b - a) * 1e3 for r in self.reqs
                for a, b in zip(r.token_t, r.token_t[1:])
                if self.t0 <= b < self.t1]
        ttft = [(r.first_t - r.due_t) * 1e3 for r in reqs
                if r.first_t is not None and not r.failed]
        missing = len(reqs) - len(ttft)
        self.samples = {"arrivals": len(reqs), "tokens": len(toks),
                        "gaps": len(gaps), "ttft": len(ttft),
                        "ttft_missing": missing}
        if gaps:
            self.samples["itl_p50_ms"] = loadgen.pct(gaps, 50)
            self.samples["itl_beyond_p95"] = sum(
                g > loadgen.pct(gaps, 95) for g in gaps)
            self.samples["itl_max_ms"] = max(gaps)
        if ttft:
            self.samples["ttft_p50_ms"] = loadgen.pct(ttft, 50)
            self.samples["ttft_beyond_p90"] = sum(
                g > loadgen.pct(ttft, 90, missing) for g in ttft) + missing
        lag = [(r.sent_t - r.due_t) * 1e3 for r in reqs
               if r.sent_t is not None and r.due_t is not None]
        if lag:
            self.samples["gen_lag_max_ms"] = max(lag)
        if getattr(self, "depth", None):
            self.samples["queue_max"] = max(self.depth)
            self.samples["queue_busy_share"] = \
                sum(d > 0 for d in self.depth) / len(self.depth)
        pauses = [(g, ms) for t, g, ms in getattr(self, "gc_pauses", [])
                  if self.t0 <= t < self.t1]
        self.samples["gc_in_window"] = len(pauses)
        self.samples["gc_gen2_in_window"] = sum(g == 2 for g, _ in pauses)
        self.samples["gc_max_ms"] = max((ms for _, ms in pauses),
                                        default=0.0)
        self.values = values = {
            "setup_s": self.setup_s,
            "tokens_per_s": len(toks) / self.seconds,
            "ttft_p90_ms": loadgen.pct(ttft, 90, missing),
            "itl_p95_ms": loadgen.pct(gaps, 95),
        }
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in self.spec["end_to_end"]}

    def _reduced_trace(self):
        if not self.trace_dir:
            return None
        import glob
        paths = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        pd = xplane.load(paths[0])
        mark = xplane.marker_ns(pd, CLOCK_MARK)
        if mark is None:
            raise RuntimeError("the clock mark is missing from the trace")

        def to_ns(t):
            return mark + (t - self.mark_t) * 1e9
        red = xplane.reduce(pd, to_ns(self.trace_t0), to_ns(self.t1))
        # the program's phase spans (its per-request "slot" spans cover a
        # request's whole life and would name every gap)
        spans = [(name, t0, dur) for ph, track, name, t0, dur, _ in
                 list(self.served.aeng.telemetry.tracer._ring)
                 if ph == "X" and not track.startswith("slot ")]
        red["idle_gaps"] = xplane.name_gaps(red["gaps"], spans, to_ns)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return red

    def per_layer(self, tokens_per_s: float) -> dict:
        view = SimpleNamespace(
            reqs=self.reqs, window=self._in_window(), t0=self.t0,
            t1=self.t1, seconds=self.seconds, stats0=self.stats0,
            stats1=self.stats1, trace=self.red, model=self.config["serve"],
            mix=self.mix, peak=self.peak, logit_bytes=self.logit_bytes,
            tokens_per_s=tokens_per_s, counts=counts)
        out = {}
        for m in self.spec["per_layer"]:
            v = reader(m["name"])(view)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out

    # ---- the whole run -----------------------------------------------------

    def go(self, engine_hook=None) -> dict:
        import jax
        dev = jax.devices()
        self.peak = peaks.peaks(dev[0].device_kind) \
            if dev[0].platform == "tpu" else None
        self._listen()
        self._watch_gc()
        self.served = Served(self.config, self.mix, self.seed)
        if engine_hook is not None:
            engine_hook(self.served.engine)
        self.traffic = loadgen.Traffic(self.mix, self.seed,
                                       self.served.vocab)
        try:
            asyncio.run(self._serve())
        finally:
            gc.callbacks.remove(self._gc_cb)
        e2e = self.end_to_end()
        mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in dev[:self.spec["cell"]["chips"]])
        self.red = self._reduced_trace() if self.trace else None
        layer = self.per_layer(len([t for r in self.reqs for t in r.token_t
                                    if self.t0 <= t < self.t1])
                               / self.seconds) if self.trace else None
        window = self._in_window()
        finished = [r for r in window if r.state is not None
                    and r.state.finish_reason in ("eos", "length",
                                                  "max_len")]
        vocab = self.served.vocab
        make, words = self.served.make_weights, self.served.words
        self.handles.clear()        # each handle holds its AsyncEngine
        self.tasks.clear()
        self.served = None          # the engine and its state go first,
        gc.collect()                # then the reference makes its own
        ref_w = make(words)         # copy of the same weights
        checks = check.compare(finished, window, ref_w, self.config,
                               self.mix, vocab, self.seed)
        del ref_w
        self.finished, self.window_reqs, self.vocab = finished, window, vocab
        self.make_weights = lambda: make(words)
        result = {
            "correct": all(c["ok"] for c in checks.values()),
            "attempted": len(window),
            "failed": sum(1 for r in window if r.failed
                          or (self.mix["arrivals"]["kind"] == "poisson"
                              and r.first_t is None)),
            "metrics": layer if self.trace else e2e,
            "device": {"platform": dev[0].platform,
                       "kind": dev[0].device_kind,
                       "count": self.spec["cell"]["chips"],
                       "memory_peak_bytes": mem},
        }
        if self.trace:
            result["device"]["busy_s"] = self.red["busy_s"]
            result["device"]["window_s"] = self.red["window_s"]
            ops = sorted(self.red["ops"].items(), key=lambda kv: -kv[1][0])
            result["breakdown"] = {
                "device_ops": [[k, v[0]] for k, v in ops[:10]],
                "idle_gaps": self.red["idle_gaps"][:10]}
        result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                            for k, c in checks.items()}
        log(f"setup_s {self.setup_s:.3f} (process start to first due "
            f"request)")
        log(f"compiles in window: {self.compiles_in_window}")
        log(f"samples: {json.dumps(self.samples)}")
        log(f"end to end: {json.dumps(e2e)}")
        if self.trace:
            log(f"programs: {json.dumps(self.red['programs'])}")
        for k, c in checks.items():
            log(f"check {k}: {c['value']} limit {c['limit']} "
                f"{'ok' if c['ok'] else 'FAILED'}")
        return result
