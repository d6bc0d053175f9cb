"""Device-time attribution (repro.obs.devtime + serving integration).

Contract groups (docs/observability.md §Device-time attribution):

  * **no-sync default** — with device timing off (serving mode) the
    injected sync capability is NEVER invoked: `DeviceTimer.sync_calls`
    stays 0 across a full engine run, and `span()` hands back the shared
    `NULL_DEV_SPAN` (PR 7's no-sync contract holds verbatim);
  * **measured brackets** — in bench mode the span syncs on the
    arrays passed to `done()` and records a true device interval into
    the `repro_device_*` families and the `device:<fn>` trace track;
  * **profiler session** — the `POST /profile` state machine leaves the
    device timer alone (no sync-on-exit: that would serialize the
    overlap the capture shows), tolerates an unbound/broken backend
    profiler, and maps backend Chrome events onto the host clock
    through its clock mark for the merged export;
  * **attribution math** — the step wall-time split prefers synced
    device seconds per kernel family and falls back to host dispatch
    spans, reporting which source produced each number;
  * **identity** — devtime on vs off is token-for-token identical
    (observation may never perturb decoding).
"""
import contextlib
import glob
import gzip
import json
import os
import time

import jax
import pytest

from repro.core.decoding import DecodeConfig
from repro.obs import (DEVICE_TRACK_PREFIX, NULL_DEV_SPAN, PROFILE_MARK,
                       DeviceTimer, MetricsRegistry, ProfilerSession,
                       Telemetry, Tracer)
from repro.serving.engine import Engine, Request

MAX_LEN = 160


def _timer(tracer=None):
    tr = tracer or Tracer()
    return DeviceTimer(MetricsRegistry(), tr), tr


# ========================= unit: DeviceTimer ==========================

def test_span_is_null_unless_enabled_and_bound():
    dt, _ = _timer()
    assert dt.span("forward") is NULL_DEV_SPAN          # neither
    dt.enabled = True
    assert dt.span("forward") is NULL_DEV_SPAN          # no sync bound
    dt.bind(lambda out: out)
    dt.enabled = False
    assert dt.span("forward") is NULL_DEV_SPAN          # serving mode
    dt.enabled = True
    assert dt.span("forward") is not NULL_DEV_SPAN


def test_null_span_never_syncs():
    dt, _ = _timer()
    dt.bind(lambda out: (_ for _ in ()).throw(AssertionError("synced")))
    with dt.span("forward") as dv:                      # disabled
        dv.done(object())
    assert dt.sync_calls == 0
    assert dt.seconds("forward") == 0.0


def test_bound_span_syncs_and_measures():
    dt, _ = _timer()
    synced = []
    dt.bind(synced.append)
    dt.enabled = True
    with dt.span("forward") as dv:
        time.sleep(0.002)
        dv.done("arrays")
    assert synced == ["arrays"]
    assert dt.sync_calls == 1
    assert dv.dur >= 0.002
    assert dt.seconds("forward") == pytest.approx(dv.dur)
    assert dt.calls("forward") == 1
    s = dt.summary()["forward"]
    assert s["calls"] == 1 and s["seconds"] == pytest.approx(dv.dur)


def test_span_without_done_records_but_never_syncs():
    dt, _ = _timer()
    dt.bind(lambda out: (_ for _ in ()).throw(AssertionError("synced")))
    dt.enabled = True
    with dt.span("forward"):
        pass
    assert dt.sync_calls == 0
    assert dt.calls("forward") == 1


def test_span_skips_sync_on_exception():
    dt, _ = _timer()
    dt.bind(lambda out: (_ for _ in ()).throw(AssertionError("synced")))
    dt.enabled = True
    with pytest.raises(RuntimeError):
        with dt.span("forward") as dv:
            dv.done("arrays")
            raise RuntimeError("step failed")
    assert dt.sync_calls == 0                   # arrays may be invalid


def test_bind_is_idempotent():
    dt, _ = _timer()
    calls = []
    dt.bind(lambda out: calls.append("first"))
    dt.bind(lambda out: calls.append("second"))  # ignored
    dt.enabled = True
    with dt.span("f") as dv:
        dv.done(1)
    assert calls == ["first"]


def test_device_track_only_while_tracing():
    dt, tr = _timer()
    dt.bind(lambda out: out)
    dt.enabled = True
    with dt.span("forward") as dv:
        dv.done(1)
    assert len(tr) == 0
    tr.start()
    with dt.span("forward") as dv:
        dv.done(1)
    tr.stop()
    assert len(tr) == 1
    evs = tr.export_chrome()["traceEvents"]
    tracks = {e["args"]["name"] for e in evs
              if e.get("name") == "thread_name"}
    assert DEVICE_TRACK_PREFIX + "forward" in tracks


def test_set_cost_surfaces_roofline_inputs():
    dt, _ = _timer()
    dt.bind(lambda out: out)
    dt.enabled = True
    dt.set_cost("forward", flops=2e9, hbm_bytes=1e8)
    with dt.span("forward") as dv:
        time.sleep(0.001)
        dv.done(1)
    s = dt.summary()["forward"]
    assert s["flops_per_call"] == 2e9
    assert s["achieved_flops_per_s"] == pytest.approx(2e9 / s["seconds"])
    text = dt.registry.render_prometheus()
    assert 'repro_device_flops_per_call{fn="forward"} 2e+09' in text \
        or 'repro_device_flops_per_call{fn="forward"} 2000000000' in text


# ======================= unit: ProfilerSession ========================

def _session(tr, mark=True):
    """A ProfilerSession over a no-op backend; with `mark`, an
    annotation factory, so start() writes the clock mark."""
    ps = ProfilerSession(tr)
    ps.bind(lambda d: None, lambda: None,
            (lambda name: contextlib.nullcontext()) if mark else None)
    return ps


def test_profiler_session_state_machine():
    tele = Telemetry(enabled=True)
    dt, tr = tele.devtime, tele.tracer
    dt.bind(lambda out: out)
    ps = tele.profiler
    assert ps.state()["active"] is False
    with pytest.raises(RuntimeError):
        ps.stop()                               # stop before start
    info = ps.start()
    assert ps.active and tr.active
    assert not dt.enabled                       # no sync-on-exit
    with tele.device_span("forward") as dv:     # a capture never syncs
        dv.done(1)
    assert dt.sync_calls == 0
    assert info["backend_profiler"] is False    # no backend bound
    assert ps.mark_t is None                    # ...so no clock mark
    with pytest.raises(RuntimeError):
        ps.start()                              # double start
    out = ps.stop()
    assert not ps.active and not dt.enabled and not tr.active
    assert out["duration_s"] > 0.0
    assert ps.collect_chrome_events() == []     # nothing captured


def test_profiler_session_restores_prior_devtime_mode():
    # the capture never changes the mode, so the one after it is the
    # one before it, on or off
    for before in (True, False):
        dt, tr = _timer()
        dt.bind(lambda out: out)
        dt.enabled = before
        ps = _session(tr)
        ps.start()
        assert dt.enabled is before
        ps.stop()
        assert dt.enabled is before


def test_profiler_session_tolerates_broken_backend():
    dt, tr = _timer()
    ps = ProfilerSession(tr)

    def broken_start(log_dir):
        raise OSError("no backend")
    ps.bind(broken_start, lambda: None,
            lambda name: contextlib.nullcontext())
    info = ps.start()
    assert info["backend_profiler"] is False    # swallowed, still capturing
    assert ps.active and ps.mark_t is None      # no capture, no mark
    ps.stop()


def _write_capture(root, events, threads, procs=()):
    """A backend profiler's Chrome trace under root/plugins/profile."""
    meta = [{"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
             "args": {"name": name}}
            for (pid, tid), name in threads.items()]
    meta += [{"ph": "M", "name": "process_name", "pid": pid,
              "args": {"name": name}} for pid, name in dict(procs).items()]
    d = root / "plugins" / "profile" / "run1"
    os.makedirs(d)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": meta + events}, f)


def test_collect_chrome_events_parses_and_rebases(tmp_path):
    _, tr = _timer()
    ps = _session(tr)
    ps.start(log_dir=str(tmp_path))
    assert ps.host_t0 <= ps.mark_t
    # synthetic backend capture: the clock mark on the python thread,
    # one device thread, one noise slice — only the device kernel slice
    # must survive, placed through the mark
    _write_capture(tmp_path, [
        {"ph": "X", "pid": 1, "tid": 3, "name": PROFILE_MARK,
         "ts": 4000.0, "dur": 2.0},
        {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.1",
         "ts": 5000.0, "dur": 40.0},
        {"ph": "X", "pid": 1, "tid": 2, "name": "ThunkExecutor work",
         "ts": 5010.0, "dur": 1.0},
        {"ph": "X", "pid": 1, "tid": 3, "name": "host_python_frame",
         "ts": 5000.0, "dur": 500.0},
    ], {(1, 2): "TFRT XLATfrtCpuClient/0", (1, 3): "python main"})
    ps.stop()
    evs = ps.collect_chrome_events()
    assert len(evs) == 1
    ev = evs[0]
    assert ev["name"] == "fusion.1"
    assert ev["track"].startswith(DEVICE_TRACK_PREFIX + "xla ")
    assert ev["ts_us"] == pytest.approx(ps.mark_t * 1e6 + 1000.0)
    assert ev["dur_us"] == 40.0
    # without the mark there is no clock to map through
    ps2 = _session(tr, mark=False)
    ps2.start(log_dir=str(tmp_path))
    ps2.stop()
    assert ps2.mark_t is None and ps2.collect_chrome_events() == []


def test_collect_chrome_events_places_late_first_event_by_mark(tmp_path):
    """The first device op comes 80 ms after start() (a step's worth of
    host work first): it lands at its own host time, not at host_t0."""
    _, tr = _timer()
    ps = _session(tr)
    ps.start(log_dir=str(tmp_path))
    _write_capture(tmp_path, [
        {"ph": "X", "pid": 7, "tid": 1, "name": PROFILE_MARK,
         "ts": 100.0, "dur": 1.0},
        {"ph": "X", "pid": 9, "tid": 4, "name": "jit_decode_step",
         "ts": 80100.0, "dur": 66000.0},
        {"ph": "X", "pid": 9, "tid": 5, "name": "fusion.3",
         "ts": 80200.0, "dur": 500.0},
    ], {(7, 1): "python", (9, 4): "XLA Modules", (9, 5): "XLA Ops"},
        {9: "/device:TPU:0"})
    ps.stop()
    evs = {e["name"]: e for e in ps.collect_chrome_events()}
    assert set(evs) == {"jit_decode_step", "fusion.3"}
    step = evs["jit_decode_step"]
    assert step["ts_us"] == pytest.approx(ps.mark_t * 1e6 + 80000.0)
    assert step["ts_us"] - ps.host_t0 * 1e6 >= 80000.0
    assert evs["fusion.3"]["ts_us"] - step["ts_us"] == pytest.approx(100.0)
    assert step["track"] == DEVICE_TRACK_PREFIX + \
        "xla /device:TPU:0 XLA Modules"


def test_live_capture_places_device_slices_after_their_dispatch(tmp_path):
    """A real jax.profiler capture on this backend, bound by devbridge:
    the step and phase spans enter the profiler's trace, and the device
    slices merged through the clock mark start after the span that
    dispatched them, 50 ms into the capture."""
    import jax.numpy as jnp

    from repro.serving.devbridge import attach
    tele = Telemetry(enabled=True)
    attach(tele)
    f = jax.jit(lambda x: (x @ x) * 0.5)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    ps = tele.profiler
    assert ps.start(log_dir=str(tmp_path))["backend_profiler"] is True
    time.sleep(0.05)
    with tele.step_span(0):
        with tele.span("forward"):
            y = f(x)
        y.block_until_ready()
    ps.stop()
    fwd = next(e for e in tele.tracer._ring if e[2] == "forward")
    evs = ps.collect_chrome_events()
    assert evs and all(e["track"].startswith(DEVICE_TRACK_PREFIX + "xla ")
                       for e in evs)
    first = min(e["ts_us"] for e in evs)
    assert first >= fwd[3] * 1e6 - 1000.0       # not pinned to host_t0
    assert first - ps.host_t0 * 1e6 >= 40e3
    assert max(e["ts_us"] + e["dur_us"] for e in evs) <= ps.host_t1 * 1e6
    names = set()
    for path in glob.glob(os.path.join(ps.log_dir, "**", "*.trace.json.gz"),
                          recursive=True):
        with gzip.open(path, "rt") as fh:
            names |= {e.get("name") for e in json.load(fh)["traceEvents"]}
    assert {PROFILE_MARK, "decode", "forward"} <= names


def test_merged_export_aligns_host_and_device_tracks():
    tele = Telemetry(enabled=True)
    tele.tracer.start()
    with tele.span("rows_build"):
        time.sleep(0.001)
    tele.tracer.stop()
    host_t0 = tele.tracer._ring[0][3]           # ("X", track, name, t0, …)
    extra = [{"track": "device:xla main", "name": "fusion.7",
              "ts_us": host_t0 * 1e6, "dur_us": 10.0}]
    doc = tele.tracer.export_chrome(extra_events=extra)
    evs = doc["traceEvents"]
    tracks = {e["args"]["name"] for e in evs
              if e.get("name") == "thread_name"}
    assert "rows_build" in tracks and "device:xla main" in tracks
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"rows_build", "fusion.7"}
    assert all(e["ts"] >= 0.0 for e in xs)      # shared rebase
    assert doc["otherData"]["merged_device_events"] == 1


# ====================== unit: attribution math ========================

def _fabricate(tele, phase_seconds=(), device_seconds=()):
    for phase, s in phase_seconds:
        tele._phase(phase)[0].inc(s)
    for fn, s in device_seconds:
        tele.devtime._record(fn, 0.0, s)


def test_attribution_host_dispatch_fallback():
    tele = Telemetry(enabled=True)
    _fabricate(tele, phase_seconds=[("ci_lookup", 0.25), ("cd_check", 0.05),
                                    ("plan", 0.1),
                                    ("mask_dispatch", 0.2),
                                    ("forward", 0.4)])
    a = tele.attribution()
    assert a["seconds"]["host_grammar"] == pytest.approx(0.4)
    assert a["seconds"]["host_grammar_ci"] == pytest.approx(0.25)
    assert a["seconds"]["host_grammar_cd"] == pytest.approx(0.05)
    assert a["seconds"]["mask_sample_kernel"] == pytest.approx(0.2)
    assert a["seconds"]["forward_kernel"] == pytest.approx(0.4)
    assert a["source"] == {"mask_sample_kernel": "host-dispatch",
                           "forward_kernel": "host-dispatch"}
    assert sum(a["fractions"].values()) == pytest.approx(1.0)


def test_attribution_prefers_device_seconds():
    tele = Telemetry(enabled=True)
    _fabricate(tele,
               phase_seconds=[("mask_dispatch", 0.001), ("forward", 0.002)],
               device_seconds=[("mask_sample", 0.25), ("forward", 0.5),
                               ("overlap_forward", 0.1)])
    a = tele.attribution()
    assert a["seconds"]["mask_sample_kernel"] == pytest.approx(0.25)
    assert a["seconds"]["forward_kernel"] == pytest.approx(0.6)
    assert a["source"] == {"mask_sample_kernel": "device",
                           "forward_kernel": "device"}
    # the scrape-time counters agree with the attribution() view
    text = tele.registry.render_prometheus()
    assert 'repro_step_attribution_seconds_total' \
           '{component="forward_kernel"} 0.6' in text


def test_overlap_hidden_is_a_real_counter():
    tele = Telemetry(enabled=True)
    tele.add_overlap_hidden(0.05)
    tele.add_overlap_hidden(-1.0)               # ignored
    assert tele.attribution()["seconds"]["overlap_hidden"] == \
        pytest.approx(0.05)
    # present (and writable) even with telemetry disabled
    off = Telemetry(enabled=False)
    off.add_overlap_hidden(0.01)
    assert off.c_overlap_hidden.value == pytest.approx(0.01)
    assert off.attribution() == {"enabled": False}


# ===================== integration: engine modes ======================

@pytest.fixture(scope="module")
def dev_engines(tokenizer, grammar_bundle):
    from dataclasses import replace

    from repro.configs import get_config
    from repro.models.model import build_model
    bundles = {}
    for name in ("json",):
        g, tab, store, _ = grammar_bundle(name)
        bundles[name] = (g, tab, store)
    cfg = get_config("syncode-demo")
    cfg = replace(cfg, vocab_size=tokenizer.vocab_size, num_layers=2,
                  d_model=128, d_ff=256, num_heads=4, num_kv_heads=2,
                  head_dim=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def make(**kw):
        kw.setdefault("slots", 4)
        return Engine(model, params, tokenizer, bundles, max_len=MAX_LEN,
                      **kw)
    return make


def _reqs(n=3, max_new=10):
    return [Request(rid=i, prompt=b"Q: generate. A:", grammar="json",
                    max_new_tokens=max_new,
                    decode=DecodeConfig(method="sample", temperature=1.0),
                    seed=i) for i in range(n)]


def _ids(states):
    return {s.req.rid: (s.token_ids, s.finish_reason) for s in states}


def _run_loop(eng, reqs):
    from repro.serving.loop import ListSource, StepLoop, make_mode
    loop = StepLoop(eng, make_mode(eng), ListSource(reqs))
    states, stats = loop.run()
    return states, stats, loop.tele


def test_serving_mode_never_syncs(dev_engines):
    """The tentpole no-sync guarantee, dynamically: a full serving-mode
    run (telemetry ON, device timing off) invokes the injected sync
    capability zero times and measures zero device seconds."""
    eng = dev_engines(telemetry=True)
    _, stats, tele = _run_loop(eng, _reqs())
    assert tele.devtime.sync_fn is not None     # devbridge DID bind it
    assert tele.devtime.sync_calls == 0         # ...but it never ran
    assert tele.devtime.seconds("forward") == 0.0
    assert stats.device_forward_s == 0.0
    assert stats.attribution["source"]["forward_kernel"] == \
        "host-dispatch"


def test_devtime_engine_measures_device_intervals(dev_engines):
    eng = dev_engines(telemetry=True, devtime=True)
    _, stats, tele = _run_loop(eng, _reqs())
    assert tele.devtime.sync_calls > 0
    assert stats.device_forward_s > 0.0
    assert stats.device_mask_sample_s > 0.0
    a = stats.attribution
    assert a["device_timing"] is True
    assert a["source"]["forward_kernel"] == "device"
    assert a["source"]["mask_sample_kernel"] == "device"
    # lazy HLO cost estimation attached roofline inputs to the fwd fn
    assert tele.devtime.costs.get("forward", {}).get("flops", 0) > 0
    fam = tele.devtime.summary()["forward"]
    assert fam["achieved_flops_per_s"] > 0


def test_devtime_identity(dev_engines):
    s_on, _ = dev_engines(telemetry=True, devtime=True).generate(_reqs())
    s_off, _ = dev_engines(telemetry=True).generate(_reqs())
    assert _ids(s_on) == _ids(s_off)
