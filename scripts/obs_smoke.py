"""End-to-end observability smoke gate (`make obs-smoke`; wired into CI).

Boots the real serving stack — paged engine, persistent async step
loop, stdlib HTTP server — against the `python_mini` grammar, turns on
trace capture, streams a few requests through `POST /generate`, then
asserts the whole telemetry surface is live:

  * `GET /metrics` exposes the step-phase counters/histograms, the
    request-lifecycle histograms (TTFT / inter-token), the KV pool
    gauges and the token/mask counters, and parses as Prometheus
    text exposition;
  * `GET /stats` returns the JSON snapshot with request summaries;
  * `POST /trace {"action": "dump"}` returns a Chrome trace-event
    document with phase slices and track-name metadata (loadable in
    ui.perfetto.dev);
  * `GET /healthz` carries uptime, queue depth, finish-reason
    counts and the build identity (git SHA / jax version / device);
  * `POST /profile start|stop|dump` captures a live profiler window
    during real `/generate` traffic, with no device sync, and dumps ONE
    merged Perfetto timeline with the step and phase tracks AND the
    profiler's device tracks, placed on the host clock through the
    capture's clock mark.

Everything runs in-process on an ephemeral port; seconds-scale, no
network dependencies. Exit code 0 iff every assertion holds.
"""
from __future__ import annotations

import asyncio
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

N_REQUESTS = 4
MAX_NEW = 12

# series that must be present (as a HELP/TYPE family with at least one
# sample) after the workload: step phases, lifecycle, KV pool, counters
REQUIRED_FAMILIES = (
    "repro_step_phase_seconds_total",
    "repro_step_phase_calls_total",
    "repro_step_phase_duration_seconds",
    "repro_request_ttft_seconds",
    "repro_request_itl_seconds",
    "repro_request_queue_wait_seconds",
    "repro_requests_total",
    "repro_tokens_total",
    "repro_mask_computations_total",
    "repro_overlap_forwards_total",
    "repro_kv_pages_total",
    "repro_kv_pages_in_use",
    "repro_queue_depth",
    "repro_uptime_seconds",
    "repro_step_attribution_seconds_total",
)

# device-attribution components /metrics must expose (scrape-time
# counters wired by Telemetry._wire_attribution)
REQUIRED_ATTRIBUTION = ("host_grammar", "host_grammar_ci",
                        "host_grammar_cd", "mask_sample_kernel",
                        "forward_kernel", "overlap_hidden")

# phases the paged workload must have timed at least once
REQUIRED_PHASES = ("admit", "feed_build", "forward", "ci_lookup",
                   "cd_check", "mask_dispatch", "select_resolve")

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"(NaN|[+-]?(\d+\.?\d*([eE][+-]?\d+)?|\.\d+([eE][+-]?\d+)?|"
    r"Inf|inf))$")


async def _http(host, port, method, path, body=b""):
    reader, writer = await asyncio.open_connection(host, port)
    req = (f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
           f"Content-Length: {len(body)}\r\n\r\n").encode() + body
    writer.write(req)
    await writer.drain()
    data = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionError, BrokenPipeError):
        pass
    head, _, rest = data.partition(b"\r\n\r\n")
    status = int(head.split(b" ")[1])
    if b"chunked" in head.lower():
        out, rem = b"", rest
        while rem:
            size, _, rem = rem.partition(b"\r\n")
            n = int(size, 16)
            if n == 0:
                break
            out += rem[:n]
            rem = rem[n + 2:]
        return status, out
    return status, rest


def _check_prometheus(text: str) -> None:
    """Every non-comment line must be a well-formed sample line."""
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"bad Prometheus line: {line!r}"


async def _run() -> int:
    from repro.launch.serve import build_engine
    from repro.serving.async_engine import AsyncEngine
    from repro.serving.server import EngineServer

    print("obs-smoke: building paged engine (python_mini, vocab=512)...")
    engine, _, _ = build_engine("syncode-demo",
                                grammars=("python_mini", "json"),
                                vocab=512, max_len=160, slots=4,
                                paged=True, page_size=8)
    aeng = AsyncEngine(engine)
    srv = EngineServer(aeng)
    host, port = await srv.start(port=0)
    print(f"obs-smoke: server on http://{host}:{port}")
    try:
        # -- tracing on before any work so slices land in the buffer
        status, body = await _http(host, port, "POST", "/trace",
                                   b'{"action": "start"}')
        assert status == 200 and json.loads(body)["tracing"] is True, \
            (status, body)

        async def gen(i):
            st, out = await _http(
                host, port, "POST", "/generate",
                json.dumps({"prompt": "x =", "grammar": "python_mini",
                            "max_new_tokens": MAX_NEW,
                            "method": "sample", "temperature": 1.0,
                            "seed": i}).encode())
            assert st == 200, (st, out)
            lines = [json.loads(l) for l in out.splitlines() if l]
            assert lines[-1]["done"] is True, lines[-1]
            return lines[-1]["tokens"]

        tokens = await asyncio.gather(*(gen(i) for i in range(N_REQUESTS)))
        total = sum(tokens)
        assert total > 0, "no tokens generated"
        print(f"obs-smoke: {N_REQUESTS} requests, {total} tokens")

        # -- /metrics: families present, phases timed, output well-formed
        status, body = await _http(host, port, "GET", "/metrics")
        assert status == 200, status
        text = body.decode()
        _check_prometheus(text)
        for fam in REQUIRED_FAMILIES:
            assert f"# TYPE {fam} " in text, f"missing family {fam}"
        for ph in REQUIRED_PHASES:
            pat = (f'repro_step_phase_calls_total{{phase="{ph}"}}')
            m = re.search("^" + re.escape(pat) + r" (\S+)$", text, re.M)
            assert m and float(m.group(1)) > 0, f"phase {ph} never timed"
        m = re.search(r'^repro_tokens_total (\S+)$', text, re.M)
        assert m and float(m.group(1)) >= total, "token counter short"
        m = re.search(r'^repro_request_ttft_seconds_count (\S+)$', text,
                      re.M)
        assert m and float(m.group(1)) == N_REQUESTS, "TTFT count wrong"
        print(f"obs-smoke: /metrics OK "
              f"({len(text.splitlines())} lines, "
              f"{len(REQUIRED_FAMILIES)} required families)")

        # -- /stats: JSON snapshot with request summaries
        status, body = await _http(host, port, "GET", "/stats")
        assert status == 200, status
        stats = json.loads(body)
        assert stats["enabled"] is True
        assert stats["requests"]["ttft"]["count"] == N_REQUESTS, stats
        assert stats["trace"]["active"] is True
        print("obs-smoke: /stats OK")

        # -- /trace dump: Chrome trace events with named tracks
        status, body = await _http(host, port, "POST", "/trace",
                                   b'{"action": "dump"}')
        assert status == 200, status
        doc = json.loads(body)
        evs = doc["traceEvents"]
        assert evs, "empty trace"
        phases = {e.get("name") for e in evs if e.get("ph") == "X"}
        assert "forward" in phases and "ci_lookup" in phases, phases
        tracks = {e["args"]["name"] for e in evs
                  if e.get("name") == "thread_name"}
        assert any(t.startswith("slot ") for t in tracks), tracks
        assert all(e["ts"] >= 0 for e in evs if "ts" in e)
        print(f"obs-smoke: /trace dump OK ({len(evs)} events, "
              f"{len(tracks)} tracks)")

        status, body = await _http(host, port, "POST", "/trace",
                                   b'{"action": "stop"}')
        assert status == 200 and json.loads(body)["tracing"] is False

        # -- attribution: every component series present and summed
        status, body = await _http(host, port, "GET", "/metrics")
        text = body.decode()
        for comp in REQUIRED_ATTRIBUTION:
            pat = ('repro_step_attribution_seconds_total'
                   f'{{component="{comp}"}}')
            m = re.search("^" + re.escape(pat) + r" (\S+)$", text, re.M)
            assert m, f"attribution component {comp} missing"
        status, body = await _http(host, port, "GET", "/stats")
        stats = json.loads(body)
        attr = stats["attribution"]
        assert attr["enabled"] is True
        assert attr["seconds"]["host_grammar"] > 0, attr
        assert attr["source"]["forward_kernel"] == "host-dispatch"
        assert stats["device"]["sync_calls"] == 0      # serving mode
        assert stats["build"]["git_sha"], stats["build"]
        print("obs-smoke: attribution OK "
              f"(host_grammar={attr['seconds']['host_grammar']:.3f}s, "
              "no syncs in serving mode)")

        # -- /profile: live profiler capture during real traffic
        status, body = await _http(host, port, "POST", "/profile",
                                   b'{"action": "dump"}')
        assert status == 409, (status, body)           # nothing captured
        status, body = await _http(host, port, "POST", "/profile",
                                   b'{"action": "start"}')
        assert status == 200, (status, body)
        prof = json.loads(body)
        assert prof["profiling"] is True, prof
        await asyncio.gather(*(gen(100 + i) for i in range(N_REQUESTS)))
        status, body = await _http(host, port, "POST", "/profile",
                                   b'{"action": "stop"}')
        assert status == 200, (status, body)
        stopped = json.loads(body)
        assert stopped["buffered_events"] > 0, stopped
        status, body = await _http(host, port, "POST", "/profile",
                                   b'{"action": "dump"}')
        assert status == 200, (status, body)
        doc = json.loads(body)
        evs = doc["traceEvents"]
        assert evs, "empty merged trace"
        tracks = {e["args"]["name"] for e in evs
                  if e.get("name") == "thread_name"}
        host_tracks = [t for t in tracks if not t.startswith("device:")]
        dev_tracks = [t for t in tracks if t.startswith("device:")]
        assert host_tracks and dev_tracks, tracks      # merged timeline
        assert "step" in host_tracks, host_tracks
        assert all(t.startswith("device:xla ") for t in dev_tracks), \
            dev_tracks                                 # profiler slices
        assert all(e["ts"] >= 0 for e in evs if "ts" in e)
        json.dumps(doc)                                # Perfetto-loadable
        status, body = await _http(host, port, "GET", "/stats")
        stats = json.loads(body)
        assert stats["device"]["sync_calls"] == 0      # no sync-on-exit
        assert stats["device"]["enabled"] is False
        assert stats["attribution"]["source"]["forward_kernel"] == \
            "host-dispatch"
        print(f"obs-smoke: /profile OK (merged trace: {len(evs)} events, "
              f"{len(dev_tracks)} device + {len(host_tracks)} host "
              "tracks)")

        # -- /healthz: uptime, queue depth, finish reasons
        status, body = await _http(host, port, "GET", "/healthz")
        assert status == 200, status
        hz = json.loads(body)
        assert hz["ok"] is True
        assert hz["uptime_seconds"] > 0
        assert hz["queue_depth"] == 0
        assert hz["finish_reasons"].get("eos", 0) + \
            hz["finish_reasons"].get("length", 0) == 2 * N_REQUESTS, hz
        b = hz["build"]
        assert b["git_sha"] and b["jax_version"] and b["device_kind"], b
        print(f"obs-smoke: /healthz OK (build {b['git_sha']} "
              f"jax {b['jax_version']} {b['device_kind']})")
    finally:
        await srv.stop(drain=False)
    print("obs-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(_run()))
