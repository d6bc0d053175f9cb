"""The one place the observability stack touches jax.

repro.obs is import-pure (no jax/numpy, source- and transitively-
asserted) and serving/{loop,engine,async_engine,server}.py are
source-scanned for device-sync tokens — so neither side may hold the
actual sync or profiler calls. This module is the deliberate exception:
it binds the two device capabilities into a Telemetry instance as
injected callables:

  * `DeviceTimer.sync_fn`  — blocks on dispatched arrays so a devtime
    bracket measures dispatch + execution. Only ever invoked when the
    timer is explicitly enabled (bench mode); in serving mode span()
    returns the shared no-op before the callable is reachable, which
    tests/test_devtime.py proves by counting sync calls.
  * `ProfilerSession.{start,stop}` — jax.profiler trace capture for
    `POST /profile`, written to a temp dir and merged into the Chrome
    export by the server.
  * `ProfilerSession.{annotate,step_annotate}` — jax.profiler's
    `TraceAnnotation` and `StepTraceAnnotation`: while a capture is
    active, each telemetry span and each step opens one, so the
    profiler's trace carries the program's phases and steps.

Binding is idempotent and failure-tolerant: a backend without a
profiler (or a jax too old to expose one) degrades to a capture of the
host spans alone instead of breaking the server.
"""
from __future__ import annotations

import jax


def attach(tele) -> None:
    """Bind jax sync + profiler capabilities into *tele* (Telemetry).

    Safe to call repeatedly (first bind wins) and safe on a disabled
    Telemetry (binding is inert until device timing or a capture turns on).
    """
    tele.devtime.bind(jax.block_until_ready)
    try:
        prof = jax.profiler
        tele.profiler.bind(prof.start_trace, prof.stop_trace,
                           prof.TraceAnnotation, prof.StepTraceAnnotation)
    except AttributeError:
        pass        # a capture still records the host spans
