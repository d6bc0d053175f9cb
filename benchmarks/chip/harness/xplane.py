"""Reduce a profiler trace (xplane) to device busy time, per-program and
per-op device time, and the idle gaps between device work.

Device planes are those named `/device:<kind>:<n>`; their "XLA Ops"
line holds one event per operation that ran (named by its HLO
instruction), "Async XLA Ops" the asynchronous copies, and "XLA Modules"
one event per program execution. Times in a trace are on the profiler's own clock;
`align` maps them onto the host's perf_counter() through a marker
annotation whose perf_counter() time the caller recorded.
"""
from __future__ import annotations

import bisect
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


_DEVICE = re.compile(r"^/device:(?!CPU)[A-Z]+:\d+$")


def device_planes(pd) -> list:
    """Planes of the accelerator chips (`/device:TPU:0`), not the host
    and not the runtime's own trace planes."""
    return [p for p in pd.planes if _DEVICE.match(p.name)]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield e.name, e.start_ns, e.start_ns + e.duration_ns


def marker_ns(pd, name: str):
    """Start of the first host event called `name`, or None."""
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == name:
                    return e.start_ns
    return None


def union(intervals) -> list:
    """Merge [start, end) intervals -> sorted disjoint list."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(hlo: str) -> str:
    """An op event is named by its whole HLO instruction; keep the
    instruction's own name (`%fused_select.1 = ...` -> fused_select.1)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def reduce(pd, lo_ns: float, hi_ns: float) -> dict:
    """-> {devices, window_s, busy_s (mean over devices), programs,
    ops ({"program/op": [seconds, calls]} summed over devices), gaps
    (idle [start_ns, end_ns) of the first device, longest first)}.

    Busy is the union of the intervals of every op, synchronous and
    asynchronous (DMA copies run beside compute). An op or program
    counts toward the window by the part of it inside [lo_ns, hi_ns);
    its calls count where it starts inside."""
    planes = device_planes(pd)
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy, programs, ops, gaps = [], {}, {}, None
    for plane in planes:
        mods = sorted((s, e, _SUFFIX.sub("", name)) for name, s, e
                      in _events(plane, MODULES_LINE))
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            c = clip([(s, e)], lo_ns, hi_ns)
            if not c:
                continue
            acc = programs.setdefault(name, [0.0, 0])
            acc[0] += (c[0][1] - c[0][0]) * 1e-9
            acc[1] += int(lo_ns <= s < hi_ns)
        iv = []
        for line in (OPS_LINE, ASYNC_LINE):
            for name, s, e in _events(plane, line):
                c = clip([(s, e)], lo_ns, hi_ns)
                if not c:
                    continue
                iv.append(c[0])
                if line != OPS_LINE:
                    continue
                k = bisect.bisect_right(starts, s) - 1
                prog = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
                acc = ops.setdefault(f"{prog}/{op_name(name)}", [0.0, 0])
                acc[0] += (c[0][1] - c[0][0]) * 1e-9
                acc[1] += int(lo_ns <= s < hi_ns)
        merged = union(iv)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if gaps is None:
            edges = [lo_ns] + [x for se in merged for x in se] + [hi_ns]
            gaps = sorted(((edges[i], edges[i + 1])
                           for i in range(0, len(edges), 2)
                           if edges[i + 1] > edges[i]),
                          key=lambda g: g[0] - g[1])
    return {"devices": len(planes), "window_s": (hi_ns - lo_ns) * 1e-9,
            "busy_s": sum(busy) / len(busy), "programs": programs,
            "ops": ops, "gaps": gaps}


def name_gaps(gaps, spans, to_ns, top: int = 10) -> list:
    """Name each of the `top` longest idle gaps by the host span that
    covers its midpoint (the innermost, i.e. shortest, one), from
    spans [(name, t0_perf, dur_s)] mapped to trace ns by `to_ns`."""
    iv = [(to_ns(t0), to_ns(t0 + d), n) for n, t0, d in spans]
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        inside = [(b - a, n) for a, b, n in iv if a <= mid < b]
        name = min(inside)[1] if inside else "no_host_span"
        out.append([name, (e - s) * 1e-9])
    return out
