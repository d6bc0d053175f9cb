"""Find an open cell's knee: the highest arrival rate at which the
waiting queue does not grow over a window. One process, one engine.

    python benchmarks/chip/tools/sweep.py --workload <open cell> \
        --seed <n> --seconds <s> --rates 2,4,6,8

For each rate (ascending) it serves a fresh open-loop window of the
cell's mix at that rate through a fresh AsyncEngine over the same
(warm) engine, samples the front end's queue depth every half second
and prints one JSON line per rate: arrivals, tokens/s, ttft p90, itl
p95, queue depth at the window's start and end and the slope of the
depth over the window's second half (requests per second). Needs a TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import run as entry  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    entry.setup_paths()
    import jax
    import numpy as np
    from harness import cell, loadgen
    from repro.serving.async_engine import AsyncEngine
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    spec = cell.load_spec(args.workload)
    run = cell.Run(spec, args.seed, args.seconds, False, T_PROCESS)
    run._listen()
    run.served = cell.Served(spec["config"], spec["mix"], args.seed)
    run.traffic = loadgen.Traffic(spec["mix"], args.seed, run.served.vocab)
    asyncio.run(run._warm())
    for rate in [float(x) for x in args.rates.split(",")]:
        run.served.aeng = AsyncEngine(run.served.engine)
        run.mix = dict(spec["mix"], arrivals={"kind": "poisson",
                                              "rate_per_s": rate})
        run.traffic = loadgen.Traffic(run.mix, args.seed, run.served.vocab)
        run.reqs, run.tasks, run.handles = [], [], {}
        depth = []

        async def watch():
            while True:
                depth.append((time.perf_counter(),
                              len(run.served.aeng._source)))
                await asyncio.sleep(0.5)

        async def go():
            run.served.aeng._ensure_started()
            w = asyncio.ensure_future(watch())
            await run._window()
            w.cancel()
        asyncio.run(go())
        run.end_to_end()
        e2e = {k: {"value": v} for k, v in run.values.items()}
        half = [(t, d) for t, d in depth if run.t0 + args.seconds / 2 <= t
                < run.t1]
        slope = float(np.polyfit([t for t, _ in half], [d for _, d in half],
                                 1)[0]) if len(half) > 2 else None
        print(json.dumps({
            "rate": rate, "arrivals": run.samples["arrivals"],
            "tokens_per_s": e2e.get("tokens_per_s", {}).get("value"),
            "ttft_p90_ms": e2e.get("ttft_p90_ms", {}).get("value"),
            "itl_p95_ms": e2e.get("itl_p95_ms", {}).get("value"),
            "depth_start": depth[0][1] if depth else None,
            "depth_end": [d for t, d in depth if t < run.t1][-1],
            "depth_slope_per_s": slope,
            "compiles": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
