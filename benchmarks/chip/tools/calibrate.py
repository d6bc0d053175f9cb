"""Readings that set the logit-gap limit: the program's and the fp8
control's, per seed, in one process.

    python benchmarks/chip/tools/calibrate.py --workload <cell> \
        --seconds <s> --seeds 1,2,3

For each seed it makes a whole run of the cell (set-up with that seed's
weights, a window at the cell's own load, the drain) and then reads, at
the same served positions of the same seeded sample, the program's
gaps (the lower readings) and the gaps of the tokens the fp8 control
puts first (the upper readings), each as the widest and the mean. One
JSON line per seed. Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import run as entry  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    entry.setup_paths()
    import jax
    from harness import cell, check
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    spec = cell.load_spec(args.workload)
    for seed in [int(x) for x in args.seeds.split(",")]:
        t = time.perf_counter()
        run = cell.Run(spec, seed, args.seconds, False, t)
        out = run.go()
        got = check.compare(run.finished, run.window_reqs,
                            run.make_weights(), run.config, run.mix,
                            run.vocab, seed, control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"],
            "program_gap": got["max_logit_gap"]["value"],
            "control_gap": got["control_gap"]["value"],
            "program_mean_gap": got.get("mean_logit_gap", {}).get("value"),
            "control_mean_gap": got.get("control_mean_gap",
                                        {}).get("value"),
            "compared_tokens": got["compared_tokens"]["value"],
            "checks": out["checks"], "metrics": out["metrics"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
