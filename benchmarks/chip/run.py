"""Chip benchmark: one run of one cell.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics with --trace 0, its per-layer metrics with --trace 1), `device`
and `checks`; the numbers compared, each with its limit, are also the
last lines of standard error. Exits non-zero, with no result line,
where JAX finds no TPU or fewer chips than the cell asks for, or where
the program's sources are not beside the benchmark. See README.md.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_paths() -> None:
    """Imports from the benchmark's own directory and the program's
    sources, and JAX's persistent compilation cache at a fixed place
    inside the checkout (set before JAX is imported; the program's
    `enable_compile_cache` honours the same variable)."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    cache = HERE / ".cache" / "jax"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # every program, however quick to compile, so that only a checkout's
    # first run of a cell compiles
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("the program's sources (src/repro) are not in this checkout",
              file=sys.stderr)
        return 2
    setup_paths()
    from harness import cell
    try:
        spec = cell.load_spec(args.workload)
    except (KeyError, OSError) as e:
        print(f"cannot load the cell: {e}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    chips = spec["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 3
    run = cell.Run(spec, args.seed, args.seconds, bool(args.trace),
                   T_PROCESS)
    result = run.go()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
