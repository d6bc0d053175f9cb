"""Benchmark driver: one function per paper table (+ substrate micro-
benches). Prints ``name,us_per_call,derived`` CSV, then the roofline
table if dry-run artifacts exist.

By default also writes the schema-versioned JSON artifact
(``artifacts/bench/BENCH_<git-sha>.json``) consumed by
scripts/bench_diff.py; disable with ``--no-json-out``.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# before jax loads: CPU host devices for the mesh rows
import benchmarks.bench_sharded  # noqa: E402,F401


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json-out", metavar="PATH", default=None,
                    help="artifact path (default: "
                         "artifacts/bench/BENCH_<git-sha>.json)")
    ap.add_argument("--no-json-out", action="store_true",
                    help="skip writing the JSON artifact")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench-fn names to run")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    from benchmarks import bench_tables, common
    errors = 0
    only = set(args.only.split(",")) if args.only else None
    for fn in bench_tables.ALL:
        if only is not None and fn.__name__ not in only:
            continue
        try:
            fn()
        except Exception:
            errors += 1
            print(f"{fn.__name__},0,ERROR")
            traceback.print_exc()
    bench_tables.assert_rows_complete(common.rows())
    if not args.no_json_out:
        path = common.write_artifact(args.json_out)
        print(f"\nwrote {len(common.rows())} rows -> {path}",
              file=sys.stderr)
    # roofline table (requires dry-run artifacts)
    try:
        from benchmarks import roofline
        recs = roofline.load_records()
        if recs:
            print("\n=== roofline (from dry-run artifacts) ===")
            roofline.main()
    except Exception:
        traceback.print_exc()
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
