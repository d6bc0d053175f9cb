"""Record the small TPU trace the trace-reduction test reads.

    python benchmarks/chip/tools/record_trace.py <out.xplane.pb>

Serves the tests' tiny cell (tests/tiny.py) on the chip for a handful of
decode steps under the profiler, with the clock mark the harness uses,
and copies the one xplane file to <out>. Needs a TPU.
"""
from __future__ import annotations

import asyncio
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE / "tests"))


def main(out: str) -> int:
    import tiny     # puts the harness and the program on sys.path
    import jax
    from harness import cell, loadgen
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    spec = tiny.spec()
    run = cell.Run(spec, 7, 1.0, False, time.perf_counter())
    run.served = cell.Served(spec["config"], spec["mix"], 7)
    run.traffic = loadgen.Traffic(spec["mix"], 7, run.served.vocab)

    async def go():
        await run._warm()
        tdir = tempfile.mkdtemp()
        jax.profiler.start_trace(tdir,
                                 profiler_options=cell.profile_options())
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(cell.CLOCK_MARK):
            pass
        for i in range(3):
            run._submit(run.traffic.request(i))
        await asyncio.gather(*run.tasks)
        jax.profiler.stop_trace()
        await run.served.aeng.drain()
        return tdir, t
    tdir, t = asyncio.run(go())
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out)
    print(f"mark perf_counter {t!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
