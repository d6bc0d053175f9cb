"""The step loop's per-layer readers on the tiny cell on the CPU.

The profiler trace of a CPU run has no device plane, so the runs are
untraced, as in test_chipbench_faults.py; the readers here read only the
program's counters and request states, which an untraced run has too.
Each metric listed for a mix's cell in BENCHMARK.json must come back
from `Run.per_layer` as a number in its range: a share in [0, 100], a
time at least 0."""
import json
import math
import time

import jax
import pytest

import tiny
from harness import cell

jax.config.update("jax_enable_compilation_cache", False)
SEED = 2**31 + 4099
NEW = ("discarded_forward_share", "admitting_step_ms",
       "admit_to_first_token_p90_ms", "oracle_reject_share")
CELLS = {"poisson": "qwen1.5-0.5b.json.open",
         "backlog": "qwen1.5-0.5b.json.batch"}
BENCH = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
LISTED = [(kind, m["name"], m["unit"]) for kind in CELLS
          for m in BENCH["per_layer"]
          if m["name"] in NEW and CELLS[kind] in m["workloads"]]


@pytest.fixture(scope="module")
def runs():
    done = {}

    def get(kind):
        if kind not in done:
            run = cell.Run(tiny.spec(kind=kind), SEED, 3.0, False,
                           time.perf_counter())
            out = run.go()
            assert out["correct"], out["checks"]
            done[kind] = run.per_layer(0.0)
        return done[kind]
    return get


def test_every_new_metric_is_listed_for_a_cell():
    assert {name for _, name, _ in LISTED} == set(NEW)
    assert ("poisson", "admit_to_first_token_p90_ms", "ms") in LISTED
    assert ("backlog", "admit_to_first_token_p90_ms", "ms") not in LISTED


@pytest.mark.parametrize("kind,name,unit", LISTED,
                         ids=[f"{k}-{n}" for k, n, _ in LISTED])
def test_reader_gives_a_number_in_range(runs, kind, name, unit):
    got = runs(kind)
    assert name in got, sorted(got)
    v = got[name]["value"]
    assert got[name]["unit"] == unit
    assert isinstance(v, float) and math.isfinite(v), v
    if unit == "%":
        assert 0.0 <= v <= 100.0, v
    else:
        assert v >= 0.0, v
