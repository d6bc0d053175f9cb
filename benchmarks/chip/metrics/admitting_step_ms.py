"""Scheduler: wall time of a step of the program's step loop (its `step`
span, admission through commit, a perf_counter bracket) over the steps
in the window that admitted a request. The other steps' mean is printed
beside it on standard error."""
import sys


def _delta(run, field):
    a = getattr(run.stats0, field, None)
    b = getattr(run.stats1, field, None)
    return None if a is None or b is None else b - a


def read(run):
    secs, steps = _delta(run, "admit_step_s"), _delta(run, "admit_steps")
    if secs is None or not steps:
        return None
    rest_s = _delta(run, "step_s") - secs
    rest = _delta(run, "steps_timed") - steps
    if rest > 0:
        print(f"admitting_step_ms: {steps} steps admitted; the other "
              f"{rest} took {1e3 * rest_s / rest:.3f} ms each",
              file=sys.stderr)
    return 1e3 * secs / steps
