"""Front end and scheduler: admission time (the program's
RequestState.admit_t) minus submit time, one perf_counter clock, 90th
percentile over the window's requests; one never admitted sorts above
every value."""
from harness.loadgen import pct


def read(run):
    sent = [r for r in run.window if r.sent_t is not None]
    waits = [(r.admit_t - r.sent_t) * 1e3 for r in sent
             if r.admit_t is not None]
    if not sent:
        return None
    return pct(waits, 90, len(sent) - len(waits))
