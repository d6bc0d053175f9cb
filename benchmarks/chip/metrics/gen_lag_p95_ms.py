"""Load generator: how late requests were sent, send time minus due time
(client clock), 95th percentile over the window's requests. Open mixes
only: a backlog sends each request when it is due."""
from harness.loadgen import pct


def read(run):
    if run.mix["arrivals"]["kind"] != "poisson":
        return None
    lag = [(r.sent_t - r.due_t) * 1e3 for r in run.window
           if r.sent_t is not None]
    return pct(lag, 95) if lag else None
