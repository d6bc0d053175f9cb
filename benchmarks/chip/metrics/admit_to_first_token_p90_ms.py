"""Front end and scheduler: the program's first commit of a request's
first token (RequestState.first_commit_t) minus its admission
(admit_t), one perf_counter clock, 90th percentile over the window's
admitted requests; one never served sorts above every value. The 90th
percentile of the client's first token minus first_commit_t (the hop
from the step-loop thread to the client) is printed on standard error."""
import sys

from harness.loadgen import pct


def read(run):
    states = [r.state for r in run.window if r.state is not None]
    if not any(hasattr(st, "first_commit_t") for st in states):
        return None
    admitted = [st for st in states if st.admit_t is not None]
    if not admitted:
        return None
    waits = [(st.first_commit_t - st.admit_t) * 1e3 for st in admitted
             if st.first_commit_t is not None]
    hops = [(r.first_t - r.state.first_commit_t) * 1e3 for r in run.window
            if r.first_t is not None and r.state is not None
            and r.state.first_commit_t is not None]
    if hops:
        print(f"admit_to_first_token_p90_ms: client first token minus "
              f"first commit, p90 {pct(hops, 90):.3f} ms over {len(hops)}",
              file=sys.stderr)
    return pct(waits, 90, len(admitted) - len(waits))
