"""Scheduler: the share of the decode program's executions in the window
whose logits were thrown away. The program counts a speculative forward
of the host/device overlap where it drops it (EngineStats
overlap_discarded: an admission, or a step the host changed); the
executions are the consumed steps (decode_steps) plus those."""


def _delta(run, field):
    a = getattr(run.stats0, field, None)
    b = getattr(run.stats1, field, None)
    return None if a is None or b is None else b - a


def read(run):
    dropped = _delta(run, "overlap_discarded")
    steps = _delta(run, "decode_steps")
    if dropped is None or not steps:
        return None
    return 100.0 * dropped / (steps + dropped)
