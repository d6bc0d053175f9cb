"""Scheduler: the share of decode-slot steps that served a request,
over the window: the program's EngineStats tokens (slot steps) over
decode_steps x batch_slots."""


def read(run):
    steps = run.stats1.decode_steps - run.stats0.decode_steps
    if steps <= 0:
        return None
    used = run.stats1.tokens - run.stats0.tokens
    return 100.0 * used / (steps * run.stats1.batch_slots)
