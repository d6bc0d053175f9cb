"""Model step: device time per execution of the jitted decode-step
program (the model's `decode_step`, which the overlap path also runs),
from the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    hits = [v for k, v in run.trace["programs"].items()
            if "decode_step" in k]
    secs, calls = sum(v[0] for v in hits), sum(v[1] for v in hits)
    return 1e3 * secs / calls if calls else None
