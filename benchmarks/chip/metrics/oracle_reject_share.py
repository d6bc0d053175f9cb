"""Host grammar: the share of the device's picks that the exact oracle
rejected on the host, over the window (EngineStats oracle_rejected over
oracle_accepted + oracle_rejected). Resample dispatches and exact-filter
fallbacks per decode step are printed on standard error."""
import sys


def _delta(run, field):
    a = getattr(run.stats0, field, None)
    b = getattr(run.stats1, field, None)
    return None if a is None or b is None else b - a


def read(run):
    rejected = _delta(run, "oracle_rejected")
    accepted = _delta(run, "oracle_accepted")
    if rejected is None or accepted is None or rejected + accepted <= 0:
        return None
    steps = _delta(run, "decode_steps")
    if steps:
        resample = _delta(run, "resample_calls") / steps
        fallback = _delta(run, "exact_fallbacks") / steps
        print(f"oracle_reject_share: per step {resample:.4f} resample "
              f"calls, {fallback:.4f} exact fallbacks", file=sys.stderr)
    return 100.0 * rejected / (rejected + accepted)
