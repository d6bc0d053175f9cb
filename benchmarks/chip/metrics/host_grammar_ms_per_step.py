"""Host grammar: the program's own host_grammar attribution seconds (its
ci_lookup, cd_check, host_oracle, plan and feed_build phase spans, a
perf_counter bracket each) over the window's decode steps."""


def _host(stats):
    return ((stats.attribution or {}).get("seconds") or {}).get(
        "host_grammar")


def read(run):
    a, b = _host(run.stats0), _host(run.stats1)
    steps = run.stats1.decode_steps - run.stats0.decode_steps
    if a is None or b is None or steps <= 0:
        return None
    return 1e3 * (b - a) / steps
