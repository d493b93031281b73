"""Milliseconds of the measured window per trainer step."""


def read(run):
    steps = run.counters.get('steps')
    if not steps:
        return None
    t0, t1 = run.window
    return 1e3 * (t1 - t0) / steps
