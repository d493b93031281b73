"""Share of the measured window the loop spent inside the named spans."""


def read(run, spans):
    if run.window is None or not run.spans:
        return None
    t0, t1 = run.window
    return 100.0 * sum(run.span_seconds(s) for s in spans) / (t1 - t0)
