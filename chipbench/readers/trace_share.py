"""Shares read from the reduced device trace: ``pallas`` (time in
``tpu_custom_call`` kernels over device busy time), ``collective``
(time in all-reduce / reduce-scatter / all-gather ops on the core's
serial op line, where nothing else runs, over the traced window) and
``idle`` (1 - union of op intervals over the traced window)."""


def read(run, what):
    t = run.trace
    if t is None:
        return None
    if what == 'pallas':
        return 100.0 * t.pallas_s / t.busy_s
    if what == 'collective':
        return 100.0 * t.collective_s / t.window_s
    if what == 'idle':
        return t.idle_share
    raise KeyError(what)
