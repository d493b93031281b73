"""Device milliseconds per launch of the executables whose name holds
``pattern``, from the trace's ``XLA Modules`` line."""


def read(run, pattern):
    if run.trace is None:
        return None
    launches, seconds = run.trace.module(pattern)
    if not launches:
        return None
    return 1e3 * seconds / launches
