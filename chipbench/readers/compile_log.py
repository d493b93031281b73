"""Executables compiled, or read from the persistent cache, inside the
measured window: entries of the program's always-on compile log
(``chainermn_tpu.telemetry.compile_log``, stamped on
``time.perf_counter``) that fall in ``run.window``.  The whole window,
not only its traced end.  ``None`` where the program keeps no such
log."""


def read(run):
    try:
        from chainermn_tpu import telemetry
    except ImportError:
        return None
    log = getattr(telemetry, 'compile_log', None)
    if log is None or run.window is None:
        return None
    lo, hi = run.window
    inside = [(t, seconds) for t, _, seconds in list(log)
              if lo <= t <= hi]
    for t, seconds in inside:
        run.say('compiled in the window: %.3f s, ending %.2f s in'
                % (seconds, t - lo))
    return len(inside)
