"""Mean duration of the scheduler ticks in the window, split by whether
the tick admitted a request (a prefill ran in it) or only decoded."""


def read(run, admitted):
    ticks = getattr(run, 'ticks', None)
    if not ticks:
        return None
    chosen = [t1 - t0 for t0, t1, n_admitted, _ in ticks
              if bool(n_admitted) == admitted]
    if not chosen:
        return None
    return 1e3 * sum(chosen) / len(chosen)
