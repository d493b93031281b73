"""Busy slots over slots, averaged over the decode ticks of the window
(a tick's tokens less its first tokens are its decoding slots)."""


def read(run):
    ticks = getattr(run, 'ticks', None)
    if not ticks:
        return None
    slots = run.counters['n_slots']
    busy = [(tokens - admitted) / slots
            for _, _, admitted, tokens in ticks if tokens > admitted]
    if not busy:
        return None
    return 100.0 * sum(busy) / len(busy)
