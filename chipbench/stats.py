"""Latency arithmetic on client stamps.  Pure Python: the same numbers
from the same stamps on any machine.

A *record* is one request as the client saw it: ``submit`` (seconds),
``tokens`` (the stamp of each output token, in order) and ``n_out``
(the output length it asked for)."""

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; ``None`` of nothing."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def ttft_samples(records, t0, t1):
    """Submit -> first token, of the requests SUBMITTED inside
    ``[t0, t1)`` (their first token may land after ``t1``: the caller
    drains those before it reduces)."""
    return [r['tokens'][0] - r['submit'] for r in records
            if t0 <= r['submit'] < t1 and r['tokens']]


def tpot_samples(records, t0, t1):
    """Per request (last token - first token) / (tokens - 1), of the
    requests that COMPLETED with first and last token inside the
    window."""
    out = []
    for r in records:
        toks = r['tokens']
        if len(toks) < 2 or len(toks) < r['n_out']:
            continue
        if toks[0] >= t0 and toks[-1] < t1:
            out.append((toks[-1] - toks[0]) / (len(toks) - 1))
    return out


def itl_samples(records, t0, t1):
    """Every gap between two consecutive tokens of one request, both
    stamped inside the window."""
    out = []
    for r in records:
        toks = r['tokens']
        out.extend(b - a for a, b in zip(toks, toks[1:])
                   if a >= t0 and b < t1)
    return out


def tokens_in_window(records, t0, t1):
    return sum(1 for r in records for t in r['tokens'] if t0 <= t < t1)


FAMILY = (('ttft', ttft_samples, (50, 75, 90, 95)),
          ('tpot', tpot_samples, (50, 90, 95)),
          ('itl', itl_samples, (50, 90, 99)))


def latency_family(records, t0, t1):
    """``{'ttft_p50_ms': ..., ..., 'n_ttft': ...}``: every tail the
    benchmark prints, from the same stamps, in milliseconds."""
    out = {}
    for name, fn, qs in FAMILY:
        xs = fn(records, t0, t1)
        out['n_%s' % name] = len(xs)
        for q in qs:
            v = percentile(xs, q)
            out['%s_p%d_ms' % (name, q)] = None if v is None else v * 1e3
    return out


def spread(values):
    """Distance between the first and the third quartile (Python's
    ``statistics.quantiles(values, n=4)``) as a share of the median:
    the driver's measure of run-to-run noise."""
    import statistics
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
