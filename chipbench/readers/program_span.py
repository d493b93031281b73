"""Milliseconds from the program's own spans: the records of
``chainermn_tpu.telemetry``'s recorder, laid onto the window's clock
through ``Recorder.to_perf_counter``.

Nothing here switches the recorder on.  In a ``--trace 1`` run the
program does that itself when it finds the profiler open, so the
records cover the traced LAST part of ``run.window`` (about 8 s), not
all of it; with ``CHAINERMN_TPU_TELEMETRY=1`` they cover all of it.
The reader says how much it found.  With no recorder, a recorder
without ``to_perf_counter`` (a program older than the spans) or no
record in the window it returns ``None``: never a number from
elsewhere.

``stat`` chooses the number:

``mean``
    mean duration of the spans called ``span``.
``p<q>``
    the ``q``-th percentile of their durations (``p75``).
``sum_per``
    the summed durations of the spans called any of ``spans``, in
    whatever thread, over the count of spans called ``per``.
``self_mean``
    per span called ``span``: its duration less its children (by
    ``parent``) called any of ``less``; the mean.  The split of it
    into the other children, by name, is printed.
"""

import collections

from chipbench import stats


def records_in_window(run):
    """``[(record, t0, t1)]`` of the span records that lie inside
    ``run.window``, times on ``time.perf_counter``; ``None`` where the
    program holds nothing to read.  Laid out once per run."""
    if hasattr(run, 'program_spans'):
        return run.program_spans
    run.program_spans = None
    try:
        from chainermn_tpu import telemetry
    except ImportError:
        return None
    rec = telemetry.active()
    lay = getattr(rec, 'to_perf_counter', None)
    if lay is None or run.window is None:
        return None
    lo, hi = run.window
    out = []
    for r in list(rec.events):
        if r.get('type') != 'span':
            continue
        t0, t1 = lay(r['t0']), lay(r['t1'])
        if t0 >= lo and t1 <= hi:
            out.append((r, t0, t1))
    if not out:
        return None
    covered = max(t1 for _, _, t1 in out) - min(t0 for _, t0, _ in out)
    run.say('program spans: %d records over %.2f s of the %.2f s '
            'window' % (len(out), covered, hi - lo))
    run.program_spans = out
    return out


def read(run, stat, span=None, spans=(), per=None, less=()):
    records = records_in_window(run)
    if records is None:
        return None
    if stat == 'sum_per':
        n = sum(1 for r, _, _ in records if r['name'] == per)
        if not n:
            return None
        total = sum(t1 - t0 for r, t0, t1 in records
                    if r['name'] in spans)
        return 1e3 * total / n
    own = [(r, t1 - t0) for r, t0, t1 in records if r['name'] == span]
    if not own:
        return None
    if stat == 'mean':
        return 1e3 * sum(d for _, d in own) / len(own)
    if stat.startswith('p'):
        return 1e3 * stats.percentile([d for _, d in own],
                                      float(stat[1:]))
    if stat != 'self_mean':
        raise KeyError(stat)
    ids = {r['id'] for r, _ in own}
    children = collections.defaultdict(float)   # (parent, name) -> s
    for r, t0, t1 in records:
        if r.get('parent') in ids:
            children[r['parent'], r['name']] += t1 - t0
    left = [d - sum(children.get((r['id'], name), 0.0) for name in less)
            for r, d in own]
    split = collections.Counter()
    for (_, name), seconds in children.items():
        if name not in less:
            split[name] += seconds
    split['self'] = sum(left) - sum(split.values())
    run.say('%s less %s, mean ms over %d: %s'
            % (span, '/'.join(less), len(own), ', '.join(
                '%s %.3f' % (k, 1e3 * v / len(own))
                for k, v in split.most_common())))
    return 1e3 * sum(left) / len(left)
