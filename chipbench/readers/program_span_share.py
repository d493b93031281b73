"""Share of the traced window that the program's own records called
``span`` cover: 100 x their summed durations over the window's seconds
(``run.trace.window_s``; the records' own extent in a run without a
trace).  ``where`` keeps the records whose attributes equal its values
(``{"cause": "admission"}``).

Read from the recorder through ``program_span.records_in_window``, so
what that reader says of coverage holds here.  The split of the kept
records by each attribute in ``split`` (``after``, ``cause``) is
printed, in points of the same share.  Where the program writes spans
called ``since`` and no record matches, the share is 0.0; a program
that writes none (older than the records) gives ``None``: never a
number from elsewhere."""

import collections

from chipbench.readers import program_span


def read(run, span, since, where=None, split=()):
    records = program_span.records_in_window(run)
    if records is None \
            or not any(r['name'] == since for r, _, _ in records):
        return None
    where = where or {}
    kept = [(r, t1 - t0) for r, t0, t1 in records
            if r['name'] == span
            and all(r.get(k) == v for k, v in where.items())]
    trace = getattr(run, 'trace', None)
    if trace is not None:
        seconds = trace.window_s
    else:
        seconds = (max(t1 for _, _, t1 in records)
                   - min(t0 for _, t0, _ in records))
    if not seconds > 0:
        return None
    total = sum(d for _, d in kept)
    label = ' '.join([span] + ['%s=%s' % kv
                               for kv in sorted(where.items())])
    for attr in split if kept else ():
        parts = collections.Counter()
        for r, d in kept:
            parts[str(r.get(attr))] += d
        run.say('%s: %d records, %.3f s of %.3f s; points by %s: %s'
                % (label, len(kept), total, seconds, attr, ', '.join(
                    '%s %.2f' % (k, 100.0 * v / seconds)
                    for k, v in parts.most_common())))
    return 100.0 * total / seconds
