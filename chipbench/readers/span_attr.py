"""A number the program hangs on its own spans as an attribute (an
expert counter on ``serve_decode``, page counts on ``serve_tick``),
over the span records of the traced window.

``attr`` alone: the mean of the attribute over the spans called
``span``, times ``scale``, over the configuration's ``over_cfg`` key
where one is given (experts touched over ``num_experts``).  With
``over``: the sum of ``attr`` over the sum of the attribute ``over``,
times ``scale``.  Spans without the attribute are left out; with none
(a program older than the attribute) the reader returns ``None``."""

from chipbench.readers import program_span


def read(run, span, attr, over=None, over_cfg=None, scale=1.0):
    records = program_span.records_in_window(run)
    if records is None:
        return None
    rows = [r for r, _, _ in records if r['name'] == span and attr in r
            and (over is None or over in r)]
    if not rows:
        return None
    top = sum(float(r[attr]) for r in rows)
    if over is not None:
        bottom = sum(float(r[over]) for r in rows)
        return None if not bottom else scale * top / bottom
    value = scale * top / len(rows)
    if over_cfg is not None:
        value /= float(run.spec.cfg[over_cfg])
    return value
