"""A serving kernel's share of its roofline: what it must move or
compute (``chipbench/rooflines.py``, from the program's own counters on
the ``serve_decode`` / ``serve_prefill`` spans) over its seconds in the
device trace.

``chipbench/trace.py`` labels a Pallas kernel by its result type alone
(``pallas custom-call bf16[512,2048]``), so the kernel is found by the
type its output has in this cell: the expert kernel's ``(slots * k,
hidden)`` rows in the decode executable, its ``(bucket * k, hidden)``
rows in the prefill executables, the paged decode kernel's ``(slots,
kv heads, group, head_dim)``.  Nothing else in those executables is a
custom call of such a type (checked on the chip, PERF.md).

The counters are per launch and the trace counts launches, so the
numerator is (mean over the recorder's spans) x (launches in the traced
window): the two windows differ by a tick at each end, the mean does
not.  Returns ``None`` without a device trace, without the counters (a
program older than them) or where the kernel's label is not in the
trace."""

from chipbench import peaks, rooflines
from chipbench.readers import program_span


def _seconds(run, labels):
    return sum(run.trace.op_seconds.get(label, 0.0) for label in labels)


def _mean_attrs(run, span, attrs):
    records = program_span.records_in_window(run)
    if records is None:
        return None
    rows = [r for r, _, _ in records if r['name'] == span
            and all(a in r for a in attrs)]
    if not rows:
        return None
    return [sum(float(r[a]) for r in rows) / len(rows) for a in attrs]


def read(run, what):
    if run.trace is None or 'num_experts_per_tok' not in run.spec.cfg:
        return None
    cfg, engine = run.spec.cfg, run.spec.mix['engine']
    kind = run.devices[0].device_kind
    k, hidden = cfg['num_experts_per_tok'], cfg['hidden_size']
    rows = 'pallas custom-call bf16[%d,%d]'
    if what == 'moe_prefill':
        from chainermn_tpu.serving.batcher import bucket_edges
        labels = [rows % (b * k, hidden)
                  for b in bucket_edges(engine['max_prompt_len'])
                  if b != engine['n_slots']]
        launches, _ = run.trace.module('prefill')
        mean = _mean_attrs(run, 'serve_prefill', ['tokens'])
        seconds = _seconds(run, labels)
        if mean is None or not launches or not seconds:
            return None
        flops = rooflines.moe_prefill_flops(cfg, mean[0]) * launches
        return rooflines.share(
            flops, 1e12 * peaks.peak(kind, 'bf16_tflops'), seconds)
    launches, _ = run.trace.module('decode')
    if what == 'moe_decode':
        labels = [rows % (engine['n_slots'] * k, hidden)]
        mean = _mean_attrs(run, 'serve_decode', ['experts_touched'])
        needed = mean and rooflines.moe_decode_bytes(cfg, *mean)
    elif what == 'attn_decode':
        heads = cfg['num_key_value_heads']
        labels = ['pallas custom-call bf16[%d,%d,%d,%d]' % (
            engine['n_slots'], heads,
            cfg['num_attention_heads'] // heads, cfg['head_dim'])]
        mean = _mean_attrs(run, 'serve_decode',
                           ['kv_positions', 'kv_window_positions'])
        needed = mean and rooflines.attn_decode_bytes(cfg, *mean)
    else:
        raise KeyError(what)
    seconds = _seconds(run, labels)
    if not needed or not launches or not seconds:
        return None
    return rooflines.share(needed * launches,
                           1e9 * peaks.peak(kind, 'hbm_gbs'), seconds)
