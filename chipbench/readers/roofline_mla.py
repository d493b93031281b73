"""A ``xing4`` serving kernel's share of its roofline, and the device
time of its residual path: what a kernel must move or compute
(``chipbench/mla_rooflines.py``, from the program's own counters on the
``serve_decode`` / ``serve_prefill`` spans) over its seconds in the
device trace.

``chipbench/trace.py`` labels an operation by its kind and result type,
so each is found by the type its output has in this cell:

``mla_decode``
    the paged decode kernel over the latent leaf: ``bf16[rows, 1,
    heads, kv_lora_rank]`` for each decode bucket.
``mla_prefill``
    the ``flash_attention`` forward at 192 / 128 in the prefill
    executables: the Pallas call whose result is ``(bf16[heads, T,
    v_head_dim], f32[heads, 1, T])``.
``moe_decode``
    the expert kernel in the decode executable: ``bf16[rows x k,
    hidden]`` (rows x k up to whole 16-row tiles) for each decode
    bucket; the prompts of this cell are all longer than 48 tokens, so
    no prefill bucket launches a kernel of such a type in the window.
``mhc_coeff``
    the coefficient kernel in the decode executable: ``f32[24, rows]``.
``mhc_decode_ms``
    milliseconds a decode launch: the coefficient kernel and every
    operation of XLA's whose result holds the streams (``[rows, 4,
    hidden]``) or the coefficients (``[24, rows]``, ``[rows, 24]``,
    ``[rows, 4, 4]``).  The read ``u = H_pre X`` has the type of any
    hidden row and is counted only where XLA fuses it into such an
    operation.

The counters are per launch and the trace counts launches, so a
numerator is (mean over the recorder's spans) x (launches in the traced
window), as ``readers/roofline.py`` has it.  Returns ``None`` without a
device trace, for a configuration of another family, without the
counters (a program older than them) or where no such operation is in
the trace."""

from chipbench import mla_rooflines, peaks
from chipbench.readers import program_span, roofline
from chipbench.readers.roofline_hybrid import _shapes


def _decode_buckets(engine):
    from chainermn_tpu.serving.batcher import bucket_edges
    return bucket_edges(engine['n_slots'])


def _sum(ops, keep):
    return sum(s for label, s in ops.items() if keep(label))


def residual_path_operation(label, cfg, rows):
    """Is this operation of a decode executable over ``rows`` rows part
    of the residual path: its result holds the streams or the
    coefficients."""
    n, d = cfg['hc_mult'], cfg['hidden_size']
    # not (rows, n): the router's chosen experts are (rows, k), k = n
    wanted = {(rows, n, d), (n * (n + 2), rows), (rows, n * (n + 2)),
              (rows, n, n)}
    return any(shape in wanted for shape in _shapes(label))


def read(run, what):
    cfg = run.spec.cfg
    if run.trace is None or 'kv_lora_rank' not in cfg:
        return None
    engine = run.spec.mix['engine']
    kind = run.devices[0].device_kind
    flops_per_s = 1e12 * peaks.peak(kind, 'bf16_tflops')
    bytes_per_s = 1e9 * peaks.peak(kind, 'hbm_gbs')
    ops = run.trace.op_seconds
    heads = cfg['num_attention_heads']
    if what == 'mla_prefill':
        launches, _ = run.trace.module('prefill')
        records = program_span.records_in_window(run)
        tokens = [float(r['tokens']) for r, _, _ in records or ()
                  if r['name'] == 'serve_prefill' and 'tokens' in r]
        head = 'pallas custom-call (bf16[%d,' % heads
        tail = ',%d], f32[%d,1,' % (cfg['v_head_dim'], heads)
        seconds = _sum(ops, lambda label: label.startswith(head)
                       and tail in label)
        if not tokens or not launches or not seconds:
            return None
        entries = sum(mla_rooflines.causal_entries(t)
                      for t in tokens) / len(tokens)
        return mla_rooflines.share(
            mla_rooflines.mla_prefill_flops(cfg, entries) * launches,
            flops_per_s, seconds)
    launches, _ = run.trace.module('decode')
    buckets = _decode_buckets(engine)
    if what == 'mla_decode':
        tail = ',1,%d,%d]' % (heads, cfg['kv_lora_rank'])
        seconds = _sum(ops, lambda label: label.startswith(
            'pallas custom-call bf16[') and label.endswith(tail))
        mean = roofline._mean_attrs(run, 'serve_decode',
                                    ['latent_positions'])
        if mean is None or not launches or not seconds:
            return None
        least = mla_rooflines.mla_decode_least_seconds(
            cfg, mean[0] * launches, flops_per_s, bytes_per_s)
        return 100.0 * least / seconds
    if what == 'moe_decode':
        k = cfg['num_experts_per_tok']
        labels = {'pallas custom-call bf16[%d,%d]'
                  % (-(-b * k // 16) * 16, cfg['hidden_size'])
                  for b in buckets}
        seconds = _sum(ops, lambda label: label in labels)
        mean = roofline._mean_attrs(run, 'serve_decode',
                                    ['experts_touched'])
        if mean is None or not launches or not seconds:
            return None
        return mla_rooflines.share(
            mla_rooflines.moe_decode_bytes(cfg, mean[0]) * launches,
            bytes_per_s, seconds)
    coefficients = cfg['hc_mult'] * (cfg['hc_mult'] + 2)
    kernels = {'pallas custom-call f32[%d,%d]' % (coefficients, b)
               for b in buckets}
    if what == 'mhc_coeff':
        seconds = _sum(ops, lambda label: label in kernels)
        mean = roofline._mean_attrs(run, 'serve_decode', ['bucket'])
        if mean is None or not launches or not seconds:
            return None
        return mla_rooflines.share(
            mla_rooflines.mhc_coeff_bytes(cfg, mean[0]) * launches,
            bytes_per_s, seconds)
    if what == 'mhc_decode_ms':
        seconds = _sum(ops, lambda label: label in kernels or (
            not label.startswith('pallas ')
            and any(residual_path_operation(label, cfg, b)
                    for b in buckets)))
        if not launches or not seconds:
            return None
        return 1e3 * seconds / launches
    raise KeyError(what)
