"""A ``phi4flash`` serving kernel's share of its roofline: what it must
move or compute (``chipbench/ssm_rooflines.py``, from the program's own
counters on the ``serve_decode`` / ``serve_prefill`` spans) over its
seconds in the device trace; and ``shared_kv``, which is counters alone.

``chipbench/trace.py`` labels a Pallas kernel by its result type, so
each is found by the type its output has in this cell:

``ssm_decode``
    the ``selective_scan_step`` kernel: the one Pallas call whose
    result holds the state leaf, ``f32[1 + slots, 1, d_state,
    d_inner]``.
``ssm_prefill``
    the ``selective_scan`` kernel of the prefill executables: the one
    Pallas call whose result holds a lone state, ``f32[d_state,
    d_inner]`` (beside ``m`` of the bucket's length).
``attn_decode``
    the paged decode kernel, all 16 calls of a tick (8 readers of the
    shared leaf, 8 window layers): ``bf16[rows, Hkv / 2, group, 2 *
    head_dim]`` for each decode bucket.
``shared_kv``
    no kernel: ``shared_kv_positions`` x 5,120 B over all the bytes a
    tick must read, summed over the ``serve_decode`` spans of the
    traced window.

The counters are per launch and the trace counts launches, so a
numerator is (mean over the recorder's spans) x (launches in the traced
window), as ``readers/roofline.py`` has it.  Returns ``None`` without a
device trace (but for ``shared_kv``), for a configuration of another
family, without the counters (a program older than them) or where no
such operation is in the trace."""

from chipbench import peaks, ssm_rooflines
from chipbench.readers import roofline

_DECODE_ATTRS = ['state_rows', 'shared_kv_positions',
                 'kv_window_positions']


def _pallas_seconds(run, part):
    return sum(s for label, s in run.trace.op_seconds.items()
               if label.startswith('pallas ') and part in label)


def read(run, what):
    cfg = run.spec.cfg
    if cfg.get('family') != 'phi4flash':
        return None
    if what == 'shared_kv':
        mean = roofline._mean_attrs(run, 'serve_decode', _DECODE_ATTRS)
        return mean and ssm_rooflines.shared_kv_read_share(cfg, *mean)
    if run.trace is None:
        return None
    di, n, _, _ = ssm_rooflines.reference.widths(cfg)
    engine = run.spec.mix['engine']
    kind = run.devices[0].device_kind
    hbm = 1e9 * peaks.peak(kind, 'hbm_gbs')
    if what == 'ssm_prefill':
        launches, _ = run.trace.module('prefill')
        mean = roofline._mean_attrs(run, 'serve_prefill', ['scan_tokens'])
        seconds = _pallas_seconds(run, ' f32[%d,%d])' % (n, di))
        if mean is None or not launches or not seconds:
            return None
        least = ssm_rooflines.scan_prefill_least_seconds(
            cfg, mean[0] * launches,
            1e12 * peaks.peak(kind, 'bf16_tflops'), hbm)
        return 100.0 * least / seconds
    launches, _ = run.trace.module('decode')
    mean = roofline._mean_attrs(run, 'serve_decode', _DECODE_ATTRS)
    if what == 'ssm_decode':
        seconds = _pallas_seconds(run, 'f32[%d,1,%d,%d]' % (
            1 + engine['n_slots'], n, di))
        needed = mean and ssm_rooflines.ssm_decode_bytes(cfg, mean[0])
    elif what == 'attn_decode':
        pairs = cfg['num_key_value_heads'] // 2
        tail = ',%d,%d,%d]' % (
            pairs, cfg['num_attention_heads'] // pairs,
            2 * cfg['hidden_size'] // cfg['num_attention_heads'])
        seconds = sum(s for label, s in run.trace.op_seconds.items()
                      if label.startswith('pallas custom-call bf16[')
                      and label.endswith(tail))
        needed = mean and ssm_rooflines.attn_decode_bytes(cfg, *mean[1:])
    else:
        raise KeyError(what)
    if not needed or not launches or not seconds:
        return None
    return ssm_rooflines.share(needed * launches, hbm, seconds)
