"""An ``olmo_hybrid`` serving kernel's share of its roofline: what it
must move or compute (``chipbench/hybrid_rooflines.py``, from the
program's own counters on the ``serve_decode`` / ``serve_prefill``
spans) over its seconds in the device trace.

``chipbench/trace.py`` labels an operation by its kind and result type,
so each is found by the type its output has in this cell:

``state_decode``
    the ``gated_delta_step`` kernel: the one Pallas call whose result
    holds the state leaf, ``f32[1 + slots, heads / P, dk, P * dv]``.
``attn_decode``
    the paged decode kernel: ``bf16[rows, kv heads, group, head_dim]``
    for each decode bucket.
``scan_prefill``
    the chunked rule in the prefill executables, which is XLA's own
    operations and no kernel: every operation whose result has the
    rule's layout, the head count with a chunk of 32 positions, a block
    of its triangular system or the ``(dk, dv)`` state
    (:func:`scan_operation`).  The projections, the convolution and the
    norms around the rule are not the rule and are left out.

The counters are per launch and the trace counts launches, so the
numerator is (mean over the recorder's spans) x (launches in the traced
window), as ``readers/roofline.py`` has it.  Returns ``None`` without a
device trace, for a configuration of another family, without the
counters (a program older than them) or where no such operation is in
the trace."""

import re

from chipbench import hybrid_rooflines, peaks
from chipbench.readers import roofline

#: positions the program's chunked rule solves at once
#: (``chainermn_tpu.ops.gated_delta.CHUNK``; a constant of the yardstick
#: here, so that a program that changes it is seen to)
CHUNK = 32
_SHAPE = re.compile(r'\[([\d,]+)\]')


def _shapes(label):
    return [tuple(int(n) for n in dims.split(','))
            for dims in _SHAPE.findall(label)]


def state_leaf(cfg, rows):
    """The state leaf's shape as the program lays it out
    (``ops.state_shape``); ``None`` of a program without one."""
    try:
        from chainermn_tpu.ops import state_shape
    except ImportError:
        return None
    return state_shape(rows, cfg['linear_num_value_heads'],
                       cfg['linear_key_head_dim'],
                       cfg['linear_value_head_dim'])


def scan_operation(label, cfg):
    """Is this operation of a prefill executable part of the chunked
    rule?  Its arrays hold the head count, and behind it a chunk of 32
    positions (``(heads, chunks, 32, .)`` before the scan over chunks,
    ``(chunks, 32, heads, .)`` where XLA transposes into that,
    ``(heads, 32, .)`` inside the scan), the ``(dk, dv)`` state, or a
    16-wide block of the chunk's triangular system; the last dim is one
    of 16, 32, dk, dv, dk + dv.  Nothing else in the
    executable holds the head count and a chunk: attention's arrays end
    in ``head_dim``, the projections' hold no head count."""
    heads, dk, dv = (cfg['linear_num_value_heads'],
                     cfg['linear_key_head_dim'],
                     cfg['linear_value_head_dim'])
    block = 16
    for shape in _shapes(label):
        if (len(shape) < 3 or heads not in shape
                or shape[-1] not in (block, CHUNK, dk, dv, dk + dv)):
            continue
        if (CHUNK in shape or shape[-2:] == (dk, dv)
                or (shape[-1] == block
                    and (shape[-2] == block or len(shape) > 3))):
            return True
    return False


def read(run, what):
    cfg = run.spec.cfg
    if run.trace is None or 'linear_num_value_heads' not in cfg:
        return None
    engine = run.spec.mix['engine']
    kind = run.devices[0].device_kind
    ops = run.trace.op_seconds
    if what == 'scan_prefill':
        launches, _ = run.trace.module('prefill')
        mean = roofline._mean_attrs(run, 'serve_prefill', ['scan_tokens'])
        seconds = sum(s for label, s in ops.items()
                      if not label.startswith('pallas ')
                      and scan_operation(label, cfg))
        if mean is None or not launches or not seconds:
            return None
        least = hybrid_rooflines.scan_prefill_least_seconds(
            cfg, mean[0] * launches,
            1e12 * peaks.peak(kind, 'bf16_tflops'),
            1e9 * peaks.peak(kind, 'hbm_gbs'))
        return 100.0 * least / seconds
    launches, _ = run.trace.module('decode')
    if what == 'state_decode':
        shape = state_leaf(cfg, 1 + engine['n_slots'])
        if shape is None:
            return None
        leaf = 'f32[%s]' % ','.join(map(str, shape))
        seconds = sum(s for label, s in ops.items()
                      if label.startswith('pallas ') and leaf in label)
        mean = roofline._mean_attrs(run, 'serve_decode', ['state_rows'])
        needed = mean and hybrid_rooflines.state_decode_bytes(cfg, *mean)
    elif what == 'attn_decode':
        heads = cfg['num_key_value_heads']
        tail = ',%d,%d,%d]' % (heads, cfg['num_attention_heads'] // heads,
                               cfg['hidden_size']
                               // cfg['num_attention_heads'])
        seconds = sum(s for label, s in ops.items()
                      if label.startswith('pallas custom-call bf16[')
                      and label.endswith(tail))
        mean = roofline._mean_attrs(run, 'serve_decode', ['kv_positions'])
        needed = mean and hybrid_rooflines.attn_decode_bytes(cfg, *mean)
    else:
        raise KeyError(what)
    if not needed or not launches or not seconds:
        return None
    return hybrid_rooflines.share(needed * launches,
                                  1e9 * peaks.peak(kind, 'hbm_gbs'),
                                  seconds)
