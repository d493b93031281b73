"""A ``solar_open2`` serving kernel's share of its roofline: what it
must move or compute (``chipbench/solar_rooflines.py``, from the
program's own counters on the ``serve_decode`` / ``serve_prefill``
spans) over its seconds in the device trace.

``chipbench/trace.py`` labels an operation by its kind and result type,
so each is found by the type its output has in this cell:

``state_decode``
    the ``gated_delta_step`` kernel with a decay per key channel: the
    one Pallas call whose result holds the state leaf, ``f32[1 + slots,
    heads, dk, dv]``.
``attn_decode``
    the paged decode kernel: ``bf16[rows, kv heads, group, head_dim]``
    for each decode bucket.
``moe_decode``
    the expert kernel in the decode executable: ``bf16[slots * k,
    hidden]`` (the prefill bucket of ``slots`` positions gives the same
    type; the cell's prompts are sixteen times longer).
``scan_prefill``
    the chunked per-channel rule in the prefill executables, which is
    XLA's own operations and no kernel: every operation whose result
    has the rule's layout (:func:`scan_operation`, at the chunk and
    segment sizes the program states: :func:`rule_sizes`), the loops
    that CONTAIN them left out.  The projections, the
    convolutions and the norms around the rule, and the copies that
    bring its operands into that layout, are not the rule and are left
    out.

The counters are per launch and the trace counts launches, so the
numerator is (mean over the recorder's spans) x (launches in the traced
window), as ``readers/roofline.py`` has it.  Returns ``None`` without a
device trace, for a configuration of another family, without the
counters or where no such operation is in the trace."""

from chipbench import peaks, solar_rooflines
from chipbench.readers import roofline
from chipbench.readers.roofline_hybrid import _shapes


def rule_sizes():
    """``(positions the program's per-channel rule solves at once,
    positions of a prompt it takes before its state moves on)``, read
    from the program, so that a program that changes either is still
    read; ``None`` of a program without the family."""
    try:
        from chainermn_tpu.models.solar_open2 import SEGMENT
        from chainermn_tpu.ops.gated_delta import CHANNEL_CHUNK
    except ImportError:
        return None
    return CHANNEL_CHUNK, SEGMENT


def scan_operation(label, cfg, chunk, segment):
    """Is this operation of a prefill executable part of the chunked
    rule?  Before the scan over chunks its arrays lead with ``(heads,
    chunks of a segment)``: at a chunk of 64 and a segment of 1,024 the
    chunks' operands ``(64, 16, 64, .)``, the sub-blocks' pair arrays
    ``(64, 16, 4, 16, .)`` and the blocks of the triangular systems;
    what the scan consumes leads with ``(chunks, heads)``; inside it
    the arrays are ``(heads, chunk or dk, .)``.  Nothing else in the
    executable has such a type: a K/V page is ``(pages, 8, 64, 128)``,
    attention's arrays ``(64, bucket, 128)`` with a bucket of at least
    1,024, the experts' rows hold no head count."""
    lin = cfg['linear_attn_config']
    heads, dim = lin['num_heads'], lin['head_dim']
    chunks = segment // chunk
    for shape in _shapes(label):
        if len(shape) < 3:
            continue
        if shape[:2] in ((heads, chunks), (chunks, heads)):
            return True
        if (len(shape) == 3 and shape[0] == heads
                and shape[1] in (chunk, dim)
                and shape[2] in (chunk, dim, 2 * dim)):
            return True
    return False


def read(run, what):
    cfg = run.spec.cfg
    if run.trace is None or cfg.get('family') != 'solar_open2':
        return None
    engine = run.spec.mix['engine']
    kind = run.devices[0].device_kind
    ops = run.trace.op_seconds
    hbm = 1e9 * peaks.peak(kind, 'hbm_gbs')
    if what == 'scan_prefill':
        launches, _ = run.trace.module('prefill')
        mean = roofline._mean_attrs(run, 'serve_prefill', ['scan_tokens'])
        sizes = rule_sizes()
        # a loop is an event OVER its body's events (the segments'
        # loop, the scan over chunks): counted, it would count its body
        # twice
        seconds = sizes and sum(
            s for label, s in ops.items()
            if not label.startswith(('pallas ', 'while '))
            and scan_operation(label, cfg, *sizes))
        if mean is None or not launches or not seconds:
            return None
        least = solar_rooflines.scan_prefill_least_seconds(
            cfg, mean[0] * launches,
            1e12 * peaks.peak(kind, 'bf16_tflops'), hbm)
        return 100.0 * least / seconds
    launches, _ = run.trace.module('decode')
    lin = cfg['linear_attn_config']
    if what == 'state_decode':
        leaf = 'f32[%d,%d,%d,%d]' % (
            1 + engine['n_slots'], lin['num_heads'], lin['head_dim'],
            lin['head_dim'])
        seconds = sum(s for label, s in ops.items()
                      if label.startswith('pallas ') and leaf in label)
        attr, needed = 'state_rows', solar_rooflines.state_decode_bytes
    elif what == 'attn_decode':
        heads = cfg['num_key_value_heads']
        tail = ',%d,%d,%d]' % (heads, cfg['num_attention_heads'] // heads,
                               cfg['head_dim'])
        seconds = sum(s for label, s in ops.items()
                      if label.startswith('pallas custom-call bf16[')
                      and label.endswith(tail))
        attr, needed = 'kv_positions', solar_rooflines.attn_decode_bytes
    elif what == 'moe_decode':
        seconds = ops.get('pallas custom-call bf16[%d,%d]' % (
            engine['n_slots'] * cfg['num_experts_per_tok'],
            cfg['hidden_size']), 0.0)
        attr, needed = 'experts_touched', solar_rooflines.moe_decode_bytes
    else:
        raise KeyError(what)
    mean = roofline._mean_attrs(run, 'serve_decode', [attr])
    if mean is None or not launches or not seconds:
        return None
    return solar_rooflines.share(needed(cfg, mean[0]) * launches, hbm,
                                 seconds)
