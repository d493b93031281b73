"""A ``deepseek_v3`` TRAINING kernel family's share of the MXU's peak:
what its kernels must compute (``chipbench/kanana_rooflines.py``) over
their seconds in the device trace.

``chipbench/trace.py`` labels a Pallas kernel by its result type, so
each is found by the type its output has in this cell (read off the
step compiled for a described v5e):

``mla_train``
    ``ops.flash_attention`` at 192 / 128 over ``batch x heads`` rows of
    ``seq_len``: the forward ``(bf16[BH,T,128], f32[BH,1,T])`` (twice a
    layer where the layer is recomputed), dQ ``bf16[BH,T,192]``, dK/dV
    ``(bf16[BH,T,192], bf16[BH,T,128])``.
``moe_train``
    ``ops.grouped_swiglu`` over the ``T x k`` assignment rows: the
    forward ``bf16[N,hidden]``, the data gradient ``(bf16[N,hidden],
    bf16[N,width] x 3)``, the weight gradient ``(bf16[E,hidden,width]
    x 2, bf16[E,width,hidden])``.  The rows COMPUTED are the held
    assignments, read from the ``held_assignments`` attribute the
    trainer hangs on its ``train_update`` span.

A numerator is a step's work x the launches of the train step in the
traced window.  Returns ``None`` without a device trace, for another
family or kind of traffic, without the counter (a program older than
it) or where no such operation is in the trace."""

from chipbench import kanana_rooflines, peaks
from chipbench.readers import roofline


def _seconds(run, labels):
    return sum(run.trace.op_seconds.get('pallas custom-call ' + label, 0.0)
               for label in labels)


def read(run, what):
    cfg, mix = run.spec.cfg, run.spec.mix
    if (run.trace is None or mix.get('kind') != 'train'
            or 'kv_lora_rank' not in cfg):
        return None
    launches, _ = run.trace.module('train_step')
    if not launches:
        return None
    peak = 1e12 * peaks.peak(run.devices[0].device_kind, 'bf16_tflops')
    rows = mix['batch'] // len(run.devices)
    t, d = mix['seq_len'], cfg['hidden_size']
    if what == 'mla_train':
        bh = rows * cfg['num_attention_heads']
        wide = 'bf16[%d,%d,%d]' % (
            bh, t, cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim'])
        narrow = 'bf16[%d,%d,%d]' % (bh, t, cfg['v_head_dim'])
        seconds = _seconds(run, [
            '(%s, f32[%d,1,%d])' % (narrow, bh, t), wide,
            '(%s, %s)' % (wide, narrow)])
        flops = kanana_rooflines.mla_train_flops(cfg, t, rows)
    elif what == 'moe_train':
        mean = roofline._mean_attrs(run, 'train_update',
                                    ['held_assignments'])
        if mean is None:
            return None
        n = rows * t * cfg['num_experts_per_tok']
        f, e = cfg['moe_intermediate_size'], cfg['n_routed_experts']
        out, side = 'bf16[%d,%d]' % (n, d), 'bf16[%d,%d]' % (n, f)
        up = 'bf16[%d,%d,%d]' % (e, d, f)
        seconds = _seconds(run, [
            out, '(%s)' % ', '.join([out] + [side] * 3),
            '(%s, %s, bf16[%d,%d,%d])' % (up, up, e, f, d)])
        flops = kanana_rooflines.moe_train_flops(cfg, mean[0])
    else:
        raise KeyError(what)
    if not seconds:
        return None
    return kanana_rooflines.share(flops * launches, peak, seconds)
