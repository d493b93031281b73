#!/usr/bin/env python
"""On-chip allreduce payload sweep: the single-chip component of the
collective story, measured (VERDICT r4 next #5).

On the one real chip the collective proper is a mesh=(1,1) loopback --
``psum`` over a size-1 axis is identity and XLA folds
slice-of-concatenate, so a bare ``allreduce_grad`` chain can
legitimately compile to nothing (the round-4 row: value 0.0,
unmeasurable).  What a single chip CAN measure honestly:

1. **HBM bandwidth roofline** -- marginal time of an elementwise
   touch of a large buffer (read + write = 2x bytes), the same
   self-calibration idea as bench.py's matmul roofline.
2. **Per-strategy staging cost** -- each scan step runs
   ``touch(c)`` then ``comm.allreduce_grad(...)``; the touch (a
   multiply by 1+1e-7 on every leaf) cannot be folded away, so every
   row has a real, linearity-checkable slope, and the difference
   ``row - baseline`` is the strategy's pack/unpack/reshard overhead
   (flat's fused big-buffer copy vs naive's per-leaf loopback vs
   hierarchical's scatter/gather staging).  That staging cost is the
   per-chip term of the scaling model in
   ``benchmarks/scaling_projection.py``; the ICI term is analytic.

Prints one JSON row per (strategy, payload); ``strategy='touch'``
rows are the elementwise floor.  Rows are suspect-gated exactly like
bench.py (linearity + signal-vs-noise).  Reference anchor: the
communicator strategy menu at
``/root/reference/chainermn/communicators/__init__.py:12-20``.

Usage::

    python benchmarks/allreduce_payload_sweep.py            # real TPU
    python benchmarks/allreduce_payload_sweep.py --cpu 8    # plumbing
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import (  # noqa: E402
    LINEARITY_GATE, SIGNAL_MULT, _noise_estimate, adaptive_marginal_time)

STRATEGIES = ('xla', 'flat', 'naive', 'hierarchical', 'bucketed')


def resnet_shaped_leaves(n_params):
    """A few large + many small leaves, like a real gradient pytree."""
    leaves = {}
    remaining = n_params
    i = 0
    for size in (2048 * 1000, 512 * 512 * 9, 2048 * 512, 1024 * 256):
        while remaining > size and len(leaves) <= 160:
            leaves['w%d' % i] = size
            remaining -= size
            i += 1
    leaves['tail'] = max(remaining, 1)
    return leaves


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--payloads', default='6400000,25600000',
                        help='comma list of payload sizes in params '
                             '(f32; default 6.4M and the '
                             'ResNet-50-sized 25.6M)')
    parser.add_argument('--strategies', default=','.join(STRATEGIES))
    parser.add_argument('--cpu', type=int, default=0, metavar='N',
                        help='force an N-virtual-device CPU platform')
    args = parser.parse_args()

    if args.cpu:
        import chainermn_tpu.utils as u
        u.force_host_devices(args.cpu)

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu

    chainermn_tpu.utils.enable_compilation_cache()

    n_dev = jax.device_count()
    inter = 2 if n_dev % 2 == 0 and n_dev > 1 else 1

    def emit(row):
        print(json.dumps(row), flush=True)

    # --- 1. HBM bandwidth roofline: touch 256 MB, marginal slope ----
    cal_words = 64 * 1024 * 1024  # 256 MB f32
    x0 = jnp.ones((cal_words,), jnp.float32)

    def make_cal(k):
        @jax.jit
        def run():
            def body(c, _):
                return c * jnp.float32(1.0 + 1e-7), ()
            out, _ = lax.scan(body, x0, None, length=k)
            return out[:1]
        return run

    # floor: read+write of the buffer at an optimistic 4 TB/s
    cal_floor = 2.0 * cal_words * 4 / 4e12
    per, _ov, times, lin, ks_used, esc = adaptive_marginal_time(
        make_cal, (4, 8, 12), reps=3, per_item_floor=cal_floor,
        max_rep_s=20.0)
    noise = _noise_estimate(times, 3)
    hbm_gbs = 2.0 * cal_words * 4 / per / 1e9
    cal_row = {
        'metric': 'hbm_touch_bandwidth', 'strategy': 'calibration',
        'payload_mb': round(cal_words * 4 / 1e6, 1),
        'value': round(per * 1e3, 4), 'unit': 'ms',
        'measured_hbm_gbs': round(hbm_gbs, 1),
        'scan_lengths': list(ks_used), 'adaptive_escalations': esc,
        'timing_noise_ms': round(noise * 1e3, 3),
        'linearity_rel_err': round(lin, 4),
        'n_devices': n_dev, 'backend': jax.default_backend(),
        'sync_method': 'device_get',
    }
    if lin > LINEARITY_GATE:
        cal_row['suspect'] = True
    if per * (ks_used[-1] - ks_used[0]) < SIGNAL_MULT * noise:
        cal_row['suspect'] = True
        cal_row['suspect_reason'] = 'marginal signal below noise floor'
    emit(cal_row)

    # --- 2. per-(payload, strategy) staging rows --------------------
    for n_params in (int(v) for v in args.payloads.split(',')):
        leaves = resnet_shaped_leaves(n_params)
        grads = {k: jnp.ones((v,), jnp.float32)
                 for k, v in leaves.items()}
        payload_bytes = n_params * 4
        touch_floor = 2.0 * payload_bytes / 4e12
        baseline_per = None
        for name in ('touch',) + tuple(args.strategies.split(',')):
            if name == 'touch':
                comm = None
            else:
                comm = chainermn_tpu.create_communicator(
                    name, mesh_shape=(inter, n_dev // inter),
                    devices=jax.devices()[:n_dev])

            def make(k, comm=comm):
                def body(c, _):
                    # the touch forbids XLA from folding the chain to
                    # identity even when the collective is a size-1
                    # loopback; carry-threading forbids reordering
                    c = {kk: v * jnp.float32(1.0 + 1e-7)
                         for kk, v in c.items()}
                    if comm is not None:
                        c = comm.allreduce_grad(c)
                    return c, ()

                def mapped(g):
                    out, _ = lax.scan(body, g, None, length=k)
                    return out

                if comm is not None:
                    fn = jax.jit(jax.shard_map(
                        mapped, mesh=comm.mesh, in_specs=P(),
                        out_specs=P(), check_vma=False))
                else:
                    fn = jax.jit(mapped)
                return lambda: fn(grads)['tail'][:1]

            per, _ov, times, lin, ks_used, esc = adaptive_marginal_time(
                make, (2, 4, 6), reps=3, per_item_floor=touch_floor,
                max_rep_s=20.0)
            noise = _noise_estimate(times, 3)
            row = {
                'metric': 'allreduce_payload_sweep',
                'strategy': name,
                'payload_mb': round(payload_bytes / 1e6, 1),
                'n_leaves': len(leaves),
                'value': round(per * 1e3, 4), 'unit': 'ms',
                'effective_gbs': round(
                    2.0 * payload_bytes / per / 1e9, 1),
                'scan_lengths': list(ks_used),
                'adaptive_escalations': esc,
                'timing_noise_ms': round(noise * 1e3, 3),
                'linearity_rel_err': round(lin, 4),
                'n_devices': n_dev, 'backend': jax.default_backend(),
                'sync_method': 'device_get',
            }
            if lin > LINEARITY_GATE:
                row['suspect'] = True
            if per * (ks_used[-1] - ks_used[0]) < SIGNAL_MULT * noise:
                row['suspect'] = True
                row['suspect_reason'] = \
                    'marginal signal below noise floor'
            # plausibility vs the run's own HBM calibration: a row
            # "moving" bytes faster than measured HBM means the
            # loop-carried pytree stayed VMEM-RESIDENT (v5e VMEM is
            # 128 MB; both sweep payloads fit, the 256 MB calibration
            # buffer does not) -- real chip behavior, but the row
            # must say its time is NOT an HBM staging cost
            if ('suspect' not in cal_row
                    and row['effective_gbs'] > hbm_gbs):
                row['vmem_resident_likely'] = True
                row['note'] = ('effective rate exceeds the measured '
                               'HBM roofline (%.0f GB/s): payload '
                               'stayed VMEM-resident across scan '
                               'iterations' % hbm_gbs)
            if name == 'touch':
                if 'suspect' not in row:
                    baseline_per = per
                    baseline_noise = noise
            elif baseline_per is not None:
                stage = (per - baseline_per) * 1e3
                row['staging_overhead_ms'] = round(stage, 4)
                # an overhead the instrument cannot distinguish from
                # zero must not be consumed downstream as a signed
                # measurement (negative values are pure rep noise)
                if abs(stage) < (noise + baseline_noise) * 1e3:
                    row['staging_below_noise'] = True
            emit(row)


if __name__ == '__main__':
    main()
