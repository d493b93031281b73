#!/usr/bin/env python
"""Allreduce scaling-efficiency harness.

The BASELINE metric is "images/sec/chip + allreduce scaling efficiency
8 -> 256 chips".  This harness measures the gradient-allreduce step in
isolation over growing mesh sizes: a ResNet-50-sized gradient pytree
(~25.6M params) is mean-reduced with each communicator strategy, and
efficiency is reported relative to the smallest mesh (perfect scaling
== the per-step time stays flat as devices are added, since the
payload per device is constant).

On real TPU slices the mesh sizes come from the slice; on CPU the
virtual-device flag provides the scaling axis for harness validation
(`--devices 1,2,4,8`).  Prints one JSON line per (strategy, mesh).

Timing follows bench.py's hardened method (a per-call Python loop
measures dispatch, not the collective): K chained
allreduces run inside ONE compiled ``lax.scan`` under the shard_map,
the per-allreduce time is the marginal slope fit over three scan
lengths (median-of-reps, device_get-synced), and the linearity
diagnostic is reported and suspect-gated per row.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--devices', default=None,
                        help='comma list of mesh sizes (default: all '
                             'visible devices in powers of two)')
    parser.add_argument('--strategies', default='xla,hierarchical,'
                        'two_dimensional,flat,naive')
    parser.add_argument('--params', type=int, default=25_600_000,
                        help='gradient payload size (default: '
                             'ResNet-50-sized)')
    parser.add_argument('--steps', type=int, default=20,
                        help='(ignored; kept for invocation compat -- '
                             'timing is the marginal slope over scan '
                             'lengths 2/4/6)')
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
    from bench import LINEARITY_GATE, SIGNAL_MULT, _noise_estimate, \
        adaptive_marginal_time

    n_all = jax.device_count()
    if args.devices:
        sizes = [int(v) for v in args.devices.split(',')]
    else:
        sizes = [s for s in (1, 2, 4, 8, 16, 32, 64, 128, 256)
                 if s <= n_all]

    # ResNet-50-shaped payload: a few large + many small leaves
    leaves = {}
    remaining = args.params
    i = 0
    for size in (2048 * 1000, 512 * 512 * 9, 2048 * 512, 1024 * 256):
        while remaining > size:
            leaves['w%d' % i] = size
            remaining -= size
            i += 1
            if len(leaves) > 160:
                break
    leaves['tail'] = max(remaining, 1)

    baseline = {}
    for name in args.strategies.split(','):
        for n in sizes:
            inter = 2 if n % 2 == 0 and n > 1 else 1
            if name == 'single_node':
                inter = 1
            comm = chainermn_tpu.create_communicator(
                name, mesh_shape=(inter, n // inter),
                devices=jax.devices()[:n])
            grads = {k: jnp.ones((v,), jnp.float32)
                     for k, v in leaves.items()}

            def make(k):
                def mapped(g):
                    def body(c, _):
                        # carry-threading makes each reduction depend
                        # on the previous one; XLA cannot collapse the
                        # chain
                        return comm.allreduce_grad(c), ()
                    out, _ = lax.scan(body, g, None, length=k)
                    return out

                fn = jax.jit(jax.shard_map(
                    mapped, mesh=comm.mesh, in_specs=P(),
                    out_specs=P(), check_vma=False))
                # thunk returns a 1-element slice: the devget sync
                # fetches real bytes without hauling a full leaf to
                # the host per measurement
                return lambda: fn(grads)['tail'][:1]

            # planning floor: one allreduce moves >= payload bytes
            # through HBM; no chip beats 2 TB/s, so this bounds the
            # adaptive span when RTT jitter hides short scans (a
            # 1-device "allreduce" can be legitimately ~free -- the
            # signal gate below marks that row unmeasurable instead
            # of publishing jitter)
            floor = args.params * 4 / 2e12
            per, _ov, times, lin, ks_used, esc = adaptive_marginal_time(
                make, (2, 4, 6), reps=3, per_item_floor=floor,
                max_rep_s=20.0, max_tries=3)
            noise = _noise_estimate(times, 3)
            row = {
                'metric': 'allreduce_time_ms',
                'strategy': name,
                'devices': n,
                'value': round(per * 1e3, 3),
                'payload_mb': round(args.params * 4 / 1e6, 1),
                'scan_lengths': list(ks_used),
                'adaptive_escalations': esc,
                'timing_noise_ms': round(noise * 1e3, 2),
                'linearity_rel_err': round(lin, 4),
                'sync_method': 'device_get',
            }
            if lin > LINEARITY_GATE:
                row['suspect'] = True
            if per * (ks_used[-1] - ks_used[0]) < SIGNAL_MULT * noise:
                row['suspect'] = True
                row['unmeasurable'] = (
                    'marginal signal below noise floor (the op may '
                    'be legitimately near-free at this mesh size)')
            # efficiency only against a TRUSTED smallest-mesh row: a
            # suspect baseline would silently poison every later
            # row's ratio (suspect data is never published raw)
            if 'suspect' not in row:
                baseline.setdefault(name, per)
            if name in baseline:
                row['scaling_efficiency'] = round(
                    baseline[name] / per, 3)
            print(json.dumps(row))


if __name__ == '__main__':
    main()
