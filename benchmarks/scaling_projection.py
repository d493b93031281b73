#!/usr/bin/env python
"""Labeled 8->256-chip allreduce scaling-efficiency PROJECTION.

BASELINE.json's metric is "allreduce scaling efficiency 8->256 chips"
on a v5e pod.  No pod is in reach (the chip tool gives one chip or
one four-chip host), so this script does the next honest thing: it
combines

1. **measured** single-chip pieces from
   ``benchmarks/results/allreduce_tpu_r5.out`` (the payload sweep's
   per-strategy staging cost and the HBM-touch bandwidth roofline) and
   the headline ResNet-50 step time from
   ``benchmarks/results/bench_resnet50_r5.out``,
2. an **analytic ICI model** of a v5e 2-D torus (assumptions printed
   with every run, and marked as such), and
3. the **CPU-mesh relative curves**
   (``allreduce_cpu8_r4.jsonl``) as a transport-scaling shape check
   (host shared-memory, so only the trend is meaningful),

into a per-mesh-size projection of gradient-allreduce time and the
resulting scaling efficiency, plus end-to-end training efficiency
bounds with and without backward/allreduce overlap (the bucketed
communicator's design point, ``bucketed_communicator.py``).

EVERY row carries ``projection: true`` -- nothing here claims to be a
measurement.  Reference anchor: the 128-GPU scaling headline the
reference exists for (``/root/reference/README.md:15-24``).

Model (stated, simple, conservative):

- ring/torus allreduce moves ``2 * P * (N-1)/N`` bytes through each
  chip's ICI egress; with reduce-scatter + all-gather split across
  both torus dimensions the effective per-chip algorithm bandwidth is
  ``ici_links * ici_gbs_per_link * ici_efficiency``.
- total time(N) = staging(P) [measured] + wire(P, N) [analytic];
  scaling efficiency(N) = t(8) / t(N)  (constant per-device payload,
  so perfect scaling = flat time).
- v5e assumptions (public "How to Scale Your Model" numbers): 4 ICI
  links/chip (2-D torus, 2 axes x 2 directions), 45 GB/s one-way per
  link, 80% achievable algorithm efficiency; bf16 gradient wire dtype
  (the multi_node_optimizer's default, bf16 wire = 2 bytes/param).
  8..256 chips stay inside one v5e slice (16x16 torus max), so no
  DCN leg enters the window; the DCN term is still modeled (25 GB/s
  per host, 8 chips/host) and reported for the hypothetical
  multi-slice case.

Usage::

    python benchmarks/scaling_projection.py [--tag r5]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
RES = os.path.join(HERE, 'results')

# --- stated v5e assumptions (analytic; see module docstring) --------
ICI_LINKS = 4
ICI_GBS_PER_LINK = 45.0          # one-way, GB/s
ICI_ALG_EFFICIENCY = 0.8
DCN_GBS_PER_HOST = 25.0          # GB/s, per 8-chip host
RESNET50_PARAMS = 25_600_000
WIRE_BYTES_PER_PARAM = 2         # bf16 wire dtype (multi_node_optimizer)
MESHES = (8, 16, 32, 64, 128, 256)


def _rows(path):
    out = []
    if not os.path.exists(path):
        return out
    for ln in open(path).read().splitlines():
        try:
            out.append(json.loads(ln))
        except ValueError:
            pass
    return out


def measured_inputs(tag):
    """Pull the measured single-chip pieces; mark what was found."""
    got = {'staging_ms': None, 'hbm_gbs': None, 'step_time_ms': None,
           'staging_strategy': None, 'staging_below_noise': False}
    raw_min = None
    for r in _rows(os.path.join(RES, 'allreduce_tpu_%s.out' % tag)):
        if r.get('suspect'):
            continue
        if r.get('metric') == 'hbm_touch_bandwidth':
            got['hbm_gbs'] = r.get('measured_hbm_gbs')
        if (r.get('metric') == 'allreduce_payload_sweep'
                and r.get('payload_mb', 0) > 50
                and r.get('staging_overhead_ms') is not None):
            s = r['staging_overhead_ms']
            # fastest measured strategy's staging = the cost a real
            # deployment would pay per step on each chip; track the
            # RAW minimum (clamping to 0 here would make the first
            # noise-negative row unbeatable and record the wrong
            # strategy) and clamp only at use
            if raw_min is None or s < raw_min:
                raw_min = s
                got['staging_strategy'] = r['strategy']
                got['staging_below_noise'] = bool(
                    r.get('staging_below_noise'))
    if raw_min is not None:
        got['staging_ms'] = max(raw_min, 0.0)
    for r in _rows(os.path.join(RES, 'bench_resnet50_%s.out' % tag)):
        if not r.get('suspect') and not r.get('error') \
                and r.get('step_time_ms'):
            got['step_time_ms'] = r['step_time_ms']
    return got


def cpu_shape_check():
    """Relative transport curve from the 8-virtual-device CPU mesh
    (host shared memory): only the TREND is meaningful, reported as
    corroboration that collective time grows sub-linearly per added
    device on a shared transport."""
    rows = [r for r in _rows(os.path.join(RES, 'allreduce_cpu8_r4.jsonl'))
            if r.get('strategy') == 'xla' and not r.get('suspect')]
    return {str(r['devices']): r['value'] for r in rows}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--tag', default='r5')
    parser.add_argument('--params', type=int, default=RESNET50_PARAMS)
    parser.add_argument('--results-dir', default=RES,
                        help='where the jsonl lands (tests point this '
                             'at a tmp dir; measured inputs are still '
                             'read from the repo results dir)')
    args = parser.parse_args()

    got = measured_inputs(args.tag)
    staging_ms = got['staging_ms'] if got['staging_ms'] is not None \
        else 0.15  # fallback: ~100MB f32 pack+unpack at ~1.3 TB/s HBM
    step_ms = got['step_time_ms'] or 12.2  # round-5 measured fallback

    payload = args.params * WIRE_BYTES_PER_PARAM
    b_alg = ICI_LINKS * ICI_GBS_PER_LINK * ICI_ALG_EFFICIENCY  # GB/s

    assumptions = {
        'projection': True,
        'ici_links': ICI_LINKS,
        'ici_gbs_per_link_oneway': ICI_GBS_PER_LINK,
        'ici_alg_efficiency': ICI_ALG_EFFICIENCY,
        'alg_bandwidth_gbs': b_alg,
        'wire_dtype': 'bf16',
        'payload_mb': round(payload / 1e6, 1),
        'staging_ms_measured': got['staging_ms'] is not None,
        'staging_ms': round(staging_ms, 4),
        'staging_strategy': got['staging_strategy'],
        # True when the sweep could not distinguish the winning
        # strategy's staging from zero (VMEM-resident payload):
        # "measured" then means "measured to be below the noise
        # floor", not a signed cost
        'staging_below_noise': got['staging_below_noise'],
        'hbm_touch_gbs_measured': got['hbm_gbs'],
        'resnet50_step_ms_measured': got['step_time_ms'] is not None,
        'resnet50_step_ms': step_ms,
        'torus': '16x16 v5e slice; 8..256 chips all ride ICI '
                 '(no DCN leg inside the projected window)',
        'cpu_mesh_shape_check_ms': cpu_shape_check(),
    }
    emitted = [{'metric': 'scaling_projection_assumptions',
                **assumptions}]
    print(json.dumps(emitted[0]))

    t8 = None
    for n in MESHES:
        wire_ms = 2.0 * payload * (n - 1) / n / (b_alg * 1e9) * 1e3
        t = staging_ms + wire_ms
        if t8 is None:
            t8 = t
        # end-to-end: allreduce either fully exposed (no overlap) or
        # hidden behind the backward (bucketed overlap design point);
        # the truth lies between the two bounds
        step_exposed = step_ms + t
        step_overlap = max(step_ms, t)
        row = {
            'metric': 'allreduce_scaling_projection',
            'projection': True,
            'devices': n,
            'allreduce_ms': round(t, 3),
            'wire_ms': round(wire_ms, 3),
            'staging_ms': round(staging_ms, 4),
            'scaling_efficiency_vs_8': round(t8 / t, 3),
            'train_step_ms_no_overlap': round(step_exposed, 3),
            'train_step_ms_full_overlap': round(step_overlap, 3),
            'train_efficiency_vs_8_no_overlap': round(
                (step_ms + t8) / step_exposed, 3),
            'train_efficiency_vs_8_full_overlap': round(
                max(step_ms, t8) / step_overlap, 3),
        }
        emitted.append(row)
        print(json.dumps(row))

    # hypothetical multi-slice leg (NOT part of the 8->256 window):
    # the DCN term that would dominate past one slice, for context
    dcn_ms = 2.0 * payload / (DCN_GBS_PER_HOST * 1e9) * 1e3
    emitted.append({
        'metric': 'dcn_leg_context', 'projection': True,
        'note': 'beyond one 256-chip v5e slice the inter-slice leg '
                'rides DCN; per-host wire time for the same payload',
        'dcn_gbs_per_host': DCN_GBS_PER_HOST,
        'dcn_wire_ms': round(dcn_ms, 3)})
    print(json.dumps(emitted[-1]))

    os.makedirs(args.results_dir, exist_ok=True)
    out_path = os.path.join(args.results_dir,
                            'scaling_projection_%s.jsonl' % args.tag)
    with open(out_path, 'w') as f:
        for row in emitted:
            f.write(json.dumps(row) + '\n')
    sys.stdout.flush()
    print('wrote %s' % out_path)


if __name__ == '__main__':
    main()
