#!/usr/bin/env python
"""Measured (not merely lowering-pinned) comparison of allreduce
strategies on one ResNet-50 train step, with profiler traces.

VERDICT r3 weak #6 / next-round item 9: the nine communicator
strategies are proven to LOWER differently (HLO pins in
``tests/test_communicator.py``), but nothing showed they differ -- or
agree -- in *time* on real hardware, and the bucketed communicator's
backward-overlap rationale (``bucketed_communicator.py:10-18``) is a
scheduler hypothesis until a trace shows it.  This script times the
same ResNet-50 step under each strategy with the bench.py marginal
method and captures a ``jax.profiler`` trace of individual jitted
steps (the per-step program, so the backward/allreduce interleaving is
visible on the op timeline), so the overlap story can be read off.

Single chip: collectives are mesh=(1,1) loopbacks, so ABSOLUTE
differences are expected to be small; the artifact this produces is
(a) the real-chip timing row per strategy and (b) the traces, which
show where XLA schedules the fused allreduce relative to the backward
ops.  On a CPU mesh (``--cpu``) it is a plumbing check.

Usage::

    python benchmarks/strategy_trace.py            # real TPU
    python benchmarks/strategy_trace.py --cpu      # 8-dev CPU mesh

Appends rows to ``benchmarks/results/strategy_timing_<platform>.jsonl``
as each strategy completes (a timeout mid-series keeps what was
measured) and writes traces under ``benchmarks/results/traces/``.
"""

import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import (  # noqa: E402
    LINEARITY_GATE, SIGNAL_MULT, _classifier_setup, _noise_estimate,
    _scan_maker, adaptive_marginal_time, devget_sync)

STRATEGIES = ('xla', 'bucketed', 'hierarchical')


def build_step(strategy, on_cpu):
    import jax
    import jax.numpy as jnp

    import chainermn_tpu

    n_dev = jax.device_count()
    inter = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    comm = chainermn_tpu.create_communicator(
        strategy, mesh_shape=(inter, n_dev // inter))
    if on_cpu:
        # plumbing only: a 2-block ResNet compiles/runs in seconds on
        # the virtual mesh; the real comparison needs the real chip
        from chainermn_tpu.models import ResNet
        insize, per_dev, n_classes = 16, 2, 10
        model = ResNet(stage_sizes=[1, 1], num_classes=n_classes,
                       dtype=jnp.float32, width=8)
    else:
        from chainermn_tpu.models import ResNet50
        insize, per_dev, n_classes = 128, 16, 1000
        model = ResNet50(num_classes=n_classes)
    batch = per_dev * n_dev
    return _classifier_setup(model, insize, batch, comm=comm,
                             n_classes=n_classes)


def main():
    argv = sys.argv[1:]
    cpu = '--cpu' in argv
    import jax
    if cpu:
        from chainermn_tpu.utils import force_host_devices
        force_host_devices(8, require=True)

    # same persistent compile cache as bench.py: a rerun must not pay
    # 9 ResNet-50 scan compiles again
    from chainermn_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    here = os.path.dirname(os.path.abspath(__file__))

    platform = jax.default_backend()
    res = os.path.join(here, 'results')
    os.makedirs(res, exist_ok=True)
    out_path = os.path.join(res, 'strategy_timing_%s.jsonl' % platform)
    # fresh file per run, but APPEND per strategy: a timeout on a
    # later strategy keeps the rows already measured
    open(out_path, 'w').close()
    for strategy in STRATEGIES:
        print('[strategy_trace] building %s' % strategy,
              file=sys.stderr, flush=True)
        upd, arrays = build_step(strategy, cpu)
        make = _scan_maker(upd, arrays)
        ks, reps = ((2, 3, 4), 2) if cpu else ((2, 4, 6), 3)
        # adaptive escalation vs timing jitter (bench.py); the
        # strategies are COMPARED against each other, so all three
        # must clear the same signal gate or the comparison is noise
        per, ov, times, lin, ks_used, esc = adaptive_marginal_time(
            make, ks, reps, max_rep_s=20.0, max_tries=5)
        noise = _noise_estimate(times, reps)
        row = {'strategy': strategy, 'platform': platform,
               'step_time_ms': round(per * 1e3, 3),
               'overhead_ms': round(ov * 1e3, 1),
               'scan_lengths': list(ks_used),
               'adaptive_escalations': esc,
               'timing_noise_ms': round(noise * 1e3, 2),
               'linearity_rel_err': round(lin, 4),
               'n_devices': jax.device_count()}
        if lin > LINEARITY_GATE:
            row['suspect'] = True
        if per * (ks_used[-1] - ks_used[0]) < SIGNAL_MULT * noise:
            row['suspect'] = True
            row['suspect_reason'] = 'marginal signal below noise floor'
        # trace INDIVIDUAL jitted steps (warmed up first), not one
        # compiled scan: the per-step program is what shows the
        # backward/allreduce interleaving on the op timeline
        # platform-scoped like the jsonl: a TPU run must not overwrite
        # the CPU plumbing traces (or vice versa)
        tdir = os.path.join(res, 'traces', platform, strategy)
        # fresh dir per capture: accumulated profiler sessions would
        # make any whole-dir analysis double-count self-times.  The
        # raw traces are local-only (.gitignore'd -- multi-MB
        # binaries); the durable artifact is trace_report.json, which
        # IS committed with the results
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir, exist_ok=True)
        from chainermn_tpu.utils.profiling import trace
        # the TIMING row above is the primary datum; a profiler that
        # cannot capture on this backend must not cost it, so the
        # capture is best-effort
        try:
            devget_sync(upd.update_core(arrays))  # compile + warm
            with trace(tdir):
                for _ in range(3):
                    metrics = upd.update_core(arrays)
                devget_sync(metrics)
            row['trace_dir'] = os.path.relpath(tdir, here)
        except Exception as e:
            row['trace_error'] = repr(e)[:300]
            # a partially-exported session must not survive for the
            # end-of-run trace_report pass to publish as a valid
            # breakdown contradicting this row's trace_error
            shutil.rmtree(tdir, ignore_errors=True)
            print('[strategy_trace] %s capture failed: %r'
                  % (strategy, e), file=sys.stderr, flush=True)
        with open(out_path, 'a') as f:
            f.write(json.dumps(row) + '\n')
        print(json.dumps(row), flush=True)
    # auto-render the step-time breakdown from the traces just
    # captured (benchmarks/trace_report.py); best-effort so a
    # converter failure cannot cost the timing rows above
    try:
        sys.path.insert(0, here)
        import trace_report
        trace_report.main(['--latest'])
    except Exception as e:
        print('[strategy_trace] trace_report failed: %r' % e,
              file=sys.stderr, flush=True)
    print('wrote %s' % out_path)


if __name__ == '__main__':
    main()
