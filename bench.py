#!/usr/bin/env python
"""Benchmark harness: honest per-chip training throughput.

Prints ONE JSON line
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``.
Default workload is the headline ResNet-50 config; ``--model`` selects
any BASELINE.md workload:

  resnet50 | vgg16 | googlenetbn | seq2seq | transformer | mlp

Baseline: the reference points at PFN's published 128-GPU ChainerMN
ResNet-50 run (``/root/reference/README.md:19``; 100 epochs of
ImageNet-1k in 4.4 hours on 128 P100s) = ~8100 images/sec total,
i.e. **~63 images/sec/chip**.  For non-ResNet models ``vs_baseline``
scales that bar by the analytic FLOPs ratio (same hardware-time budget
per item; documented per line as ``baseline_derivation``).

MEASUREMENT METHOD (round 3).  Round 2 recorded a physically
impossible number (170% of bf16 peak) because on the backend of that
round ``block_until_ready`` returned without waiting for an
async-dispatched chain.  The harness trusts nothing it has not
verified:

1. **Sync**: the ONLY sync primitive used for timing is
   ``jax.device_get`` of the program's outputs -- bytes on the host
   cannot lie.  ``block_until_ready`` is probed once and its
   trustworthiness recorded (``block_until_ready_trustworthy``).
2. **Dispatch amortization**: a per-step Python loop times dispatch
   as much as compute.  K train steps run inside ONE compiled
   program (``lax.scan`` carrying params), and the per-step time is
   the MARGINAL cost fit across THREE scan lengths
   (least-squares slope of median-of-reps times vs K); the fixed
   dispatch overhead estimate is the intercept (``overhead_ms``), and
   the worst relative deviation of a consecutive-segment slope from
   the fitted slope is reported (``linearity_rel_err``) and
   suspect-gated (``LINEARITY_GATE``) -- a nonlinear t(K) means the
   sync or the backend is lying at some length, and gating on SLOPE
   deviation keeps the check sensitive even when the fixed overhead
   dwarfs per-step time.
3. **Roofline self-calibration**: the same scan+marginal method times
   a big bf16 matmul chain on the same chip
   (``measured_matmul_tflops``); no table peak is trusted blind.
4. **FLOP cross-check**: XLA's cost analysis AND an analytic estimate
   are both reported; the HEADLINE ``achieved_tflops_per_chip`` /
   ``pct_of_bf16_peak`` use the conservative analytic (model-flops)
   convention, with XLA's executed-flop count as the ``_xla`` sidecar
   fields (round 5; XLA counts ResNet convs ~2x the model-flops
   convention and would overstate MFU by the same factor).
5. **Suspect gating**: a result claiming more than the self-calibrated
   matmul roofline (or >100% of the device's table peak, or wildly
   unstable step times) is emitted with ``"suspect": true`` and a
   reason -- never published raw as a win.

Robustness: the parent process never imports jax; a subprocess probe
with a hard timeout turns a hung backend into machine-readable
``{"error": "backend_unavailable"}``; the measurement runs in a
watchdogged ``--child`` with a persistent XLA compile cache.  One
process holds a chip at a time: the probe has exited before the child
starts, and the parent itself never touches the backend.

Flags: ``--model NAME``, ``--quick`` (shorter scans), ``--cpu``
(8-device virtual CPU mesh, plumbing check), ``--no-cost`` (skip cost
analysis), ``--check`` (transformer only: pin Pallas kernels against
the jnp oracle on-device and record ``numerics_vs_oracle_ok``),
``--batch N`` (per-device batch override, the MFU-chase lever),
``--policy NAME`` (mixed-precision arm: ``bf16`` = bf16
compute/reduce with f32 master weights via
``chainermn_tpu.precision.Policy`` -- rows record the policy dtypes
so the A/B pair against the default row is self-describing; see
``docs/mixed_precision.md``),
``--s2d`` (resnet50 only: MXU-friendly space-to-depth stem, exact
weight-mapped equivalent of the 7x7/2 stem -- ``models/resnet50.py``),
``--no-adopt`` (resnet50 only: keep the default batch-32 config even
when a banked MFU-sweep artifact crowns a faster one; see
``adopt_tuned_config``),
``--tp N`` (transformer only: composed dp x tp MeshPlan arm -- rows
carry ``tp``/``mesh``/per-axis collective bytes and the PERF.md
90-115k tok/s/chip anchor; ``docs/mesh_parallelism.md``),
``--pp K`` (transformer only, composes with ``--tp``: the 3-D
dp x tp x pp MeshPlan arm -- stage-sliced transformer trained 1F1B
through the unified ``MeshPipelineUpdater``; rows add
``pp``/``n_microbatches``/``bubble_fraction``),
``--donate`` (resnet50 only: donation + remat headline arm -- how
real training runs; PERF.md knob #6),
``--serve`` (open-loop serving arm over
``chainermn_tpu/serving`` -- AOT per-bucket executables + dynamic
batching; the row's value is served req/s/chip with p50/p99 latency
from telemetry histograms, pad-waste fraction, bucket hit-rate and
typed-shed fraction; ``--int8`` serves int8-quantized weights,
``--serve-rate``/``--serve-requests``/``--serve-max-batch`` tune the
load; see ``docs/serving.md``),
``--serve --generate`` (autoregressive arm over
``chainermn_tpu/serving/generate.py`` -- bucketed KV-cache decode
with continuous token-level batching over a prefill/decode AOT
split; the row's value is generated tokens/s/chip with TTFT and
inter-token p50/p99 sidecars, anchored against PERF.md's ~290k
tok/s/chip perfect-MXU number; ``--int8-kv`` stores the KV cache
int8, ``--gen-slots``/``--gen-max-new`` size the slot table).
"""

import json
import math
import os
import re
import subprocess
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 63.0
# suspect-gate threshold on the linearity diagnostic (worst relative
# deviation of a consecutive-segment slope from the fitted marginal
# slope); shared with benchmarks/flash_attention_bench.py
LINEARITY_GATE = 0.25
# adaptive scan-length escalation (round 4): per-device_get jitter
# can swamp the marginal compute of short scans -- the round-4
# series' first mlp line measured a NEGATIVE slope at ks=(2,4,6)
# because 4 extra 14us steps were invisible under +-40ms of noise.
# Escalate the scan span until the fitted signal (slope * span)
# exceeds SIGNAL_MULT x the measured median-of-reps noise, so the
# per-step estimate has a ~few-percent error bound instead of being
# jitter in disguise.
SIGNAL_MULT = 25.0
# dense bf16 TFLOP/s per chip, by device_kind substring (table peak;
# the harness also self-calibrates, see measured_matmul_tflops)
BF16_PEAK_TFLOPS = {
    'v4': 275.0,
    'v5e': 197.0,
    'v5 lite': 197.0,
    'v5p': 459.0,
    'v6e': 918.0,
    'v6 lite': 918.0,
}
# HBM bandwidth spec GB/s per chip, by device_kind substring (the
# allreduce sweep also measures a touch rate on the same chip)
HBM_SPEC_GBS = {
    'v4': 1228.0,
    'v5e': 819.0,
    'v5 lite': 819.0,
    'v5p': 2765.0,
    'v6e': 1640.0,
    'v6 lite': 1640.0,
}
MODELS = ('resnet50', 'vgg16', 'googlenetbn', 'seq2seq', 'transformer',
          'mlp')


def spec_lookup(table, device_kind):
    """Device-kind-substring lookup shared by every spec table (peak
    TFLOP/s, HBM GB/s): ONE matching rule, so a new chip generation
    added to one table cannot silently miss the idiom elsewhere.  A
    device the table does not know is an error, never a default."""
    kind = device_kind.lower()
    for key, value in table.items():
        if key in kind:
            return value
    raise KeyError('device kind %r is in no spec table (knows: %s)'
                   % (device_kind, ', '.join(sorted(table))))


PROBE_SRC = """
import jax, jax.numpy as jnp
d = jax.devices()
assert d, 'no devices'
y = jax.jit(lambda a: a @ a)(jnp.ones((512, 512), jnp.bfloat16))
v = jax.device_get(y[:1, :1])  # real sync: bytes must arrive
print('PROBE_OK', jax.default_backend(), len(d))
"""


def _log(msg):
    print('[bench %.1fs] %s' % (time.monotonic() - _log.t0, msg),
          file=sys.stderr, flush=True)


_log.t0 = time.monotonic()


def metric_stub(model):
    if model == 'serve_fleet_recovery':
        # the self-healing arm (--serve --fleet --recovery): the
        # product number is how fast a hard-killed replica's
        # generations resume on a survivor -- kill to first
        # recovered token (docs/fault_tolerance.md "Serving
        # self-healing")
        return {'metric': 'serve_fleet_recovery_mttr_ms',
                'unit': 'ms'}
    if model == 'serve_fleet':
        # the continuous-deployment arm (--serve --fleet): the
        # product number is how fast weights can roll through a
        # serving fleet with zero dropped requests (docs/serving.md
        # "Continuous deployment")
        return {'metric': 'serve_fleet_rolls_per_minute',
                'unit': 'rolls/min'}
    if model.startswith('serve_generate'):
        # the autoregressive arm (--serve --generate): generated
        # tokens, not requests -- decode throughput is the product
        # number (docs/serving.md)
        return {'metric': '%s_tokens_per_sec_per_chip' % model,
                'unit': 'tokens/sec/chip'}
    if model.startswith('serve_'):
        # the serving arms (--serve): request throughput, not
        # training items -- 'serve_<model>' keys the banked-artifact
        # lookup at bench_serve_<model>_rN.out
        return {'metric': '%s_requests_per_sec_per_chip' % model,
                'unit': 'req/sec/chip'}
    if model.startswith('loader_'):
        # the streaming input-pipeline arm (--loader): streamed
        # samples through the real train step, A/B'd against the
        # device-resident feed (docs/data_pipeline.md)
        return {'metric': '%s_streamed_samples_per_sec_per_chip'
                          % model,
                'unit': 'samples/sec/chip'}
    unit = {'seq2seq': 'tokens/sec/chip',
            'transformer': 'tokens/sec/chip',
            'mlp': 'images/sec/chip'}.get(model, 'images/sec/chip')
    return {'metric': '%s_train_%s' % (model, unit.replace('/', '_per_')),
            'unit': unit}


def emit(result, rc=0):
    print(json.dumps(result), flush=True)
    sys.exit(rc)


def probe_backend(attempts=4, timeout=150, interval=60):
    """True if a subprocess can init the backend and run a tiny jit
    with a REAL device_get sync; otherwise the failure detail."""
    detail = ''
    for i in range(attempts):
        _log('backend probe attempt %d/%d (timeout %ds)'
             % (i + 1, attempts, timeout))
        try:
            p = subprocess.run(
                [sys.executable, '-c', PROBE_SRC], timeout=timeout,
                capture_output=True, text=True, cwd=os.path.dirname(
                    os.path.abspath(__file__)))
            if p.returncode == 0 and 'PROBE_OK' in p.stdout:
                _log('probe ok: %s' % p.stdout.strip())
                return True
            detail = (p.stderr or p.stdout).strip()[-2000:]
        except subprocess.TimeoutExpired:
            detail = 'probe timed out after %ds (backend hung)' % timeout
        last = detail.splitlines()[-1] if detail else '(no output)'
        _log('probe failed: %s' % last)
        if i + 1 < attempts:
            time.sleep(interval)
    return detail


def run_child(argv, model):
    """Watchdog wrapper: run the measurement in a child process,
    relaying stderr; on timeout/crash emit diagnostic JSON."""
    quick = '--quick' in argv
    # adaptive scan escalation can add a few compile rounds + up to
    # ~30s/rep of deliberately-long scans; budget for it
    timeout = 1800 if quick else 3000
    cmd = [sys.executable, os.path.abspath(__file__), '--child'] + argv
    _log('starting measurement child (timeout %ds)' % timeout)
    try:
        p = subprocess.run(cmd, timeout=timeout, stdout=subprocess.PIPE,
                           text=True)  # stderr inherited -> live progress
    except subprocess.TimeoutExpired:
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bench_timeout',
                  detail='child exceeded %ds' % timeout), rc=1)
    lines = [ln for ln in (p.stdout or '').splitlines() if ln.strip()]
    if p.returncode == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except ValueError:
            emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                      error='bad_child_output',
                      detail=lines[-1][-2000:]), rc=1)
        emit(result, rc=1 if result.get('error') else 0)
    emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
              error='bench_failed',
              detail='child rc=%d, stdout tail: %s'
              % (p.returncode, '\n'.join(lines)[-2000:])), rc=1)


# ======================================================================
# measurement primitives (child side)

def devget_sync(x):
    """The only trustworthy sync on this backend: fetch real bytes."""
    import jax
    leaves = jax.tree_util.tree_leaves(x)
    return jax.device_get(leaves[-1])


def probe_block_until_ready():
    """Is block_until_ready a real sync here?  Times a dependent chain
    of matmuls under both sync methods; records the verdict instead of
    assuming (VERDICT r2 weak #1)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(a, b):
        return a @ b * 0.5

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    warm = step(a, a)
    devget_sync(warm)

    def chain(sync):
        t0 = time.perf_counter()
        x = a
        for _ in range(8):
            x = step(x, a)
        sync(x)
        return time.perf_counter() - t0

    t_block = min(chain(lambda v: v.block_until_ready())
                  for _ in range(2))
    t_get = min(chain(devget_sync) for _ in range(2))
    trustworthy = t_block > 0.5 * t_get
    _log('block_until_ready probe: block=%.4fs devget=%.4fs -> %s'
         % (t_block, t_get,
            'trustworthy' if trustworthy else 'NOT a real sync'))
    return trustworthy


def marginal_time(make_fn, ks, reps):
    """Compile fn(k) for each scan length in ``ks``; time each (devget
    sync, MEDIAN over reps -- a single anomalous rep must not move
    the estimate); least-squares fit t(k) = overhead +
    per_item * k across all lengths.  Returns (per_item, overhead,
    times_dict, linearity_rel_err) where the last is the worst relative
    deviation of a consecutive-segment slope from the fitted slope
    (99.0 sentinel when the fitted slope is non-positive) -- a
    nonlinearity (caching, throttling, a sync that stops being a sync
    at one length) shows up here instead of silently biasing per_item
    (VERDICT r3 weak #1 watch item)."""
    ks = sorted(ks)
    fns = {}
    for k in ks:
        _log('compiling scan length %d' % k)
        fns[k] = make_fn(k)
        devget_sync(fns[k]())  # compile + warm
    times = {}
    for k in ks:
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            devget_sync(fns[k]())
            samples.append(time.perf_counter() - t0)
        times[k] = samples
    import statistics
    med = {k: statistics.median(v) for k, v in times.items()}
    kbar = sum(ks) / len(ks)
    tbar = sum(med.values()) / len(ks)
    denom = sum((k - kbar) ** 2 for k in ks)
    slope = sum((k - kbar) * (med[k] - tbar) for k in ks) / denom
    intercept = tbar - kbar * slope
    # Linearity diagnostic on the MARGINAL component only: worst
    # relative deviation of a consecutive-segment slope from the
    # fitted slope.  Normalizing residuals by total time would let
    # per-step nonlinearity hide under a large fixed intercept (a
    # dispatch overhead that dwarfs per-step time at small k).
    segs = [(med[ks[i + 1]] - med[ks[i]]) / (ks[i + 1] - ks[i])
            for i in range(len(ks) - 1)]
    lin_err = max(abs(s - slope) for s in segs) / max(abs(slope), 1e-9)
    if slope <= 0:
        # t(K) did not increase with scan length: the sync is lying
        # outright, OR the marginal compute is below the noise floor
        # (adaptive_marginal_time escalates that case).  A consistent
        # negative slope would otherwise show lin_err ~ 0 and the 1e-9
        # clamp below would publish an absurd throughput un-gated;
        # poison the diagnostic instead (finite sentinel so JSON rows
        # stay strict-parseable).
        lin_err = 99.0
    per_item = max(slope, 1e-9)
    overhead = max(intercept, 0.0)
    return per_item, overhead, times, lin_err


def _noise_estimate(times, reps):
    """Per-median timing noise (seconds): median across scan lengths of
    the rep stddev, scaled to the error of a median of ``reps`` samples
    (~1.25/sqrt(n) for a normal), floored so a zero-variance fluke
    cannot declare infinite precision."""
    import statistics
    sds = [statistics.pstdev(v) for v in times.values() if len(v) > 1]
    sigma = statistics.median(sds) if sds else 0.0
    return max(sigma * 1.25 / math.sqrt(max(reps, 1)), 1e-4)


def adaptive_marginal_time(make_fn, base_ks, reps, per_item_floor=None,
                           max_rep_s=30.0, max_k=200000, max_tries=4):
    """``marginal_time`` with scan-span escalation: retry with longer
    scans until slope * span >= SIGNAL_MULT * noise.

    ``per_item_floor`` is a LOWER bound on the true per-step time
    (e.g. analytic flops / an optimistic peak); it plans the rescaled
    span when the observed slope is unusable (<= 0) and caps the span
    so one rep stays under ``max_rep_s``.  Returns
    (per_item, overhead, times, lin_err, ks_used, escalations).
    """
    ks = tuple(sorted(base_ks))
    attempt = 0
    while True:
        per, ov, times, lin = marginal_time(make_fn, ks, reps)
        sigma = _noise_estimate(times, reps)
        slope_raw = per if per > 1e-9 else 0.0
        signal = slope_raw * (ks[-1] - ks[0])
        if signal >= SIGNAL_MULT * sigma or attempt + 1 >= max_tries:
            return per, ov, times, lin, ks, attempt
        per_est = max(slope_raw, per_item_floor or 0.0)
        if per_est > 0:
            span = SIGNAL_MULT * sigma / per_est
            s = max(int(math.ceil(span / 2.0)), ks[0] * 2)
            # keep the longest rep inside the wall budget (3s ~= the
            # longest length; ov is the fixed RTT component)
            s_cap = max(int((max_rep_s - ov) / (3.0 * per_est)), 1)
            s = min(s, s_cap, max_k // 3)
        else:
            s = min(ks[0] * 8, max_k // 3)  # blind geometric growth
        new_ks = (s, 2 * s, 3 * s)
        if new_ks == ks or s <= ks[0]:
            return per, ov, times, lin, ks, attempt
        _log('adaptive: signal %.2fms < %.0fx noise %.2fms at ks=%s; '
             'rescaling to ks=%s'
             % (signal * 1e3, SIGNAL_MULT, sigma * 1e3, list(ks),
                list(new_ks)))
        ks = new_ks
        attempt += 1


def calibrate_matmul_roofline(quick):
    """Self-calibrated compute roofline: marginal time of one big bf16
    matmul inside a scanned chain on this very chip."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = 4096 if quick else 8192
    flop = 2.0 * n ** 3

    def make(k):
        @jax.jit
        def run():
            a = jnp.ones((n, n), jnp.bfloat16)

            def body(c, _):
                return c @ a * 0.5, ()

            out, _ = lax.scan(body, a, None, length=k)
            return out[:1, :1]

        return run

    ks = (4, 8, 12) if quick else (8, 16, 24)
    # floor: no chip sustains 1 PFLOP/s dense bf16 on one core; the
    # floor only PLANS the escalated span (overshoot = longer scans)
    per, ov, _, lin, ks_used, esc = adaptive_marginal_time(
        make, ks, reps=3, per_item_floor=flop / 1e15, max_rep_s=20.0)
    tflops = flop / per / 1e12
    _log('matmul roofline: %d^3 bf16 %.2fms/matmul -> %.1f TFLOP/s '
         '(linearity %.3f, ks=%s, %d escalations)'
         % (n, per * 1e3, tflops, lin, list(ks_used), esc))
    return tflops, lin


# ======================================================================
# per-model builders: return dict(updater-free scan maker, items/step,
# analytic train flops/step, extras)

def _resolve_policy(policy):
    """``--policy`` name -> ``chainermn_tpu.precision.Policy`` (child
    side only; the parent validates the NAME without importing jax)."""
    if policy is None:
        return None
    from chainermn_tpu.precision import Policy
    return Policy.from_string(policy)


def _policy_row(pol, default_compute='bfloat16'):
    """The ``policy`` descriptor every bench row carries: which dtypes
    the measured step computed/reduced in, so an A/B pair (f32-master
    default vs ``--policy bf16``) is self-describing in the banked
    artifacts.  ``default_compute`` is the model's native compute
    dtype when no policy is applied (conv zoo models are bf16-compute
    by construction; grads still reduce at master precision)."""
    if pol is None:
        return {'param_dtype': 'float32',
                'compute_dtype': default_compute,
                'reduce_dtype': None,
                'loss_scaling': False}
    return {'param_dtype': str(pol.param_dtype),
            'compute_dtype': str(pol.compute_dtype),
            'reduce_dtype': (str(pol.reduce_dtype)
                             if pol.reduce_dtype is not None else None),
            'loss_scaling': pol.loss_scale is not None}


def _classifier_setup(model, insize, batch, seed=0, comm=None,
                      n_classes=1000, policy=None, donate=False,
                      remat=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu
    from chainermn_tpu import training
    from chainermn_tpu.models import StatefulClassifier

    if comm is None:
        comm = chainermn_tpu.create_communicator('xla')
    x0 = jnp.zeros((1, insize, insize, 3), jnp.float32)
    variables = model.init({'params': jax.random.PRNGKey(seed)}, x0,
                           train=False)
    params = variables['params']
    model_state = {k: v for k, v in variables.items() if k != 'params'}
    rng = np.random.RandomState(0)
    x = rng.rand(batch, insize, insize, 3).astype(np.float32)
    y = rng.randint(0, n_classes, batch).astype(np.int32)
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm)
    # StatefulClassifier handles BN state AND dropout rngs; models
    # with neither just see an empty mutable set
    clf = StatefulClassifier(model)
    upd = training.StandardUpdater(
        iter([]), optimizer, clf.loss, params, comm,
        model_state=model_state, donate=donate, policy=policy,
        remat=remat)
    arrays = upd.shard_batch([(x[i], y[i]) for i in range(batch)])
    return upd, arrays


def _scan_maker(upd, arrays):
    """One compiled program running k train steps back to back; sync
    value is the stack of per-step losses."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    step = upd._build_step(donate=False)
    has_state = upd._has_state
    rng0 = upd._rng
    p0, ms0, os0 = upd.params, upd.model_state, upd.opt_state

    def make(k):
        @jax.jit
        def run():
            def body(carry, i):
                p, ms, os_ = carry
                r = (jax.random.fold_in(rng0, i) if has_state else rng0)
                p, ms, os_, metrics = step(p, ms, os_, r, *arrays)
                return (p, ms, os_), metrics['loss']

            (_, _, _), losses = lax.scan(
                body, (p0, ms0, os0), jnp.arange(k))
            return losses

        return run

    return make


def _donating_scan_maker(upd, arrays):
    """Scan maker with REAL training donation (PERF.md knob #6): the
    carried params/state/opt buffers are donated at the OUTER jit
    boundary so XLA reuses them across the scanned steps instead of
    holding the replay copies the default ``donate=False``
    measurement keeps.  Donation consumes the inputs, so each timed
    call re-places fresh copies from host snapshots -- a per-call
    FIXED cost that the marginal-slope fit absorbs into the
    ``overhead_ms`` intercept, never into the per-step estimate."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    step = upd._build_step(donate=False)  # donate at the outer jit
    has_state = upd._has_state
    rng0 = upd._rng
    live = (upd.params, upd.model_state, upd.opt_state)
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, live)
    host = jax.device_get(live)

    def make(k):
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def run(p, ms, os_):
            def body(carry, i):
                p, ms, os_ = carry
                r = (jax.random.fold_in(rng0, i) if has_state
                     else rng0)
                p, ms, os_, metrics = step(p, ms, os_, r, *arrays)
                return (p, ms, os_), metrics['loss']

            _, losses = lax.scan(body, (p, ms, os_), jnp.arange(k))
            return losses

        def call():
            return run(*jax.device_put(host, shardings))

        return call

    return make


# (model-class name, fwd GFLOPs/image at 224px, per-device batch on
# TPU / on CPU): the three BASELINE conv workloads share one builder
_CONV_MODELS = {
    'resnet50': ('ResNet50', 4.1, 32, 8),
    'vgg16': ('VGG16', 15.5, 32, 4),
    'googlenetbn': ('GoogLeNetBN', 2.0, 32, 8),
}


def _build_conv(name, quick, on_cpu, per_dev_override=None,
                s2d=False, policy=None, fused_norm=False,
                donate=False):
    import jax

    import chainermn_tpu.models as zoo

    cls_name, fwd_gf, per_dev_tpu, per_dev_cpu = _CONV_MODELS[name]
    insize = 64 if on_cpu else 224
    per_dev = per_dev_override or (per_dev_cpu if on_cpu
                                   else per_dev_tpu)
    batch = per_dev * jax.device_count()
    # analytic_flops deliberately stays the REFERENCE model's useful
    # work even under --s2d: images/sec is the judged rate and the s2d
    # stem's extra MACs (4x4x12 vs 7x7x3 per output, ~1.7% of the
    # model) are layout overhead, not useful work.  XLA's own count
    # includes them, so flop_count_ratio_xla_over_analytic reads
    # ~1.017 on s2d rows by design.
    model = getattr(zoo, cls_name)(
        num_classes=1000, fused_norm=fused_norm,
        **({'stem': 'space_to_depth'} if s2d else {}))
    pol = _resolve_policy(policy)
    # --donate: measure the headline the way real training runs --
    # buffers donated into the step and the backward rematerializing
    # the forward (PERF.md knob #6: the default donate=False replay
    # scan understates training)
    upd, arrays = _classifier_setup(model, insize, batch, policy=pol,
                                    donate=donate, remat=donate)
    fwd = fwd_gf * 1e9 * (insize / 224.0) ** 2
    base = BASELINE_IMG_PER_SEC_PER_CHIP * (4.1 / fwd_gf) \
        * (224.0 / insize) ** 2
    deriv = ('PFN 128xP100 resnet50 published throughput, per chip, '
             'flops-normalized to insize' if name == 'resnet50' else
             'resnet50 baseline scaled by analytic flops ratio '
             '4.1/%s (same hardware-time budget per image)' % fwd_gf)
    maker = (_donating_scan_maker if donate else _scan_maker)
    return dict(make=maker(upd, arrays), upd=upd, arrays=arrays,
                items=batch, insize=insize,
                analytic_flops=3.0 * fwd * batch, baseline=base,
                policy=_policy_row(pol), donate=donate, remat=donate,
                baseline_derivation=deriv)


def _updater_setup(loss, params, examples, policy=None, comm=None,
                   param_specs=None):
    """Shared LM/MLP bench plumbing: communicator + multi-node adam +
    StandardUpdater (donate=False so scans can replay from the same
    buffers) + sharded batch -- ONE place for the updater-construction
    contract the three non-conv builders share.  ``comm``/
    ``param_specs`` override for the composed-mesh tp arm (a MeshPlan
    communicator + per-leaf shardings)."""
    import optax

    import chainermn_tpu
    from chainermn_tpu import training

    if comm is None:
        comm = chainermn_tpu.create_communicator('xla')
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm)
    upd = training.StandardUpdater(
        iter([]), optimizer, loss, params, comm, has_aux=True,
        donate=False, policy=policy, param_specs=param_specs)
    return upd, upd.shard_batch(examples)


def build_seq2seq(quick, on_cpu, per_dev_override=None, policy=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import Seq2seq, seq2seq_loss

    layers, units, vocab = (2, 256, 4000) if on_cpu else (2, 512, 8000)
    seq_len = 32 if on_cpu else 64
    per_dev = per_dev_override or (8 if on_cpu else 64)
    batch = per_dev * jax.device_count()
    model = Seq2seq(n_layers=layers, n_source_vocab=vocab,
                    n_target_vocab=vocab, n_units=units)
    rng = np.random.RandomState(0)
    xs = rng.randint(1, vocab, (batch, seq_len)).astype(np.int32)
    ys_in = rng.randint(1, vocab, (batch, seq_len)).astype(np.int32)
    ys_out = rng.randint(1, vocab, (batch, seq_len)).astype(np.int32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32),
        jnp.zeros((1, seq_len), jnp.int32))['params']
    loss = seq2seq_loss(
        lambda p, a, b: model.apply({'params': p}, a, b))
    pol = _resolve_policy(policy)
    upd, arrays = _updater_setup(
        loss, params,
        [(xs[i], ys_in[i], ys_out[i]) for i in range(batch)],
        policy=pol)
    # LSTM train flops/token/layer ~ 3 * 16u^2 (fwd 8u^2 MACs x2);
    # + decoder softmax 3 * 2uV per target token; enc+dec tokens
    tokens = batch * seq_len  # target tokens (the reported unit)
    flops = (3.0 * 16.0 * units ** 2 * layers * (2 * tokens)
             + 3.0 * 2.0 * units * vocab * tokens)
    base = BASELINE_IMG_PER_SEC_PER_CHIP * 4.1e9 * 3.0 / (
        flops / tokens)
    return dict(make=_scan_maker(upd, arrays), upd=upd, arrays=arrays,
                items=tokens, analytic_flops=flops, baseline=base,
                policy=_policy_row(pol),
                baseline_derivation='resnet50 baseline converted to '
                'tokens/sec via analytic flops per item')


def build_transformer(quick, on_cpu, per_dev_override=None,
                      policy=None, tp=None, pp=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import TransformerLM, lm_loss

    if on_cpu:
        d_model, n_heads, n_layers, seq, vocab, per_dev = \
            128, 4, 2, 128, 1000, 2
    else:
        d_model, n_heads, n_layers, seq, vocab, per_dev = \
            512, 8, 6, 1024, 32000, 8
    per_dev = per_dev_override or per_dev
    batch = per_dev * jax.device_count()
    if pp:
        return _build_transformer_pp(
            quick, on_cpu, d_model, n_heads, n_layers, seq, vocab,
            batch, policy=policy, tp=tp, pp=pp,
            anchor_config_match=bool(not on_cpu
                                     and per_dev_override is None))
    plan = comm = specs = None
    tp_kw = {}
    if tp:
        # composed dp x tp mesh (docs/mesh_parallelism.md): heads and
        # MLP columns/rows split on the `model` axis, batch shards on
        # `data` only -- each data replica spans `tp` chips
        from chainermn_tpu.parallel.meshplan import MeshPlan
        plan = MeshPlan.create(tp=tp)
        comm = plan.communicator()
        tp_kw = {'tp_axis': plan.model_axis}
    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers,
                          d_ff=4 * d_model, max_len=seq, **tp_kw)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    tgts = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    if tp:
        from chainermn_tpu.models import tp_oracle, tp_param_specs
        # the tp model's parameter tree IS the oracle's: init the
        # unsharded twin, shard by specs (the updater places them)
        params = tp_oracle(model).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, seq), jnp.int32))['params']
        specs = tp_param_specs(params, plan.model_axis)
    else:
        params = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, seq), jnp.int32))['params']
    loss = lm_loss(lambda p, t: model.apply({'params': p}, t))
    pol = _resolve_policy(policy)
    upd, arrays = _updater_setup(
        loss, params, [(toks[i], tgts[i]) for i in range(batch)],
        policy=pol, comm=comm, param_specs=specs)
    tokens = batch * seq
    # per token fwd: 12 d^2 per layer (qkvo + 2-layer 4d MLP) +
    # 4*seq*d attention matmuls per layer (causal halves it) + lm head
    ff = 4 * d_model
    per_tok_fwd = n_layers * (
        8.0 * d_model ** 2 + 2.0 * 2.0 * d_model * ff
        + 2.0 * 2.0 * seq * d_model / 2.0) + 2.0 * d_model * vocab
    flops = 3.0 * per_tok_fwd * tokens
    base = BASELINE_IMG_PER_SEC_PER_CHIP * 4.1e9 * 3.0 / (
        flops / tokens)
    out = dict(make=_scan_maker(upd, arrays), upd=upd, arrays=arrays,
               items=tokens, analytic_flops=flops, baseline=base,
               policy=_policy_row(pol),
               baseline_derivation='resnet50 baseline converted to '
               'tokens/sec via analytic flops per item',
               # PERF.md transformer roofline anchor: ~290k tok/s/chip
               # perfect-MXU for the d512/L6/seq1024/V32k config on
               # v5e, 30-40% MFU => 90-115k -- attached to every
               # transformer row so the banked artifact carries its
               # own bar (the CPU/plumbing configs differ from the
               # anchor config; anchor_config_match says so)
               anchor_tok_s_per_chip=[90000.0, 115000.0],
               anchor_source='PERF.md: d512/L6/seq1024/V32k @ '
               '30-40%% MFU of 197 TF/s',
               anchor_config_match=bool(
                   not on_cpu and per_dev_override is None))
    if not tp:
        out['check_fn'] = lambda: _transformer_numerics_check(
            model, params, toks, tgts)
    if tp:
        out['tp'] = int(plan.model_size)
        out['mesh'] = plan.describe()
    return out


def _pipeline_scan_maker(upd, arrays):
    """Scan maker for the pipeline updaters: k 1F1B steps back to
    back inside ONE outer jit over the raw (unjitted) step, carrying
    (params, extra, opt_state) -- the pipeline twin of
    ``_scan_maker``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    step = upd._raw_step
    p0, e0, o0 = upd.params, upd.extra, upd.opt_state

    def make(k):
        @jax.jit
        def run():
            def body(carry, i):
                p, e, o = carry
                p, e, o, metrics = step(p, e, o, *arrays)
                return (p, e, o), metrics['loss']

            _, losses = lax.scan(body, (p0, e0, o0), jnp.arange(k))
            return losses

        return run

    return make


def _build_transformer_pp(quick, on_cpu, d_model, n_heads, n_layers,
                          seq, vocab, batch, policy=None, tp=None,
                          pp=2, anchor_config_match=False):
    """``--pp K`` arm: the stage-sliced ``TransformerLM`` trained
    1F1B through the unified :class:`chainermn_tpu.training.
    MeshPipelineUpdater` on a 3-D ``(data, model, pipe)`` MeshPlan
    (``docs/mesh_parallelism.md``).  The stage count clamps to the
    largest value <= K that both divides ``n_layers`` and survives
    the plan's shape-only mesh degradation; rows carry ``pp`` /
    ``n_microbatches`` / ``bubble_fraction`` (the static schedule
    cost) next to the usual anchor fields."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chainermn_tpu import training
    from chainermn_tpu.models import (TransformerLM, pipeline_parts,
                                      pipeline_stage_specs)
    from chainermn_tpu.parallel.meshplan import MeshPlan
    from chainermn_tpu.parallel.pipeline import bubble_fraction

    plan = None
    for p in range(min(int(pp), n_layers), 0, -1):
        if n_layers % p:
            continue
        cand = MeshPlan.create(tp=tp or 1, pp=p)
        if cand.pipe_size == p:
            plan = cand
            break
    n_stages = plan.pipe_size
    tp_axis = plan.model_axis if plan.model_size > 1 else None
    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers,
                          d_ff=4 * d_model, max_len=seq)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    tgts = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, seq), jnp.int32))['params']
    stage_fn, prologue, loss_on_last, stacked, extra = pipeline_parts(
        model, params, n_stages=n_stages, local_loss=True,
        tp_axis=tp_axis)
    specs = pipeline_stage_specs(stacked, pipe_axis=plan.pipe_axis,
                                 tp_axis=tp_axis)
    per_replica = batch // plan.data_size
    n_micro = next(m for m in (8, 4, 2, 1) if per_replica % m == 0)
    pol = _resolve_policy(policy)
    upd = training.MeshPipelineUpdater(
        iter([]), optax.adam(1e-3), stage_fn, loss_on_last, stacked,
        plan, n_micro=n_micro, prologue=prologue, extra_params=extra,
        param_specs=specs, policy=pol, donate=False)
    arrays = upd.shard_batch([(toks[i], tgts[i])
                              for i in range(batch)])
    tokens = batch * seq
    ff = 4 * d_model
    per_tok_fwd = n_layers * (
        8.0 * d_model ** 2 + 2.0 * 2.0 * d_model * ff
        + 2.0 * 2.0 * seq * d_model / 2.0) + 2.0 * d_model * vocab
    flops = 3.0 * per_tok_fwd * tokens
    base = BASELINE_IMG_PER_SEC_PER_CHIP * 4.1e9 * 3.0 / (
        flops / tokens)
    out = dict(make=_pipeline_scan_maker(upd, arrays), upd=upd,
               arrays=arrays, items=tokens, analytic_flops=flops,
               baseline=base, policy=_policy_row(pol),
               baseline_derivation='resnet50 baseline converted to '
               'tokens/sec via analytic flops per item',
               anchor_tok_s_per_chip=[90000.0, 115000.0],
               anchor_source='PERF.md: d512/L6/seq1024/V32k @ '
               '30-40%% MFU of 197 TF/s',
               anchor_config_match=anchor_config_match,
               pp=int(plan.pipe_size), n_microbatches=int(n_micro),
               bubble_fraction=round(
                   bubble_fraction(n_micro, n_stages), 6),
               mesh=plan.describe())
    if tp:
        out['tp'] = int(plan.model_size)
    return out


def _transformer_numerics_check(model, params, toks, tgts):
    """Pin the Pallas-kernel model against the jnp oracle ON-DEVICE:
    same params, same batch, loss+grad-norm agreement (VERDICT r2
    item 2)."""
    import jax
    import numpy as np

    from chainermn_tpu.models.transformer import lm_loss

    def loss_and_gnorm():
        loss_fn = lm_loss(lambda p, t: model.apply({'params': p}, t))
        val, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, toks[:2], tgts[:2])[0]))(params)
        gn = sum(float(np.asarray(jax.device_get(
            (g.astype('float32') ** 2).sum())))
            for g in jax.tree_util.tree_leaves(grads))
        return float(np.asarray(jax.device_get(val))), math.sqrt(gn)

    # pallas_mode() reads the env at trace time and each
    # loss_and_gnorm call jits a fresh lambda, so flipping the env
    # switches implementations.  Save/restore any ambient setting and
    # force it OFF for the kernel arm -- otherwise an inherited
    # CHAINERMN_TPU_PALLAS=0 would compare oracle to oracle and
    # "pass" without touching a kernel.
    prior = os.environ.pop('CHAINERMN_TPU_PALLAS', None)
    try:
        l_pallas, g_pallas = loss_and_gnorm()
        os.environ['CHAINERMN_TPU_PALLAS'] = '0'
        l_oracle, g_oracle = loss_and_gnorm()
    finally:
        if prior is None:
            os.environ.pop('CHAINERMN_TPU_PALLAS', None)
        else:
            os.environ['CHAINERMN_TPU_PALLAS'] = prior
    rel_l = abs(l_pallas - l_oracle) / max(abs(l_oracle), 1e-6)
    rel_g = abs(g_pallas - g_oracle) / max(abs(g_oracle), 1e-6)
    _log('numerics: loss pallas=%.6f oracle=%.6f (rel %.2e); '
         'gnorm rel %.2e' % (l_pallas, l_oracle, rel_l, rel_g))
    return {'numerics_vs_oracle_ok': bool(rel_l < 2e-2 and rel_g < 5e-2),
            'numerics_loss_rel_err': round(rel_l, 6),
            'numerics_gnorm_rel_err': round(rel_g, 6)}


def build_mlp(quick, on_cpu, per_dev_override=None, policy=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import MLP, classifier_loss

    per_dev = per_dev_override or 128
    batch = per_dev * jax.device_count()
    pol = _resolve_policy(policy)
    # policy-aware construction: the MLP computes in the policy's
    # compute dtype (params stay f32 masters via the updater)
    model = MLP(n_units=1000, n_out=10,
                dtype=pol.compute_dtype if pol is not None else None)
    rng = np.random.RandomState(0)
    x = rng.rand(batch, 784).astype(np.float32)
    y = rng.randint(0, 10, batch).astype(np.int32)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 784), jnp.float32))['params']
    loss = classifier_loss(lambda p, xx: model.apply({'params': p}, xx))
    upd, arrays = _updater_setup(
        loss, params, [(x[i], y[i]) for i in range(batch)], policy=pol)
    fwd = 2.0 * (784 * 1000 + 1000 * 1000 + 1000 * 10)
    base = BASELINE_IMG_PER_SEC_PER_CHIP * 4.1e9 * 3.0 / (3.0 * fwd)
    return dict(make=_scan_maker(upd, arrays), upd=upd, arrays=arrays,
                items=batch, analytic_flops=3.0 * fwd * batch,
                baseline=base,
                policy=_policy_row(pol, default_compute='float32'),
                baseline_derivation='resnet50 baseline converted via '
                'analytic flops per image')


BUILDERS = dict(
    {name: (lambda q, c, b=None, n=name, **kw:
            _build_conv(n, q, c, b, **kw))
     for name in _CONV_MODELS},
    seq2seq=build_seq2seq, transformer=build_transformer,
    mlp=build_mlp)
assert set(BUILDERS) == set(MODELS)


def phase_stats(cfg, quick, trace_steps=3):
    """Per-step evidence for the row (ISSUE 6): individually timed
    ``update_core`` calls give step-time p50/p99 (the scan-based
    headline measures the mean only, and a claim without tails is
    half a claim), and a short ``jax.profiler`` capture of the same
    steps runs through ``benchmarks/trace_report.py``'s overlap
    computation -- collective span time hidden behind compute vs
    exposed -- so every future perf number ships with its own
    overlap evidence.  Best-effort by contract: a converter/profiler
    failure yields a partial dict with ``phase_stats_error``, never a
    dead row."""
    import shutil
    import tempfile

    import jax

    out = {}
    upd, arrays = cfg['upd'], cfg['arrays']
    n_steps = 5 if quick else 10
    try:
        jax.block_until_ready(upd.update_core(arrays))  # warm/compile
        times = []
        for _ in range(n_steps):
            t0 = time.perf_counter()
            jax.block_until_ready(upd.update_core(arrays))
            times.append((time.perf_counter() - t0) * 1e3)
        times.sort()
        n = len(times)
        out['step_time_p50_ms'] = round(times[n // 2], 3)
        out['step_time_p99_ms'] = round(
            times[min(n - 1, int(n * 0.99))], 3)
    except Exception as e:
        out['phase_stats_error'] = 'step timing: %r' % e
        return out
    td = tempfile.mkdtemp(prefix='bench_overlap_')
    try:
        with jax.profiler.trace(td):
            for _ in range(trace_steps):
                metrics = upd.update_core(arrays)
            jax.block_until_ready(metrics)
        from benchmarks import trace_report
        import glob as _glob
        paths = sorted(_glob.glob(
            os.path.join(td, '**', '*.xplane.pb'), recursive=True))
        ov = trace_report.overlap_stats_from_paths(paths)
        out['overlap_fraction'] = ov['overlap_fraction']
        exposed = ov['exposed_collective_ms']
        out['exposed_collective_ms'] = (
            round(exposed / trace_steps, 3) if exposed is not None
            else None)
    except Exception as e:
        out.setdefault('overlap_fraction', None)
        out.setdefault('exposed_collective_ms', None)
        out['phase_stats_error'] = 'overlap capture: %r' % e
    finally:
        shutil.rmtree(td, ignore_errors=True)
    # cross-rank diagnosis fields (ISSUE 8): a short telemetry-
    # recorded window through the doctor's skew engine.  Honest
    # Nones on a single-controller bench -- collective pairing needs
    # spans from >= 2 ranks (a multi-process capture run through
    # `telemetry doctor` fills them for real); the fields exist on
    # every row so outage-window and multihost rows stay comparable.
    try:
        from chainermn_tpu import telemetry
        from chainermn_tpu.telemetry import diagnosis
        was_active = telemetry.live()
        rec = was_active or telemetry.enable()  # in-memory recorder
        try:
            n0 = len(rec.events)
            for _ in range(2):
                metrics = upd.update_core(arrays)
            jax.block_until_ready(metrics)
            spans = [dict(e, rank=e.get('rank', 0))
                     for e in rec.events[n0:]
                     if e.get('type') == 'span']
        finally:
            # a failing step must not leave the in-memory recorder
            # installed for the rest of the bench process
            if was_active is None:
                telemetry.disable()
        out.update(diagnosis.skew_summary(spans))
    except Exception as e:
        out.setdefault('collective_skew_p99_ms', None)
        out.setdefault('straggler_rank', None)
        out.setdefault('phase_stats_error', 'skew capture: %r' % e)
    return out


def measure(argv):
    """The actual benchmark (runs inside the watchdogged child)."""
    quick = '--quick' in argv
    want_cost = '--no-cost' not in argv
    want_check = '--check' in argv
    model_name = parse_model(argv)

    import jax

    from chainermn_tpu.utils.platform import enable_compilation_cache
    enable_compilation_cache()

    if '--cpu' in argv:
        from chainermn_tpu.utils import force_host_devices
        force_host_devices(8)

    n_dev = jax.device_count()
    on_cpu = jax.default_backend() == 'cpu'
    _log('backend=%s n_dev=%d model=%s'
         % (jax.default_backend(), n_dev, model_name))

    bur_trustworthy = None
    matmul_tflops = None
    roofline_lin = None
    if not on_cpu:
        bur_trustworthy = probe_block_until_ready()
        matmul_tflops, roofline_lin = calibrate_matmul_roofline(quick)

    per_dev = parse_batch(argv, model_name)
    s2d = parse_s2d(argv, model_name)
    policy_name = parse_policy(argv, model_name)
    fused_norm = parse_fused_norm(argv, model_name)
    tp = parse_tp(argv, model_name)
    pp = parse_pp(argv, model_name)
    donate = parse_donate(argv, model_name)
    _log('building %s%s%s%s%s%s%s%s' % (
        model_name,
        ' (per-device batch %d)' % per_dev if per_dev else '',
        ' (s2d stem)' if s2d else '',
        ' (policy %s)' % policy_name if policy_name else '',
        ' (fused norm)' if fused_norm else '',
        ' (tp %d)' % tp if tp else '',
        ' (pp %d)' % pp if pp else '',
        ' (donate+remat)' if donate else ''))
    extra_kw = {}
    if s2d:
        extra_kw['s2d'] = True
    if policy_name:
        extra_kw['policy'] = policy_name
    if fused_norm:
        extra_kw['fused_norm'] = True
    if tp:
        extra_kw['tp'] = tp
    if pp:
        extra_kw['pp'] = pp
    if donate:
        extra_kw['donate'] = True
    cfg = BUILDERS[model_name](quick, on_cpu, per_dev, **extra_kw)
    make = cfg['make']

    if on_cpu:
        # no length-1: XLA special-cases (unrolls) a scan of 1 and the
        # resulting program times wildly off the k>=2 line; reps>=3 so
        # the median actually rejects a single anomalous rep
        ks, reps = (2, 4, 6), 3
    elif quick:
        ks, reps = (2, 4, 6), 3
    else:
        ks, reps = (4, 8, 12), 4
    _log('timing: scan lengths %s x%d reps (first compile of a big '
         'model is minutes uncached)' % (list(ks), reps))
    # per-step floor from analytic flops at an optimistic 2x table
    # peak: plans the adaptive span escalation when timing jitter
    # hides the marginal compute of short scans (see SIGNAL_MULT).
    # The CPU plumbing run has no table entry and plans blind.
    kind = jax.devices()[0].device_kind
    floor = None
    if not on_cpu:
        # analytic_flops is the ALL-device total per step; the bound
        # must be per-step wall time, so divide by the mesh's
        # aggregate peak
        floor = float(cfg['analytic_flops']) / (
            n_dev * 2.0 * spec_lookup(BF16_PEAK_TFLOPS, kind) * 1e12)
    per_step, overhead, times, lin_err, ks, escalations = (
        adaptive_marginal_time(make, ks, reps, per_item_floor=floor))
    _log('per-step %.2fms, overhead %.1fms (ks=%s, %d escalations)'
         % (per_step * 1e3, overhead * 1e3, list(ks), escalations))

    items_per_sec = cfg['items'] / per_step
    per_chip = items_per_sec / n_dev
    baseline = cfg['baseline']
    k_long = max(ks)
    spread = (max(times[k_long]) - min(times[k_long])) / max(
        min(times[k_long]), 1e-9)
    result = dict(
        metric_stub(model_name),
        value=round(per_chip, 2),
        vs_baseline=round(per_chip / baseline, 3),
        n_devices=n_dev,
        backend=jax.default_backend(),
        step_time_ms=round(per_step * 1e3, 3),
        overhead_ms=round(overhead * 1e3, 1),
        scan_lengths=list(ks),
        adaptive_escalations=escalations,
        timing_noise_ms=round(_noise_estimate(times, reps) * 1e3, 2),
        linearity_rel_err=round(lin_err, 4),
        rep_times_s={str(k): [round(t, 4) for t in v]
                     for k, v in times.items()},
        rep_spread=round(spread, 3),
        quick=quick,
        sync_method='device_get',
        baseline_derivation=cfg['baseline_derivation'],
        global_batch_items=cfg['items'],
        per_device_batch_override=per_dev,
        stem='space_to_depth' if s2d else None,
        policy=cfg.get('policy'),
        # the HBM-traffic A/B lever (conv zoo only; None elsewhere
        # so LM rows don't carry a false 'unfused' claim)
        fused_norm=(fused_norm if model_name in _CONV_MODELS
                    else None),
    )
    if 'insize' in cfg:
        result['insize'] = cfg['insize']
    if 'donate' in cfg:
        # donation + remat arm: how real training runs; the default
        # rows replay with donate=False (PERF.md knob #6)
        result['donate'] = bool(cfg['donate'])
        result['remat'] = bool(cfg['remat'])
    if model_name == 'transformer':
        # tokens/s/chip vs the PERF.md roofline anchor, on every
        # transformer row (the tp arm's acceptance bar)
        result['anchor_tok_s_per_chip'] = cfg['anchor_tok_s_per_chip']
        result['anchor_source'] = cfg['anchor_source']
        result['anchor_config_match'] = cfg['anchor_config_match']
        lo, hi = cfg['anchor_tok_s_per_chip']
        result['pct_of_anchor_mid'] = round(
            100.0 * per_chip / ((lo + hi) / 2.0), 1)
    if cfg.get('pp'):
        # pipeline arm provenance: stage count, micro-batch count and
        # the schedule's static bubble (docs/mesh_parallelism.md)
        result['pp'] = cfg['pp']
        result['n_microbatches'] = cfg['n_microbatches']
        result['bubble_fraction'] = cfg['bubble_fraction']
    if cfg.get('tp') or cfg.get('pp'):
        if cfg.get('tp'):
            result['tp'] = cfg['tp']
        result['mesh'] = cfg['mesh']
        try:
            # per-axis collective bytes of the traced per-device step
            # (dp vs tp wire traffic, jaxpr-level -- no capture
            # needed); see analysis/memtraffic.py
            import jax as _jax
            from chainermn_tpu.analysis.memtraffic import (
                collective_bytes_by_axis)
            fn, args = cfg['upd'].traceable_step(cfg['arrays'])
            by_axis = collective_bytes_by_axis(
                _jax.make_jaxpr(fn)(*args))
            result['collective_bytes_per_axis_mb'] = {
                k: round(v / 1e6, 3) for k, v in sorted(
                    by_axis.items())}
        except Exception as e:
            result['collective_bytes_per_axis_error'] = repr(e)[:300]
    # headline-tuning adoption provenance (set by adopt_tuned_config
    # in the parent; inherited by this child via the environment)
    if os.environ.get('CHAINERMN_TPU_ADOPTED_FROM'):
        result['adopted_config_from'] = \
            os.environ['CHAINERMN_TPU_ADOPTED_FROM']
    if os.environ.get('CHAINERMN_TPU_ADOPTED_COMPARISON'):
        # the crowning comparison (winner vs incumbent sources,
        # values, quickness, scan_lengths, device_kind) rides the row
        # so adoption fairness is auditable from the artifact alone
        try:
            result['adopted_comparison'] = json.loads(
                os.environ['CHAINERMN_TPU_ADOPTED_COMPARISON'])
        except ValueError:
            pass
    if bur_trustworthy is not None:
        result['block_until_ready_trustworthy'] = bool(bur_trustworthy)
    if matmul_tflops is not None:
        result['measured_matmul_tflops'] = round(matmul_tflops, 1)
        result['roofline_linearity_rel_err'] = round(roofline_lin, 4)

    suspect_reasons = []
    if want_cost:
        _log('cost analysis')
        xla_flops = 0.0
        xla_bytes = 0.0
        try:
            cost = cfg['upd'].compiled_cost_analysis(cfg['arrays'])
            # XLA cost analysis reports the LOCAL executable's flops,
            # i.e. per participating device of the SPMD program
            xla_flops = float(cost.get('flops', 0.0)) * n_dev
            xla_bytes = float(cost.get('bytes accessed', 0.0))
        except Exception as e:
            _log('cost analysis failed: %r' % e)
        analytic = float(cfg['analytic_flops'])
        # HEADLINE accounting is the conservative model-flops (analytic)
        # convention -- XLA counts ResNet conv flops ~2x the standard
        # model-flops convention, which round 4 showed can overstate MFU
        # by the same factor (VERDICT r4 weak #1).  XLA's count (the
        # flops the chip actually executed) is kept as a sidecar AND
        # used for the impossible-claim suspect gates, where the HIGHER
        # count is the sensitive one.
        achieved = analytic / per_step / 1e12      # model-flops TF/s
        achieved_xla = (xla_flops / per_step / 1e12) if xla_flops \
            else None
        result['xla_flops_per_step'] = round(xla_flops / 1e9, 2)
        result['analytic_flops_per_step'] = round(analytic / 1e9, 2)
        result['flop_count_ratio_xla_over_analytic'] = round(
            xla_flops / analytic, 3) if xla_flops else None
        result['achieved_tflops_per_chip'] = round(achieved / n_dev, 3)
        if achieved_xla is not None:
            result['achieved_tflops_per_chip_xla'] = round(
                achieved_xla / n_dev, 3)
        if xla_bytes:
            # post-fusion op-level bytes of the PER-DEVICE executable:
            # an estimate of the step's HBM traffic (VMEM-resident
            # reuse is still counted, so boundedness reads high).
            # hbm_roofline_ms = the floor a perfectly-streamed step of
            # this traffic could reach; hbm_explained_pct ~ how much
            # of the measured step the HBM spec rate accounts for --
            # the direct test of the HBM-bound hypothesis (PERF.md,
            # "What the batch sweep's first point says").
            result['xla_bytes_accessed_per_step_gb'] = round(
                xla_bytes / 1e9, 3)
            # traffic divided down to the judged unit (images for the
            # conv zoo, items elsewhere): PERF.md's hand-derived
            # "~316 MB/img" as a first-class row field on EVERY model
            # row -- the number the --fused-norm arm exists to move
            result['hbm_bytes_per_image'] = round(
                xla_bytes * n_dev / cfg['items'], 1)
            if not on_cpu:
                hbm = spec_lookup(HBM_SPEC_GBS, kind)
                hbm_ms = xla_bytes / (hbm * 1e9) * 1e3
                result['hbm_roofline_ms'] = round(hbm_ms, 3)
                # achieved HBM stream rate as % of the chip's spec
                # bandwidth: ~100 means the step IS the bandwidth
                # wall (the batch-sweep diagnosis); small means the
                # traffic cannot explain the step time
                result['hbm_explained_pct'] = round(
                    100.0 * hbm_ms / (per_step * 1e3), 1)
                result['pct_of_hbm_peak'] = \
                    result['hbm_explained_pct']
        if not on_cpu:
            peak = spec_lookup(BF16_PEAK_TFLOPS, kind)
            result['device_kind'] = kind
            result['table_peak_bf16_tflops'] = peak
            pct = 100.0 * achieved / n_dev / peak
            result['pct_of_bf16_peak'] = round(pct, 1)
            pct_xla = None
            if achieved_xla is not None:
                pct_xla = 100.0 * achieved_xla / n_dev / peak
                result['pct_of_bf16_peak_xla'] = round(pct_xla, 1)
            # name WHICH accounting tripped the gate -- a reason
            # quoting the max() would contradict the row's own
            # analytic-convention pct_of_bf16_peak field
            if pct > 100.0:
                suspect_reasons.append(
                    'achieved %.1f%% of table bf16 peak '
                    '(analytic flops)' % pct)
            elif pct_xla is not None and pct_xla > 100.0:
                suspect_reasons.append(
                    'achieved %.1f%% of table bf16 peak (XLA '
                    'executed-flop count sidecar)' % pct_xla)
        gate_tf = max(achieved, achieved_xla or 0.0) / n_dev
        if matmul_tflops and gate_tf > matmul_tflops:
            suspect_reasons.append(
                'achieved %.1f TF/s exceeds self-calibrated matmul '
                'roofline %.1f TF/s' % (gate_tf, matmul_tflops))
    if ('--no-phase-stats' not in argv and 'upd' in cfg
            and 'arrays' in cfg):
        _log('phase stats: per-step p50/p99 + overlap capture')
        result.update(phase_stats(cfg, quick))

    noise = _noise_estimate(times, reps)
    if per_step * (ks[-1] - ks[0]) < SIGNAL_MULT * noise:
        suspect_reasons.append(
            'marginal signal %.1fms below %.0fx noise floor %.1fms '
            'even after adaptive escalation'
            % (per_step * (ks[-1] - ks[0]) * 1e3, SIGNAL_MULT,
               noise * 1e3))
    if spread > 0.5:
        suspect_reasons.append(
            'step-time spread %.0f%% across reps' % (spread * 100))
    if per_step <= 1e-9:
        suspect_reasons.append(
            'fitted per-step slope non-positive: t(K) did not '
            'increase with scan length (sync not real)')
    elif lin_err > LINEARITY_GATE:
        # elif: under a non-positive slope lin_err is the 99.0
        # sentinel; the message above already covers it
        suspect_reasons.append(
            'scan timing nonlinear: segment slopes deviate %.0f%% '
            'from the fitted per-step time' % (lin_err * 100))
    if roofline_lin is not None and roofline_lin > LINEARITY_GATE:
        # independent measurement (calibration scan), independent gate
        suspect_reasons.append(
            'matmul roofline calibration nonlinear (%.0f%%) -- '
            'measured_matmul_tflops and the roofline gate are '
            'unreliable' % (roofline_lin * 100))
    if suspect_reasons:
        result['suspect'] = True
        result['suspect_reason'] = '; '.join(suspect_reasons)

    if want_check and 'check_fn' in cfg:
        result.update(cfg['check_fn']())

    print(json.dumps(result), flush=True)


def parse_batch(argv, model):
    """Extract and validate ``--batch N`` (per-device override, the
    MFU-chase lever -- VERDICT r3 item 3); structured error on a
    missing/non-positive/non-integer value.  Called in the PARENT
    before the expensive backend probe, and again in the child."""
    if '--batch' not in argv:
        return None
    i = argv.index('--batch')
    raw = argv[i + 1] if i + 1 < len(argv) else None
    try:
        val = int(raw)
        if val <= 0:
            raise ValueError
    except (TypeError, ValueError):
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_batch',
                  detail='--batch needs a positive integer, got %r'
                  % (raw,)), rc=1)
    return val


# mirror of chainermn_tpu.precision.Policy.from_string's registry --
# the PARENT process never imports jax, so the flag is validated
# against this static table and resolved to a Policy in the child
POLICY_NAMES = ('f32', 'float32', 'bf16', 'bfloat16', 'f16',
                'float16')


def parse_policy(argv, model):
    """Extract and validate ``--policy NAME`` (mixed-precision
    bench arm: bf16 compute/reduce with f32 masters -- the A/B lever
    against the default row).  Called in the PARENT before the
    backend probe, and again in the child."""
    if '--policy' not in argv:
        return None
    i = argv.index('--policy')
    raw = argv[i + 1] if i + 1 < len(argv) else None
    if raw is None or raw.lower() not in POLICY_NAMES:
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_policy',
                  detail='--policy needs one of %s, got %r'
                  % ('/'.join(POLICY_NAMES), raw)), rc=1)
    return raw.lower()


def parse_s2d(argv, model):
    """``--s2d`` (space-to-depth stem) is resnet50-only; validated in
    the PARENT before the backend probe, like the other flags."""
    if '--s2d' not in argv:
        return False
    if model != 'resnet50':
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_flag',
                  detail='--s2d (space-to-depth stem) applies to '
                  '--model resnet50 only'), rc=1)
    return True


def parse_fused_norm(argv, model):
    """``--fused-norm`` (the fused BN+relu+add ``batch_norm_act``
    Pallas path, ``docs/kernels.md``) is the HBM-traffic A/B arm of
    the conv zoo; validated in the PARENT like the other flags.
    Norm-free zoo members (vgg16) accept the model flag as a no-op,
    but a no-op BENCH ARM would bank a row indistinguishable from its
    baseline -- so the bench flag is limited to the normed models."""
    if '--fused-norm' not in argv:
        return False
    if model not in ('resnet50', 'googlenetbn'):
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_flag',
                  detail='--fused-norm (fused batch_norm_act) '
                  'applies to the BN-carrying conv models '
                  '(resnet50/googlenetbn) only'), rc=1)
    return True


def parse_tp(argv, model):
    """``--tp N`` (transformer only): composed dp x tp MeshPlan arm
    -- attention heads / MLP columns+rows split over the ``model``
    mesh axis (docs/mesh_parallelism.md).  Validated in the PARENT
    before the backend probe, like the other flags."""
    if '--tp' not in argv:
        return None
    if model != 'transformer':
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_flag',
                  detail='--tp (tensor-parallel MeshPlan arm) '
                  'applies to --model transformer only'), rc=1)
    i = argv.index('--tp')
    raw = argv[i + 1] if i + 1 < len(argv) else None
    try:
        val = int(raw)
        if val <= 0:
            raise ValueError
    except (TypeError, ValueError):
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_tp',
                  detail='--tp needs a positive integer, got %r'
                  % (raw,)), rc=1)
    return val


def parse_pp(argv, model):
    """``--pp K`` (transformer only): the pipeline-parallel MeshPlan
    arm -- the stage-sliced transformer trained 1F1B through the
    unified ``MeshPipelineUpdater`` on a 3-D ``(data, model, pipe)``
    mesh (``docs/mesh_parallelism.md``); composes with ``--tp``.
    Validated in the PARENT before the backend probe, like the other
    flags."""
    if '--pp' not in argv:
        return None
    if model != 'transformer':
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_flag',
                  detail='--pp (pipeline-parallel MeshPlan arm) '
                  'applies to --model transformer only'), rc=1)
    i = argv.index('--pp')
    raw = argv[i + 1] if i + 1 < len(argv) else None
    try:
        val = int(raw)
        if val <= 0:
            raise ValueError
    except (TypeError, ValueError):
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_pp',
                  detail='--pp needs a positive integer, got %r'
                  % (raw,)), rc=1)
    return val


def parse_donate(argv, model):
    """``--donate`` (resnet50 only): the donation+remat headline arm
    -- buffers donated into the step and the backward rematerializing
    the forward, i.e. how real training runs (PERF.md knob #6: the
    default replay scan measures with donate=False and understates
    it)."""
    if '--donate' not in argv:
        return False
    if model != 'resnet50':
        emit(dict(metric_stub(model), value=0.0, vs_baseline=0.0,
                  error='bad_flag',
                  detail='--donate (donation + remat headline arm) '
                  'applies to --model resnet50 only'), rc=1)
    return True


def _last_json_row(path):
    """Parse the last non-blank line of a bench artifact as JSON (the
    one-JSON-line-last contract every ``bench_*.out`` follows; the
    same contract ci/run_tpu_round.sh's pred_json_row checks).
    Returns None on any read/parse failure."""
    try:
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        row = json.loads(lines[-1])
    except (OSError, ValueError, IndexError):
        return None
    return row if isinstance(row, dict) else None


def _trustworthy_value(row, model='resnet50'):
    """The row's value when it is a trustworthy ``model`` measurement
    (real-TPU, error-free, suspect-free, not flagged ``retracted`` in
    the row, finite positive value), else None.  ONE filter shared by
    the winner pick, the newest-tag search and the banked-last-good
    lookup so they can never disagree on what counts."""
    if (not isinstance(row, dict)
            or not str(row.get('metric', '')).startswith(model)
            or row.get('backend') != 'tpu' or row.get('error')
            or row.get('suspect') or row.get('retracted')):
        return None
    try:
        value = float(row.get('value', 0.0))
    except (TypeError, ValueError):
        return None
    if not math.isfinite(value) or value <= 0:
        return None
    return value


def _row_quickness(row):
    """``'quick'`` / ``'full'`` / ``None`` (unknown) for a bench row.
    Rows measured from this round on carry ``quick`` directly; older
    rows are inferred from ``scan_lengths`` (the --quick sweep used
    max length 6, the full config 12+).  ADVICE r5 #1: quick and
    non-quick rows have different measurement bias, so adoption must
    not crown a winner across the boundary."""
    if isinstance(row.get('quick'), bool):
        return 'quick' if row['quick'] else 'full'
    ks = row.get('scan_lengths')
    if isinstance(ks, list) and ks:
        try:
            return 'quick' if max(ks) <= 6 else 'full'
        except TypeError:
            return None
    return None


def _quickness_matches(a, b):
    """Rows are comparable when their quickness classes agree; an
    unknown class (legacy rows) matches anything -- strictness cannot
    retroactively orphan every pre-ledger artifact."""
    return a is None or b is None or a == b


def _round_tag_of(source):
    """The round tag (window ordinal) a bench artifact name carries
    (``bench_resnet50_b64_r5.out`` -> ``r5``); None when the name
    follows no round convention."""
    m = re.search(r'_(r[a-zA-Z0-9]+)\.out$', str(source or ''))
    return m.group(1) if m else None


def _pick_tuned(rows, fallback_incumbent=None):
    """Adoption decision over bench JSON rows (rich form).

    Returns a dict: ``flags``/``source``/``value`` for the winning
    tuned config (``flags`` None = keep the default config), plus the
    comparison provenance -- incumbent source/value, both sides'
    quickness class, ``scan_lengths`` and ``device_kind``, and a
    ``declined`` reason when adoption was refused.

    Fairness rules (ADVICE r5 #1/#2):

    - a tuned winner is only crowned against an incumbent of MATCHING
      quickness (``--quick`` sweep rows measure with shorter scans
      and different bias than the non-quick headline; legacy rows
      without the ``quick`` field are inferred from ``scan_lengths``
      and unknowns match anything);
    - when the deciding rows hold NO trustworthy default-config
      incumbent, the caller-supplied ``fallback_incumbent`` (the
      newest trustworthy default-config row from an OLDER tag) is
      used for the comparison; with neither, adoption is DECLINED --
      a tuned row must never be adopted uncompared, it could be
      slower than the proven default.
    """
    best, incumbents = None, []
    for row in rows:
        value = _trustworthy_value(row)
        if value is None:
            continue
        tuned = bool(row.get('per_device_batch_override')
                     or row.get('stem'))
        if tuned and (best is None or value > best[0]):
            best = (value, row)
        if not tuned:
            incumbents.append((value, row))
    out = {'flags': None, 'source': None, 'value': None}
    if best is None:
        return out
    value, row = best
    quickness = _row_quickness(row)
    matching = [iv for iv in incumbents
                if _quickness_matches(quickness,
                                      _row_quickness(iv[1]))]
    if not matching and fallback_incumbent is not None:
        fb_value = _trustworthy_value(fallback_incumbent)
        if fb_value is not None and _quickness_matches(
                quickness, _row_quickness(fallback_incumbent)):
            matching = [(fb_value, fallback_incumbent)]
            out['incumbent_fallback'] = True
    if not matching:
        out['declined'] = ('no trustworthy default-config incumbent '
                           'of matching quickness (%s) to compare '
                           'against' % (quickness or 'unknown'))
        return out
    inc_value, inc_row = max(matching, key=lambda iv: iv[0])
    # window/device identity (ADVICE r5 adoption-fairness residual):
    # the round tag is the chip-window ordinal and device_kind the
    # hardware identity -- a winner crowned across two windows (or
    # two chip generations) is visible in the provenance instead of
    # silently passing as a same-conditions comparison
    w_tag = _round_tag_of(row.get('_source'))
    i_tag = _round_tag_of(inc_row.get('_source'))
    w_kind = row.get('device_kind')
    i_kind = inc_row.get('device_kind')
    out.update(
        incumbent_source=inc_row.get('_source', '(unknown artifact)'),
        incumbent_value=inc_value,
        incumbent_quick=_row_quickness(inc_row),
        winner_quick=quickness,
        winner_scan_lengths=row.get('scan_lengths'),
        incumbent_scan_lengths=inc_row.get('scan_lengths'),
        winner_device_kind=w_kind,
        incumbent_device_kind=i_kind,
        winner_round_tag=w_tag,
        incumbent_round_tag=i_tag,
        cross_window=bool(
            (w_tag is not None and i_tag is not None
             and w_tag != i_tag)
            or (w_kind is not None and i_kind is not None
                and w_kind != i_kind)),
    )
    if value <= inc_value:
        return out  # default config still wins
    flags = []
    if row.get('per_device_batch_override'):
        flags += ['--batch', str(int(row['per_device_batch_override']))]
    if row.get('stem'):
        flags.append('--s2d')
    out.update(flags=flags,
               source=row.get('_source', '(unknown artifact)'),
               value=value)
    return out


def pick_tuned_resnet50(rows, fallback_incumbent=None):
    """Back-compat 3-tuple view of :func:`_pick_tuned`:
    ``(flags, source, value)``, all None when the default config wins
    or adoption is declined."""
    d = _pick_tuned(rows, fallback_incumbent)
    return d['flags'], d['source'], d['value']


#: diagnostic sidecars carried along with ``banked_value`` on a
#: backend_unavailable row (each lands as ``banked_<key>``): the
#: HBM-traffic accounting and MFU fields that keep BENCH_r0N.json
#: diagnosable through a backend outage (the r3-r5 gap had the value
#: but none of the bandwidth evidence)
BANKED_SIDECAR_KEYS = (
    'hbm_bytes_per_image', 'pct_of_hbm_peak', 'hbm_explained_pct',
    'pct_of_bf16_peak', 'xla_bytes_accessed_per_step_gb',
    'step_time_ms', 'fused_norm')


def banked_last_good_row(model):
    """Newest banked trustworthy row for ``model`` from the committed
    round artifacts (``benchmarks/results/bench_<model>*_rN.out``):
    ``(row, value, round_tag, source_name)``, all None when no
    trustworthy row is banked."""
    res = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'benchmarks', 'results')
    try:
        names = sorted(os.listdir(res))
    except OSError:
        return None, None, None, None
    best_by_tag = {}
    for name in names:
        if not (name.startswith('bench_' + model)
                and name.endswith('.out')):
            continue
        m = re.search(r'_(r[a-zA-Z0-9]+)\.out$', name)
        if not m:
            continue
        row = _last_json_row(os.path.join(res, name))
        value = _trustworthy_value(row, model)
        if value is None:
            continue
        tag = m.group(1)
        if tag not in best_by_tag or value > best_by_tag[tag][0]:
            best_by_tag[tag] = (value, name, row)
    if not best_by_tag:
        return None, None, None, None

    def tag_key(tag):
        m2 = re.match(r'r(\d+)', tag)
        return (int(m2.group(1)) if m2 else -1, tag)

    tag = max(best_by_tag, key=tag_key)
    value, name, row = best_by_tag[tag]
    return row, value, tag, name


def banked_last_good(model):
    """Newest banked trustworthy measurement for ``model``:
    ``(value, round_tag, source_name)``, or ``(None, None, None)``
    when no trustworthy row is banked.

    Consumed by the ``backend_unavailable`` path: a backend that does
    not answer degrades to a 0.0 row that still CARRIES the last-good
    measurement, labeled as banked, instead of erasing the trajectory
    for the window.
    """
    _, value, tag, name = banked_last_good_row(model)
    return value, tag, name


def adopt_tuned_config(argv, model):
    """Parent-side headline tuning adoption (round 5; VERDICT r4 next
    #2): a plain ``python bench.py`` consults the banked MFU-sweep
    artifacts (``benchmarks/results/bench_resnet50*_*.out``, written
    by ``ci/run_tpu_round.sh`` tier 3) and adopts the winning batch /
    stem config, so the driver's end-of-round run (and the series'
    own ``bench_resnet50_best`` step, which runs AFTER the sweep)
    measures the best *measured* configuration rather than the
    batch-32 floor.  The row stays honest:
    ``per_device_batch_override`` / ``stem`` record the config and
    ``adopted_config_from`` records the artifact that crowned it.
    Explicit ``--batch`` / ``--s2d`` / ``--cpu`` / ``--no-adopt``
    disable adoption.

    Only artifacts from the NEWEST round tag with a trustworthy row
    are considered (``bench_resnet50*_rN.out``): a winner crowned in
    an earlier round -- possibly under a different chip allocation or
    a since-fixed harness -- must not silently steer today's headline
    config.  Fairness (ADVICE r5 #1/#2, implemented in
    ``_pick_tuned``): winners are only crowned against incumbents of
    matching --quick-ness; when the deciding tag holds no trustworthy
    default-config incumbent, the newest trustworthy default-config
    row from an OLDER tag stands in, and with neither, adoption is
    declined outright.  The full comparison (winner/incumbent
    sources, values, quickness, scan_lengths, device_kind) is
    exported via ``CHAINERMN_TPU_ADOPTED_COMPARISON`` and lands in
    the measured row as ``adopted_comparison``.
    """
    # cleared unconditionally so a value inherited from a wrapper's
    # environment can never fabricate provenance on a run where
    # adoption was disabled or declined
    os.environ.pop('CHAINERMN_TPU_ADOPTED_FROM', None)
    os.environ.pop('CHAINERMN_TPU_ADOPTED_COMPARISON', None)
    if (model != 'resnet50' or '--batch' in argv or '--s2d' in argv
            or '--cpu' in argv or '--no-adopt' in argv):
        return argv
    res = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'benchmarks', 'results')
    by_tag = {}
    tag_mtime = {}
    try:
        names = sorted(os.listdir(res))
    except OSError:
        return argv
    for name in names:
        if not (name.startswith('bench_resnet50')
                and name.endswith('.out')):
            continue
        # any r-prefixed tag participates (r5, r5hotfix, ...); other
        # suffixes are not round artifacts.  No underscore in the
        # class: \w would swallow '..._b128_r5' into one bogus tag
        m = re.search(r'_(r[a-zA-Z0-9]+)\.out$', name)
        if not m:
            continue
        row = _last_json_row(os.path.join(res, name))
        if row is not None:
            tag = m.group(1)
            row['_source'] = name
            by_tag.setdefault(tag, []).append(row)
            try:
                mt = os.path.getmtime(os.path.join(res, name))
            except OSError:
                mt = 0.0
            tag_mtime[tag] = max(tag_mtime.get(tag, 0.0), mt)

    def tag_key(tag):
        # numeric round FIRST (git checkouts do not preserve mtimes,
        # so r10 must beat r5 regardless of file timestamps); artifact
        # mtime breaks ties between same-number tags (r5 vs a later
        # r5hotfix), then the tag string for full determinism
        m2 = re.match(r'r(\d+)', tag)
        return (int(m2.group(1)) if m2 else -1,
                tag_mtime.get(tag, 0.0), tag)

    ordered = sorted(by_tag, key=tag_key, reverse=True)
    decision, deciding_idx = None, None
    for i, tag in enumerate(ordered):
        if any(_trustworthy_value(r) is not None
               for r in by_tag[tag]):
            deciding_idx = i  # newest tag with any trustworthy row
            break
    if deciding_idx is None:
        return argv
    # fallback incumbent (ADVICE r5 #2): the newest trustworthy
    # DEFAULT-CONFIG row from any OLDER tag, for when the deciding
    # tag banked only tuned rows
    fallback = None
    for tag in ordered[deciding_idx + 1:]:
        candidates = [
            r for r in by_tag[tag]
            if _trustworthy_value(r) is not None
            and not (r.get('per_device_batch_override')
                     or r.get('stem'))]
        if candidates:
            fallback = max(candidates,
                           key=lambda r: float(r.get('value', 0.0)))
            break
    decision = _pick_tuned(by_tag[ordered[deciding_idx]],
                           fallback_incumbent=fallback)
    flags, source, value = (decision['flags'], decision['source'],
                            decision['value'])
    if not flags:
        if decision.get('declined'):
            _log('tuned-config adoption declined: %s'
                 % decision['declined'])
        return argv
    _log('adopting tuned resnet50 config %s from %s '
         '(banked %.1f items/s/chip vs incumbent %s at %.1f)'
         % (' '.join(flags), source, value,
            decision.get('incumbent_source'),
            decision.get('incumbent_value') or 0.0))
    os.environ['CHAINERMN_TPU_ADOPTED_FROM'] = source
    os.environ['CHAINERMN_TPU_ADOPTED_COMPARISON'] = json.dumps(
        {k: v for k, v in decision.items()
         if k not in ('flags',)}, sort_keys=True)
    return argv + flags


def parse_model(argv):
    """Extract and validate --model; emits the standard error line on
    a missing/unknown value (never a raw traceback)."""
    if '--model' not in argv:
        return 'resnet50'
    i = argv.index('--model')
    model = argv[i + 1] if i + 1 < len(argv) else None
    if model not in BUILDERS:
        emit(dict(metric_stub('resnet50'), value=0.0, vs_baseline=0.0,
                  error='unknown_model',
                  detail='--model %r; choose from %s'
                  % (model, '/'.join(MODELS))), rc=1)
    return model


def measure_recovery(argv):
    """``--recovery``: the self-healing recovery-time row (ISSUE 9).

    Runs ONE supervised chaos scenario end-to-end on real CPU
    ``jax.distributed`` worker subprocesses -- rank 1 hard-killed
    mid-train, the supervisor classifies, elastically shrinks 2 -> 1
    and resumes from the periodic checkpoint -- and reports the
    ledger's own recovery accounting: MTTR (failure detection to
    first post-resume progress) as the row value, with downtime,
    cause, world sizes and resumed step as fields -- plus the
    unified goodput decomposition
    (:mod:`chainermn_tpu.telemetry.goodput`): ``goodput_fraction``
    and the per-bucket wall-clock split are banked alongside MTTR so
    the recovery row prices not just how fast the supervisor healed
    but what the whole incident cost.  No accelerator involved: this
    row prices the CONTROL loop, so it stays measurable through TPU
    outage windows."""
    import shutil
    import tempfile

    quick = '--quick' in argv
    from chainermn_tpu.training.supervisor import (
        Ledger, RestartPolicy, Supervisor)
    from chainermn_tpu.utils import failure as _failure

    out = tempfile.mkdtemp(prefix='bench_recovery.')
    env = dict(os.environ)
    env['CHAINERMN_TPU_CHAOS'] = 'rank=1;kill_step=@2'
    steps = 3 if quick else 4
    policy = RestartPolicy(
        max_restarts=3, crash_threshold=3,
        backoff=_failure.Backoff(initial=0.2, factor=2.0,
                                 max_delay=2.0))
    sup = Supervisor(
        nprocs=2, out=out, steps=steps, ckpt_every=1, policy=policy,
        stall_timeout=90.0, startup_grace=240.0, term_grace=6.0,
        drain_grace=2.0, attempt_timeout=420.0, oracle=False,
        env=env)
    _log('recovery: supervising 2 procs, kill_step=@2 on rank 1, '
         '%d steps' % steps)
    t0 = time.monotonic()
    try:
        rc = sup.run()
        wall = time.monotonic() - t0
        ledger = Ledger.read(os.path.join(out, 'supervisor_ledger.jsonl'))
        fails = [e for e in ledger if e['event'] == 'failure']
        recs = [e for e in ledger if e['event'] == 'recovered']
        comps = [e for e in ledger if e['event'] == 'complete']
        mttr = comps[0].get('mttr_s') if comps else None
        result = {
            'metric': 'supervisor_recovery_mttr_seconds',
            'unit': 'seconds',
            'value': mttr,
            'supervisor_rc': rc,
            'wall_s': round(wall, 3),
            'downtime_s': (recs[0]['downtime_s'] if recs else None),
            'cause': (fails[0]['cause'] if fails else None),
            'chaos_site': (fails[0].get('chaos_site')
                           if fails else None),
            'dead_rank': (fails[0].get('rank') if fails else None),
            'world_before': 2,
            'world_after': (comps[0]['world_size'] if comps
                            else None),
            'resumed_step': (comps[0].get('resumed_step') if comps
                             else None),
            'restarts': (comps[0]['restarts'] if comps else None),
            'steps': steps,
            'quick': quick,
            'backend': 'cpu-subprocess',
        }
        from chainermn_tpu.telemetry import goodput as _goodput
        gp = _goodput.build_goodput(out)
        if gp.get('wall_s') is not None:
            result['goodput_fraction'] = gp['goodput_fraction']
            result['goodput_wall_s'] = gp['wall_s']
            result['goodput_buckets_s'] = gp['buckets_s']
            result['restart_downtime_s'] = \
                gp['buckets_s']['restart_downtime']
        if rc != 0 or mttr is None:
            result['error'] = 'recovery_incomplete'
        emit(result, rc=0 if rc == 0 and mttr is not None else 1)
    finally:
        shutil.rmtree(out, ignore_errors=True)


#: loader-row sidecars (--loader): the input-pipeline A/B's
#: vocabulary -- the device-resident twin, the streamed/resident
#: efficiency ratio, H2D overlap and loader-pressure percentiles
LOADER_SIDECAR_KEYS = (
    'device_resident_samples_per_s', 'loader_efficiency',
    'h2d_overlap_fraction', 'data_queue_depth_p50',
    'data_worker_busy_fraction', 'corrupt_skipped')


def measure_loader(argv):
    """``--loader``: the streamed-vs-device-resident A/B row
    (ISSUE 15).

    Runs the SAME ``update_core`` training loop twice -- once fed the
    pre-sharded device-resident arrays every bench arm uses, once fed
    real record shards through
    :class:`~chainermn_tpu.data.StreamingLoader` (decode thread pool)
    composed with ``DevicePrefetchIterator`` (double-buffered
    ``device_put``) -- and reports streamed samples/s/chip as the
    value with the resident twin, their ratio
    (``loader_efficiency``: 1.0 = the pipeline fully hides under the
    step), the measured H2D overlap fraction (telemetry interval
    intersection of ``host_batch_prep``/``h2d`` spans vs
    ``jitted_step``), and the loader-pressure gauges
    (queue-depth p50, worker busy fraction)."""
    import shutil
    import tempfile

    import numpy as np

    quick = '--quick' in argv
    on_cpu = '--cpu' in argv
    model = parse_model(argv)
    if model not in ('resnet50', 'mlp'):
        emit(dict(metric_stub('loader_' + model), value=0.0,
                  error='unsupported_model',
                  detail='--loader supports resnet50/mlp'), rc=1)
    n_workers = int(_flag_value(argv, '--loader-workers', 2))
    prefetch = int(_flag_value(argv, '--loader-prefetch', 2))
    steps = 6 if quick else 24
    warm = 2

    import jax

    from chainermn_tpu import telemetry
    from chainermn_tpu.data import (ShardSet, StreamingLoader,
                                    write_examples)
    from chainermn_tpu.telemetry.report import (load_rank_logs,
                                                overlap_from_intervals)
    from chainermn_tpu.training.iterators import DevicePrefetchIterator

    cfg = BUILDERS[model](quick, on_cpu)
    upd, arrays, batch = cfg['upd'], cfg['arrays'], cfg['items']

    def timed_loop(next_batch):
        for _ in range(warm):
            upd.update_core(next_batch())
        jax.block_until_ready(upd.params)
        t0 = time.monotonic()
        for _ in range(steps):
            upd.update_core(next_batch())
        jax.block_until_ready(upd.params)
        return time.monotonic() - t0

    # A: device-resident feed (every other bench arm's regime)
    _log('loader A/B: device-resident %d steps of %d samples'
         % (steps, batch))
    wall_res = timed_loop(lambda: arrays)
    resident_sps = batch * steps / wall_res / jax.device_count()

    # B: streamed shards through the full pipeline, telemetry on so
    # the overlap fraction is measured, not inferred
    shard_dir = tempfile.mkdtemp(prefix='bench_loader_shards.')
    tele_dir = tempfile.mkdtemp(prefix='bench_loader_tele.')
    try:
        rng = np.random.RandomState(7)
        n = batch * 3
        if model == 'mlp':
            examples = [(rng.rand(784).astype(np.float32),
                         np.int32(rng.randint(10)))
                        for _ in range(n)]
        else:
            insize = cfg['insize']
            examples = [
                (rng.rand(insize, insize, 3).astype(np.float32),
                 np.int32(rng.randint(1000))) for _ in range(n)]
        paths = write_examples(examples, shard_dir,
                               n_shards=max(2, n_workers))
        loader = StreamingLoader(
            ShardSet(paths), batch, size=1, rank=0, seed=11,
            n_workers=n_workers, prefetch=prefetch)
        rec = telemetry.enable(tele_dir)
        it = DevicePrefetchIterator(loader, upd.shard_batch,
                                    depth=prefetch)
        _log('loader A/B: streamed %d steps (%d workers, prefetch %d)'
             % (steps, n_workers, prefetch))
        try:
            wall_str = timed_loop(lambda: next(it))
        finally:
            it.finalize()
            rec.flush()
            telemetry.disable()
        streamed_sps = batch * steps / wall_str / jax.device_count()

        _, spans, _, _ = load_rank_logs(tele_dir)
        input_iv = [(s['t0'], s['t1']) for s in spans
                    if s.get('name') in ('host_batch_prep', 'h2d')]
        compute_iv = [(s['t0'], s['t1']) for s in spans
                      if s.get('name') == 'jitted_step']
        ov = overlap_from_intervals(input_iv, compute_iv)
        depth = sorted(loader.depth_samples)
        result = dict(
            metric_stub('loader_' + model),
            value=round(streamed_sps, 3),
            vs_baseline=round(streamed_sps / max(resident_sps, 1e-9),
                              4),
            device_resident_samples_per_s=round(resident_sps, 3),
            loader_efficiency=round(
                streamed_sps / max(resident_sps, 1e-9), 4),
            h2d_overlap_fraction=ov['overlap_fraction'],
            data_queue_depth_p50=(
                float(depth[len(depth) // 2]) if depth else None),
            data_worker_busy_fraction=round(loader.busy_fraction(), 4),
            corrupt_skipped=loader.corrupt_skipped,
            loader_workers=n_workers,
            loader_prefetch=prefetch,
            batch=batch, steps=steps, quick=quick,
            backend=jax.default_backend(),
            device_kind=jax.devices()[0].device_kind,
            n_devices=jax.device_count(),
        )
        loader.finalize()
        emit(result, rc=0)
    finally:
        shutil.rmtree(shard_dir, ignore_errors=True)
        shutil.rmtree(tele_dir, ignore_errors=True)


#: serve-row sidecar fields carried through backend_unavailable
#: windows (the serving twin of BANKED_SIDECAR_KEYS)
SERVE_SIDECAR_KEYS = (
    'latency_p50_ms', 'latency_p99_ms', 'pad_waste_fraction',
    'bucket_hit_rate', 'shed_fraction', 'capacity_req_per_s')

#: generate-row sidecars (--serve --generate): the decode regime's
#: own vocabulary -- tokens/s, TTFT and inter-token latency, plus
#: the live SLO monitor's ok/warn/breach verdict (ISSUE 12) and the
#: paged-KV memory-economy trio (ISSUE 17; None on slot-cache rows)
GENERATE_SIDECAR_KEYS = (
    'tokens_per_s', 'ttft_p50_ms', 'ttft_p99_ms',
    'intertoken_p50_ms', 'intertoken_p99_ms', 'shed_fraction',
    'capacity_tok_per_s', 'slo_verdict', 'prefix_hit_rate',
    'pages_per_request', 'kv_bytes_per_token',
    'accepted_draft_rate', 'verify_per_token')

#: fleet-row sidecars (--serve --fleet): the deployment regime's
#: vocabulary -- swap downtime, swap-attributable drops (the zero
#: the whole subsystem exists for), and the roll ledger's outcomes
FLEET_SIDECAR_KEYS = (
    'swap_downtime_p50_ms', 'swap_downtime_p99_ms',
    'dropped_during_swap', 'promotes', 'rollbacks',
    'served', 'shed_fraction')


def _serve_capture_dir(argv):
    """``--capture DIR``: record the serve window as a full telemetry
    capture (per-request trace spans + serve metrics flushed into
    DIR) so ``telemetry report``/``slo``/``doctor`` can replay it --
    the CI slo smoke leg drives exactly this path."""
    capture = _flag_value(argv, '--capture', None, str)
    if capture:
        from chainermn_tpu import telemetry
        telemetry.enable(capture)
    return capture


def _flag_value(argv, flag, default, cast=float):
    if flag not in argv:
        return default
    i = argv.index(flag)
    if i + 1 >= len(argv):
        emit(dict(metric_stub('resnet50'), value=0.0,
                  vs_baseline=0.0, error='bad_flag',
                  detail='%s needs a value' % flag), rc=1)
    try:
        return cast(argv[i + 1])
    except ValueError:
        emit(dict(metric_stub('resnet50'), value=0.0,
                  vs_baseline=0.0, error='bad_flag',
                  detail='%s %r' % (flag, argv[i + 1])), rc=1)


def measure_serve(argv):
    """``--serve``: the open-loop serving row (ISSUE 10).

    Builds a zoo model's :class:`~chainermn_tpu.serving.
    InferenceEngine` (AOT per-bucket executables over the persistent
    compile cache, ``--int8`` for the quantized-weight policy),
    probes its batch capacity, then offers an OPEN-loop request
    stream ABOVE capacity by default (``--serve-rate`` overrides) so
    the row measures the whole contract: served req/s/chip as the
    value, p50/p99 latency from the telemetry raw-sample histograms,
    pad-waste fraction, bucket hit-rate, and the typed-shed fraction
    -- overload degrading gracefully IS the product claim
    (``docs/serving.md``)."""
    quick = '--quick' in argv
    model_name = parse_model(argv)
    stub = metric_stub('serve_' + model_name)

    import numpy as np

    import jax

    if '--cpu' in argv:
        from chainermn_tpu.utils import force_host_devices
        force_host_devices(8)
    n_dev = jax.device_count()
    on_cpu = jax.default_backend() == 'cpu'
    _log('serve: backend=%s n_dev=%d model=%s'
         % (jax.default_backend(), n_dev, model_name))

    from chainermn_tpu import serving
    from chainermn_tpu.precision import (Int8Policy, Policy,
                                         quantization_error)

    int8 = '--int8' in argv
    if int8:
        policy = Int8Policy() if on_cpu else Int8Policy.bf16()
    else:
        policy = None if on_cpu else Policy.bf16()

    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    if model_name == 'mlp':
        from chainermn_tpu.models import MLP
        model = MLP(n_units=1000, n_out=10)
        example = rng.rand(784).astype(np.float32)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 784)))
        apply_kwargs = {}
    elif model_name in ('resnet50', 'vgg16', 'googlenetbn'):
        from chainermn_tpu import models as zoo
        insize = 64 if (quick or on_cpu) else 224
        model = zoo.get_arch(model_name, num_classes=1000)
        example = rng.rand(insize, insize, 3).astype(np.float32)
        variables = model.init(
            {'params': jax.random.PRNGKey(0)},
            jnp.zeros((1, insize, insize, 3)), train=False)
        apply_kwargs = {'train': False}
    else:
        emit(dict(stub, value=0.0, vs_baseline=0.0,
                  error='unknown_model',
                  detail='--serve supports mlp/resnet50/vgg16/'
                         'googlenetbn, got %r' % model_name), rc=1)

    max_batch = int(_flag_value(argv, '--serve-max-batch',
                                32 if not on_cpu else 16, int))
    engine = serving.InferenceEngine.for_model(
        model, variables, example, apply_kwargs=apply_kwargs,
        max_batch=max_batch, policy=policy)
    _log('serve: warmup over buckets %s (AOT + persistent cache)'
         % list(engine.edges))
    t0 = time.perf_counter()
    aot_map = engine.warmup()
    warmup_s = time.perf_counter() - t0

    # capacity probe: steady-state max-bucket throughput bounds what
    # any admission policy can serve; the offered rate defaults to
    # 2x it so the row exercises overload shedding for real
    big = engine.edges[-1]
    x = np.repeat(example[None], big, axis=0)
    engine.infer(x)
    t0 = time.perf_counter()
    probe_reps = 3 if quick else 6
    for _ in range(probe_reps):
        engine.infer(x)
    batch_s = (time.perf_counter() - t0) / probe_reps
    max_items = max(1, max_batch // 2)
    mean_req_items = (1 + max_items) / 2.0
    capacity = big / batch_s / mean_req_items
    rate = _flag_value(argv, '--serve-rate', 2.0 * capacity)
    n_requests = int(_flag_value(argv, '--serve-requests',
                                 200 if quick else 1000, int))
    _log('serve: capacity ~%.0f req/s; offering %.0f req/s x %d '
         'requests' % (capacity, rate, n_requests))

    capture = _serve_capture_dir(argv)
    queue = serving.RequestQueue(
        max_batch=max_batch, max_wait=0.005,
        max_queue=max(4 * max_batch, 64), edges=engine.edges)
    rep = serving.open_loop(engine, queue, rate=rate,
                            n_requests=n_requests, seed=0,
                            capture_dir=capture)

    row = dict(
        stub,
        value=round(rep['served_req_per_s'] / n_dev, 2),
        # no serving baseline exists yet -- first round of this
        # metric family; the reference never served (PAPER.md)
        vs_baseline=0.0,
        baseline_derivation='none: first serving metric family '
                            'round (reference has no serving path)',
        n_devices=n_dev,
        backend=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        quick=quick,
        model=model_name,
        offered_req_per_s=round(rate, 1),
        capacity_req_per_s=round(capacity, 1),
        served_req_per_s=round(rep['served_req_per_s'], 2),
        latency_p50_ms=rep['latency_p50_ms'],
        latency_p99_ms=rep['latency_p99_ms'],
        queue_wait_p50_ms=rep['queue_wait_p50_ms'],
        queue_wait_p99_ms=rep['queue_wait_p99_ms'],
        pad_waste_fraction=rep['pad_waste_fraction'],
        bucket_hit_rate=rep['bucket_hit_rate'],
        shed_fraction=round(rep['shed_fraction'], 4),
        served=rep['served'],
        offered=rep['offered'],
        worst_request=rep.get('worst_request'),
        buckets=list(engine.edges),
        max_batch=max_batch,
        aot=all(aot_map.values()),
        warmup_s=round(warmup_s, 3),
        compile_count=rep['compile_count'],
        trace_count=rep['trace_count'],
        int8=int8,
        policy={'compute': str(policy.compute_dtype),
                'param': str(policy.param_dtype)}
        if policy is not None else None,
    )
    if int8:
        row['quantization_rel_error'] = round(quantization_error(
            variables['params'], engine.params['params']), 5)
    if rep['served'] == 0:
        row['error'] = 'serve_no_completions'
    emit(row, rc=0 if rep['served'] else 1)


def measure_fleet(argv):
    """``--serve --fleet``: the continuous-deployment row
    (ISSUE 13).

    Boots the demo-LM fleet (``serving.fleet.build_local_fleet``, 2
    in-process replicas), trains real sgd steps between rolls, and
    rolls each manifest-tagged snapshot through the fleet UNDER
    open-loop traffic -- canary, judge, promote -- timing the whole
    deployment machine.  Row value = sustained rolls/minute; the
    sidecars are the contract numbers: ``dropped_during_swap`` (must
    be 0 -- a roll that sheds is a failed roll, rc 1),
    per-replica out-of-rotation downtime p50/p99, and the ledger's
    promote/rollback outcomes."""
    quick = '--quick' in argv
    stub = metric_stub('serve_fleet')

    import tempfile

    import jax

    if '--cpu' in argv:
        from chainermn_tpu.utils import force_host_devices
        force_host_devices(8)
    n_dev = jax.device_count()
    _log('fleet: backend=%s n_dev=%d'
         % (jax.default_backend(), n_dev))

    from chainermn_tpu import telemetry
    from chainermn_tpu.serving import fleet as fleet_mod
    from chainermn_tpu.utils.ledger import Ledger, events

    telemetry.enable()   # the canary judge reads the record stream
    n_replicas = int(_flag_value(argv, '--fleet-replicas', 2, int))
    rolls = int(_flag_value(argv, '--fleet-rolls',
                            1 if quick else 3, int))
    rate = _flag_value(argv, '--serve-rate', 30.0)
    canary_s = _flag_value(argv, '--canary-seconds', 2.0)
    work = tempfile.mkdtemp(prefix='bench_fleet_')
    ck, out = (os.path.join(work, 'ckpt'), os.path.join(work, 'out'))
    fleet_mod.demo_train(ck, steps=2, snapshot_every=2)
    controller = fleet_mod.build_local_fleet(
        ck, out, n_replicas=n_replicas, canary_seconds=canary_s,
        judge_interval=0.25, drain_timeout=60.0)
    controller.watcher.debounce_s = 0.15
    controller.start()
    _log('fleet: %d replicas booted at version %d; offering %.0f '
         'req/s, rolling %d snapshot(s)'
         % (n_replicas, controller.current_version, rate, rolls))

    import threading
    traffic = fleet_mod._TrafficGen(controller.front, rate=rate,
                                    max_new_tokens=4).start()
    stop = threading.Event()
    ctl_thread = threading.Thread(target=controller.run,
                                  args=(stop,), daemon=True)
    ctl_thread.start()
    t_roll0 = time.perf_counter()
    timed_out = False
    try:
        for k in range(rolls):
            fleet_mod.demo_train(ck, steps=2, snapshot_every=2)
            target = controller.current_version + 2 \
                if controller.last_handled_version is None \
                else controller.last_handled_version + 2
            deadline = time.monotonic() + 240.0
            while time.monotonic() < deadline:
                if controller.last_handled_version == target:
                    break
                time.sleep(0.05)
            else:
                timed_out = True
                break
    finally:
        roll_window_s = time.perf_counter() - t_roll0
        traffic.stop()
        stop.set()
        ctl_thread.join(timeout=30.0)
        controller.complete(traffic=traffic.stats())
        controller.close()

    ledger = Ledger.read(os.path.join(out, fleet_mod.LEDGER_NAME))
    swaps = events(ledger, 'replica_swap')
    downtimes = sorted(controller.swap_downtimes)

    def pct(p):
        if not downtimes:
            return None
        return round(
            downtimes[min(len(downtimes) - 1,
                          int(p * len(downtimes)))] * 1e3, 3)

    tstats = traffic.stats()
    rolls_done = controller.promotes + controller.rollbacks
    value = 60.0 * rolls_done / max(roll_window_s, 1e-9)
    shed = tstats['shed_submit'] + tstats['shed_result']
    row = dict(
        stub,
        value=round(value, 3),
        vs_baseline=0.0,
        baseline_derivation='none: first continuous-deployment '
                            'metric family round (reference has no '
                            'serving path)',
        n_devices=n_dev,
        backend=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        quick=quick,
        n_replicas=n_replicas,
        rolls_requested=rolls,
        rolls_done=rolls_done,
        promotes=controller.promotes,
        rollbacks=controller.rollbacks,
        swap_failures=controller.swap_failures,
        roll_window_s=round(roll_window_s, 3),
        dropped_during_swap=controller.dropped_during_swap,
        swap_downtime_p50_ms=pct(0.50),
        swap_downtime_p99_ms=pct(0.99),
        replica_swaps=len(swaps),
        offered=tstats['offered'],
        served=tstats['served'],
        shed_fraction=round(shed / max(tstats['offered'], 1), 4),
        tokens=tstats['tokens'],
        canary_seconds=canary_s,
        offered_req_per_s=round(rate, 2),
        final_version=controller.current_version,
    )
    ok = (rolls_done >= rolls and not timed_out
          and controller.dropped_during_swap == 0
          and controller.swap_failures == 0)
    if timed_out:
        row['error'] = 'fleet_roll_timeout'
    elif controller.dropped_during_swap:
        row['error'] = 'fleet_dropped_requests_during_swap'
    emit(row, rc=0 if ok else 1)


def measure_fleet_recovery(argv):
    """``--serve --fleet --recovery``: the serving self-healing row
    (ISSUE 20).

    Boots the journaled demo-LM fleet with a live
    :class:`~chainermn_tpu.serving.fleet.ReplicaSupervisor`, hard-
    kills a replica MID-DECODE under open-loop traffic, and times the
    healing machine.  Row value = MTTR in ms from the kill to the
    first journaled token of a requeued continuation on a survivor.
    Sidecars: detection latency, requeued/shed counts, respawn count,
    degradation-rung occupancy, and ``lost_requests`` -- which is a
    HARD rc-1 gate: a journal with open entries after recovery means
    the self-healing contract is broken, whatever the MTTR says."""
    quick = '--quick' in argv
    stub = metric_stub('serve_fleet_recovery')

    import tempfile
    import threading

    if '--cpu' in argv:
        from chainermn_tpu.utils import force_host_devices
        force_host_devices(8)
    import jax

    from chainermn_tpu import telemetry
    from chainermn_tpu.serving import fleet as fleet_mod
    from chainermn_tpu.utils.ledger import Ledger, events

    telemetry.enable()
    n_replicas = int(_flag_value(argv, '--fleet-replicas', 2, int))
    rate = _flag_value(argv, '--serve-rate', 30.0)
    max_new = 8
    work = tempfile.mkdtemp(prefix='bench_fleet_recovery_')
    ck, out = (os.path.join(work, 'ckpt'), os.path.join(work, 'out'))
    fleet_mod.demo_train(ck, steps=2, snapshot_every=2)
    controller = fleet_mod.build_local_fleet(
        ck, out, n_replicas=n_replicas, n_slots=2,
        max_prompt_len=16, journal=True)
    controller.watcher.debounce_s = 0.15
    controller.start()
    degradation = fleet_mod.DegradationPolicy()
    supervisor = fleet_mod.ReplicaSupervisor(
        controller,
        spawn_fn=fleet_mod.local_respawn_fn(n_slots=2,
                                            max_prompt_len=16),
        degradation=degradation).start()
    _log('fleet-recovery: %d replicas at version %d; offering %.0f '
         'req/s' % (n_replicas, controller.current_version, rate))

    stop = threading.Event()
    ctl_thread = threading.Thread(target=controller.run,
                                  args=(stop,), daemon=True)
    ctl_thread.start()
    # traffic prompts stay short: a continuation prefill needs
    # prompt + emitted <= max_prompt_len headroom
    traffic = fleet_mod._TrafficGen(
        controller.front, rate=rate, max_new_tokens=max_new,
        prompt_len_range=(1, 4)).start()
    victim = controller.front.replicas[-1]
    journal = controller.front.journal
    killed_inflight = 0
    t_kill = None
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:   # arm: wait MID-decode
            inf = journal.inflight(replica=victim.name)
            if any(e['emitted'] for e in inf.values()):
                break
            time.sleep(0.002)
        t_kill = time.time()
        victim.kill()
        killed_inflight = len(journal.inflight(replica=victim.name))
        _log('fleet-recovery: killed %s with %d in flight'
             % (victim.name, killed_inflight))
        t_end = time.monotonic() + (3.0 if quick else 8.0)
        while time.monotonic() < t_end:
            if supervisor.aborted:
                break
            time.sleep(0.05)
    finally:
        traffic.stop()
        supervisor.stop()
        stop.set()
        ctl_thread.join(timeout=30.0)
        controller.complete(traffic=traffic.stats())
        controller.close()

    ledger = Ledger.read(os.path.join(out, fleet_mod.LEDGER_NAME))
    dead_ev = events(ledger, 'replica_dead')
    requeues = events(ledger, 'requeue')
    requeue_ids = [e['request_id'] for e in requeues]
    jevents = Ledger.read(os.path.join(out, fleet_mod.JOURNAL_NAME))
    # first token journaled AFTER a request's own requeue event --
    # gating on the kill time instead would count the victim's final
    # pre-death frame as "recovered"
    t_first = min(
        (min((e['t'] for e in jevents
              if e.get('event') == 'token'
              and e.get('request_id') == rq['request_id']
              and e['t'] >= rq['t']), default=float('inf'))
         for rq in requeues), default=None)
    if t_first == float('inf'):
        t_first = None
    mttr_ms = (round((t_first - t_kill) * 1e3, 3)
               if t_first is not None else None)
    detect_ms = (round((dead_ev[0]['t'] - t_kill) * 1e3, 3)
                 if dead_ev and t_kill is not None else None)
    d = supervisor.describe()
    tstats = traffic.stats()
    row = dict(
        stub,
        value=mttr_ms if mttr_ms is not None else 0.0,
        vs_baseline=0.0,
        baseline_derivation='none: first serving self-healing '
                            'metric family round (reference has no '
                            'serving path)',
        n_devices=jax.device_count(),
        backend=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        quick=quick,
        n_replicas=n_replicas,
        killed_inflight=killed_inflight,
        detect_ms=detect_ms,
        requeued=len(requeue_ids),
        requeue_shed=len(d['shed']),
        deaths=d['deaths'],
        respawns=d['respawns'],
        lost_requests=d['lost_requests'],
        rung_occupancy_s=d['degradation']['occupancy_s'],
        degradation_transitions=d['degradation']['transitions'],
        offered=tstats['offered'],
        served=tstats['served'],
        traffic_errors=tstats['errors'],
        offered_req_per_s=round(rate, 2),
    )
    ok = (d['lost_requests'] == 0 and not d['aborted']
          and d['respawns'] >= 1 and mttr_ms is not None
          and tstats['errors'] == 0)
    if d['lost_requests']:
        row['error'] = 'fleet_recovery_lost_requests'
    elif mttr_ms is None:
        row['error'] = 'fleet_recovery_no_recovered_token'
    elif d['aborted']:
        row['error'] = 'fleet_recovery_aborted'
    emit(row, rc=0 if ok else 1)


def generate_family(argv):
    """Metric-family name for the autoregressive arm: the --int8-kv
    and --paged A/Bs bank under their own tags so sidecars never
    cross-pollinate."""
    name = 'serve_generate'
    if '--paged' in argv:
        name += '_paged'
    if '--int8-kv' in argv:
        name += '_int8kv'
    if '--speculative' in argv:
        name += '_spec'
    return name


def measure_generate(argv):
    """``--serve --generate``: the autoregressive serving row
    (ISSUE 11).

    Builds a ``TransformerLM`` :class:`~chainermn_tpu.serving.
    GenerationEngine` (prefill bucketed by prompt length, decode by
    active-slot count, AOT over the persistent cache; ``--int8-kv``
    stores the KV cache int8; ``--speculative`` adds a half-depth
    draft model proposing ``--spec-tokens`` per tick with the target
    verifying in one pass -- an in-bench probe asserts exact greedy
    equivalence vs a non-speculative oracle twin, and the
    ``accepted_draft_rate`` / ``verify_per_token`` sidecars carry the
    amortization), probes steady-state decode capacity at
    full occupancy, then offers an OPEN-loop prompt stream above
    capacity so continuous batching and typed shedding are both in
    the measurement.  Row value = generated tokens/s/chip; TTFT and
    inter-token p50/p99 ride as sidecars, anchored against PERF.md's
    ~290k tok/s/chip perfect-MXU transformer number (decode is
    HBM-bound -- the fraction of that ceiling it reaches IS the
    bandwidth story; ``docs/serving.md``)."""
    quick = '--quick' in argv
    stub = metric_stub(generate_family(argv))

    import numpy as np  # noqa: F401

    import jax

    if '--cpu' in argv:
        from chainermn_tpu.utils import force_host_devices
        force_host_devices(8)
    n_dev = jax.device_count()
    on_cpu = jax.default_backend() == 'cpu'
    int8_kv = '--int8-kv' in argv
    paged = '--paged' in argv
    prefill_chunk = _flag_value(argv, '--prefill-chunk', None, int)
    speculative = '--speculative' in argv
    spec_tokens = int(_flag_value(argv, '--spec-tokens', 4, int))
    _log('generate: backend=%s n_dev=%d int8_kv=%s paged=%s '
         'prefill_chunk=%s speculative=%s'
         % (jax.default_backend(), n_dev, int8_kv, paged,
            prefill_chunk, speculative))

    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.precision import Policy

    small = quick or on_cpu
    if small:
        model = TransformerLM(vocab_size=2048, d_model=128, n_heads=8,
                              n_layers=2, d_ff=512, max_len=256,
                              dtype=jnp.float32 if on_cpu
                              else jnp.bfloat16)
        n_slots, max_prompt, max_new = 8, 32, 12
    else:
        # the PERF.md anchor config family (d512/L6/V32k), cache depth
        # sized to prompt + generation
        model = TransformerLM(vocab_size=32000, d_model=512,
                              n_heads=8, n_layers=6, d_ff=2048,
                              max_len=512)
        n_slots, max_prompt, max_new = 32, 128, 32
    n_slots = int(_flag_value(argv, '--gen-slots', n_slots, int))
    max_new = int(_flag_value(argv, '--gen-max-new', max_new, int))
    policy = None if on_cpu else Policy.bf16()

    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))['params']
    paged_kw = {}
    if paged:
        paged_kw = dict(paged=True,
                        page_size=int(_flag_value(
                            argv, '--page-size', 16, int)),
                        prefill_chunk=prefill_chunk)
    spec_kw = {}
    if speculative:
        # the draft: same vocab (hard requirement -- the accept rule
        # compares token ids), a fraction of the target's depth; its
        # own params from a DIFFERENT seed, so acceptance is earned,
        # never an artifact of identical weights
        draft = TransformerLM(
            vocab_size=model.vocab_size, d_model=model.d_model,
            n_heads=model.n_heads,
            n_layers=max(1, model.n_layers // 2),
            d_ff=model.d_ff, max_len=model.max_len,
            dtype=model.dtype)
        draft_params = draft.init(
            jax.random.PRNGKey(7),
            jnp.zeros((1, 8), jnp.int32))['params']
        spec_kw = dict(draft_model=draft, draft_params=draft_params,
                       spec_tokens=spec_tokens)
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        policy=policy, int8_kv=int8_kv, **paged_kw, **spec_kw)
    _log('generate: warmup over prefill buckets %s + decode buckets '
         '%s' % (list(engine.prefill_edges),
                 list(engine.decode_edges)))
    t0 = time.perf_counter()
    aot_map = engine.warmup()
    warmup_s = time.perf_counter() - t0

    # the speculative correctness pin, measured IN the bench so the
    # CI smoke leg asserts it off the row: the same prompt set drained
    # through the speculative engine and a non-speculative oracle
    # twin must produce token-for-token identical outputs (exact
    # greedy equivalence, not a similarity bound)
    spec_equivalent = None
    if speculative:
        oracle = serving.GenerationEngine(
            model, params, n_slots=n_slots, max_prompt_len=max_prompt,
            policy=policy, int8_kv=int8_kv, **paged_kw)
        oracle.warmup()
        eq_rng = np.random.RandomState(3)
        eq_prompts = [eq_rng.randint(0, model.vocab_size,
                                     size=int(n)).astype(np.int32)
                      for n in eq_rng.randint(4, max_prompt + 1,
                                              size=2 * n_slots)]

        def _drain_probe(eng):
            q = serving.GenerationQueue(
                max_prompt_len=max_prompt, max_queue=4 * n_slots,
                page_size=eng.page_size if paged else None)
            reqs = [q.submit(p, max_new) for p in eq_prompts]
            deadline = time.perf_counter() + 300.0
            while not all(r.done() for r in reqs):
                eng.step(q)
                if time.perf_counter() > deadline:
                    break
            return [list(r.result(timeout=1.0)) for r in reqs]

        spec_out = _drain_probe(engine)
        oracle_out = _drain_probe(oracle)
        spec_equivalent = bool(spec_out == oracle_out)
        _log('generate: speculative equivalence probe over %d '
             'prompts: %s' % (len(eq_prompts),
                              'EXACT' if spec_equivalent
                              else 'MISMATCH'))

    # capacity probe: saturate every slot once (arrivals effectively
    # instantaneous, queue sized to hold them all) and read the
    # steady-state token rate -- the ceiling any open-loop offered
    # rate is then set against
    probe_q = serving.GenerationQueue(
        max_prompt_len=max_prompt, max_queue=4 * n_slots,
        page_size=engine.page_size if paged else None)
    probe = serving.open_loop_generate(
        engine, probe_q, rate=1e9, n_requests=2 * n_slots, seed=1,
        prompt_len_range=(4, max_prompt), max_new_tokens=max_new)
    capacity_tok = probe['tokens_per_s']
    capacity_req = capacity_tok / float(max_new)
    rate = _flag_value(argv, '--serve-rate', 2.0 * capacity_req)
    n_requests = int(_flag_value(argv, '--serve-requests',
                                 4 * n_slots if quick
                                 else 12 * n_slots, int))
    _log('generate: capacity ~%.0f tok/s (~%.1f req/s); offering '
         '%.1f req/s x %d requests'
         % (capacity_tok, capacity_req, rate, n_requests))

    # the live SLO monitor rides the measured window (ISSUE 12): its
    # multi-window burn-rate verdict lands in the row (and, with
    # --capture, a slo_snapshot.json next to the flushed capture
    # that `telemetry slo DIR` then reproduces offline)
    capture = _serve_capture_dir(argv)
    from chainermn_tpu.telemetry import slo as slo_mod
    monitor = slo_mod.SLOMonitor(n_slots=n_slots, outdir=capture)
    queue = serving.GenerationQueue(
        max_prompt_len=max_prompt, max_queue=max(2 * n_slots, 16),
        page_size=engine.page_size if paged else None)
    rep = serving.open_loop_generate(
        engine, queue, rate=rate, n_requests=n_requests, seed=0,
        prompt_len_range=(4, max_prompt), max_new_tokens=max_new,
        capture_dir=capture, slo_monitor=monitor)

    mxu_anchor = 290000.0
    value = rep['tokens_per_s'] / n_dev

    # the paged-KV memory-economy sidecars ride EVERY generate row so
    # the A/B is one column-wise diff: bytes a stored token costs
    # (cache dtype + int8 scale rows), pages a resident sequence pins
    # at peak, and the radix index's prefix hit rate (slot-cache rows
    # carry the bytes number and None for the page-economy pair)
    d_head = model.d_model // model.n_heads
    kv_bytes = 2 * model.n_layers * model.n_heads * d_head \
        * (1 if int8_kv else jnp.dtype(model.dtype).itemsize)
    if int8_kv:
        kv_bytes += 2 * model.n_layers * model.n_heads * 4  # scales
    paged_rep = rep.get('paged')
    prefix_hit_rate = (
        round(paged_rep['prefix_hit_rate'], 4)
        if paged_rep and paged_rep.get('prefix_hit_rate') is not None
        else None)
    pages_per_request = (
        round(paged_rep['peak_pages_in_use'] / float(n_slots), 2)
        if paged_rep else None)

    row = dict(
        stub,
        value=round(value, 2),
        vs_baseline=0.0,
        baseline_derivation='none: first autoregressive serving '
                            'metric family round (reference has no '
                            'serving path)',
        n_devices=n_dev,
        backend=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        quick=quick,
        model='transformer',
        mxu_anchor_tok_s_per_chip=mxu_anchor,
        anchor_source='PERF.md: perfect-MXU d512/L6/seq1024/V32k @ '
                      '197 TF/s on v5e (decode is HBM-bound; the '
                      'gap to this ceiling is the bandwidth story)',
        anchor_config_match=bool(not small),
        pct_of_mxu_anchor=round(100.0 * value / mxu_anchor, 3),
        offered_req_per_s=round(rate, 2),
        capacity_tok_per_s=round(capacity_tok, 1),
        tokens_per_s=round(rep['tokens_per_s'], 1),
        tokens_served=rep['tokens_served'],
        served=rep['served'],
        offered=rep['offered'],
        shed_fraction=round(rep['shed_fraction'], 4),
        cancelled=rep['cancelled'],
        ttft_p50_ms=rep['ttft_p50_ms'],
        ttft_p99_ms=rep['ttft_p99_ms'],
        intertoken_p50_ms=rep['intertoken_p50_ms'],
        intertoken_p99_ms=rep['intertoken_p99_ms'],
        decode_step_p50_ms=rep['decode_step_p50_ms'],
        slo_verdict=(rep['slo'] or {}).get(
            'verdict', {}).get('overall'),
        slo_verdicts={name: row_['verdict'] for name, row_ in
                      sorted(((rep['slo'] or {}).get('slos')
                              or {}).items())},
        worst_request=rep.get('worst_request'),
        n_slots=n_slots,
        max_new_tokens=max_new,
        prefill_buckets=list(engine.prefill_edges),
        decode_buckets=list(engine.decode_edges),
        aot=all(list(aot_map['prefill'].values())
                + list(aot_map['decode'].values())),
        warmup_s=round(warmup_s, 3),
        compile_count=rep['compile_count'],
        prefill_trace_count=rep['prefill_trace_count'],
        decode_trace_count=rep['decode_trace_count'],
        int8_kv=int8_kv,
        paged=paged,
        paged_kv=paged_rep,
        prefix_hit_rate=prefix_hit_rate,
        pages_per_request=pages_per_request,
        kv_bytes_per_token=kv_bytes,
        speculative=rep.get('speculative'),
        accepted_draft_rate=(rep.get('speculative') or {}).get(
            'accepted_draft_rate'),
        verify_per_token=(rep.get('speculative') or {}).get(
            'verify_per_token'),
        spec_equivalent=spec_equivalent,
        policy={'compute': str(policy.compute_dtype)}
        if policy is not None else None,
    )
    ok = bool(rep['served']) and spec_equivalent is not False
    if rep['served'] == 0:
        row['error'] = 'generate_no_completions'
    elif spec_equivalent is False:
        row['error'] = 'speculative_mismatch'
    emit(row, rc=0 if ok else 1)


def main():
    argv = [a for a in sys.argv[1:]]
    if '--recovery' in argv:
        if '--serve' in argv and '--fleet' in argv:
            # the serving self-healing arm: in-process fleet, so
            # self-contained like the training recovery row below
            measure_fleet_recovery(argv)
            return
        # self-contained CPU-subprocess scenario: no backend probe,
        # no watchdog child (the supervisor bounds its own attempts)
        measure_recovery(argv)
        return
    if '--loader' in argv:
        # the streaming input-pipeline arm: same probe/child/banked
        # conventions, keyed on the 'loader_<model>' metric family
        family = 'loader_' + parse_model(argv)
        if '--child' in argv:
            measure_loader([a for a in argv if a != '--child'])
            return
        if '--cpu' not in argv:
            ok = probe_backend()
            if ok is not True:
                row = dict(metric_stub(family), value=0.0,
                           vs_baseline=0.0,
                           error='backend_unavailable', detail=ok)
                brow, banked, tag, src = banked_last_good_row(family)
                if banked is not None:
                    row.update(banked_value=banked, banked_round=tag,
                               banked_source=src)
                    for key in LOADER_SIDECAR_KEYS:
                        if brow.get(key) is not None:
                            row['banked_' + key] = brow[key]
                emit(row, rc=1)
        run_child(argv, family)
        return
    if '--serve' in argv:
        # serving arms: same probe/child/banked-row conventions as
        # training arms, keyed on the 'serve_<model>' metric family
        # (--generate: the autoregressive tokens/s family, with its
        # own sidecar vocabulary)
        generate = '--generate' in argv
        fleet = '--fleet' in argv
        if fleet:
            family = 'serve_fleet'
            sidecars = FLEET_SIDECAR_KEYS
        elif generate:
            family = generate_family(argv)
            sidecars = GENERATE_SIDECAR_KEYS
        else:
            family = 'serve_' + parse_model(argv)
            sidecars = SERVE_SIDECAR_KEYS
        if '--child' in argv:
            child_argv = [a for a in argv if a != '--child']
            if fleet:
                measure_fleet(child_argv)
            elif generate:
                measure_generate(child_argv)
            else:
                measure_serve(child_argv)
            return
        if '--cpu' not in argv:
            ok = probe_backend()
            if ok is not True:
                row = dict(metric_stub(family), value=0.0,
                           vs_baseline=0.0,
                           error='backend_unavailable', detail=ok)
                brow, banked, tag, src = banked_last_good_row(family)
                if banked is not None:
                    row.update(banked_value=banked, banked_round=tag,
                               banked_source=src)
                    for key in sidecars:
                        if brow.get(key) is not None:
                            row['banked_' + key] = brow[key]
                emit(row, rc=1)
        run_child(argv, family)
        return
    model = parse_model(argv)
    # fail fast on flag mistakes BEFORE the backend probe
    parse_batch(argv, model)
    parse_s2d(argv, model)
    parse_policy(argv, model)
    parse_fused_norm(argv, model)
    parse_tp(argv, model)
    parse_pp(argv, model)
    parse_donate(argv, model)
    if '--child' in argv:
        measure([a for a in argv if a != '--child'])
        return
    argv = adopt_tuned_config(argv, model)
    if '--cpu' not in argv:
        ok = probe_backend()
        if ok is not True:
            row = dict(metric_stub(model), value=0.0,
                       vs_baseline=0.0,
                       error='backend_unavailable', detail=ok)
            # a backend that does not answer still reports the banked
            # last-good measurement, clearly labeled (never as `value`: a
            # banked number is not a measurement of THIS window) --
            # plus the HBM-traffic / MFU sidecars of that row, so
            # BENCH_r0N.json stays diagnosable through the outage
            # (the r3-r5 gap carried only the bare value)
            brow, banked, tag, src = banked_last_good_row(model)
            if banked is not None:
                row.update(banked_value=banked, banked_round=tag,
                           banked_source=src)
                for key in BANKED_SIDECAR_KEYS:
                    if brow.get(key) is not None:
                        row['banked_' + key] = brow[key]
            emit(row, rc=1)
    run_child(argv, model)


if __name__ == '__main__':
    main()
