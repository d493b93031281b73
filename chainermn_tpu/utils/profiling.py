"""Profiling / tracing.

The reference has NO tracing subsystem (SURVEY 5: closest artifact is
the dummy communicator built to time pack/unpack overhead,
``dummy_communicator.py:8-12``).  Here profiling is first-class:
``jax.profiler`` device traces (viewable in TensorBoard/Perfetto), a
step timer with throughput accounting, and a pack/unpack-style
microbenchmark helper that fills the dummy communicator's role.

Timing source of truth: :mod:`chainermn_tpu.telemetry`.  ``StepTimer``
and ``benchmark_op`` record into a telemetry
:class:`~chainermn_tpu.telemetry.Histogram` -- the ACTIVE session's
registry when telemetry is enabled (so step times ride the same
metrics export as everything else: ``metrics.json``, Prometheus), a
standalone histogram otherwise.  ``StepTimer`` additionally emits one
``step`` span per tick into the event timeline when a session is
active.
"""

import contextlib
import json
import os
import time

import jax

from chainermn_tpu import telemetry as _telemetry


@contextlib.contextmanager
def trace(logdir):
    """Capture a device trace for the enclosed block.

    Produces a TensorBoard-loadable trace under ``logdir`` (XLA op
    timeline, HBM usage on TPU)."""
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def annotate(name):
    """Named region: a :func:`chainermn_tpu.telemetry.span`, so it is
    ``cmn:<name>`` in the profiler's trace and a record of the
    telemetry session -- the repo's one way to name a region.  With
    neither a profiler session nor a recorder it is a no-op."""
    return _telemetry.span(name, kind='region')


class StepTimer:
    """Throughput accounting for a training loop.

    Trainer extension AND standalone: call ``tick(n_items)`` per step;
    ``summary()`` gives steps/sec, items/sec and latency percentiles
    (compile-affected first steps excluded via ``warmup``).

    Step durations land in a telemetry histogram (the active
    session's registry under ``metric_name`` when telemetry is
    enabled -- one timing source of truth, exported with everything
    else -- or a standalone :class:`~chainermn_tpu.telemetry.Histogram`
    otherwise); each tick additionally emits a ``step`` span into the
    active event timeline.
    """

    trigger = (1, 'iteration')
    priority = 150
    name = 'step_timer'

    def __init__(self, items_per_step=None, warmup=2,
                 metric_name='step_time_seconds'):
        self.items_per_step = items_per_step
        self.warmup = warmup
        self.metric_name = metric_name
        reg = _telemetry.registry()
        self._hist = (reg.histogram(metric_name) if reg is not None
                      else _telemetry.Histogram(metric_name))
        self._last = None
        self._ticks = 0

    def __call__(self, trainer):  # extension protocol
        self.tick()
        if self._hist.samples:
            trainer.observation.setdefault(
                'steps_per_sec', 1.0 / self._hist.samples[-1])

    def tick(self, n_items=None):
        now = time.perf_counter()
        if self._last is not None:
            dt = now - self._last
            self._hist.observe(dt)
            rec = _telemetry.live()
            if rec is not None:
                rec._append({'type': 'span', 'name': 'step',
                             'kind': 'compute',
                             't0': rec.now() - dt, 't1': rec.now(),
                             'timer': self.metric_name,
                             'tick': self._ticks})
        self._last = now
        self._ticks += 1

    def summary(self):
        times = (self._hist.samples[self.warmup:]
                 or self._hist.samples)
        if not times:
            return {}
        times = sorted(times)
        n = len(times)
        mean = sum(times) / n
        out = {
            'steps': n,
            'mean_step_s': mean,
            'steps_per_sec': 1.0 / mean,
            'p50_step_s': times[n // 2],
            'p99_step_s': times[min(n - 1, int(n * 0.99))],
        }
        if self.items_per_step:
            out['items_per_sec'] = self.items_per_step / mean
        return out

    def dump(self, path):
        with open(path, 'w') as f:
            json.dump(self.summary(), f, indent=1)


def benchmark_op(fn, *args, n_steps=20, warmup=3,
                 metric_name='benchmark_op_seconds'):
    """Time a jitted callable end-to-end (the role the reference's
    dummy communicator plays for pack/unpack overhead).  Returns
    mean seconds per call; the mean is also recorded into the active
    telemetry registry's ``metric_name`` histogram when a session is
    enabled."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        out = fn(*args)
    jax.block_until_ready(out)
    mean = (time.perf_counter() - t0) / n_steps
    reg = _telemetry.registry()
    if reg is not None:
        reg.histogram(metric_name).observe(mean)
    return mean


def memory_stats(device=None):
    """Per-device memory statistics where the backend exposes them
    (TPU: bytes_in_use / peak_bytes_in_use; CPU returns {})."""
    device = device or jax.devices()[0]
    stats = getattr(device, 'memory_stats', lambda: None)()
    return stats or {}


def save_device_profile(logdir, fn, *args):
    """Trace one execution of ``fn(*args)`` into ``logdir`` and return
    the output; convenience wrapper used by the examples'
    ``--profile`` flags."""
    os.makedirs(logdir, exist_ok=True)
    with trace(logdir):
        out = fn(*args)
        jax.block_until_ready(out)
    return out
