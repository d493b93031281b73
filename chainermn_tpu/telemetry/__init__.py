"""Unified runtime telemetry: per-step event timeline, collective
spans, metrics export (ROADMAP item 5's evidence layer).

The reference stack has no observability subsystem at all; here the
runtime narrates itself.  A low-overhead per-process recorder
(:mod:`chainermn_tpu.telemetry.recorder`) is threaded through the
layers that matter -- communicator eager collectives and the object
p2p channel (``communicators/base.py``), step phases in both updaters
(host batch prep / H2D / jitted step / metrics sync), checkpoint
write/verify/resume (``training/recovery.py``), and chaos fault
injections (``utils/chaos.py``) -- so a fault and its latency
consequences correlate in ONE timeline.  On top: a metrics registry
(counters / gauges / histograms with p50/p99), per-rank JSONL event
logs, an aggregated ``metrics.json``, and a Prometheus text exporter;
``python -m chainermn_tpu.telemetry report`` merges per-rank logs
into a step timeline and computes the **overlap fraction** (collective
time hidden behind compute vs exposed) -- the dynamic twin of the
static shardlint rule SL009 -- and ``... telemetry doctor`` runs the
cross-rank diagnosis (:mod:`chainermn_tpu.telemetry.diagnosis`):
collective skew attribution, straggler naming with the lagging
phase, and the crash post-mortem from the crash-safe flight recorder
(:func:`dump_flight` / ``flight-rank*.json``) merged with
peer-liveness heartbeats.  See ``docs/observability.md``.

Activation (exactly the chaos discipline -- zero cost when off)::

    CHAINERMN_TPU_TELEMETRY=/path/to/outdir python train.py

or programmatically::

    from chainermn_tpu import telemetry
    rec = telemetry.enable('/tmp/tele')   # or enable() for in-memory
    ...
    rec.flush()                           # also registered atexit

or by profiling: **an open JAX profiler session is an enabled
telemetry session**.  Where :func:`span` or a hot-site guard
(:func:`live`) finds no recorder but ``jax.profiler`` tracing, it
installs the in-memory recorder and goes on as in any enabled session
-- until the profiler session closes.  Every :meth:`Recorder.span`
also enters a ``jax.profiler.TraceAnnotation('cmn:<name>')``, so the
layer-boundary spans sit in the profiler's trace beside the device's
own lines.

Hot call sites guard on ``telemetry.live() is not None`` (with no
recorder: one attribute load and one ``TraceAnnotation.is_enabled()``,
~0.2 us); :func:`span`/:func:`event` are additionally safe to call
unconditionally -- disabled, they return a preallocated no-op context.

One counter is always on, the **compile log** (:data:`compile_log`,
:func:`install_compile_log`): every backend compile of the process (a
read from the persistent cache counts as one), stamped on
``time.perf_counter()``.
"""

import collections
import os
import threading
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

from chainermn_tpu.telemetry.recorder import (  # noqa: F401
    Counter, FLIGHT_RING, Gauge, Histogram, NULL_SPAN, Recorder,
    Registry, escape_help, escape_label_value, snapshot_to_prometheus)

ENV_VAR = 'CHAINERMN_TPU_TELEMETRY'

_active = None
_env_checked = False
_install_lock = threading.Lock()

#: ``(time.perf_counter() at the end, 'backend_compile', seconds)`` of
#: every backend compile -- a compile or a read from the persistent
#: cache, once per executable -- newest last; filled once
#: :func:`install_compile_log` has run
compile_log = collections.deque(maxlen=4096)
_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'
_compile_log_installed = False
_flush_registered = False


def _on_duration_event(event, duration_secs, **_):
    if event == _COMPILE_EVENT:
        compile_log.append((time.perf_counter(), 'backend_compile',
                            float(duration_secs)))


def install_compile_log():
    """Register the ``jax.monitoring`` listener that fills
    :data:`compile_log` (idempotent; the updaters and the generation
    engine call it when they are built).  Nothing runs in steady
    state: the listener fires when JAX compiles."""
    global _compile_log_installed
    with _install_lock:
        if not _compile_log_installed:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event)
            _compile_log_installed = True


def active():
    """The installed :class:`Recorder`, or None: for reading what was
    recorded (a recorder that followed a profiler session stays
    installed, with its records, after the session closed).  Whether
    telemetry is ON is :func:`live`'s to say, never this one's."""
    return _active


def live():
    """The recorder a hot site writes to right now, or None: THE
    predicate for "telemetry is on", at every guard.  It is the
    installed recorder; with none installed and a JAX profiler session
    open, the in-memory one this call installs.  A recorder installed
    that way records only while a profiler session is open."""
    rec = _active
    if rec is None:
        if not _TraceAnnotation.is_enabled():
            return None
        return _install(None, follows_profiler=True)
    if rec.follows_profiler and not _TraceAnnotation.is_enabled():
        return None
    return rec


def enabled():
    return live() is not None


def _install(outdir, follows_profiler=False):
    global _active
    with _install_lock:   # two threads can get here at once
        rec = _active
        if rec is None:
            rec = Recorder(outdir=outdir)
            rec.follows_profiler = follows_profiler
            _active = rec
        elif not follows_profiler:
            rec.follows_profiler = False   # an explicit enable wins
        return rec


def enable(outdir=None):
    """Install a recorder (idempotent per process: re-enabling with a
    different outdir re-points the existing recorder's flush so spans
    recorded before ``enable`` are not lost)."""
    global _flush_registered
    rec = _install(outdir)
    if outdir is not None:
        if rec.outdir is None:
            rec.outdir = outdir
        if not _flush_registered:
            import atexit
            atexit.register(_flush_at_exit)
            _flush_registered = True
    return rec


def disable():
    """Uninstall (testing hook; does NOT flush)."""
    global _active, _env_checked
    _active, _env_checked = None, False


def _flush_at_exit():
    rec = _active
    if rec is not None and rec.outdir is not None:
        try:
            rec.flush()
        except Exception:
            pass  # interpreter teardown: never mask the real exit


def maybe_enable_from_env(env_var=ENV_VAR):
    """Install a recorder from ``CHAINERMN_TPU_TELEMETRY`` once per
    process (no-op when unset or already checked).  The value is the
    session output directory; the literal ``1`` enables an in-memory
    recorder (programmatic flush only).  A recorder that only follows
    a profiler session does not stand in for the variable: it is read
    all the same, and where set makes that recorder a lasting one."""
    global _env_checked
    rec = _active
    if _env_checked or (rec is not None and not rec.follows_profiler):
        return rec
    _env_checked = True
    value = os.environ.get(env_var)
    if not value:
        return rec
    return enable(outdir=None if value == '1' else value)


def span(name, kind='generic', **attrs):
    """Context manager timing the enclosed block into the active
    recorder; the disabled path returns a no-op singleton."""
    rec = live()
    if rec is None:
        return NULL_SPAN
    return rec.span(name, kind=kind, **attrs)


def event(name, kind='event', **attrs):
    """Record a point-in-time event (no-op when disabled)."""
    rec = live()
    if rec is not None:
        rec.event(name, kind=kind, **attrs)


def request_stage(request_id, name, t0, t1=None, **attrs):
    """Record one completed stage of a per-request trace
    (``kind='request'`` span via :meth:`Recorder.child_span`); no-op
    when disabled.  The serving path threads a request's lifecycle
    through these -- ``queue_wait`` -> ``admit_wait`` ->
    ``bucket_pack`` -> ``prefill`` -> per-tick ``decode`` (or
    ``execute`` on the batch path) -- with
    each stage's ``t0`` equal to the previous stage's ``t1``, so
    ``telemetry report`` reconstructs a gap-free timeline whose stage
    budgets sum to the end-to-end latency."""
    rec = live()
    if rec is not None:
        rec.child_span(request_id, name, t0, t1, **attrs)


def request_event(request_id, name, **attrs):
    """Record a terminal request event (``complete`` / ``shed`` /
    ``error``) as a ``kind='request'`` event; no-op when disabled."""
    rec = live()
    if rec is not None:
        rec.event(name, kind='request', request_id=request_id, **attrs)


def registry():
    """The live recorder's metrics registry, or None."""
    rec = live()
    return rec.registry if rec is not None else None


def flush(outdir=None):
    rec = _active
    return rec.flush(outdir) if rec is not None else None


def dump_flight(reason, outdir=None, blocking=True, **attrs):
    """Write the crash-safe flight record (last-N-records ring, open
    spans, last completed collective) for this rank -- see
    :meth:`Recorder.dump_flight`.  Signal handlers MUST pass
    ``blocking=False`` (non-reentrant recorder lock).  No-op (None)
    when telemetry is disabled or the session is in-memory; never
    raises."""
    rec = _active
    if rec is None:
        return None
    return rec.dump_flight(reason, outdir=outdir, blocking=blocking,
                           **attrs)
