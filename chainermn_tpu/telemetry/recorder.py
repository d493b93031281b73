"""Span/event recorder and metrics registry for runtime telemetry.

Design constraints (mirroring :mod:`chainermn_tpu.utils.chaos`, the
other env-activated runtime layer):

- **Zero cost when off.**  Nothing in this module runs on a
  telemetry-free hot path; call sites guard on the package-level
  ``telemetry.live() is None`` (one attribute load, an identity check
  and ``TraceAnnotation.is_enabled()``)
  or go through :func:`chainermn_tpu.telemetry.span`, whose off path
  returns a preallocated no-op context.
- **Monotonic spans, wall-aligned at record time.**  Durations come
  from ``time.perf_counter()`` (immune to NTP steps); every recorded
  timestamp is expressed on the wall clock via a per-recorder anchor
  pair captured at construction, so per-rank logs from one machine
  (the CPU multi-controller harness) merge into one timeline without
  post-hoc skew fitting.
- **One span primitive, two sinks.**  :meth:`Recorder.span` writes its
  record AND enters ``jax.profiler.TraceAnnotation('cmn:<name>')``, so
  every layer-boundary span also lands in the profiler's trace, on the
  clock the device events are on.  A span around device work measures
  DISPATCH; completion is what the trace's device lines show (the
  fences that made a host span wait for the device are gone: they
  serialised what they measured).
- **Every span says what caused it.**  A span record carries ``id``,
  ``parent`` (the innermost span open on the same thread when it was
  entered, or None) and ``thread`` (the OS thread id the profiler's
  host lines are keyed by), so a layer's self time is its span less
  what its children cover.

Event-log schema (JSONL, one file per rank, first line is ``meta``)::

    {"type": "meta", "rank": 0, "pid": 123, "wall0": ..., "argv": ...}
    {"type": "span", "name": "jitted_step", "kind": "compute",
     "t0": <wall s>, "t1": <wall s>, "id": 7, "parent": 5,
     "thread": 4242, "rank": 0, ...attrs}
    {"type": "event", "name": "chaos:drop_send", "kind": "chaos",
     "t": <wall s>, "rank": 0, ...attrs}

``kind`` is the timeline vocabulary the overlap computation consumes:
``compute`` (the jitted step), ``collective`` (eager collectives /
bounded rendezvous), ``p2p`` (eager object channel), ``host`` (batch
collation), ``h2d`` (host-to-device placement), ``checkpoint``,
``chaos``, and ``collective_trace`` (trace-time collective-issue
marks -- they fire once per compilation, not per step).
"""

import collections
import itertools
import json
import os
import sys
import threading
import time

from jax.profiler import TraceAnnotation

#: histogram sample retention cap -- long trainings must not grow
#: memory without bound; percentile accuracy over the newest samples
#: is what the exporters need
MAX_SAMPLES = 65536
#: event-log retention cap per rank (a week-long run with telemetry
#: left on must not OOM the host; the newest window wins)
MAX_EVENTS = 1 << 20
#: flight-recorder ring size -- the last N records a crash dump
#: preserves (`Recorder.dump_flight`); small on purpose: the flight
#: record is the black box read AFTER a death, not the full log
FLIGHT_RING = 256
#: prefix of a span's name in the profiler's trace
TRACE_PREFIX = 'cmn:'
#: the scalar attributes a span's ``TraceAnnotation`` carries
TRACE_ATTRS = ('iteration', 'step', 'bucket', 'active_slots')


def _percentile(sorted_vals, q):
    """Nearest-rank percentile of an ascending list (the convention
    ``StepTimer.summary`` always used)."""
    if not sorted_vals:
        return None
    n = len(sorted_vals)
    return sorted_vals[min(n - 1, int(n * q))]


class Counter:
    """Monotonically increasing count (Prometheus ``counter``)."""

    kind = 'counter'

    def __init__(self, name, help=''):
        self.name = name
        self.help = help
        self.value = 0.0

    def inc(self, n=1.0):
        self.value += n

    def snapshot(self):
        snap = {'type': 'counter', 'value': self.value}
        if self.help:
            snap['help'] = self.help
        return snap


class Gauge:
    """Last-written value (Prometheus ``gauge``)."""

    kind = 'gauge'

    def __init__(self, name, help=''):
        self.name = name
        self.help = help
        self.value = None

    def set(self, v):
        self.value = float(v)

    def snapshot(self):
        snap = {'type': 'gauge', 'value': self.value}
        if self.help:
            snap['help'] = self.help
        return snap


class Histogram:
    """Sample-retaining distribution with p50/p99 summaries.

    Retains raw samples (at least the newest :data:`MAX_SAMPLES`,
    trimmed an eighth at a time: a trim moves the whole list, and one
    per ``observe`` cost a full serving tick 0.3 ms) so per-rank
    snapshots can be MERGED exactly -- aggregated percentiles are
    recomputed from the union of samples, not averaged from per-rank
    percentiles (which would be wrong for skewed step times).
    """

    kind = 'histogram'

    def __init__(self, name, help=''):
        self.name = name
        self.help = help
        self.samples = []
        self.count = 0
        self.total = 0.0

    def observe(self, v):
        v = float(v)
        self.count += 1
        self.total += v
        self.samples.append(v)
        if len(self.samples) > MAX_SAMPLES + MAX_SAMPLES // 8:
            del self.samples[:len(self.samples) - MAX_SAMPLES]

    def summary(self):
        s = sorted(self.samples)
        if not s:
            return {'count': 0, 'sum': 0.0}
        return {
            'count': self.count,
            'sum': self.total,
            'min': s[0],
            'max': s[-1],
            'mean': sum(s) / len(s),
            'p50': _percentile(s, 0.50),
            'p90': _percentile(s, 0.90),
            'p99': _percentile(s, 0.99),
        }

    def snapshot(self):
        snap = {'type': 'histogram', 'count': self.count,
                'sum': self.total, 'samples': list(self.samples),
                'summary': self.summary()}
        if self.help:
            snap['help'] = self.help
        return snap


class Registry:
    """Named metrics, one instance per recorder (plus standalone use
    by :class:`~chainermn_tpu.utils.profiling.StepTimer` when
    telemetry is off)."""

    def __init__(self):
        self._metrics = {}
        self._lock = threading.Lock()

    def _get(self, cls, name, help):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help)
            elif not isinstance(m, cls):
                raise TypeError(
                    'metric %r already registered as %s, requested %s'
                    % (name, type(m).__name__, cls.__name__))
            return m

    def counter(self, name, help=''):
        return self._get(Counter, name, help)

    def gauge(self, name, help=''):
        return self._get(Gauge, name, help)

    def histogram(self, name, help=''):
        return self._get(Histogram, name, help)

    def names(self):
        return sorted(self._metrics)

    def snapshot(self):
        return {name: m.snapshot()
                for name, m in sorted(self._metrics.items())}

    def to_prometheus(self, prefix='chainermn_tpu_'):
        """Prometheus text exposition (0.0.4).  Histograms export as
        summaries: ``<name>{quantile="0.5"}``, ``_count``, ``_sum``.
        """
        return snapshot_to_prometheus(self.snapshot(), prefix=prefix)


def _prom_name(prefix, name):
    out = []
    for ch in prefix + name:
        out.append(ch if (ch.isalnum() and ch.isascii()) or ch in '_:'
                   else '_')
    head = out[0] if out else '_'
    if not (head.isalpha() or head in '_:'):
        out.insert(0, '_')
    return ''.join(out)


def escape_label_value(value):
    """Prometheus label-value escaping (text exposition 0.0.4):
    backslash, double-quote and newline must be escaped or the scrape
    silently truncates/mangles the sample."""
    return (str(value).replace('\\', r'\\').replace('"', r'\"')
            .replace('\n', r'\n'))


def escape_help(text):
    """``# HELP`` line escaping: backslash and newline only (quotes
    are legal in help text)."""
    return str(text).replace('\\', r'\\').replace('\n', r'\n')


def _labels_text(labels):
    if not labels:
        return ''
    return '{%s}' % ','.join(
        '%s="%s"' % (k, escape_label_value(v))
        for k, v in sorted(labels.items()))


def snapshot_to_prometheus(snapshot, prefix='chainermn_tpu_'):
    """Render a (possibly merged) registry snapshot as Prometheus
    text.  Shared by the live registry and the offline aggregator in
    :mod:`chainermn_tpu.telemetry.report`.

    Emits ``# HELP`` (escaped) alongside ``# TYPE`` when the metric
    carries help text, and escapes every label value (``\\``, ``"``,
    newline) -- a snapshot's optional ``labels`` dict is rendered on
    counter/gauge sample lines."""
    lines = []
    for name, snap in sorted(snapshot.items()):
        pname = _prom_name(prefix, name)
        kind = snap.get('type')
        help_text = snap.get('help')
        if kind in ('counter', 'gauge'):
            v = snap.get('value')
            if v is None:
                continue
            if help_text:
                lines.append('# HELP %s %s'
                             % (pname, escape_help(help_text)))
            lines.append('# TYPE %s %s' % (pname, kind))
            lines.append('%s%s %s' % (pname,
                                      _labels_text(snap.get('labels')),
                                      repr(float(v))))
        elif kind == 'histogram':
            summ = snap.get('summary') or {}
            if help_text:
                lines.append('# HELP %s %s'
                             % (pname, escape_help(help_text)))
            lines.append('# TYPE %s summary' % pname)
            for q in ('p50', 'p90', 'p99'):
                if summ.get(q) is not None:
                    lines.append('%s{quantile="0.%s"} %s'
                                 % (pname, q[1:], repr(summ[q])))
            lines.append('%s_count %s'
                         % (pname, repr(float(snap.get('count', 0)))))
            lines.append('%s_sum %s'
                         % (pname, repr(float(snap.get('sum', 0.0)))))
    return '\n'.join(lines) + '\n' if lines else ''


class _SpanHandle:
    """``recorder.span(...)``: the context manager, and what ``with
    ... as sp`` yields -- the caller attaches attributes discovered
    mid-span with :meth:`set`, reads its two ends (``t0``, ``t1``) and
    may hang ONE callable on ``at_exit``: called with the span once it
    has ended and its record is written."""

    __slots__ = ('recorder', '_annotation', 'name', 'kind', 'attrs',
                 'id', 'parent', 't0', 't1', 'at_exit')

    def __init__(self, recorder, name, kind, attrs):
        self.recorder = recorder
        self.name, self.kind, self.attrs = name, kind, attrs
        self.at_exit = None

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        rec = self.recorder
        stack = rec._span_stack()
        self.parent = stack[-1] if stack else None
        self.id = next(rec._span_ids)
        stack.append(self.id)
        attrs = self.attrs
        self._annotation = TraceAnnotation(
            TRACE_PREFIX + self.name,
            **{k: attrs[k] for k in TRACE_ATTRS if k in attrs})
        self._annotation.__enter__()
        self.t0 = rec.now()
        # `attrs` is the LIVE dict: attributes set mid-span are visible
        # in a flight dump of the open span.  Lock-free on purpose
        # (id-keyed dict set/del are GIL-atomic): this sits on the
        # enabled hot path the <2% overhead pin bounds; dump_flight
        # tolerates a transiently-inconsistent view
        rec._open_spans[self.id] = {'name': self.name,
                                    'kind': self.kind, 't0': self.t0,
                                    'attrs': attrs}
        return self

    def __exit__(self, *exc):
        rec = self.recorder
        t1 = self.t1 = rec.now()
        self._annotation.__exit__(*exc)
        stack = rec._span_stack()
        if stack and stack[-1] == self.id:
            stack.pop()
        rec._open_spans.pop(self.id, None)
        record = {'type': 'span', 'name': self.name, 'kind': self.kind,
                  't0': self.t0, 't1': t1, 'id': self.id,
                  'parent': self.parent,
                  'thread': threading.get_native_id()}
        if self.attrs:
            record.update(self.attrs)
        rec._append(record)
        if self.at_exit is not None:
            self.at_exit(self)
        return False


class _NullSpan:
    """Preallocated no-op context for the disabled path."""

    __slots__ = ()
    attrs = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


NULL_SPAN = _NullSpan()


class Recorder:
    """One process's telemetry session: spans, events, metrics, and
    the per-rank JSONL/JSON flush."""

    #: set by ``telemetry.live`` on the recorder it installs because
    #: a JAX profiler session was found open: records only while one is
    follows_profiler = False

    def __init__(self, outdir=None, flight_ring=FLIGHT_RING):
        self.outdir = outdir
        self.registry = Registry()
        self.events = []
        self._lock = threading.Lock()
        # wall-clock anchor: every recorded time is
        # wall0 + (perf_counter() - mono0)
        self._mono0 = time.perf_counter()
        self._wall0 = time.time()
        self._flushed_upto = 0
        self._meta_written = False
        self._span_ids = itertools.count(1)
        self._threads = threading.local()
        # flight recorder: the last N records, cheap to maintain and
        # small enough to dump atomically from a dying process
        self._flight = collections.deque(maxlen=flight_ring)
        # spans currently OPEN (entered, not yet exited) -- the dump
        # includes them so "where was this rank blocked" is answerable
        # even though unclosed spans never reach the event log
        self._open_spans = {}
        # newest closed collective span (and p2p separately) -- the
        # "last completed collective seq" a post-mortem names
        self._last_collective = None
        self._last_p2p = None
        #: liveness directory handed off by
        #: ``CommunicatorBase.enable_peer_liveness`` so the doctor can
        #: find the heartbeat files that pair with this capture
        self.liveness_dir = None
        self.flight_dumps = 0
        #: streaming record consumers (the live SLO monitor,
        #: :class:`chainermn_tpu.telemetry.slo.SLOMonitor`): called
        #: with every appended record OUTSIDE the recorder lock.  The
        #: empty-list check is the only hot-path cost when nothing is
        #: attached -- and none of this runs at all when telemetry is
        #: off (the zero-cost-off contract lives at the call sites).
        self._listeners = []
        #: named zero-arg callables whose return value is embedded in
        #: every flight dump -- components register LIVE state tables
        #: here (the generation engine's in-flight request table), so
        #: a crash mid-generation names which requests died where
        self.flight_sources = {}

    # -- clock ---------------------------------------------------------
    def now(self):
        return self._wall0 + (time.perf_counter() - self._mono0)

    def to_perf_counter(self, t):
        """A recorded time on ``time.perf_counter()``'s axis -- what a
        reader needs to lay records onto a window it timed itself."""
        return t - self._wall0 + self._mono0

    def _span_stack(self):
        """Ids of the spans open on the calling thread, innermost
        last."""
        try:
            return self._threads.stack
        except AttributeError:
            stack = self._threads.stack = []
            return stack

    # -- recording -----------------------------------------------------
    def _append(self, rec):
        with self._lock:
            self.events.append(rec)
            self._flight.append(rec)
            kind = rec.get('kind')
            if kind == 'collective':
                self._last_collective = rec
            elif kind == 'p2p':
                self._last_p2p = rec
            if len(self.events) > MAX_EVENTS:
                # drop the oldest UNFLUSHED window is wrong -- flushed
                # records are already on disk, so trim from the front
                # and move the flush cursor with it
                drop = len(self.events) - MAX_EVENTS
                del self.events[:drop]
                self._flushed_upto = max(0, self._flushed_upto - drop)
        if self._listeners:
            # outside the lock: a listener that re-enters the recorder
            # (or blocks) must not deadlock or stall span close paths
            for fn in list(self._listeners):
                try:
                    fn(rec)
                except Exception:
                    pass  # a broken consumer never breaks recording

    def add_listener(self, fn):
        """Register a streaming record consumer (called with every
        appended span/event record, after it is recorded)."""
        if fn not in self._listeners:
            self._listeners.append(fn)

    def remove_listener(self, fn):
        try:
            self._listeners.remove(fn)
        except ValueError:
            pass

    def span(self, name, kind='generic', **attrs):
        """Context manager timing the enclosed block: one record here
        and one ``cmn:<name>`` annotation in the profiler's trace.  A
        caller that must act at the span's end (the serving tick's
        phases: a boundary of its starved-device probe) sets the
        handle's ``at_exit``."""
        return _SpanHandle(self, name, kind, attrs)

    def event(self, name, kind='event', **attrs):
        rec = {'type': 'event', 'name': name, 'kind': kind,
               't': self.now()}
        if attrs:
            rec.update(attrs)
        self._append(rec)

    def child_span(self, request_id, name, t0, t1=None, kind='request',
                   **attrs):
        """Record one already-timed child span of a request trace --
        the per-request tracing primitive the serving path uses.

        Cheaper than :meth:`span` on purpose (one dict + append, no
        context manager, no open-span registry entry): the decode
        scheduler records one of these per live slot per tick.  The
        caller supplies ``t0`` (and optionally ``t1``) on THIS
        recorder's clock (:meth:`now`), which is what lets stage spans
        tile a request's timeline exactly -- each stage starts where
        the previous one ended, so the per-stage budgets telescope to
        the end-to-end latency with no gaps to fabricate."""
        rec = {'type': 'span', 'name': name, 'kind': kind,
               'request_id': request_id, 't0': t0,
               't1': self.now() if t1 is None else t1}
        if attrs:
            rec.update(attrs)
        self._append(rec)

    def interval(self, name, t0, t1, kind='generic', **attrs):
        """Record one already-timed span that belongs to no request
        and to no thread's stack (no ``id``, no ``parent``, no
        annotation): something the caller only knows the two ends of,
        on THIS recorder's clock, once it is over -- the serving
        scheduler's ``device_idle`` records."""
        rec = {'type': 'span', 'name': name, 'kind': kind, 't0': t0,
               't1': t1}
        if attrs:
            rec.update(attrs)
        self._append(rec)

    # -- flush ---------------------------------------------------------
    def _rank(self):
        try:
            import jax
            return int(jax.process_index())
        except Exception:
            return 0

    def flush(self, outdir=None, blocking=True):
        """Append unwritten events to ``events-rank<N>.jsonl`` and
        rewrite ``metrics-rank<N>.json`` under the session directory.
        Idempotent and incremental; safe to call repeatedly (the
        enable path registers it atexit).

        ``blocking=False`` is the signal-handler mode: CPython runs
        handlers between bytecodes of the interrupted thread, so if
        that thread holds ``_lock`` (it is taken on every span/event
        close), a blocking acquire here would self-deadlock.  When the
        lock is unavailable the flush is SKIPPED (returns None) rather
        than risking a duplicate window; the next boundary flush picks
        the pending events up."""
        outdir = outdir or self.outdir
        if outdir is None:
            return None
        os.makedirs(outdir, exist_ok=True)
        rank = self._rank()
        epath = os.path.join(outdir, 'events-rank%d.jsonl' % rank)
        if not self._lock.acquire(blocking=blocking):
            return None
        try:
            pending = self.events[self._flushed_upto:]
            self._flushed_upto = len(self.events)
        finally:
            self._lock.release()
        with open(epath, 'a') as f:
            if not self._meta_written:
                f.write(json.dumps({
                    'type': 'meta', 'rank': rank, 'pid': os.getpid(),
                    'wall0': self._wall0,
                    'argv': list(sys.argv)}) + '\n')
                self._meta_written = True
            for rec in pending:
                f.write(json.dumps(dict(rec, rank=rank)) + '\n')
        mpath = os.path.join(outdir, 'metrics-rank%d.json' % rank)
        tmp = mpath + '.tmp'
        with open(tmp, 'w') as f:
            json.dump({'rank': rank,
                       'metrics': self.registry.snapshot()}, f)
        os.replace(tmp, mpath)
        return epath

    def dump_flight(self, reason, outdir=None, blocking=True, **attrs):
        """Crash-safe black-box dump: atomically (tmp + rename, with
        the serializers' write-complete sentinel convention) write
        ``flight-rank<N>.json`` holding the last :data:`FLIGHT_RING`
        records, every OPEN span (where this rank is blocked right
        now), the newest completed collective/p2p span, and the
        caller's ``reason``/attrs.  The event log is flushed first so
        the JSONL tail is as current as the flight record.

        Called from the places a process dies or detects death: chaos
        kill sites before ``os._exit``, the typed-failure
        constructors (``ChannelTimeout`` / ``PeerDeadError`` /
        ``CheckpointCorruptError``), and the preemption SIGTERM hook.
        Latest dump wins (one file per rank); ``n_dumps`` counts how
        many this process wrote.  Best-effort by contract: returns
        the path or None, never raises.

        ``blocking=False`` is REQUIRED from signal handlers: the
        recorder lock is non-reentrant and taken by the interrupted
        thread on every span close, so blocking on it from a handler
        self-deadlocks the process.  When the lock cannot be acquired
        the dump degrades -- the incremental flush is skipped and the
        ring is snapshotted lock-free (consistent when the holder is
        the interrupted frame of this same thread; a cross-thread
        mid-mutation copy is retried, then dropped) -- and the record
        carries ``degraded: true``."""
        outdir = outdir or self.outdir
        if outdir is None:
            return None
        try:
            try:
                self.flush(outdir, blocking=blocking)
            except Exception:
                pass  # the flight record must still be attempted
            rank = self._rank()
            locked = self._lock.acquire(blocking=blocking)
            try:
                ring = []
                for _ in range(3):
                    try:
                        ring = list(self._flight)
                        break
                    except RuntimeError:
                        # deque mutated mid-copy: only possible on the
                        # lock-free path with a concurrent appender
                        continue
                last_coll = (dict(self._last_collective)
                             if self._last_collective else None)
                last_p2p = (dict(self._last_p2p)
                            if self._last_p2p else None)
            finally:
                if locked:
                    self._lock.release()
            open_spans = [
                dict({k: v for k, v in rec.items()
                      if k != 'attrs'}, **(rec.get('attrs') or {}))
                for rec in list(self._open_spans.values())]
            self.flight_dumps += 1
            record = {
                'rank': rank,
                'pid': os.getpid(),
                'reason': reason,
                't': self.now(),
                'wall0': self._wall0,
                'n_dumps': self.flight_dumps,
                'liveness_dir': self.liveness_dir,
                'last_collective': last_coll,
                'last_p2p': last_p2p,
                'open_spans': open_spans,
                'ring': ring,
            }
            if attrs:
                record['attrs'] = attrs
            # live state tables registered by components (the
            # generation engine's in-flight request table): a crash
            # mid-generation then names which requests died where.
            # Each source is best-effort -- a racing mutation on the
            # dying process must not void the black box
            for name, fn in list(self.flight_sources.items()):
                try:
                    record[name] = fn()
                except Exception:
                    continue
            if not locked:
                record['degraded'] = True  # lock-free snapshot
            record['complete'] = True  # write-complete sentinel
            path = os.path.join(outdir, 'flight-rank%d.json' % rank)
            tmp = path + '.tmp.%d' % os.getpid()
            with open(tmp, 'w') as f:
                # default=repr: an exotic attr value must not void the
                # whole black box
                json.dump(record, f, default=repr)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            return path
        except Exception:
            return None  # a failing dump must never mask the fault
