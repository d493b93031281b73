"""Failure detection and the recovery-side failure taxonomy.

The reference has NONE (SURVEY 5: MPI fail-stop only -- a hung or
diverged rank is discovered by the human).  This module supplies the
detectors a distributed run actually needs, plus the typed errors and
bounded-wait arithmetic the recovery layer acts on:

- numeric: :func:`check_finite` / :class:`NanGuard` -- divergence
  (NaN/Inf in loss, metrics, or params) stops the run with the first
  offending pytree paths named (optionally snapshotting state for
  post-mortem, see ``checkpoint_on_divergence``).
- liveness: :class:`Heartbeat` / :func:`detect_stall` -- each process
  writes a heartbeat file; any watcher (another rank, the launcher, a
  cron) can flag a stalled process without MPI-style global failure.
- timeout: the native collective engine returns CMN_TIMEOUT from a
  barrier whose peers never arrive (``csrc/chainermn_core.cpp``),
  surfacing single-rank death to the surviving ranks.  The eager
  Python channel mirrors that taxonomy: :class:`ChannelTimeout` (the
  wait expired, the peer MAY still be alive) vs :class:`PeerDeadError`
  (the peer is positively detected dead via its stalled heartbeat).
- bounded waits: :class:`Deadline` (absolute budget arithmetic) and
  :class:`Backoff` (deterministic exponential retry schedule) shared
  by every blocking path in ``communicators/base.py`` -- no wait in
  the eager stack is unbounded.

Acted on by :mod:`chainermn_tpu.utils.chaos` (deterministic fault
injection) and :mod:`chainermn_tpu.training.recovery` (preemption
checkpoint + auto-resume); see ``docs/fault_tolerance.md``.
"""

import json
import os
import signal as _signal
import threading
import time

import jax
import numpy as np


# ----------------------------------------------------------------------
# Typed failure taxonomy (eager-channel mirror of the native engine's
# CMN_* status codes, ``csrc/chainermn_core.cpp`` / ``native/core.py``)
# ----------------------------------------------------------------------

def _flight_dump(reason, **attrs):
    """Drop the telemetry flight record at a typed-failure raise
    site.  The typed constructors call this so EVERY raise path --
    present and future -- leaves the black box behind without each
    call site remembering to; a no-op when telemetry is off or
    in-memory, and never raises (a failing dump must not mask the
    typed verdict)."""
    try:
        from chainermn_tpu import telemetry
        if telemetry.live() is not None:
            telemetry.dump_flight(reason, **attrs)
    except Exception:
        pass


class CommFailure(RuntimeError):
    """Base of the eager-channel failure taxonomy (Python twin of the
    native engine's :class:`~chainermn_tpu.native.core.CommError`)."""

    status_name = 'CMN_ERROR'


class ChannelTimeout(CommFailure, TimeoutError):
    """A bounded wait expired without evidence the peer is dead --
    mirrors the native barrier's ``CMN_TIMEOUT``.  Retryable: the
    sequence cursor of the waiting stream is never advanced on
    timeout, so the same call can simply be issued again."""

    status_name = 'CMN_TIMEOUT'

    def __init__(self, *args):
        super().__init__(*args)
        _flight_dump('ChannelTimeout',
                     message=str(args[0]) if args else '')


class PeerDeadError(CommFailure):
    """A peer process is POSITIVELY detected dead (its heartbeat file
    went stale past the liveness window, or it is known to have
    exited).  Unlike :class:`ChannelTimeout` this verdict is terminal
    for the conversation: retrying the same wait cannot succeed.

    ``process_index`` names the dead peer."""

    status_name = 'CMN_PEER_DEAD'

    def __init__(self, message, process_index=None):
        super().__init__(message)
        self.process_index = process_index
        _flight_dump('PeerDeadError', message=str(message),
                     process_index=process_index)


class ReplicaDeadError(CommFailure):
    """A serving replica is POSITIVELY detected dead (its stdout
    stream hit EOF, its process exited, or a typed RPC found the
    connection closed).  The serving-fleet sibling of
    :class:`PeerDeadError`: terminal for every request the replica
    was carrying, but -- unlike a training peer -- the fleet front
    can *recover* those requests by replaying their journaled
    ``prompt + emitted`` prefix on a survivor (exact-greedy
    continuation, ``docs/fault_tolerance.md`` "Serving
    self-healing").

    ``replica`` names the dead replica; ``request_ids`` lists the
    in-flight request ids it was carrying when it died (the requeue
    worklist)."""

    status_name = 'CMN_REPLICA_DEAD'

    def __init__(self, message, replica=None, request_ids=()):
        super().__init__(message)
        self.replica = replica
        self.request_ids = tuple(request_ids)
        _flight_dump('ReplicaDeadError', message=str(message),
                     replica=replica,
                     request_ids=list(self.request_ids))


class CheckpointCorruptError(ValueError):
    """A checkpoint failed integrity verification and must NOT be
    restored: truncated/unreadable file, per-leaf crc32 mismatch,
    missing write-complete sentinel, a leaf missing from the snapshot,
    or a shape/dtype mismatch against the restore template.

    The checkpoint-trust member of the failure taxonomy (see
    ``docs/fault_tolerance.md``): where :class:`ChannelTimeout` /
    :class:`PeerDeadError` make *communication* failure typed, this
    makes *state* failure typed -- ``auto_resume`` catches it to walk
    the snapshot chain to the newest VALID snapshot instead of
    silently loading poison or dying inside npz/zipfile internals.

    ``path`` names the snapshot, ``leaf`` the offending tree path
    (when one is identifiable), and ``kind`` classifies the defect:
    ``'unreadable'`` | ``'incomplete'`` | ``'crc'`` | ``'missing'`` |
    ``'shape'`` | ``'dtype'`` | ``'topology'``.  Subclasses
    ``ValueError`` so pre-taxonomy callers that caught the old bare
    errors keep working.
    """

    status_name = 'CMN_CKPT_CORRUPT'

    def __init__(self, message, path=None, leaf=None, kind=None):
        super().__init__(message)
        self.path = path
        self.leaf = leaf
        self.kind = kind
        _flight_dump('CheckpointCorruptError', message=str(message),
                     path=path, leaf=leaf, corruption_kind=kind)


class DataCorruptError(ValueError):
    """An input record failed integrity verification and must NOT be
    consumed: a flipped byte caught by the record crc32, a record
    extending past the shard's EOF (torn file), or a missing/
    unparseable index sidecar.

    The input-data member of the failure taxonomy (see
    ``docs/data_pipeline.md``): where
    :class:`CheckpointCorruptError` makes *state* failure typed, this
    makes *data* failure typed -- the streaming loader catches it to
    SKIP AND COUNT the sample (``corrupt_skipped`` +
    ``data_corrupt_skipped`` telemetry events) instead of silently
    training on poison or dying inside zipfile internals.

    ``shard`` names the file, ``offset`` the byte offset and
    ``record`` the in-shard record index (when identifiable);
    ``kind`` classifies the defect: ``'crc'`` | ``'truncated'`` |
    ``'unreadable'``.  Subclasses ``ValueError`` to mirror
    :class:`CheckpointCorruptError`'s compatibility contract."""

    status_name = 'CMN_DATA_CORRUPT'

    def __init__(self, message, shard=None, offset=None, record=None,
                 kind=None):
        super().__init__(message)
        self.shard = shard
        self.offset = offset
        self.record = record
        self.kind = kind
        _flight_dump('DataCorruptError', message=str(message),
                     shard=shard, offset=offset, record=record,
                     corruption_kind=kind)


class OverloadError(CommFailure):
    """The serving admission layer REFUSED work instead of wedging:
    the bounded request queue is full, or a request's deadline expired
    before (or while) it could be batched/executed.  The load-shedding
    member of the failure taxonomy -- under sustained overload the
    engine keeps serving what it admitted at a bounded latency and
    answers the rest with this typed verdict, which a client can back
    off on (``docs/serving.md``).

    ``reason`` classifies the shed: ``'queue_full'`` |
    ``'deadline'`` | ``'shutdown'``.  ``queue_depth`` records the
    depth observed at the decision.

    Unlike the other typed constructors this one does NOT drop a
    telemetry flight record: sheds fire at request rate when
    saturated (thousands/s), and a black-box dump per shed would
    thrash the disk the flight recorder exists to protect.  The
    batcher counts sheds in the ``serve_shed_total`` metric instead.
    """

    status_name = 'CMN_OVERLOAD'

    def __init__(self, message, reason='queue_full', queue_depth=None):
        super().__init__(message)
        self.reason = reason
        self.queue_depth = queue_depth


class WeightSwapError(RuntimeError):
    """A live weight hot-swap was REFUSED or failed validation before
    cutover: the engine still holds (and keeps serving) its previous
    parameter version.  Raised by ``swap_params`` when the new tree
    produces non-finite outputs on the validation forward, or when a
    generation engine is asked to swap with sequences still in flight
    (mid-sequence weight changes would corrupt the KV cache the
    in-flight sequences already banked).  The fleet records the
    refusal in ``fleet_ledger.jsonl`` and keeps routing to the
    incumbent -- a failed swap never takes a replica down."""

    def __init__(self, message, version=None):
        _flight_dump('weight_swap_failed', version=version)
        super().__init__(message)
        self.version = version


class CheckpointSkippedWarning(UserWarning):
    """Emitted (via ``warnings.warn``) each time ``auto_resume`` skips
    a corrupt or incomplete snapshot while walking the chain
    newest-to-oldest -- the typed, greppable record that a fallback
    happened and why."""


class Deadline:
    """Absolute time budget for a (possibly multi-step) blocking
    operation.  ``timeout=None`` means unbounded (every query reports
    time remaining as ``inf``); all arithmetic is monotonic-clock.

    The one place deadline arithmetic lives (ADVICE r4's timeout-
    arithmetic bug class: nested timeouts that do not add up): slices
    handed to sub-waits are ``min(want, remaining)``, so the sum of
    slices can never exceed the budget.
    """

    def __init__(self, timeout, clock=time.monotonic):
        self._clock = clock
        self.timeout = timeout
        self._t0 = clock()

    def elapsed(self):
        return self._clock() - self._t0

    def remaining(self):
        if self.timeout is None:
            return float('inf')
        return self.timeout - self.elapsed()

    def expired(self):
        return self.remaining() <= 0.0

    def slice(self, want, floor=1e-3):
        """Clamp a sub-wait to the remaining budget (never below
        ``floor`` so a wait API that rejects non-positive timeouts
        still gets a valid value; the caller checks :meth:`expired`
        before trusting the slice)."""
        return max(min(want, self.remaining()), floor)


class Backoff:
    """Deterministic exponential backoff schedule:
    ``initial * factor**k`` capped at ``max_delay``, with optional
    decorrelation jitter drawn from a SEEDED rng so two processes (or
    two runs) given the same seed replay the identical schedule --
    the property the chaos harness's determinism tests pin.

    Use :meth:`next` for the next delay (advances the schedule),
    :meth:`sleep` to also sleep it, :meth:`reset` after a success.
    """

    def __init__(self, initial=0.05, factor=2.0, max_delay=2.0,
                 jitter=0.0, seed=0):
        if initial <= 0 or factor < 1.0 or max_delay < initial:
            raise ValueError(
                'need initial > 0, factor >= 1, max_delay >= initial')
        self.initial = initial
        self.factor = factor
        self.max_delay = max_delay
        self.jitter = jitter
        self._seed = seed
        self.reset()

    def reset(self):
        import random
        self.attempt = 0
        self._rng = random.Random(self._seed)

    def peek(self):
        """The delay :meth:`next` would return, without advancing
        (jitter excluded -- it is drawn only when the step is
        consumed)."""
        return min(self.initial * self.factor ** self.attempt,
                   self.max_delay)

    def next(self):
        base = self.peek()
        self.attempt += 1
        if self.jitter:
            base += base * self.jitter * self._rng.random()
        return min(base, self.max_delay * (1.0 + self.jitter))

    def sleep(self, deadline=None):
        """Sleep the next delay (clamped to ``deadline.remaining()``
        when given); returns the time actually slept."""
        d = self.next()
        if deadline is not None:
            d = max(min(d, deadline.remaining()), 0.0)
        if d > 0:
            time.sleep(d)
        return d

    def delays(self, n):
        """Preview of the first ``n`` un-jittered delays (schedule
        introspection for tests/docs; does not advance state)."""
        return [min(self.initial * self.factor ** k, self.max_delay)
                for k in range(n)]


def check_finite(tree, prefix=''):
    """Return the paths of non-finite leaves (empty list == healthy)."""
    bad = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        arr = np.asarray(leaf)
        if arr.dtype.kind in 'fc' and not np.all(np.isfinite(arr)):
            key = prefix + '/'.join(
                str(getattr(p, 'key', getattr(p, 'idx', p)))
                for p in path)
            bad.append(key)
    return bad


class DivergenceError(RuntimeError):
    """Raised by NanGuard when training produces non-finite values."""


# ----------------------------------------------------------------------
# Exit-code taxonomy: the typed failures, flattened to the one channel
# that survives a process death -- its exit status.  The supervisor
# (:mod:`chainermn_tpu.training.supervisor`) classifies a dead worker
# from this code first and cross-checks the telemetry doctor's verdict
# second; ``worker_main`` maps the exceptions on the way out.  Codes
# live in the 70-79 band (EX_SOFTWARE neighborhood) so they cannot
# collide with shells (126/127), signals (128+N) or the chaos
# injector's hard-kill defaults (42/43).
# ----------------------------------------------------------------------

EXIT_OK = 0
EXIT_UNCAUGHT = 70         # untyped exception escaped worker_main
EXIT_PREEMPTED = 71        # clean SIGTERM evacuation, checkpoint written
EXIT_DIVERGENCE = 72       # NanGuard verdict (DivergenceError)
EXIT_CHANNEL_TIMEOUT = 73  # bounded wait expired (ChannelTimeout)
EXIT_PEER_DEAD = 74        # typed peer death observed (PeerDeadError)
EXIT_CKPT_CORRUPT = 75     # checkpoint trust failure (CheckpointCorruptError)

#: exit status -> taxonomy name (the supervisor's first classifier)
EXIT_NAMES = {
    EXIT_OK: 'clean',
    EXIT_UNCAUGHT: 'uncaught',
    EXIT_PREEMPTED: 'preempted',
    EXIT_DIVERGENCE: 'divergence',
    EXIT_CHANNEL_TIMEOUT: 'channel_timeout',
    EXIT_PEER_DEAD: 'peer_dead',
    EXIT_CKPT_CORRUPT: 'checkpoint_corrupt',
}


def exit_code_for(exc):
    """The taxonomy exit code for an exception instance -- typed
    failures map to their own code, anything else to
    :data:`EXIT_UNCAUGHT`.  Subclass checks are ordered most-specific
    first (``PeerDeadError`` is a ``CommFailure``; ``ChannelTimeout``
    is also a ``TimeoutError``)."""
    if isinstance(exc, PeerDeadError):
        return EXIT_PEER_DEAD
    if isinstance(exc, ChannelTimeout):
        return EXIT_CHANNEL_TIMEOUT
    if isinstance(exc, CheckpointCorruptError):
        return EXIT_CKPT_CORRUPT
    if isinstance(exc, DivergenceError):
        return EXIT_DIVERGENCE
    return EXIT_UNCAUGHT


def classify_exit(returncode):
    """Taxonomy name for a worker's exit status: ``'clean'`` /
    ``'running'`` (still alive, status None), a typed name from
    :data:`EXIT_NAMES`, ``'signal:NAME'`` for signal deaths (Popen
    reports them as negative), or ``'crash'`` for any other nonzero
    code (the chaos injector's hard-kill defaults 42/43 land here --
    deliberately: an ``os._exit`` mid-step looks exactly like a
    machine loss, and the doctor's flight records are what refine
    it)."""
    if returncode is None:
        return 'running'
    if returncode == 0:
        return 'clean'
    if returncode < 0:
        try:
            return 'signal:' + _signal.Signals(-returncode).name
        except ValueError:
            return 'signal:%d' % -returncode
    return EXIT_NAMES.get(returncode, 'crash')


class NanGuard:
    """Trainer extension: stop on non-finite metrics (every iteration)
    and, every ``param_interval`` iterations, audit the parameters
    themselves (catches silent corruption that metrics lag behind).

    ``checkpoint_on_divergence``: a directory (or ``True`` for
    ``{trainer.out}/divergence``) receiving a forensic npz snapshot of
    the FULL updater state (params, optimizer state, loss-scale state,
    counters) plus a ``divergence.json`` naming the iteration and the
    offending keys, written BEFORE the raise.  The poisoned state is
    preserved for post-mortem while
    :func:`chainermn_tpu.training.recovery.auto_resume` restarts from
    the last healthy periodic snapshot -- divergence becomes a
    checkpoint-and-restart event instead of a lost run (see
    ``docs/fault_tolerance.md``).
    """

    trigger = (1, 'iteration')
    priority = 250  # before LogReport records garbage
    name = 'nan_guard'

    def __init__(self, param_interval=100, raise_on_divergence=True,
                 checkpoint_on_divergence=None):
        self.param_interval = param_interval
        self.raise_on_divergence = raise_on_divergence
        self.checkpoint_on_divergence = checkpoint_on_divergence
        self.divergence_checkpoint = None  # path once written

    def _snapshot_divergence(self, trainer, bad):
        out = self.checkpoint_on_divergence
        if out is True:
            out = os.path.join(trainer.out or '.', 'divergence')
        try:
            from chainermn_tpu import serializers
            os.makedirs(out, exist_ok=True)
            it = trainer.updater.iteration
            path = serializers.save_npz(
                os.path.join(out, 'divergence_iter_%d' % it),
                serializers.updater_state(trainer.updater))
            with open(os.path.join(out, 'divergence.json'), 'w') as f:
                json.dump({'iteration': it, 'bad': bad,
                           'checkpoint': path,
                           'process_index': jax.process_index()}, f)
            self.divergence_checkpoint = path
        except Exception as e:  # forensics must not mask the verdict
            import sys
            sys.stderr.write(
                'NanGuard: divergence checkpoint failed: %r\n' % e)

    def __call__(self, trainer):
        obs = trainer.observation
        bad = [k for k, v in obs.items()
               if isinstance(v, float) and not np.isfinite(v)]
        audit = (self.param_interval and
                 trainer.updater.iteration % self.param_interval == 0)
        if not bad and audit:
            # device-resident metrics (Trainer async_metrics=True) are
            # deliberately NOT fetched per iteration -- that would
            # reintroduce the per-step host sync async mode removes --
            # but the periodic audit is a sync point anyway, so check
            # them here alongside the parameters
            for k, v in obs.items():
                if getattr(v, 'ndim', None) == 0 and not np.isfinite(
                        np.asarray(v)):
                    bad.append(k)
            if not bad:
                bad = check_finite(trainer.updater.params, 'params/')
        if bad:
            msg = ('non-finite values at iteration %d: %s'
                   % (trainer.updater.iteration, ', '.join(bad)))
            if self.checkpoint_on_divergence:
                self._snapshot_divergence(trainer, bad)
            if self.raise_on_divergence:
                raise DivergenceError(msg)
            import sys
            sys.stderr.write('NanGuard: %s\n' % msg)


class Heartbeat:
    """Per-process liveness file, updated from a daemon thread.

    ``{path}`` gets JSON ``{pid, process_index, time, iteration}``
    every ``interval`` seconds; pair with :func:`detect_stall` on any
    observer."""

    def __init__(self, path, interval=10.0):
        self.path = path
        self.interval = interval
        self.iteration = 0
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _write(self, stopped=False):
        tmp = self.path + '.tmp'
        with open(tmp, 'w') as f:
            json.dump({'pid': os.getpid(),
                       'process_index': jax.process_index(),
                       'time': time.time(),
                       'iteration': self.iteration,
                       'stopped': stopped}, f)
        os.replace(tmp, self.path)

    def _run(self):
        while not self._stop.is_set():
            try:
                self._write()
            except OSError:
                pass
            self._stop.wait(self.interval)

    def beat(self, iteration=None):
        """Optionally called from the training loop to stamp progress."""
        if iteration is not None:
            self.iteration = iteration

    def stop(self):
        """Stop the beat thread and stamp a final ``stopped: true``
        beat, so any observer can distinguish a clean exit from a
        stall instead of reading one last fresh "alive" timestamp.
        The final write is guarded like ``_run``'s: teardown on a
        removed or read-only out dir must not crash the process it
        was supposed to be cleaning up."""
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)
            try:
                self._write(stopped=True)
            except OSError:
                pass


def read_heartbeat(path):
    """The parsed heartbeat dict at ``path``, or None when the file
    is missing or torn (a beat mid-``os.replace`` can never be torn,
    but the destination may not exist yet)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def detect_stall(path, timeout=60.0, now=None, missing='stalled'):
    """True if the heartbeat at ``path`` is older than ``timeout``
    seconds -- the liveness check the reference's MPI stack cannot
    express short of a hang.

    ``missing`` decides the never-started case (no file, or an
    unreadable one): ``'stalled'`` (default; back-compatible --
    absence of a beat is treated as a stall) or ``'alive'`` (absence
    is NOT a stall -- the startup-grace mode the supervisor uses
    while a freshly spawned worker is still booting, so never-started
    and stalled stop being conflated without call-site
    special-casing)."""
    if missing not in ('stalled', 'alive'):
        raise ValueError(
            "detect_stall: missing= must be 'stalled' or 'alive', "
            'got %r' % (missing,))
    beat = read_heartbeat(path)
    if beat is None:
        return missing == 'stalled'
    now = time.time() if now is None else now
    return (now - beat.get('time', 0)) > timeout


def heartbeat_extension(out_dir, interval=10.0):
    """Trainer extension wiring: one heartbeat file per process under
    ``out_dir`` (``heartbeat-{process_index}.json``), iteration stamped
    each call."""
    hb = Heartbeat(os.path.join(
        out_dir, 'heartbeat-%d.json' % jax.process_index()),
        interval=interval)
    hb.start()

    def ext(trainer):
        hb.beat(trainer.updater.iteration)
    ext.trigger = (1, 'iteration')
    ext.priority = 20
    ext.name = 'heartbeat'
    ext.heartbeat = hb
    # the Trainer calls extension finalizers when the run ends:
    # without this the daemon thread keeps beating "alive" forever in
    # a long-lived process -- false liveness to any watcher
    ext.finalize = hb.stop
    return ext
