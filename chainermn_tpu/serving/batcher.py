"""Dynamic request batching: coalesce variable-size requests into
padded, power-of-two-bucketed batches.

The jitted/AOT forward executables the engine holds are keyed on
batch SHAPE, so admission must map every traffic pattern onto a small
finite shape set -- that is the whole job of this module:

- **Buckets.**  :func:`bucket_edges` yields power-of-two edges up to
  ``max_batch`` (configurable); :func:`bucket_of` maps an item count
  to the smallest edge that fits.  A request larger than the largest
  edge is a CLIENT error (typed ``ValueError`` at submit, before it
  can occupy queue space it can never leave).
- **Deterministic packing.**  :func:`pack_sizes` packs a drained
  snapshot first-fit-decreasing over a CANONICAL order (size
  descending, arrival sequence among equals).  Grouping therefore
  depends only on the MULTISET of request sizes -- the same mix in
  any arrival order yields identical group sizes, identical bucket
  assignments and identical padded shapes (the no-recompile
  property ``tests/test_serving.py`` pins via the engine's
  SL007-style signature hash).  FFD also happens to be the classic
  low-waste bin packing, so determinism and pad-waste pull the same
  direction.
- **Bounded admission.**  ``max_queue`` items; a submit past it is
  answered NOW with the typed
  :class:`~chainermn_tpu.utils.failure.OverloadError` instead of
  growing an unbounded backlog (overload must degrade, not wedge --
  the chaos ``serve_burst`` site drives this path on purpose).
  Requests carry optional DEADLINES; a request whose deadline passed
  while queued is shed with the same typed error at drain time, not
  executed late for nobody.
- **Admission knobs.**  A drain triggers when ``max_batch`` items
  are waiting or the oldest request has waited ``max_wait`` --
  the latency/throughput trade dial.

Host-side collation reuses the precision layer's
:func:`~chainermn_tpu.training.convert.concat_examples` host-casting
(padding + f32 validity mask, floating columns cast to the policy's
compute dtype BEFORE the device copy).
"""

import itertools
import threading
import time

import numpy as np

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.training.convert import concat_examples
from chainermn_tpu.utils import chaos as _chaos
from chainermn_tpu.utils.failure import OverloadError

#: default admission knobs
DEFAULT_MAX_BATCH = 32
DEFAULT_MAX_WAIT = 0.005
DEFAULT_MAX_QUEUE = 256

#: process-wide request-id source shared by every serving queue
#: (batch and generation): the numeric part is the MONOTONIC
#: admission stamp, so ids order by admission across queues
_request_counter = itertools.count(1)


def next_request_id():
    """Process-unique request id (``r<N>``); the counter is shared by
    the batch and generation queues, so the numeric suffix is a
    monotonic admission stamp across the whole serving process --
    what lets a merged capture order requests without a clock."""
    return 'r%d' % next(_request_counter)


def admission_order(request_id):
    """Sort key recovering the monotonic admission stamp from a
    :func:`next_request_id` id (``'r7'`` -> ``(0, 7)``) -- what the
    fleet's exact-replay recovery sorts a dead replica's in-flight
    worklist by, so requeue order is deterministic and matches the
    original admission order regardless of dict/journal iteration
    order.  Foreign ids (not ``r<N>``-shaped) sort after every native
    one, lexicographically."""
    try:
        return (0, int(str(request_id).lstrip('r')))
    except (TypeError, ValueError):
        return (1, str(request_id))


def record_shed(reason, request_id=None, queue_depth=None,
                count_total=True, **attrs):
    """Shed forensics, one call per turned-away request: bump the
    aggregate ``serve_shed_total`` (``count_total=False`` for
    shutdown drains, which the aggregate never counted) plus the
    per-reason ``serve_shed_<reason>_total`` counter, and emit a
    lightweight ``kind='request'`` ``shed`` event carrying the
    request id, the reason, and the queue depth at shed time -- so
    ``report.serve_summary`` shows a shed-reason breakdown and a
    single shed request's trace ends in a named verdict.  Zero-cost
    when telemetry is off; deliberately NO flight dump (sheds fire at
    request rate)."""
    reg = _telemetry.registry()
    if reg is not None:
        if count_total:
            reg.counter('serve_shed_total',
                        help='requests shed by the admission layer '
                             '(queue_full + deadline)').inc()
        reg.counter('serve_shed_%s_total' % reason,
                    help='requests shed with reason=%s' % reason).inc()
    _telemetry.request_event(request_id, 'shed', reason=reason,
                             queue_depth=queue_depth, **attrs)


def bucket_edges(max_batch, base=2):
    """Ascending bucket edges ``base**k`` up to and including
    ``max_batch`` (the top edge is always exactly ``max_batch`` so
    the largest executable matches the admission cap)."""
    if max_batch < 1:
        raise ValueError('max_batch must be >= 1, got %r' % max_batch)
    if base < 2:
        raise ValueError('bucket base must be >= 2, got %r' % base)
    edges, e = [], 1
    while e < max_batch:
        edges.append(e)
        e *= base
    edges.append(max_batch)
    return tuple(edges)


def bucket_of(n, edges):
    """The smallest edge >= ``n``.  ``n`` over the largest edge is a
    typed client error (the request can never be served whole)."""
    if n < 1:
        raise ValueError('request size must be >= 1, got %d' % n)
    for e in edges:
        if n <= e:
            return e
    raise ValueError(
        'request of %d items exceeds the largest bucket %d; split it '
        'client-side or raise max_batch' % (n, edges[-1]))


def pack_sizes(sizes, max_batch, edges):
    """Deterministic first-fit-decreasing packing of request sizes
    into groups of at most ``max_batch`` items (requests never split).

    ``sizes`` is indexable by request position; returns
    ``[(bucket, [positions])]``.  Canonical order -- size descending,
    position ascending among equal sizes -- makes the grouping a pure
    function of the size multiset: identical bucket assignments and
    padded shapes for the same mix in any arrival order."""
    order = sorted(range(len(sizes)), key=lambda i: (-sizes[i], i))
    groups = []  # [(remaining, [positions])]
    for i in order:
        n = sizes[i]
        if n > max_batch:
            raise ValueError(
                'request of %d items exceeds max_batch %d'
                % (n, max_batch))
        for g in groups:
            if g[0] >= n:
                g[0] -= n
                g[1].append(i)
                break
        else:
            groups.append([max_batch - n, [i]])
    return [(bucket_of(max_batch - rem, edges), members)
            for rem, members in groups]


class Request:
    """One in-flight request: payload ``x`` (leading dim = item
    count), optional absolute ``deadline`` (``clock()`` units), and a
    one-shot completion cell the engine fills with the result slice
    or a typed error.  ``request_id`` is the process-unique trace id
    (:func:`next_request_id`); ``t_trace0`` is the admission instant
    on the telemetry recorder's clock (None when telemetry was off at
    admission) -- the t0 of the request's ``queue_wait`` stage span.
    """

    __slots__ = ('x', 'n', 'deadline', 'seq', 't_submit', 'synthetic',
                 'request_id', 't_trace0', '_done', '_result',
                 '_error')

    def __init__(self, x, deadline=None, seq=0, t_submit=0.0,
                 synthetic=False, request_id=None):
        self.x = x
        self.n = int(x.shape[0])
        self.deadline = deadline
        self.seq = seq
        self.t_submit = t_submit
        self.synthetic = synthetic
        self.request_id = request_id or next_request_id()
        rec = _telemetry.live()
        self.t_trace0 = rec.now() if rec is not None else None
        self._done = threading.Event()
        self._result = None
        self._error = None

    def set_result(self, value):
        self._result = value
        self._done.set()

    def set_error(self, exc):
        self._error = exc
        self._done.set()

    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        """Block for the response; re-raises the typed shed error."""
        if not self._done.wait(timeout):
            raise TimeoutError('request %d not completed within %rs'
                               % (self.seq, timeout))
        if self._error is not None:
            raise self._error
        return self._result


class PackedBatch:
    """One drained group ready for execution: the member requests (in
    canonical pack order), their total item count, and the bucket the
    padded batch fills."""

    __slots__ = ('requests', 'bucket', 'total', 't_drain')

    def __init__(self, requests, bucket, t_drain):
        self.requests = list(requests)
        self.bucket = int(bucket)
        self.total = sum(r.n for r in self.requests)
        self.t_drain = t_drain

    def collate(self, dtype=None):
        """``(x_padded, mask)``: member payloads stacked row-wise and
        padded to the bucket, floating data cast host-side to
        ``dtype`` (the policy compute dtype) -- the precision layer's
        ``concat_examples`` host-cast reused verbatim.  ``mask`` is
        the f32 validity row mask (padding rows 0)."""
        rows = [row for req in self.requests for row in req.x]
        x, mask = concat_examples(rows, padding=(self.bucket, 0.0),
                                  dtype=dtype)
        return x, mask

    def pad_waste(self):
        """Fraction of the padded batch that is padding."""
        return (self.bucket - self.total) / float(self.bucket)


class RequestQueue:
    """Bounded, deadline-aware coalescing queue (module docstring).

    ``submit`` is the client edge (any thread); ``take`` is the
    engine edge -- it blocks until an admission trigger, drains the
    ENTIRE waiting snapshot and returns it packed into
    :class:`PackedBatch` groups (every drain serves everything that
    was waiting, so canonical pack order cannot starve anyone).
    """

    def __init__(self, max_batch=DEFAULT_MAX_BATCH,
                 max_wait=DEFAULT_MAX_WAIT,
                 max_queue=DEFAULT_MAX_QUEUE, edges=None,
                 clock=time.monotonic, label=None):
        #: fleet replica name; when set, shed forensics carry it so a
        #: per-replica SLO monitor can attribute sheds
        self.label = label
        if max_queue < max_batch:
            raise ValueError('max_queue %d < max_batch %d: the queue '
                             'could never fill one full batch'
                             % (max_queue, max_batch))
        self.max_batch = int(max_batch)
        self.max_wait = float(max_wait)
        self.max_queue = int(max_queue)
        self.edges = tuple(edges) if edges else bucket_edges(max_batch)
        if self.edges[-1] != self.max_batch:
            raise ValueError(
                'largest bucket edge %d must equal max_batch %d'
                % (self.edges[-1], self.max_batch))
        self._clock = clock
        self._cond = threading.Condition()
        self._waiting = []
        self._seq = 0
        self._closed = False
        self.submitted = 0
        self.shed_queue_full = 0
        self.shed_deadline = 0

    # -- client edge ---------------------------------------------------
    def submit(self, x, deadline=None, timeout=None, request_id=None):
        """Enqueue one request (payload leading dim = item count >= 1)
        and return its :class:`Request` handle.

        Raises the typed :class:`OverloadError` when the bounded
        queue is full (``reason='queue_full'``) or the queue is
        closed (``reason='shutdown'``); an over-bucket payload raises
        ``ValueError`` before touching queue state.  The chaos
        ``serve_burst`` site amplifies this submit with synthetic
        copies through the SAME bounded admission.  ``request_id``
        lets an admission front (the fleet) pre-assign the trace id
        it already routed on."""
        x = np.asarray(x)
        if x.ndim < 1:
            x = x[None]
        bucket_of(x.shape[0], self.edges)  # typed oversize reject
        burst = (_chaos.on_serve_submit()
                 if _chaos._active is not None else 0)
        with self._cond:
            req = self._admit(x, deadline, request_id=request_id)
            for _ in range(burst):
                try:
                    self._admit(x, deadline, synthetic=True)
                except OverloadError:
                    break  # burst past capacity sheds; the real
                    # request above was already admitted
            self._cond.notify_all()
        return req

    def _admit(self, x, deadline, synthetic=False, request_id=None):
        if self._closed:
            raise OverloadError('serving queue is shut down',
                                reason='shutdown',
                                queue_depth=len(self._waiting))
        if len(self._waiting) >= self.max_queue:
            self.shed_queue_full += 1
            # the request never existed as an object; the routed id
            # (or a fresh one) still names this rejection
            record_shed('queue_full',
                        request_id=request_id or next_request_id(),
                        queue_depth=len(self._waiting),
                        **self._shed_attrs())
            raise OverloadError(
                'serving queue full (%d waiting requests); retry '
                'with backoff' % len(self._waiting),
                reason='queue_full', queue_depth=len(self._waiting))
        self._seq += 1
        self.submitted += 1
        req = Request(x, deadline=deadline, seq=self._seq,
                      t_submit=self._clock(), synthetic=synthetic,
                      request_id=request_id)
        self._waiting.append(req)
        return req

    def _shed_attrs(self):
        return {'replica': self.label} if self.label else {}

    # -- engine edge ---------------------------------------------------
    def depth(self):
        with self._cond:
            return len(self._waiting)

    def _ready_locked(self, now):
        if not self._waiting:
            return False
        if sum(r.n for r in self._waiting) >= self.max_batch:
            return True
        return (now - self._waiting[0].t_submit) >= self.max_wait

    def take(self, timeout=None):
        """Block until an admission trigger (or ``timeout``), then
        drain the whole waiting snapshot into packed batches.
        Expired-deadline requests are shed typed here -- executing
        them would spend device time on answers nobody waits for.
        Returns ``[]`` on timeout or when closed and drained."""
        deadline = (None if timeout is None
                    else self._clock() + timeout)
        with self._cond:
            while not self._ready_locked(self._clock()):
                if self._closed:
                    break
                wait = None
                if self._waiting:
                    wait = self.max_wait - (
                        self._clock() - self._waiting[0].t_submit)
                if deadline is not None:
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        return []
                    wait = (remaining if wait is None
                            else min(wait, remaining))
                self._cond.wait(wait if wait is None
                                else max(wait, 1e-4))
            snapshot, self._waiting = self._waiting, []
        now = self._clock()
        live = []
        for req in snapshot:
            if req.deadline is not None and now > req.deadline:
                self.shed_deadline += 1
                record_shed('deadline', request_id=req.request_id,
                            queue_depth=len(snapshot),
                            waited_ms=round(
                                (now - req.t_submit) * 1e3, 3),
                            **self._shed_attrs())
                req.set_error(OverloadError(
                    'deadline expired after %.1f ms in queue'
                    % ((now - req.t_submit) * 1e3), reason='deadline'))
                continue
            live.append(req)
        if not live:
            return []
        packed = pack_sizes([r.n for r in live], self.max_batch,
                            self.edges)
        return [PackedBatch([live[i] for i in members], bucket, now)
                for bucket, members in packed]

    def close(self):
        """Refuse new work and shed everything still waiting
        (``reason='shutdown'``; counted per-reason but NOT in
        ``serve_shed_total``, which stays the overload aggregate)."""
        with self._cond:
            self._closed = True
            pending, self._waiting = self._waiting, []
            self._cond.notify_all()
        for req in pending:
            record_shed('shutdown', request_id=req.request_id,
                        queue_depth=len(pending), count_total=False,
                        **self._shed_attrs())
            req.set_error(OverloadError('serving queue shut down',
                                        reason='shutdown'))

    def stats(self):
        return {'submitted': self.submitted,
                'shed_queue_full': self.shed_queue_full,
                'shed_deadline': self.shed_deadline,
                'depth': self.depth(),
                'edges': list(self.edges)}
