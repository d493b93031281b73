"""Dataset iterators.

Standalone equivalents of the Chainer iterators the reference examples
use (``SerialIterator`` at ``train_mnist.py:96-97``,
``MultiprocessIterator`` at ``train_imagenet.py:174-178``).  Host-side
data handling stays in numpy; device placement is the updater's job.
"""

import threading
import queue as queue_mod

import numpy as np

from chainermn_tpu import telemetry as _telemetry


class SerialIterator:
    """Single-thread batch iterator with epoch accounting."""

    def __init__(self, dataset, batch_size, repeat=True, shuffle=True,
                 seed=0):
        self.dataset = dataset
        self.batch_size = batch_size
        self._repeat = repeat
        self._shuffle = shuffle
        self._rng = np.random.RandomState(seed)
        self.reset()

    def reset(self):
        self.epoch = 0
        self.iteration = 0
        self.is_new_epoch = False
        self._pos = 0
        self._order = self._new_order()

    def _new_order(self):
        n = len(self.dataset)
        return (self._rng.permutation(n) if self._shuffle
                else np.arange(n))

    def restore_epoch(self, epoch):
        """Continue epoch accounting from a checkpoint."""
        self.epoch = int(epoch)

    def restore_position(self, epoch_detail):
        """Elastic twin of :meth:`restore_epoch`: land at the same
        GLOBAL epoch fraction re-expressed in THIS topology's shard
        length (``dataset.epoch_position``), so a run resumed at a
        different process count keeps its epoch boundary where the
        interrupted run would have hit it.  The shuffle order is
        freshly drawn -- the position, not the permutation, is the
        contract."""
        from chainermn_tpu.dataset import epoch_position
        self.epoch, self._pos = epoch_position(
            float(epoch_detail), len(self.dataset))
        self.is_new_epoch = False
        self._order = self._new_order()

    @property
    def epoch_detail(self):
        return self.epoch + self._pos / max(1, len(self.dataset))

    def __iter__(self):
        return self

    def __next__(self):
        n = len(self.dataset)
        if n == 0:
            raise StopIteration
        if self._pos >= n:
            if not self._repeat:
                raise StopIteration
            self._pos = 0
            self._order = self._new_order()
        i, i_end = self._pos, min(self._pos + self.batch_size, n)
        batch = [self.dataset[int(self._order[k])] for k in range(i, i_end)]
        self._pos = i_end
        self.is_new_epoch = False
        if self._pos >= n:
            self.epoch += 1
            self.is_new_epoch = True
            if self._repeat:
                self._pos = 0
                self._order = self._new_order()
        # top up to a constant batch size when repeating (static shapes
        # keep the jitted step cache-hot)
        while self._repeat and len(batch) < self.batch_size:
            batch.append(self.dataset[int(self._order[self._pos])])
            self._pos += 1
        self.iteration += 1
        return batch

    next = __next__


class PipelineIterator:
    """Batch-level iterator over a
    :class:`chainermn_tpu.datasets.BatchAugmentPipeline` (or anything
    with ``__len__`` and ``batch(indices) -> (X, Y)``): yields
    pre-collated column arrays assembled by the native C++ thread-pool
    kernel, replacing per-item Python work entirely.  Epoch accounting
    matches :class:`SerialIterator`."""

    def __init__(self, pipeline, batch_size, repeat=True, shuffle=True,
                 seed=0):
        self.pipeline = pipeline
        self.batch_size = batch_size
        self._repeat = repeat
        self._shuffle = shuffle
        self._rng = np.random.RandomState(seed)
        self.reset()

    def reset(self):
        self.epoch = 0
        self.iteration = 0
        self.is_new_epoch = False
        self._pos = 0
        self._order = self._new_order()

    def restore_epoch(self, epoch):
        self.epoch = int(epoch)

    def restore_position(self, epoch_detail):
        """Same elastic contract as
        :meth:`SerialIterator.restore_position`."""
        from chainermn_tpu.dataset import epoch_position
        self.epoch, self._pos = epoch_position(
            float(epoch_detail), len(self.pipeline))
        self.is_new_epoch = False
        self._order = self._new_order()

    def _new_order(self):
        n = len(self.pipeline)
        return (self._rng.permutation(n) if self._shuffle
                else np.arange(n))

    @property
    def epoch_detail(self):
        return self.epoch + self._pos / max(1, len(self.pipeline))

    def __iter__(self):
        return self

    def __next__(self):
        n = len(self.pipeline)
        if n == 0:
            raise StopIteration
        if self._pos >= n:
            if not self._repeat:
                raise StopIteration
            self._pos = 0
            self._order = self._new_order()
        i_end = min(self._pos + self.batch_size, n)
        idx = self._order[self._pos:i_end]
        self._pos = i_end
        self.is_new_epoch = False
        if self._pos >= n:
            self.epoch += 1
            self.is_new_epoch = True
            if self._repeat:
                self._pos = 0
                self._order = self._new_order()
        # top up to a constant batch size when repeating (static
        # shapes keep the jitted step cache-hot)
        if self._repeat and len(idx) < self.batch_size:
            extra = self.batch_size - len(idx)
            idx = np.concatenate([idx, self._order[:extra]])
            self._pos = extra
        self.iteration += 1
        return self.pipeline.batch(idx.astype(np.int64))

    next = __next__


class _PrefetchingIterator:
    """Shared worker/queue machinery for the prefetching iterators.

    A daemon thread repeatedly calls :meth:`_produce` (subclass hook:
    pull from the inner iterator, optionally transform, snapshot the
    inner counters) and feeds a bounded queue; the consumer side
    unpacks items in ``__next__``.  Threading invariants concentrated
    here ONCE (they are subtle):

    - the worker captures ITS OWN queue/stop event, so a stale worker
      that outlives a reset (join timeout) keeps observing its
      original, set stop event and abandoned queue rather than the
      replacements -- it can never race the new worker on the shared
      inner iterator once it finishes its in-flight item;
    - puts are bounded with a stop check, so a producer blocked on a
      full abandoned queue parks on stop, not forever;
    - the terminal sentinel (StopIteration or a worker exception) is
      REMEMBERED: the worker thread exits after sending it, so a
      second ``next()`` would otherwise block on an empty queue for
      good.  Post-terminal calls re-raise until :meth:`reset`.
    """

    def _start_worker(self):
        self._queue = queue_mod.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._terminal = None
        self._thread = threading.Thread(
            target=self._worker_loop, args=(self._queue, self._stop),
            daemon=True)
        self._thread.start()

    def _stop_worker(self):
        self._stop.set()
        # drain so a producer blocked on put() can observe the stop flag
        while self._thread.is_alive():
            try:
                while True:
                    self._queue.get_nowait()
            except queue_mod.Empty:
                pass
            self._thread.join(timeout=0.2)

    def _worker_loop(self, out_queue, stop):
        try:
            while not stop.is_set():
                try:
                    item = self._produce()
                except StopIteration:
                    out_queue.put(StopIteration)
                    return
                while not stop.is_set():
                    try:
                        out_queue.put(item, timeout=0.2)
                        break
                    except queue_mod.Full:
                        continue
        except Exception as e:  # surface worker failures to the consumer
            out_queue.put(e)

    def _next_item(self):
        if self._terminal is not None:
            raise self._terminal
        item = self._queue.get()
        if item is StopIteration:
            self._terminal = StopIteration()
            raise StopIteration
        if isinstance(item, Exception):
            self._terminal = item
            raise item
        return item

    def __iter__(self):
        return self

    def finalize(self):
        self._stop.set()
        fin = getattr(self._source, 'finalize', None)
        if fin is not None:
            fin()  # the documented composition: stop the inner worker too


class MultiprocessIterator(_PrefetchingIterator):
    """Prefetching iterator.

    The reference needs real worker *processes* (and ``forkserver``
    gymnastics, ``train_imagenet.py:174-182``) because Python-side JPEG
    decode is the bottleneck and MPI forks poorly.  Our pipeline is
    numpy-light (augmentation lives in the jitted step where the VPU
    does it), so a prefetch thread over an inner :class:`SerialIterator`
    hides host latency without fork hazards; the class name is kept for
    the reference's API surface.  Epoch accounting attributes reflect
    what the *consumer* has taken, not the producer's read-ahead.
    """

    def __init__(self, dataset, batch_size, repeat=True, shuffle=True,
                 seed=0, n_prefetch=4, n_processes=None):
        self.dataset = dataset
        self.batch_size = batch_size
        self._source = SerialIterator(dataset, batch_size, repeat,
                                      shuffle, seed)
        self._inner = self._source  # kept name: pre-refactor API
        self.epoch = 0
        self.iteration = 0
        self.is_new_epoch = False
        self._consumed_pos = 0
        self._depth = n_prefetch
        self._start_worker()

    def _produce(self):
        inner = self._source
        # dataset indexing to a list of examples, in the producer
        # thread (so the span has no parent)
        with _telemetry.span('batch_fetch', kind='host',
                             iteration=inner.iteration):
            batch = next(inner)
        return (batch, inner.epoch, inner.iteration,
                inner.is_new_epoch, inner._pos)

    def reset(self):
        """Stop the current producer and restart from a fresh pass
        (needed for repeat=False evaluation iterators reused across
        epochs)."""
        self._stop_worker()
        self._source.reset()
        self.epoch = 0
        self.iteration = 0
        self.is_new_epoch = False
        self._consumed_pos = 0
        self._start_worker()

    def restore_epoch(self, epoch):
        """Continue epoch accounting from a checkpoint: the producer's
        counters are rebased so prefetched tuples carry the restored
        epoch (plain attribute assignment would be overwritten by the
        next ``__next__``)."""
        self._stop_worker()
        self._source.epoch = int(epoch)
        self.epoch = int(epoch)
        self._consumed_pos = 0  # epoch_detail == restored epoch exactly
        self._start_worker()

    def restore_position(self, epoch_detail):
        """Elastic restore: position the inner iterator at the saved
        global epoch fraction (re-expressed at this shard length) and
        rebase the consumer-side counters to match, discarding any
        read-ahead from the pre-restore position."""
        self._stop_worker()
        self._source.restore_position(float(epoch_detail))
        self.epoch = self._source.epoch
        self._consumed_pos = self._source._pos
        self.is_new_epoch = False
        self._start_worker()

    def __next__(self):
        batch, self.epoch, self.iteration, self.is_new_epoch, \
            self._consumed_pos = self._next_item()
        return batch

    next = __next__

    @property
    def epoch_detail(self):
        return self.epoch + self._consumed_pos / max(1, len(self.dataset))


class DevicePrefetchIterator(_PrefetchingIterator):
    """Overlap host collation + host->device transfer with the running
    step: a worker thread pulls batches from ``inner``, runs
    ``place_fn`` (typically ``StandardUpdater.shard_batch``: collate +
    sharded ``device_put``) and queues the DEVICE-RESIDENT trees, so
    ``__next__`` hands the train loop arrays that are already on (or
    in flight to) the chips while the previous step executes.

    The worker is ONE thread and stays one; what it calls is not:
    ``shard_batch``'s collate (``training/convert.py``) splits the
    rows of a large column over a small pool, so a 77 MB image batch
    costs this thread ~35 ms on a TPU v5e host and a 64 KB token
    batch, written by this thread alone, well under one.

    This is the device-side half of the input pipeline
    (:class:`MultiprocessIterator` is the host-side half; they
    compose: wrap one in the other -- ``finalize`` propagates).  On
    TPU the win is hiding the PCIe/ICI transfer and the numpy
    collation behind the step; ``jax.device_put`` is async and
    thread-safe, so the worker never blocks on the device.

    Epoch accounting reflects what the CONSUMER has taken, not the
    producer's read-ahead (same contract as
    :class:`MultiprocessIterator`): the producer threads its counters
    through the queue with each batch.

    Used via ``StandardUpdater(..., device_prefetch=N)`` or directly::

        it = DevicePrefetchIterator(SerialIterator(ds, bs),
                                    upd.shard_batch, depth=2)
        metrics = upd.update_core(next(it))
    """

    def __init__(self, inner, place_fn, depth=2):
        if depth < 1:
            raise ValueError('depth must be >= 1')
        self.inner = inner
        self._source = inner
        self._place = place_fn
        self._depth = depth
        self._rebase_counters()
        self._start_worker()

    def _rebase_counters(self):
        inner = self._source
        self.epoch = getattr(inner, 'epoch', 0)
        self.iteration = getattr(inner, 'iteration', 0)
        self.is_new_epoch = False
        self._consumed_detail = float(getattr(inner, 'epoch_detail',
                                              0.0))
        self._consumed_cursor = getattr(inner, 'stream_cursor', None)

    def _produce(self):
        inner = self._source
        batch = next(inner)
        placed = self._place(batch)
        return (placed, getattr(inner, 'epoch', 0),
                getattr(inner, 'iteration', 0),
                getattr(inner, 'is_new_epoch', False),
                float(getattr(inner, 'epoch_detail', 0.0)),
                getattr(inner, 'stream_cursor', None))

    def __next__(self):
        (placed, self.epoch, self.iteration, self.is_new_epoch,
         self._consumed_detail, self._consumed_cursor) = \
            self._next_item()
        return placed

    next = __next__

    @property
    def epoch_detail(self):
        return self._consumed_detail

    @property
    def stream_cursor(self):
        """The streaming loader's elastic cursor AS CONSUMED (the
        producer reads ahead; checkpoints must reflect what the train
        loop actually took -- same contract as ``epoch_detail``).
        ``None`` over inner iterators without a cursor, which makes
        ``serializers.updater_state`` skip the field entirely."""
        return self._consumed_cursor

    def reset(self):
        self._stop_worker()
        if hasattr(self.inner, 'reset'):
            self.inner.reset()
        self._rebase_counters()
        self._start_worker()

    def restore_epoch(self, epoch):
        self._stop_worker()
        if hasattr(self.inner, 'restore_epoch'):
            self.inner.restore_epoch(epoch)
        else:
            self.inner.epoch = int(epoch)
        self._rebase_counters()
        # consumed-detail rebases to the restored epoch boundary so
        # epoch/epoch_detail agree in the first post-resume log entry
        self.epoch = int(epoch)
        self._consumed_detail = float(int(epoch))
        self._start_worker()

    def restore_cursor(self, epoch, cursor):
        """Exact elastic restore (streaming loader inner): position
        the inner stream at global ``(epoch, cursor)`` and rebase the
        consumer-side counters, discarding pre-restore read-ahead.
        Only meaningful when the inner iterator supports it
        (``serializers.restore_counters`` probes with hasattr, and
        this method is only present via delegation)."""
        if not hasattr(self.inner, 'restore_cursor'):
            # cursor saved by a different pipeline shape: degrade to
            # the epoch-boundary restore rather than crash the resume
            return self.restore_position(float(int(epoch)))
        self._stop_worker()
        self.inner.restore_cursor(int(epoch), int(cursor))
        self._rebase_counters()
        self._start_worker()

    def restore_position(self, epoch_detail):
        """Elastic restore: delegate the fractional position to the
        inner iterator (falling back to integer-epoch restore when it
        cannot express one) and rebase the consumer-side counters,
        discarding pre-restore read-ahead."""
        self._stop_worker()
        if hasattr(self.inner, 'restore_position'):
            self.inner.restore_position(float(epoch_detail))
        elif hasattr(self.inner, 'restore_epoch'):
            self.inner.restore_epoch(int(epoch_detail))
        else:
            self.inner.epoch = int(epoch_detail)
        self._rebase_counters()
        self._consumed_detail = float(getattr(
            self.inner, 'epoch_detail', float(epoch_detail)))
        self._start_worker()
