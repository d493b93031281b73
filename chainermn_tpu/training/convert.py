"""Batch collation (the role of ``chainer.dataset.convert.concat_examples``
in the reference examples, e.g. ``train_mnist.py:99``).

ONE pass: every output column is allocated once, at the dtype it ships
at, and each example is written straight into its row (numpy casts in
the assignment).  There is no float32 stack that a second pass then
narrows.  A column large enough to pay for it has its rows split into
contiguous ranges over a small thread pool (numpy's copy and cast
loops let the interpreter lock go); how many workers is worked out
from the column's bytes and the machine's cores, never set by a
caller.
"""

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: shipped bytes a worker must have to itself before a column's rows
#: are split: ~1 ms of writing into a fresh batch on a TPU v5e host
#: (0.8-0.9 MB a millisecond on one thread, most of it the first touch
#: of the new pages: PERF.md section 6, PR 32), under which handing a
#: range to the pool costs more than writing it.  An LM batch's int32
#: columns (33 KB .. 131 KB) are far below it and are written by the
#: calling thread.
_TASK_MIN_BYTES = 1 << 20
#: most workers a column is split over
_MAX_WORKERS = 8
#: the machine's cores, asked once: the answer does not change and the
#: question is a system call, 34-60 us on a TPU v5e host where an LM
#: batch's whole collate is 22 (PERF.md section 6, PR 32)
_CORES = os.cpu_count() or 2

_pool = None
_pool_lock = threading.Lock()


def _executor():
    """The module's one pool, made on first use; shared by every
    caller (the buffers never are)."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                _MAX_WORKERS, thread_name_prefix='cmn-collate')
        return _pool


def _workers_for(nbytes, rows):
    """How many threads write a column of ``rows`` rows and ``nbytes``
    shipped bytes: 1 (the caller alone) unless every worker gets
    ``_TASK_MIN_BYTES`` of it."""
    cap = min(_CORES // 2, _MAX_WORKERS, rows)
    return max(1, min(cap, nbytes // _TASK_MIN_BYTES))


def _write_rows(out, src, lo, hi):
    if isinstance(src, np.ndarray):      # an already-collated column
        out[lo:hi] = src[lo:hi]
        return
    for i in range(lo, hi):
        out[i] = src[i]


def _fill(out, src, n):
    """Write ``src[0:n]`` into ``out[0:n]``; returns the worker count.
    The caller writes the last range itself; a worker's exception is
    re-raised here."""
    workers = _workers_for(out[:n].nbytes, n)
    if workers == 1:
        _write_rows(out, src, 0, n)
        return 1
    bounds = [n * k // workers for k in range(workers + 1)]
    pool = _executor()
    futures = [pool.submit(_write_rows, out, src, lo, hi)
               for lo, hi in zip(bounds[:-2], bounds[1:-1])]
    try:
        _write_rows(out, src, bounds[-2], n)
    finally:
        for f in futures:
            f.result()
    return workers


def _ship_dtype(src, dtype):
    """The dtype a column of ``src`` dtype ships at: floating columns
    take ``dtype`` (a mixed-precision policy's compute dtype), integer
    labels and everything else stay as they are."""
    if dtype is not None and np.issubdtype(src, np.floating):
        return np.dtype(dtype)
    return src


def _column(examples, dtype, pad_to, fill):
    """One output column from one field of every example:
    ``(array, workers)``."""
    arrs = [np.asarray(e) for e in examples]  # noqa: shardlint - collate
    shape = arrs[0].shape
    for i, a in enumerate(arrs):
        if a.shape != shape:
            raise ValueError(
                'all input arrays must have the same shape: example %d '
                'has shape %s, example 0 has %s' % (i, a.shape, shape))
    kinds = {a.dtype for a in arrs}
    src = kinds.pop() if len(kinds) == 1 else np.result_type(*kinds)
    n = len(arrs)
    out = np.empty((n if pad_to is None else pad_to,) + shape,
                   _ship_dtype(src, dtype))
    workers = _fill(out, arrs, n)
    out[n:] = fill
    return out, workers


def collate(batch, padding=None, dtype=None):
    """:func:`concat_examples` and what it took:
    ``(columns, workers, nbytes)`` -- the most threads a column was
    written by (1: the caller alone) and the bytes of the columns as
    shipped.  ``shard_batch`` puts both on its ``host_batch_prep``
    span."""
    if len(batch) == 0:
        raise ValueError('batch is empty')
    first = batch[0]
    if (isinstance(batch, tuple)
            and all(isinstance(b, np.ndarray) and b.ndim >= 1
                    for b in batch)):
        # already-collated column arrays (batch-level pipelines like
        # datasets.BatchAugmentPipeline produce these directly)
        if padding is not None:
            raise ValueError('padding is only supported for lists of '
                             'examples, not pre-collated arrays')
        cols, workers = [], 1
        for a in batch:
            dt = _ship_dtype(a.dtype, dtype)
            if dt != a.dtype:
                out = np.empty(a.shape, dt)
                workers = max(workers, _fill(out, a, len(a)))
                a = out
            cols.append(a)
        return tuple(cols), workers, sum(c.nbytes for c in cols)
    n = len(batch)
    pad_to, fill = (None, 0) if padding is None else padding
    if pad_to is not None and pad_to < n:
        raise ValueError('pad_to %d < batch size %d' % (pad_to, n))
    if isinstance(first, tuple):
        fields = [[b[i] for b in batch] for i in range(len(first))]
    elif isinstance(first, dict):
        fields = [[b[k] for b in batch] for k in first]
    else:
        fields = [batch]
    built = [_column(f, dtype, pad_to, fill) for f in fields]
    cols = [c for c, _ in built]
    workers = max(w for _, w in built)
    if pad_to is not None:
        mask = np.zeros((pad_to,), np.float32)
        mask[:n] = 1.0
        cols.append(mask)
    nbytes = sum(c.nbytes for c in cols)
    if isinstance(first, dict):
        keys = list(first) + (['mask'] if pad_to is not None else [])
        return dict(zip(keys, cols)), workers, nbytes
    return tuple(cols), workers, nbytes


def concat_examples(batch, padding=None, dtype=None):
    """Collate a list of examples into batched arrays, in one pass.

    Examples may be tuples (``(x, y)`` -> ``(X, Y)``), dicts, or bare
    arrays; shape and dtype are read from the examples and a ragged
    batch raises ``ValueError`` naming the first offending index.
    Each column is allocated once and every example is written
    straight into its row, the rows of a large column split over the
    module's thread pool (see :func:`collate` for what was engaged).
    With ``padding=(pad_to, fill)`` the leading dimension is
    padded to ``pad_to`` (for static-shape jit steps on final partial
    batches; the pad rows are filled in the same buffer) and a float32
    validity ``mask`` of shape ``(pad_to,)`` is appended to the result
    tuple.  ``dtype`` is the dtype floating columns SHIP at (a
    mixed-precision policy's compute dtype): the cast happens in the
    same assignment that collates, bit for bit what ``astype`` of a
    float32 stack gives; integer columns and the validity mask (metric
    averages are kept in f32) are untouched.

    A tuple of arrays is taken as already collated (batch-level
    pipelines produce these): columns that need no cast are returned
    as they are.
    """
    return collate(batch, padding=padding, dtype=dtype)[0]
