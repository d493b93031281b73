#!/usr/bin/env python
"""Collation microbench: what one batch costs the HOST before it ships.

The shape is ``resnet50-train-b256``'s: 256 per-example float32
``(224, 224, 3)`` arrays, each its own allocation, with an int32 label
each, out of a dataset of 1,024 (616 MB: a batch's examples are never
in cache when their turn comes, as in the cell).  Host only: nothing
here touches JAX or a device, so it runs on a chip's host without
taking the chip.

Stages, each in a child process under its own ``timeout`` (a stage that
hangs costs its limit and no more), one JSON line per variant:

``parent``
    the two passes the input path made until PR 32: ``np.stack`` into a
    float32 batch, then ``astype`` to the shipped dtype.
``onepass``
    one pass, each example assigned into its row of an ``np.empty``
    batch (numpy casts in the assignment), to bfloat16 and to float32,
    the rows split over 1 / 2 / 4 / 6 / 8 threads, with a fresh ``np.empty``
    a batch and into a batch allocated once.
``gil``
    does the float32 -> bfloat16 assignment hold the interpreter lock?
    A Python counter runs beside it in the main thread; its rate beside
    the cast over its rate alone (about a half or less: held, the two
    threads take turns; ~1: released).
``repo``
    ``chainermn_tpu.training.convert.concat_examples`` as the tree has
    it, on the same batches: the path the trainer runs.  Then the
    batch that must NOT take the pool, ``gpt2m-train-1k``'s: 8 rows of
    two int32 columns of 1,024 (64 KB), and what one
    ``os.cpu_count()`` costs there.

Usage::

    python benchmarks/collate_bench.py              # every stage
    python benchmarks/collate_bench.py --stage repo # one, in-process
    python benchmarks/collate_bench.py --rows 32    # a smaller batch

Run whole, every line also goes to ``chiprun_out/collate_bench.jsonl``
under the checkout (the chip tool brings that directory back).
"""

import argparse
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import timeit
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes  # noqa: F401 - registers bfloat16 with numpy
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ('parent', 'onepass', 'gil', 'repo')
#: seconds a stage may take before it is cut
STAGE_TIMEOUT = 240
EXAMPLE_SHAPE = (224, 224, 3)
DATASET_BATCHES = 4
REPS = 6
THREADS = (1, 2, 4, 6, 8)


def make_dataset(rows):
    """``DATASET_BATCHES`` batches of ``rows`` ``(image, label)``
    examples, every image its own allocation."""
    rng = np.random.default_rng(32)
    return [[(rng.random(EXAMPLE_SHAPE, dtype=np.float32),
              np.int32(rng.integers(0, 1000)))
             for _ in range(rows)]
            for _ in range(DATASET_BATCHES)]


def timed(fn, dataset):
    """ms of ``fn(batch)`` over ``REPS`` turns through the dataset:
    ``(best, median)``."""
    ms = []
    for rep in range(REPS + 1):
        batch = dataset[rep % len(dataset)]
        t0 = time.perf_counter()
        fn(batch)
        ms.append((time.perf_counter() - t0) * 1e3)
    ms = sorted(ms[1:])     # the first turn warms the allocator
    return ms[0], ms[len(ms) // 2]


def emit(stage, variant, best, median, **more):
    line = dict(stage=stage, variant=variant, best_ms=round(best, 4),
                median_ms=round(median, 4), cpu_count=os.cpu_count(),
                **more)
    print(json.dumps(line), flush=True)


def stage_parent(dataset):
    def stack(batch):
        return np.stack([np.asarray(b[0]) for b in batch])

    emit('parent', 'np.stack float32', *timed(stack, dataset))
    x = stack(dataset[0])
    for name in ('bfloat16', 'float16'):
        emit('parent', 'astype ' + name,
             *timed(lambda _batch: x.astype(name), dataset))
    emit('parent', 'np.stack + astype bfloat16',
         *timed(lambda batch: stack(batch).astype('bfloat16'), dataset))


def fill_rows(out, batch, lo, hi):
    for i in range(lo, hi):
        out[i] = batch[i][0]


def stage_onepass(dataset):
    rows = len(dataset[0])
    for name in ('bfloat16', 'float32'):
        dt = np.dtype(name)
        kept = np.empty((rows,) + EXAMPLE_SHAPE, dt)
        for threads in THREADS:
            pool = ThreadPoolExecutor(threads) if threads > 1 else None
            bounds = [rows * k // threads for k in range(threads + 1)]
            for fresh in (True, False):
                def collate(batch):
                    out = (np.empty((rows,) + EXAMPLE_SHAPE, dt)
                           if fresh else kept)
                    if pool is None:
                        fill_rows(out, batch, 0, rows)
                        return
                    futures = [pool.submit(fill_rows, out, batch, lo, hi)
                               for lo, hi in zip(bounds, bounds[1:])]
                    for f in futures:
                        f.result()

                emit('onepass', 'assign into %s, %d thread%s, %s'
                     % (name, threads, '' if threads == 1 else 's',
                        'fresh np.empty' if fresh else 'kept buffer'),
                     *timed(collate, dataset), threads=threads,
                     dtype=name, fresh=fresh)
            if pool is not None:
                pool.shutdown()


def stage_gil(dataset):
    """A Python counter beside the cast: the share of its own rate it
    keeps says whether the cast's loop lets the lock go."""
    rows = len(dataset[0])

    def count_for(seconds):
        n, end = 0, time.perf_counter() + seconds
        while time.perf_counter() < end:
            n += 1
        return n / seconds

    alone = count_for(0.5)
    for name in ('bfloat16', 'float32'):
        out = np.empty((rows,) + EXAMPLE_SHAPE, np.dtype(name))
        stop = threading.Event()

        def cast_loop():
            while not stop.is_set():
                fill_rows(out, dataset[0], 0, rows)

        worker = threading.Thread(target=cast_loop, daemon=True)
        worker.start()
        time.sleep(0.05)
        beside = count_for(0.5)
        stop.set()
        worker.join(timeout=30)
        line = dict(stage='gil', variant='counter beside assign into '
                    + name, counter_rate_alone=round(alone),
                    counter_rate_beside=round(beside),
                    share_kept=round(beside / alone, 3),
                    cpu_count=os.cpu_count())
        print(json.dumps(line), flush=True)


def stage_repo(dataset):
    """The tree's own collate, loaded from its file so that the stage
    imports neither JAX nor the package around it."""
    spec = importlib.util.spec_from_file_location(
        'convert', os.path.join(ROOT, 'chainermn_tpu', 'training',
                                'convert.py'))
    convert = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(convert)
    collate = getattr(convert, 'collate', None)
    for name in ('bfloat16', None):
        kwargs = {} if name is None else {'dtype': name}
        more = {}
        if collate is not None:
            _, more['collate_workers'], more['collate_bytes'] = collate(
                dataset[0], **kwargs)
        emit('repo', 'concat_examples(batch%s)'
             % ('' if name is None else ", dtype='%s'" % name),
             *timed(lambda batch: convert.concat_examples(batch, **kwargs),
                    dataset), **more)
    tokens = np.random.default_rng(32).integers(
        0, 50257, size=(8, 1025)).astype(np.int32)
    lm_batch = [(r[:-1], r[1:]) for r in tokens]
    for variant, fn in (
            ("concat_examples(8 x 2 x int32[1024], dtype='bfloat16')",
             lambda: convert.concat_examples(lm_batch, dtype='bfloat16')),
            ('os.cpu_count()', os.cpu_count)):
        ms = sorted(t / 200 * 1e3
                    for t in timeit.repeat(fn, number=200, repeat=5))
        emit('repo', variant, ms[0], ms[2])


def main():
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--stage', choices=STAGES,
                   help='run one stage in this process')
    p.add_argument('--rows', type=int, default=256)
    args = p.parse_args()
    if args.stage:
        globals()['stage_' + args.stage](make_dataset(args.rows))
        return 0
    out_dir = os.path.join(ROOT, 'chiprun_out')
    os.makedirs(out_dir, exist_ok=True)
    rc = 0
    with open(os.path.join(out_dir, 'collate_bench.jsonl'), 'a') as log:
        for stage in STAGES:
            try:
                done = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     '--stage', stage, '--rows', str(args.rows)],
                    capture_output=True, text=True, timeout=STAGE_TIMEOUT)
                text, failed = done.stdout, done.returncode != 0
                if failed:
                    sys.stderr.write(done.stderr[-2000:])
            except subprocess.TimeoutExpired as e:
                # what the stage printed before it was cut (bytes here,
                # whatever ``text=`` says)
                text = e.stdout.decode() if e.stdout else ''
                text += json.dumps({'stage': stage, 'timeout_s':
                                    STAGE_TIMEOUT}) + '\n'
                failed = True
            sys.stdout.write(text)
            sys.stdout.flush()
            log.write(text)
            rc = rc or int(failed)
    return rc


if __name__ == '__main__':
    sys.exit(main())
