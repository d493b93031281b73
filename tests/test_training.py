"""Training machinery tests: iterators, collation, serializers,
snapshot/resume (reference delegates these to Chainer; ours are
standalone so they need their own coverage)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu import serializers
from chainermn_tpu.datasets.mnist import TupleDataset
from chainermn_tpu.models import MLP, Classifier
from chainermn_tpu import training
from chainermn_tpu.training import extensions
from chainermn_tpu.training.convert import concat_examples


def _toy_dataset(n=64):
    rng = np.random.RandomState(0)
    return TupleDataset(rng.randn(n, 8).astype(np.float32),
                        rng.randint(0, 3, n).astype(np.int32))


def test_serial_iterator_epochs():
    it = training.SerialIterator(list(range(10)), 4, shuffle=False)
    seen = []
    for _ in range(5):
        seen.append(it.next())
    assert it.epoch == 2
    assert all(len(b) == 4 for b in seen)  # constant batch size


def test_serial_iterator_no_repeat():
    it = training.SerialIterator(list(range(10)), 4, repeat=False,
                                 shuffle=False)
    batches = list(it)
    assert [len(b) for b in batches] == [4, 4, 2]
    assert it.epoch == 1


def test_multiprocess_iterator_prefetch():
    it = training.iterators.MultiprocessIterator(
        list(range(20)), 5, shuffle=False)
    first = it.next()
    assert len(first) == 5
    for _ in range(3):
        it.next()
    assert it.epoch == 1
    it.finalize()


def test_serial_iterator_restore_position_across_shard_sizes():
    """Elastic resume: the saved GLOBAL epoch fraction lands at the
    equivalent position of a DIFFERENT-length shard, so the epoch
    boundary fires where the interrupted run would have hit it."""
    it = training.SerialIterator(list(range(10)), 2, shuffle=False)
    for _ in range(3):
        next(it)
    assert it.epoch_detail == 0.6
    # resume on a 5-item shard (e.g. 2x the process count)
    it2 = training.SerialIterator(list(range(5)), 1, shuffle=False)
    it2.restore_position(it.epoch_detail)
    assert it2.epoch == 0
    assert it2.epoch_detail == 0.6
    next(it2)
    next(it2)
    assert it2.is_new_epoch and it2.epoch == 1


def test_multiprocess_iterator_restore_position():
    it = training.iterators.MultiprocessIterator(
        list(range(8)), 2, shuffle=False)
    it.restore_position(1.5)
    assert it.epoch == 1
    assert it.epoch_detail == 1.5
    assert len(next(it)) == 2  # still serves batches after rebase
    it.finalize()


def test_concat_examples_padding():
    batch = [(np.ones((3,), np.float32), 1), (np.zeros((3,), np.float32),
                                              2)]
    x, y, mask = concat_examples(batch, padding=(4, 0))
    assert x.shape == (4, 3) and y.shape == (4,)
    np.testing.assert_array_equal(mask, [1, 1, 0, 0])


# ------------------------------------------------ one-pass collation
def _two_pass_collate(batch, padding, dtype):
    """The oracle: ``np.stack``, then ``astype`` of the floating
    columns, then ``np.pad`` -- the passes the one-pass collate
    replaced.  Returns the columns as a list (dict values in order)."""
    first = batch[0]
    if isinstance(batch, tuple):
        cols = list(batch)
    elif isinstance(first, tuple):
        cols = [np.stack([np.asarray(b[i]) for b in batch])
                for i in range(len(first))]
    elif isinstance(first, dict):
        cols = [np.stack([np.asarray(b[k]) for b in batch])
                for k in first]
    else:
        cols = [np.stack([np.asarray(b) for b in batch])]
    if dtype is not None:
        cols = [c.astype(dtype) if np.issubdtype(c.dtype, np.floating)
                else c for c in cols]
    if padding is not None:
        pad_to, fill = padding
        n = len(batch)
        cols = [np.pad(c, [(0, pad_to - n)] + [(0, 0)] * (c.ndim - 1),
                       constant_values=fill) for c in cols]
        mask = np.zeros((pad_to,), np.float32)
        mask[:n] = 1.0
        cols.append(mask)
    return cols


def _collate_examples(form, n=6):
    """Examples with a non-contiguous float32 image, a 0-d int32
    label and a contiguous float32 vector, in the asked form."""
    rng = np.random.RandomState(7)
    wide = rng.randn(n, 5, 8, 3).astype(np.float32) * 300.0
    images = [wide[i, :, ::2] for i in range(n)]      # strided views
    assert not images[0].flags['C_CONTIGUOUS']
    labels = [np.int32(v) for v in rng.randint(0, 9, n)]
    vecs = [rng.rand(4).astype(np.float32) for _ in range(n)]
    if form == 'tuple':
        return list(zip(images, labels, vecs))
    if form == 'dict':
        return [{'x': x, 'y': y, 'v': v}
                for x, y, v in zip(images, labels, vecs)]
    if form == 'bare':
        return images
    assert form == 'collated'
    return (np.stack(images), np.asarray(labels), np.stack(vecs))


@pytest.fixture
def pooled_collate(monkeypatch):
    """Every column of more than a few bytes takes the pool: the
    threshold is a module constant (no argument), patched here."""
    from chainermn_tpu.training import convert
    monkeypatch.setattr(convert, '_TASK_MIN_BYTES', 8)
    monkeypatch.setattr(convert, '_CORES', 8)


@pytest.mark.parametrize('pooled', [False, True])
@pytest.mark.parametrize('padding', [None, (9, -1.5)])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float16', None])
@pytest.mark.parametrize('form', ['tuple', 'dict', 'bare', 'collated'])
def test_collate_one_pass_matches_stack_then_astype(
        form, dtype, padding, pooled, request):
    """Bit for bit what ``np.stack`` -> ``astype`` -> ``np.pad`` gave,
    whoever writes the rows; integer labels and the float32 mask are
    untouched."""
    from chainermn_tpu.training import convert
    if pooled:
        request.getfixturevalue('pooled_collate')
    batch = _collate_examples(form)
    if form == 'collated' and padding is not None:
        with pytest.raises(ValueError, match='pre-collated'):
            concat_examples(batch, padding=padding, dtype=dtype)
        return
    want = _two_pass_collate(batch, padding, dtype)
    got, workers, nbytes = convert.collate(batch, padding=padding,
                                           dtype=dtype)
    if form == 'dict':
        assert list(got) == ['x', 'y', 'v'] + (
            ['mask'] if padding else [])
        got = list(got.values())
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    assert nbytes == sum(w.nbytes for w in want)
    casts = dtype is not None
    assert (workers > 1) == (pooled and (form != 'collated' or casts))
    if form != 'bare':
        assert got[1].dtype == np.int32
    if form == 'collated' and not casts:
        assert all(g is b for g, b in zip(got, batch))


@pytest.mark.parametrize('case', [
    'python_scalars', 'zero_d_only', 'mixed_dtypes', 'transposed',
    'single_example', 'pad_to_equals_n'])
def test_collate_edge_examples(case):
    rng = np.random.RandomState(3)
    padding = None
    if case == 'python_scalars':
        batch = [(rng.rand(3).astype(np.float32), 1, 2.5),
                 (rng.rand(3).astype(np.float32), 2, 3.5)]
    elif case == 'zero_d_only':
        batch = [np.float32(v) for v in rng.rand(5)]
    elif case == 'mixed_dtypes':    # np.stack promotes: so do we
        batch = [(np.int32(1),), (np.float64(2.5),), (np.int8(3),)]
    elif case == 'transposed':
        batch = [rng.rand(4, 6).astype(np.float32).T for _ in range(3)]
    elif case == 'single_example':
        batch = [(rng.rand(2, 2).astype(np.float32), np.int32(4))]
    else:
        batch = [(rng.rand(3).astype(np.float32), i) for i in range(4)]
        padding = (4, 0)
    for dtype in (None, 'bfloat16'):
        want = _two_pass_collate(batch, padding, dtype)
        got = concat_examples(batch, padding=padding, dtype=dtype)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize('pooled', [False, True])
@pytest.mark.parametrize('bad', [1, 5])
def test_collate_ragged_batch_names_the_index(bad, pooled, request):
    if pooled:
        request.getfixturevalue('pooled_collate')
    batch = [(np.zeros((4, 3), np.float32), i) for i in range(6)]
    batch[bad] = (np.zeros((4, 2), np.float32), bad)
    with pytest.raises(ValueError, match='example %d ' % bad):
        concat_examples(batch)
    with pytest.raises(ValueError, match='same shape'):
        np.stack([b[0] for b in batch])     # what it replaced raised


def test_collate_errors_kept():
    with pytest.raises(ValueError, match='empty'):
        concat_examples([])
    with pytest.raises(ValueError, match='pad_to 1 < batch size 2'):
        concat_examples([np.zeros(2), np.zeros(2)], padding=(1, 0))


def test_collate_worker_exception_reaches_the_caller(
        pooled_collate, monkeypatch):
    from chainermn_tpu.training import convert
    write_rows = convert._write_rows

    def failing(out, src, lo, hi):
        if lo == 0:          # the first range is a pool worker's
            raise RuntimeError('rows %d..%d' % (lo, hi))
        write_rows(out, src, lo, hi)

    monkeypatch.setattr(convert, '_write_rows', failing)
    with pytest.raises(RuntimeError, match='rows 0'):
        concat_examples(_collate_examples('tuple'))


@pytest.mark.parametrize('pooled', [False, True])
def test_shard_batch_span_says_how_wide_the_collate_was(
        pooled, request):
    """``cmn:host_batch_prep`` carries ``collate_workers`` (1: the
    caller alone, as every small batch must be) and
    ``collate_bytes``."""
    from chainermn_tpu import telemetry
    if pooled:
        request.getfixturevalue('pooled_collate')
    upd = _prefetch_updater(0)
    batch = [upd.iterator.dataset[i] for i in range(32)]
    telemetry.disable()
    rec = telemetry.enable()  # in-memory
    try:
        upd.shard_batch(batch)
        spans = [r for r in rec.events
                 if r.get('name') == 'host_batch_prep']
    finally:
        telemetry.disable()
    assert len(spans) == 1
    assert spans[0]['collate_bytes'] == 32 * 8 * 4 + 32 * 4
    if pooled:
        assert 1 < spans[0]['collate_workers'] <= 4
    else:
        assert spans[0]['collate_workers'] == 1


def test_collate_width_follows_bytes_rows_and_cores(monkeypatch):
    """No argument sets the width: it is the column's bytes over what
    a worker must have to itself, under half the cores, 8 and the
    rows.  The LM cells' int32 columns (8 and 32 rows of 1,024) stay
    with the caller."""
    from chainermn_tpu.training import convert
    monkeypatch.setattr(convert, '_CORES', 13)
    task = convert._TASK_MIN_BYTES
    assert convert._workers_for(8 * 1024 * 4, 8) == 1
    assert convert._workers_for(32 * 1024 * 4, 32) == 1
    assert convert._workers_for(2 * task - 1, 256) == 1
    assert convert._workers_for(2 * task, 256) == 2
    assert convert._workers_for(256 * 224 * 224 * 3 * 2, 256) == 6
    assert convert._workers_for(1 << 30, 3) == 3
    monkeypatch.setattr(convert, '_CORES', 64)
    assert convert._workers_for(1 << 30, 256) == 8
    monkeypatch.setattr(convert, '_CORES', 1)
    assert convert._workers_for(1 << 30, 256) == 1


def test_two_threads_collating_at_once_get_their_own_batches(
        pooled_collate):
    """``DevicePrefetchIterator``'s thread and a caller's own may be
    in ``shard_batch`` together: the pool is shared, the buffers are
    not."""
    import sys
    import threading
    upd = _prefetch_updater(0)
    ds = upd.iterator.dataset
    batches = [[ds[i] for i in range(32)],
               [ds[i] for i in range(32, 64)]]
    want = [_two_pass_collate(b, None, None) for b in batches]
    wrong, errors = [], []

    def hammer(k):
        try:
            for _ in range(40):
                got = upd.shard_batch(batches[k])
                for g, w in zip(got, want[k]):
                    if not np.array_equal(np.asarray(g), w):
                        wrong.append(k)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=hammer, args=(k % 2,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong


def test_serializers_roundtrip(tmp_path):
    tree = {'a': jnp.arange(6.).reshape(2, 3),
            'nested': {'b': jnp.ones((4,), jnp.bfloat16)}, 'step': 7}
    path = serializers.save_npz(str(tmp_path / 'ckpt'), tree)
    loaded = serializers.load_npz(path, tree)
    np.testing.assert_array_equal(np.asarray(loaded['a']),
                                  np.asarray(tree['a']))
    assert loaded['nested']['b'].dtype == jnp.bfloat16
    # template mismatch raises the TYPED error (a ValueError
    # subclass) naming the offending leaf path
    from chainermn_tpu.utils import failure
    bad = {'a': jnp.zeros((3, 2)), 'nested': {'b': jnp.ones((4,))},
           'step': 0}
    with pytest.raises(failure.CheckpointCorruptError) as ei:
        serializers.load_npz(path, bad)
    assert ei.value.leaf == 'a' and ei.value.kind == 'shape'
    assert isinstance(ei.value, ValueError)


def _small_trainer(tmp_path, n_epoch=1):
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    ds = _toy_dataset()
    model = MLP(n_units=16, n_out=3)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    clf = Classifier(model.apply)
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    it = training.SerialIterator(ds, 16)
    upd = training.StandardUpdater(it, opt, clf, params, comm,
                                   has_aux=True)
    tr = training.Trainer(upd, (n_epoch, 'epoch'), out=str(tmp_path))
    return tr, upd


def test_snapshot_and_resume(tmp_path):
    tr, upd = _small_trainer(tmp_path, n_epoch=2)
    tr.extend(extensions.snapshot(), trigger=(1, 'epoch'))
    tr.run()
    snaps = sorted(glob.glob(os.path.join(str(tmp_path), 'snapshot_*')))
    assert snaps, 'no snapshot written'
    template = {'params': upd.params, 'opt_state': upd.opt_state,
                'iteration': 0, 'epoch': 0}
    state = serializers.load_npz(snaps[-1], template)
    assert int(state['iteration']) == upd.iteration
    # params in snapshot match live params
    live = jax.tree_util.tree_leaves(upd.params)
    saved = jax.tree_util.tree_leaves(state['params'])
    for a, b in zip(live, saved):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)


def test_trainer_iteration_trigger(tmp_path):
    tr, upd = _small_trainer(tmp_path)
    fired = []
    tr.extend(lambda t: fired.append(t.updater.iteration),
              trigger=(2, 'iteration'), name='probe')
    tr.run()
    assert fired == [2, 4]  # 64/16 = 4 iterations per epoch


def test_iteration_stop_trigger_runs(tmp_path):
    """A (N, 'iteration') stop trigger must not fire at iteration 0."""
    tr, upd = _small_trainer(tmp_path)
    tr.stop_trigger = training.triggers.get_trigger((3, 'iteration'))
    tr.run()
    assert upd.iteration == 3


def test_trainer_finalizes_extensions(tmp_path):
    """ISSUE 9: extensions with a ``finalize`` are torn down when the
    run ends -- normally AND when the loop raises (the
    heartbeat_extension daemon-thread-leak fix rides this hook)."""
    tr, upd = _small_trainer(tmp_path)
    done = []

    def probe(t):
        pass
    probe.finalize = lambda: done.append('probe')
    tr.extend(probe, trigger=(1, 'iteration'), name='probe')
    tr.run()
    assert done == ['probe']

    tr2, _ = _small_trainer(tmp_path)
    tr2.extend(probe, trigger=(1, 'iteration'), name='probe')

    def boom(t):
        raise RuntimeError('loop died')
    tr2.extend(boom, trigger=(2, 'iteration'), name='boom')
    done.clear()
    with pytest.raises(RuntimeError):
        tr2.run()
    assert done == ['probe']  # finalized despite the crash


def test_log_report_averages(tmp_path):
    tr, upd = _small_trainer(tmp_path, n_epoch=1)
    log = extensions.LogReport()
    tr.extend(log)
    tr.run()
    # 4 iterations/epoch accumulated into one entry: the logged loss is
    # the mean, not the last batch's value
    assert len(log.log) == 1
    per_iter = []

    tr2, upd2 = _small_trainer(tmp_path, n_epoch=1)
    tr2.extend(lambda t: per_iter.append(t.observation['loss']),
               trigger=(1, 'iteration'), name='probe', priority=500)
    tr2.run()
    assert log.log[0]['loss'] == pytest.approx(
        sum(per_iter) / len(per_iter), rel=1e-6)


def test_async_metrics_trainer_matches_sync(tmp_path):
    """Trainer(async_metrics=True) must produce the SAME logged means
    as the blocking path -- metrics stay device-resident between
    LogReport emits, accumulate on device, and are fetched lazily."""
    tr, upd = _small_trainer(tmp_path, n_epoch=2)
    log = extensions.LogReport()
    tr.extend(log)
    tr.run()

    tr2, upd2 = _small_trainer(tmp_path, n_epoch=2)
    tr2._async = True  # what Trainer(async_metrics=True) sets
    tr2._sync_interval = 2
    log2 = extensions.LogReport()
    tr2.extend(log2)
    seen_kinds = []
    tr2.extend(lambda t: seen_kinds.append(
        getattr(t.observation.get('loss'), 'ndim', None)),
        trigger=(1, 'iteration'), name='probe', priority=500)
    tr2.run()

    # during the run the loss is a device array (ndim 0), not a float
    assert all(k == 0 for k in seen_kinds) and seen_kinds
    assert len(log.log) == len(log2.log) == 2
    for a, b in zip(log.log, log2.log):
        assert a['loss'] == pytest.approx(b['loss'], rel=1e-6)
        assert a['accuracy'] == pytest.approx(b['accuracy'], rel=1e-6)


def test_multiprocess_iterator_reset_reuse():
    it = training.iterators.MultiprocessIterator(
        list(range(10)), 4, repeat=False, shuffle=False)
    first_pass = list(it)
    it.reset()
    second_pass = list(it)
    assert [len(b) for b in first_pass] == [len(b) for b in second_pass] \
        == [4, 4, 2]
    it.finalize()


def test_resume_updater_restores_counters(tmp_path):
    tr, upd = _small_trainer(tmp_path, n_epoch=2)
    tr.extend(extensions.snapshot(), trigger=(1, 'epoch'))
    tr.run()
    snaps = sorted(glob.glob(os.path.join(str(tmp_path), 'snapshot_*')))

    tr2, upd2 = _small_trainer(tmp_path, n_epoch=2)
    from chainermn_tpu import serializers
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    serializers.resume_updater(snaps[-1], upd2, comm)
    assert upd2.iteration == upd.iteration
    assert upd2.epoch == upd.epoch
    for a, b in zip(jax.tree_util.tree_leaves(upd2.params),
                    jax.tree_util.tree_leaves(upd.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)


def test_updater_batch_divisibility(tmp_path):
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    ds = _toy_dataset(30)
    model = MLP(n_units=16, n_out=3)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    clf = Classifier(model.apply)
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    it = training.SerialIterator(ds, 15)  # 15 % 8 != 0
    upd = training.StandardUpdater(it, opt, clf, params, comm,
                                   has_aux=True)
    with pytest.raises(ValueError):
        upd.update()


@pytest.mark.parametrize('device_prefetch', [0, 2])
def test_dropped_updater_frees_its_state_without_a_collection(
        device_prefetch):
    """The updater owns the parameters and the optimizer state on the
    device; dropping the last reference gives them back at once, by
    reference count, also where the caller stored wrappers of the
    updater's own methods on it (a profiler shim does).  The cyclic
    collector is off: a process full of long-lived objects puts a full
    collection off past the next thing that needs the memory."""
    import gc
    import weakref

    def spanned(fn):
        return lambda *a, **kw: fn(*a, **kw)

    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    model = MLP(n_units=16, n_out=3)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-3),
                                                    comm)
    gc.collect()
    gc.disable()
    try:
        upd = training.StandardUpdater(
            training.SerialIterator(_toy_dataset(64), 16), opt,
            Classifier(model.apply), params, comm, has_aux=True,
            device_prefetch=device_prefetch)
        upd.shard_batch = spanned(upd.shard_batch)
        upd.update_core = spanned(upd.update_core)
        upd.update()
        upd.update()
        assert upd.trace_count == 1
        state = [weakref.ref(leaf) for leaf in
                 jax.tree_util.tree_leaves((upd.params, upd.opt_state))]
        me = weakref.ref(upd)
        finalize = getattr(upd.iterator, 'finalize', None)
        if finalize is not None:
            finalize()
            # finalize() raises the stop flag and does not wait: on a
            # busy machine the producer can still be inside
            # shard_batch, its frame holding the updater
            upd.iterator._thread.join(timeout=30)
        del upd, finalize
        assert me() is None
        assert all(ref() is None for ref in state)
    finally:
        gc.enable()


def test_orbax_sharded_checkpoint(tmp_path):
    """Sharded checkpoint via orbax (the rank-aware snapshot path
    SURVEY 5 flags as the reference's gap)."""
    import warnings
    import jax.numpy as jnp
    from chainermn_tpu import serializers
    tree = {'a': jnp.arange(8.0),
            'b': {'c': jnp.ones((2, 3), jnp.bfloat16)}}
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        serializers.save_checkpoint(str(tmp_path / 'ckpt'), tree, step=3)
        back = serializers.restore_checkpoint(str(tmp_path / 'ckpt'),
                                              tree, step=3)
    np.testing.assert_allclose(back['a'], tree['a'])
    assert back['b']['c'].dtype == jnp.bfloat16


def test_orbax_async_checkpoint(tmp_path):
    """async_=True returns before the write commits; restore joins the
    in-flight write (wait_checkpoints) and reads back the same tree."""
    import warnings
    import jax.numpy as jnp
    from chainermn_tpu import serializers
    tree = {'w': jnp.arange(16.0).reshape(4, 4),
            's': jnp.float32(7.0)}
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        serializers.save_checkpoint(str(tmp_path / 'ck'), tree, step=1,
                                    async_=True)
        # immediate restore must see the committed write, not a
        # partial directory
        back = serializers.restore_checkpoint(str(tmp_path / 'ck'),
                                              tree, step=1)
    np.testing.assert_allclose(back['w'], tree['w'])
    assert float(back['s']) == 7.0


def test_gradient_accumulation_matches_full_batch():
    """accum_steps=k with the same global batch must match the k=1
    trajectory (SGD is linear in the gradient mean)."""
    from chainermn_tpu.models import MLP, classifier_loss
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    rng = np.random.RandomState(1)
    x = rng.rand(32, 5).astype(np.float32)
    y = (x.sum(axis=1) > 2.5).astype(np.int32)
    ds = list(zip(x, y))
    model = MLP(n_units=16, n_out=2)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 5)))['params']
    loss_fn = classifier_loss(
        lambda p, xb: model.apply({'params': p}, xb))

    def run(accum):
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm)
        it = training.SerialIterator(ds, 32, shuffle=False)
        upd = training.StandardUpdater(it, opt, loss_fn, params, comm,
                                       has_aux=True, accum_steps=accum)
        return [upd.update()['loss'] for _ in range(3)], upd.params

    losses1, p1 = run(1)
    losses2, p2 = run(2)
    np.testing.assert_allclose(losses1, losses2, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_pipeline_iterator_with_updater():
    """PipelineIterator yields pre-collated arrays straight through
    concat_examples into the jitted step."""
    from chainermn_tpu.datasets.imagenet import (
        BatchAugmentPipeline, SyntheticImageNet)
    from chainermn_tpu.models import MLP, classifier_loss
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(1, 8))
    base = SyntheticImageNet(n=32, size=12, n_classes=4)
    pipe = BatchAugmentPipeline(base, crop_size=8)
    it = training.PipelineIterator(pipe, 16)
    model = MLP(n_units=8, n_out=4)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8 * 8 * 3)))['params']
    loss_fn = classifier_loss(
        lambda p, xb: model.apply({'params': p},
                                  xb.reshape(xb.shape[0], -1)))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    upd = training.StandardUpdater(it, opt, loss_fn, params, comm,
                                   has_aux=True)
    m = upd.update()
    m = upd.update()
    assert np.isfinite(m['loss'])
    assert it.epoch == 1  # 32 samples / batch 16 -> 2 iterations


def test_batch_pipeline_uint8_store():
    """uint8-backed datasets stay uint8 in the preload store (4x
    smaller; ADVICE r1) and produce the same batches as float32."""
    from chainermn_tpu.datasets.imagenet import BatchAugmentPipeline

    class U8Set:
        def __len__(self):
            return 6

        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            return (rng.randint(0, 255, (12, 12, 3)).astype(np.uint8),
                    np.int32(i % 3))

    class F32Set(U8Set):
        def __getitem__(self, i):
            img, label = U8Set.__getitem__(self, i)
            return img.astype(np.float32), label

    mean = np.full((12, 12, 3), 100.0, np.float32)
    pu = BatchAugmentPipeline(U8Set(), crop_size=8, mean=mean, seed=3)
    pf = BatchAugmentPipeline(F32Set(), crop_size=8, mean=mean, seed=3)
    assert pu._store.dtype == np.uint8
    assert pf._store.dtype == np.float32
    iu, lu = pu.batch([0, 2, 5, 1])
    if_, lf = pf.batch([0, 2, 5, 1])
    assert iu.dtype == np.float32
    np.testing.assert_allclose(iu, if_, atol=1e-5)
    np.testing.assert_array_equal(lu, lf)


def _prefetch_updater(device_prefetch):
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    ds = _toy_dataset(64)
    model = MLP(n_units=8, n_out=3)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.float32))
    clf = Classifier(model.apply)
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm)
    it = training.SerialIterator(ds, 32, shuffle=False)
    return training.StandardUpdater(
        it, opt, clf, params, comm, has_aux=True,
        device_prefetch=device_prefetch)


@pytest.mark.parametrize('pooled', [False, True])
def test_device_prefetch_matches_unprefetched(pooled, request):
    """device_prefetch=N must be a pure latency optimization: same
    batches in the same order, identical trajectory, and epoch
    accounting that reflects CONSUMED batches (not the worker's
    read-ahead) -- with the collate on the caller alone and with its
    rows over the pool."""
    if pooled:
        request.getfixturevalue('pooled_collate')
    upd_ref = _prefetch_updater(0)
    upd_pre = _prefetch_updater(2)
    # worker reads ahead immediately; the consumer has taken nothing,
    # so consumer-visible accounting must still be at zero
    assert upd_pre.epoch == 0
    assert upd_pre.epoch_detail == 0.0
    for i in range(6):  # 2 batches/epoch: crosses epoch boundaries
        m_ref = upd_ref.update()
        m_pre = upd_pre.update()
        assert abs(m_ref['loss'] - m_pre['loss']) < 1e-6, \
            (i, m_ref, m_pre)
        assert upd_pre.epoch == upd_ref.epoch, i
        assert upd_pre.is_new_epoch == upd_ref.is_new_epoch, i
        assert abs(upd_pre.epoch_detail - upd_ref.epoch_detail) < 1e-9
    for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(upd_ref.params)),
            jax.tree_util.tree_leaves(jax.device_get(upd_pre.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_prefetch_places_on_mesh():
    """The prefetched trees are already device-resident with the
    batch sharding (that is the point: the transfer happened behind
    the previous step)."""
    upd = _prefetch_updater(2)
    arrays = next(upd.iterator)
    ref = upd.shard_batch([upd.iterator.inner.dataset[i]
                           for i in range(32)])
    for got, want in zip(arrays, ref):
        assert got.sharding == want.sharding
        assert got.shape == want.shape


def test_device_prefetch_propagates_worker_errors():
    from chainermn_tpu.training import DevicePrefetchIterator

    def boom(_batch):
        raise RuntimeError('collate failed')

    it = DevicePrefetchIterator(
        training.SerialIterator(_toy_dataset(8), 4), boom, depth=1)
    with pytest.raises(RuntimeError, match='collate failed'):
        next(it)
    with pytest.raises(ValueError, match='depth'):
        DevicePrefetchIterator(
            training.SerialIterator(_toy_dataset(8), 4),
            lambda b: b, depth=0)


def test_prefetch_iterators_reraise_after_exhaustion():
    """Iterator protocol: next() after the terminal StopIteration (or
    a worker error) must re-raise, not deadlock on the dead worker's
    empty queue."""
    from chainermn_tpu.training import DevicePrefetchIterator

    it = training.iterators.MultiprocessIterator(
        _toy_dataset(8), 4, repeat=False, shuffle=False)
    assert len(list(it)) == 2
    with pytest.raises(StopIteration):
        next(it)  # second terminal call: must not hang
    it.reset()
    assert len(list(it)) == 2

    dit = DevicePrefetchIterator(
        training.SerialIterator(_toy_dataset(8), 4, repeat=False,
                                shuffle=False),
        lambda b: b, depth=1)
    assert len(list(dit)) == 2
    with pytest.raises(StopIteration):
        next(dit)

    def boom(_b):
        raise RuntimeError('collate failed')

    bad = DevicePrefetchIterator(
        training.SerialIterator(_toy_dataset(8), 4), boom, depth=1)
    for _ in range(2):  # error is sticky, not a hang
        with pytest.raises(RuntimeError, match='collate failed'):
            next(bad)


def test_device_prefetch_finalize_propagates():
    """The documented composition (device wrapper over the host-side
    MultiprocessIterator) must not leak the inner worker thread on
    finalize."""
    from chainermn_tpu.training import DevicePrefetchIterator

    inner = training.iterators.MultiprocessIterator(
        _toy_dataset(16), 4, n_prefetch=2)
    outer = DevicePrefetchIterator(inner, lambda b: b, depth=1)
    next(outer)
    outer.finalize()
    assert inner._stop.is_set()
    inner._thread.join(timeout=5)
    assert not inner._thread.is_alive()


def test_device_prefetch_reset_reuse():
    """reset() restarts a repeat=False prefetched pass (the Evaluator
    usage pattern) with consumer counters rebased."""
    from chainermn_tpu.training import DevicePrefetchIterator

    it = DevicePrefetchIterator(
        training.SerialIterator(_toy_dataset(8), 4, repeat=False,
                                shuffle=False),
        lambda b: b, depth=1)
    first = [len(b) for b in it]
    it.reset()
    assert it.epoch == 0 and it.epoch_detail == 0.0
    second = [len(b) for b in it]
    assert first == second == [4, 4]


def test_device_prefetch_composes_with_zero():
    """device_prefetch and zero=True cross paths in update():
    prefetched (already-placed) arrays must feed the ZeRO step with
    its needs_bcast plumbing intact."""
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    ds = _toy_dataset(64)
    model = MLP(n_units=9, n_out=3)  # odd size: shard padding
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.float32))
    clf = Classifier(model.apply)
    it = training.SerialIterator(ds, 32, shuffle=False)
    upd = training.StandardUpdater(
        it, optax.adam(1e-2), clf, params, comm, has_aux=True,
        zero=True, device_prefetch=2)
    # 6 steps: the first is the broadcast-only sync, and adam needs a
    # few real updates before the loss durably dips under its start
    losses = [upd.update()['loss'] for _ in range(6)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
