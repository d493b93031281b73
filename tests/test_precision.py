"""Mixed-precision policy suite (``chainermn_tpu.precision``).

Pins the ISSUE 2 acceptance criteria on the 8-device CPU mesh: policy
casting round-trips, dynamic loss-scale step/unscale/skip-on-nonfinite
semantics, bf16-vs-f32 end-to-end loss agreement on the mlp example
(with gradients PROVEN to reduce in bf16 from the step's jaxpr, master
weights pinned f32), and the reduce-dtype sweep across every
registered communicator strategy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu import precision, training
from chainermn_tpu.analysis import walker
from chainermn_tpu.communicators import _COMMUNICATORS
from chainermn_tpu.models import MLP, Classifier
from chainermn_tpu.training.convert import concat_examples


# ------------------------------------------------------------- Policy
def test_policy_cast_round_trip():
    pol = precision.Policy.bf16()
    tree = {'w': jnp.ones((3, 2), jnp.float32),
            'idx': jnp.arange(3, dtype=jnp.int32)}
    comp = pol.cast_to_compute(tree)
    assert comp['w'].dtype == jnp.bfloat16
    assert comp['idx'].dtype == jnp.int32  # ints untouched
    back = pol.cast_to_param(comp)
    assert back['w'].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(back['w']), 1.0)


def test_policy_registry():
    assert precision.Policy.from_string('bf16') == \
        precision.Policy.bf16()
    assert precision.Policy.from_string('f32') == precision.Policy()
    f16 = precision.Policy.from_string('float16')
    assert f16.compute_dtype == jnp.float16
    assert isinstance(f16.loss_scale, precision.DynamicLossScale)
    with pytest.raises(ValueError):
        precision.Policy.from_string('int8')


def test_policy_declared_dtypes():
    assert precision.Policy.bf16().declared_dtypes() == {'bfloat16'}
    assert precision.Policy().declared_dtypes() == {'float32'}


def test_all_finite():
    assert bool(precision.all_finite(
        {'a': jnp.ones((3,)), 'i': jnp.arange(2)}))
    assert not bool(precision.all_finite(
        {'a': jnp.asarray([1.0, np.inf])}))
    assert not bool(precision.all_finite(
        {'a': jnp.asarray([np.nan])}))
    assert bool(precision.all_finite({'i': jnp.arange(2)}))  # no floats


# --------------------------------------------------------- loss scale
def test_dynamic_loss_scale_grow_backoff_clamp():
    ls = precision.DynamicLossScale(
        initial_scale=8.0, growth_interval=2, growth_factor=2.0,
        backoff_factor=0.5, min_scale=1.0)
    st = ls.init()
    scaled = ls.scale({'g': jnp.ones((2,))}, st)
    np.testing.assert_allclose(np.asarray(scaled['g']), 8.0)
    unscaled = ls.unscale(scaled, st)
    np.testing.assert_allclose(np.asarray(unscaled['g']), 1.0)
    # two finite steps -> growth, counter reset
    st = ls.adjust(st, jnp.asarray(True))
    assert float(st.scale) == 8.0 and int(st.growth_count) == 1
    st = ls.adjust(st, jnp.asarray(True))
    assert float(st.scale) == 16.0 and int(st.growth_count) == 0
    # non-finite -> backoff, counter reset
    st = ls.adjust(st, jnp.asarray(False))
    assert float(st.scale) == 8.0 and int(st.growth_count) == 0
    # repeated backoff clamps at min_scale
    for _ in range(10):
        st = ls.adjust(st, jnp.asarray(False))
    assert float(st.scale) == 1.0


def test_static_loss_scale_is_fixed():
    ls = precision.StaticLossScale(128.0)
    st = ls.adjust(ls.init(), jnp.asarray(False))
    assert float(st.scale) == 128.0


def test_loss_scale_validation():
    with pytest.raises(ValueError):
        precision.StaticLossScale(0.0)
    with pytest.raises(ValueError):
        precision.DynamicLossScale(backoff_factor=1.5)
    with pytest.raises(ValueError):
        precision.DynamicLossScale(growth_factor=1.0)


# ------------------------------------------------------- concat dtype
def test_concat_examples_dtype_casts_floats_only():
    batch = [(np.ones((3,), np.float32), 1), (np.zeros((3,),
                                              np.float32), 2)]
    x, y = concat_examples(batch, dtype='bfloat16')
    assert x.dtype == np.dtype('bfloat16')
    assert y.dtype == np.int64 or np.issubdtype(y.dtype, np.integer)
    # the validity mask stays f32 (metric averages are f32)
    x, y, mask = concat_examples(batch, padding=(4, 0),
                                 dtype='bfloat16')
    assert x.dtype == np.dtype('bfloat16')
    assert mask.dtype == np.float32
    # pre-collated column arrays cast too
    cols = concat_examples((np.ones((4, 3), np.float32),
                            np.arange(4)), dtype='bfloat16')
    assert cols[0].dtype == np.dtype('bfloat16')
    assert np.issubdtype(cols[1].dtype, np.integer)


@pytest.mark.parametrize('form', ['examples', 'collated'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float16'])
def test_collate_cast_rounds_exactly_as_astype(dtype, form):
    """The cast that happens in the collating assignment is the one
    ``astype`` makes, on EVERY float32 pattern: ties to even, overflow
    to inf, subnormals, signed zeros, NaNs."""
    rng = np.random.RandomState(11)
    bits = rng.randint(0, 2 ** 32, size=(16, 257), dtype=np.uint64)
    x = bits.astype(np.uint32).view(np.float32)
    x[0, :6] = [np.inf, -np.inf, np.nan, -0.0, 1e-45, 65520.0]
    batch = (x,) if form == 'collated' else [(row,) for row in x]
    with np.errstate(over='ignore', invalid='ignore'):
        got, = concat_examples(batch, dtype=dtype)
        want = x.astype(dtype)
    assert got.dtype == np.dtype(dtype)
    assert got.tobytes() == want.tobytes()


# ------------------------------------------- strategy reduce dtype
@pytest.mark.parametrize('strategy', sorted(_COMMUNICATORS))
def test_reduce_dtype_round_trips_every_strategy(strategy):
    """Every registered strategy accepts reduce_dtype: output dtype is
    restored to the gradients' own, values survive the bf16 wire
    round-trip, and the declared hook reports the narrowing."""
    from jax.sharding import PartitionSpec as P

    mesh_shape = (1, 8) if strategy == 'single_node' else (2, 4)
    comm = chainermn_tpu.create_communicator(
        strategy, mesh_shape=mesh_shape, reduce_dtype='bfloat16')
    assert comm.declared_reduce_dtypes() == {'bfloat16'}
    grads = {'w': jnp.full((13, 3), 0.5, jnp.float32),
             'b': jnp.full((5,), -2.0, jnp.float32)}
    out = jax.jit(jax.shard_map(
        comm.allreduce_grad, mesh=comm.mesh, in_specs=P(),
        out_specs=P(), check_vma=False))(grads)
    assert out['w'].dtype == jnp.float32
    assert out['b'].dtype == jnp.float32
    # replicated input: the mean of identical values is the value
    # (0.5 and -2.0 are bf16-exact, so exact equality holds)
    np.testing.assert_allclose(np.asarray(out['w']), 0.5)
    np.testing.assert_allclose(np.asarray(out['b']), -2.0)


def test_reduce_dtype_actually_averages():
    """Rank-dependent values: the bf16-wire mean matches the true mean
    within bf16 resolution (naive = per-leaf collective, the strategy
    where the narrowing is directly visible to SL004)."""
    from jax.sharding import PartitionSpec as P

    comm = chainermn_tpu.create_communicator(
        'naive', mesh_shape=(2, 4), reduce_dtype='bfloat16')

    def run(x):
        r = comm.axis_rank().astype(x.dtype)
        return comm.allreduce_grad({'w': x + r})

    out = jax.jit(jax.shard_map(
        run, mesh=comm.mesh, in_specs=P(), out_specs=P(),
        check_vma=False))(jnp.ones((16,), jnp.float32))
    # mean over ranks 0..7 of (1 + r) = 4.5
    np.testing.assert_allclose(np.asarray(out['w']), 4.5,
                               rtol=1e-2)


# --------------------------------------- StandardUpdater + bf16 policy
def _mlp_updater(policy, comm_name='xla', n_units=16, lr=1e-2,
                 seed=0):
    comm = chainermn_tpu.create_communicator(comm_name)
    model = MLP(n_units=n_units, n_out=10,
                dtype=policy.compute_dtype if policy else None)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 784), jnp.float32))['params']
    clf = Classifier(lambda p, x: model.apply({'params': p}, x))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(lr), comm)
    upd = training.StandardUpdater(iter([]), opt, clf, params, comm,
                                   has_aux=True, policy=policy,
                                   donate=False)
    rng = np.random.RandomState(0)
    x = rng.rand(64, 784).astype(np.float32)
    y = rng.randint(0, 10, 64).astype(np.int32)
    arrays = upd.shard_batch([(x[i], y[i]) for i in range(64)])
    return upd, arrays


def test_bf16_policy_loss_matches_f32_on_mlp():
    """The acceptance pin: Policy.bf16() end-to-end on the mlp example
    -- final loss within rtol 5e-2 of the f32 run, master weights
    f32, batch shipped bf16."""
    u32, a32 = _mlp_updater(None)
    ubf, abf = _mlp_updater(precision.Policy.bf16())
    assert abf[0].dtype == jnp.bfloat16  # host-side compute cast
    assert a32[0].dtype == jnp.float32
    for _ in range(20):
        # each step read back before the other updater's is dispatched:
        # two eight-device programs in flight at once can starve each
        # other's all-reduce of threads on the CPU backend (a 40 s
        # rendezvous timeout, then abort: a third of runs in which
        # test_training.py had run first in the process)
        l32 = float(u32.update_core(a32)['loss'])
        lbf = float(ubf.update_core(abf)['loss'])
    assert lbf == pytest.approx(l32, rel=5e-2)
    # master weights stayed f32
    for leaf in jax.tree_util.tree_leaves(ubf.params):
        assert leaf.dtype == jnp.float32
    # metric averages stay f32 regardless of the bf16 compute
    metrics = ubf.update_core(abf)
    assert metrics['loss'].dtype == jnp.float32


def test_bf16_policy_reduces_gradients_in_bf16():
    """Structural proof from the step's jaxpr: at least one reduce
    collective runs on bf16 operands (the gradient allreduce), and
    the updater declares the narrowing for shardlint."""
    ubf, abf = _mlp_updater(precision.Policy.bf16())
    assert ubf.comm.reduce_dtype == jnp.bfloat16  # policy imposed
    assert 'bfloat16' in ubf.declared_reduce_dtypes()
    fn, args = ubf.traceable_step(abf, iteration=1)
    jaxpr = jax.make_jaxpr(fn)(*args)
    reduce_dtypes = {
        str(eqn.invars[0].aval.dtype)
        for eqn, _ in walker.iter_eqns(jaxpr)
        if eqn.primitive.name in walker.REDUCE_PRIMS}
    assert 'bfloat16' in reduce_dtypes, reduce_dtypes


def test_policy_zero_reduce_dtype_conflict_rejected():
    comm = chainermn_tpu.create_communicator('xla')
    with pytest.raises(ValueError, match='subsumed'):
        training.StandardUpdater(
            iter([]), optax.adam(1e-3),
            lambda p, x: (p['w'] * x).sum(), {'w': jnp.ones((4,))},
            comm, zero=True, zero_reduce_dtype='bfloat16',
            policy=precision.Policy.bf16())


def test_bf16_policy_zero_path():
    """zero=True + Policy.bf16(): the policy's reduce dtype drives the
    ZeRO reduce-scatter (subsuming zero_reduce_dtype) and the
    trajectory tracks the f32 zero run."""
    def build(policy):
        comm = chainermn_tpu.create_communicator('xla')
        model = MLP(n_units=16, n_out=10)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 784), jnp.float32))['params']
        clf = Classifier(lambda p, x: model.apply({'params': p}, x))
        upd = training.StandardUpdater(
            iter([]), optax.adam(1e-2), clf, params, comm,
            has_aux=True, zero=True, policy=policy, donate=False)
        rng = np.random.RandomState(0)
        x = rng.rand(64, 784).astype(np.float32)
        y = rng.randint(0, 10, 64).astype(np.int32)
        return upd, upd.shard_batch([(x[i], y[i]) for i in range(64)])

    u32, a32 = build(None)
    ubf, abf = build(precision.Policy.bf16())
    for _ in range(10):
        l32 = u32.update_core(a32)['loss']
        lbf = ubf.update_core(abf)['loss']
    assert float(lbf) == pytest.approx(float(l32), rel=5e-2)
    for leaf in jax.tree_util.tree_leaves(ubf.params):
        assert leaf.dtype == jnp.float32


# ----------------------------------------------- loss-scaled training
def test_loss_scale_skips_nonfinite_step_and_backs_off():
    comm = chainermn_tpu.create_communicator('naive')
    pol = precision.Policy(
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
        loss_scale=precision.DynamicLossScale(initial_scale=4.0,
                                              growth_interval=2))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1), comm, broadcast_first=False)
    upd = training.StandardUpdater(
        iter([]), opt, lambda p, x: ((p['w'] * x).sum(), {}),
        {'w': jnp.ones((4,))}, comm, has_aux=True, policy=pol,
        donate=False)
    bad = np.ones((8, 4), np.float32)
    bad[0, 0] = np.inf  # ONE device overflows; all must skip
    m = {k: float(v) for k, v in
         upd.update_core(upd.shard_batch((bad,))).items()}
    assert m['grads_finite'] == 0.0 and m['loss_scale'] == 4.0
    assert float(upd.scale_state.scale) == 2.0  # backed off
    np.testing.assert_array_equal(np.asarray(upd.params['w']), 1.0)
    good = np.ones((8, 4), np.float32)
    m = {k: float(v) for k, v in
         upd.update_core(upd.shard_batch((good,))).items()}
    assert m['grads_finite'] == 1.0
    assert int(upd.scale_state.growth_count) == 1
    assert not np.allclose(np.asarray(upd.params['w']), 1.0)


def test_loss_scaled_trajectory_matches_unscaled():
    """Scaling is exact (powers of two): a loss-scaled f32 run takes
    the same trajectory as the unscaled one on finite data."""
    pol = precision.Policy(
        loss_scale=precision.StaticLossScale(1024.0))
    u_plain, a = _mlp_updater(None, comm_name='naive')
    u_scaled, a_s = _mlp_updater(pol, comm_name='naive')
    for _ in range(5):
        lp = u_plain.update_core(a)['loss']
        ls = u_scaled.update_core(a_s)['loss']
    assert float(ls) == pytest.approx(float(lp), rel=1e-4)


# -------------------------------------------------- pipeline updater
def test_pipeline_policy_bf16_runs_and_rejects_f16():
    from chainermn_tpu.training.pipeline_updater import (
        PipelineUpdater, pipeline_mesh)

    mesh = pipeline_mesh(2)
    d = 8

    def stage_fn(p, x):
        return jnp.tanh(x @ p['w'] + p['b'])

    def loss_on_last(outs, y_micro):
        loss = jnp.mean((outs - y_micro) ** 2)
        return loss, {'mse': loss}

    rng = np.random.RandomState(0)
    params = {'w': jnp.asarray(rng.randn(2, d, d) * 0.1, jnp.float32),
              'b': jnp.zeros((2, d), jnp.float32)}
    n_data = mesh.shape['data']
    x = rng.randn(4 * n_data, d).astype(np.float32)
    y = rng.randn(4 * n_data, d).astype(np.float32)

    def build(policy, schedule):
        upd = PipelineUpdater(
            iter([]), optax.sgd(1e-2), stage_fn, loss_on_last,
            params, mesh, n_micro=2, schedule=schedule,
            policy=policy, donate=False)
        return upd, upd.shard_batch(
            [(x[i], y[i]) for i in range(4 * n_data)])

    for schedule in ('gpipe', '1f1b'):
        u32, a32 = build(None, schedule)
        ubf, abf = build(precision.Policy.bf16(), schedule)
        assert abf[0].dtype == jnp.bfloat16
        for _ in range(5):
            l32 = u32.update_core(a32)['loss']
            lbf = ubf.update_core(abf)['loss']
        assert float(lbf) == pytest.approx(float(l32), rel=5e-2)
        for leaf in jax.tree_util.tree_leaves(ubf.params):
            assert leaf.dtype == jnp.float32
        assert ubf.declared_reduce_dtypes() == {'bfloat16'}

    with pytest.raises(ValueError, match='loss-scaled'):
        build(precision.Policy.f16(), 'gpipe')
