"""Multi-node optimizer semantics tests (reference
``multi_node_optimizer.py:11-29``: first update broadcasts, later
updates allreduce+step)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.communicators.mesh_utility import AXES


def _run_steps(comm, broadcast_first=True, n_steps=3):
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, broadcast_first=broadcast_first)

    def steps():
        r = comm.axis_rank().astype(jnp.float32)
        # deliberately rank-divergent initial params
        params = {'w': jnp.full((2,), r)}
        state = opt.init(params)
        history = []
        for _ in range(n_steps):
            grads = {'w': jnp.full((2,), r + 1.0)}  # mean = (size+1)/2
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            history.append(params['w'][0])
        return jnp.stack(history)

    fn = jax.jit(jax.shard_map(steps, mesh=comm.mesh, in_specs=(),
                               out_specs=P(AXES), check_vma=False))
    return np.asarray(fn()).reshape(comm.size, n_steps)


def test_first_update_broadcasts():
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    hist = _run_steps(comm)
    # step 0: every device snapped to root (rank 0) params = 0.0;
    # no optimizer step taken
    np.testing.assert_allclose(hist[:, 0], np.zeros(8))
    # step 1: sgd(1.0) with mean grad (0+1+...+7)/8 + 1 = 4.5
    np.testing.assert_allclose(hist[:, 1], np.full(8, -4.5))
    np.testing.assert_allclose(hist[:, 2], np.full(8, -9.0))


def test_no_broadcast_mode():
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    hist = _run_steps(comm, broadcast_first=False)
    # step 0 already applies the mean-gradient step from divergent
    # starts: rank r starts at r, grad mean 4.5 -> r - 4.5
    np.testing.assert_allclose(hist[:, 0],
                               np.arange(8, dtype=np.float32) - 4.5)


def test_params_required():
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(1.0), comm)
    state = opt.init({'w': jnp.zeros((2,))})
    with pytest.raises(ValueError, match='requires params'):
        opt.update({'w': jnp.ones((2,))}, state)


@pytest.mark.parametrize('dtype', ['bfloat16', 'float16'])
def test_allreduce_dtype_close_to_full_precision(dtype):
    """allreduce_dtype halves collective bytes; the reduced-precision
    mean must track the f32 mean within the narrow dtype's tolerance,
    and updates must come back in the PARAM dtype."""
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))

    def run(allreduce_dtype):
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.5), comm, allreduce_dtype=allreduce_dtype)

        def steps():
            r = comm.axis_rank().astype(jnp.float32)
            params = {'w': jnp.zeros((4,), jnp.float32)}
            state = opt.init(params)
            for i in range(3):
                grads = {'w': jnp.full((4,), (r + 1.0) * 0.125
                                       * (i + 1))}
                updates, state = opt.update(grads, state, params)
                params = optax.apply_updates(params, updates)
            return params['w']

        fn = jax.jit(jax.shard_map(steps, mesh=comm.mesh, in_specs=(),
                                   out_specs=P(AXES), check_vma=False))
        return np.asarray(fn(), np.float32)

    full = run(None)
    narrow = run(dtype)
    # identical across devices either way, and close across precisions
    assert np.ptp(narrow) == 0.0
    np.testing.assert_allclose(narrow, full, rtol=2e-2, atol=1e-3)
    assert not np.allclose(narrow, 0.0)


def test_double_buffering_staleness_semantics():
    """double_buffering applies the PREVIOUS step's reduced gradients:
    broadcast step, then a buffer-fill step with no update, then each
    step applies the reduction issued one step earlier."""
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, double_buffering=True)

    def steps():
        r = comm.axis_rank().astype(jnp.float32)
        params = {'w': jnp.full((2,), r)}
        state = opt.init(params)
        history = []
        for t in range(4):
            # mean over ranks of (r + 1 + t) = 4.5 + t
            grads = {'w': jnp.full((2,), r + 1.0 + t)}
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            history.append(params['w'][0])
        return jnp.stack(history)

    fn = jax.jit(jax.shard_map(steps, mesh=comm.mesh, in_specs=(),
                               out_specs=P(AXES), check_vma=False))
    hist = np.asarray(fn()).reshape(comm.size, 4)
    # t=0: broadcast to root params (0.0); gradients dropped unreduced
    np.testing.assert_allclose(hist[:, 0], np.zeros(8))
    # t=1: buffer fill (reduces mean 5.5) but applies NO update
    np.testing.assert_allclose(hist[:, 1], np.zeros(8))
    # t=2: applies the 5.5 from t=1; reduces 6.5
    np.testing.assert_allclose(hist[:, 2], np.full(8, -5.5))
    # t=3: applies 6.5
    np.testing.assert_allclose(hist[:, 3], np.full(8, -12.0))


def test_double_buffering_converges():
    """Staleness-1 trajectories still converge at a stable step size:
    minimize a quadratic under double buffering across the mesh.
    (Aggressive momentum settings genuinely oscillate under staleness
    -- the docstring's lower-LR advice is real, not boilerplate.)"""
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1), comm, double_buffering=True)
    target = jnp.asarray(np.linspace(-2.0, 2.0, 8), jnp.float32)

    def steps():
        params = {'w': jnp.zeros((8,), jnp.float32)}
        state = opt.init(params)
        for _ in range(80):
            grads = {'w': 2.0 * (params['w'] - target)}
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        return params['w']

    fn = jax.jit(jax.shard_map(steps, mesh=comm.mesh, in_specs=(),
                               out_specs=P(AXES), check_vma=False))
    out = np.asarray(fn(), np.float32).reshape(comm.size, 8)
    for row in out:
        np.testing.assert_allclose(row, np.asarray(target), atol=1e-2)


def test_double_buffering_composes_with_bucketed():
    """The two overlap knobs together: double buffering over the
    bucketed communicator's fused allreduce -- same trajectory as
    double buffering over the plain xla communicator."""
    def run(name):
        comm = chainermn_tpu.create_communicator(name,
                                                 mesh_shape=(2, 4))
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm, double_buffering=True)

        def steps():
            r = comm.axis_rank().astype(jnp.float32)
            params = {'w': jnp.full((16,), r),
                      'b': jnp.full((4,), -r)}
            state = opt.init(params)
            for t in range(4):
                grads = {'w': jnp.full((16,), r + 1.0 + t),
                         'b': jnp.full((4,), 0.5 * (r + t))}
                updates, state = opt.update(grads, state, params)
                params = optax.apply_updates(params, updates)
            return jnp.concatenate([params['w'], params['b']])

        fn = jax.jit(jax.shard_map(steps, mesh=comm.mesh, in_specs=(),
                                   out_specs=P(AXES), check_vma=False))
        return np.asarray(fn(), np.float32).reshape(comm.size, 20)

    plain = run('xla')
    bucketed = run('bucketed')
    np.testing.assert_allclose(bucketed, plain, rtol=1e-6, atol=1e-6)
    # and identical across devices
    assert np.ptp(bucketed, axis=0).max() == 0.0


# ---------------------------------------------------------------------
# ISSUE 38: the gradient reduction sits OUTSIDE the wrapper's cond,
# every large gradient reduced alone in its own shape

N_LARGE, N_SMALL = 3, 23   # leaves of _wide_params over / under 1 MiB


def _wide_params():
    """Three 1.08 MB weights (over `xla`'s 1 MiB threshold), their
    biases and twenty small scales: 26 leaves, 23 of them packed."""
    rs = np.random.RandomState(0)
    params = {}
    for k in range(N_LARGE):
        params['w%d' % k] = (rs.randn(520, 520) / 23).astype(np.float32)
        params['b%d' % k] = np.zeros((520,), np.float32)
    for k in range(N_SMALL - N_LARGE):
        params['s%02d' % k] = np.ones((16,), np.float32)
    return params


def _wide_loss(p, x):
    h = x
    for k in range(N_LARGE):
        h = jnp.tanh(h @ p['w%d' % k] + p['b%d' % k])
    scale = sum(jnp.mean(p['s%02d' % k])
                for k in range(N_SMALL - N_LARGE))
    return jnp.mean(h ** 2) * scale


def _wide_updater(name='xla'):
    from chainermn_tpu import training
    comm = chainermn_tpu.create_communicator(name, mesh_shape=(2, 4))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm)
    upd = training.StandardUpdater(iter([]), opt, _wide_loss,
                                   _wide_params(), comm)
    rs = np.random.RandomState(1)
    batch = [(rs.randn(520).astype(np.float32),) for _ in range(16)]
    return upd, batch


def test_step_reduces_outside_the_cond_leaf_by_leaf():
    """The lowered train step: one all_reduce a large leaf + one a
    packed bucket (+ the loss metric's own) BEFORE the wrapper's
    `case`, none in the branch that steps the optimizer (only the
    first call's weight sync keeps its psums, inside its own branch),
    and the one concatenate left packs the small leaves alone."""
    import re

    from conftest import stablehlo_case_branches

    upd, batch = _wide_updater()
    txt = upd._step.lower(
        *upd._step_args(upd.shard_batch(batch))).as_text()
    outside, (later, first) = stablehlo_case_branches(txt)
    assert outside.count('stablehlo.all_reduce') == N_LARGE + 1 + 1
    assert later.count('stablehlo.all_reduce') == 0
    # the first call's broadcast: a masked psum a leaf, where it was
    assert first.count('stablehlo.all_reduce') == N_LARGE + N_SMALL
    sizes = [int(n) for n in re.findall(
        r'stablehlo\.concatenate.*-> tensor<(\d+)xf32>', txt)]
    params = _wide_params()
    small = sum(v.size for k, v in params.items() if k[0] != 'w')
    # (jnp.concatenate joins 16 operands at a time, then the joins)
    assert max(sizes) == small
    assert small < sum(v.size for v in params.values()) // 100
    # every large leaf goes over the wire in its own shape
    assert len(re.findall(
        r'stablehlo\.all_reduce[^\n]*\n(?:[^\n]*\n){0,6}?[^\n]*'
        r'\(tensor<520x520xf32>\) -> tensor<520x520xf32>',
        outside)) == N_LARGE


@pytest.mark.parametrize('double_buffering', [False, True])
def test_first_call_only_syncs_after_the_hoist(double_buffering):
    """The first `update()` still returns root's params minus mine, bit
    for bit what `broadcast_data` alone gives, and hands the inner
    optimizer's state back untouched -- the hoisted reduction runs,
    and its result is dropped."""
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm, double_buffering=double_buffering)

    def first():
        r = comm.axis_rank().astype(jnp.float32)
        params = {'w': jnp.full((4, 3), r + 0.25),
                  'b': jnp.full((5,), -r)}
        grads = {'w': jnp.full((4, 3), r + 1.0),
                 'b': jnp.full((5,), 2.0 * r)}
        state = opt.init(params)
        updates, new_state = opt.update(grads, state, params)
        want = jax.tree_util.tree_map(
            lambda s, p: s - p, comm.broadcast_data(params), params)
        same = jnp.stack(
            [jnp.all(a == b) for a, b in zip(
                jax.tree_util.tree_leaves((updates,
                                           new_state.actual_state)),
                jax.tree_util.tree_leaves((want,
                                           state.actual_state)))])
        synced = optax.apply_updates(params, updates)
        return (jnp.all(same)[None], new_state.needs_broadcast[None],
                synced['w'][None, 0, 0])

    same, needs, w = jax.jit(jax.shard_map(
        first, mesh=comm.mesh, in_specs=(),
        out_specs=(P(AXES), P(AXES), P(AXES)), check_vma=False))()
    assert np.asarray(same).all()
    assert not np.asarray(needs).any()
    np.testing.assert_array_equal(np.asarray(w), np.full(8, 0.25))


@pytest.mark.parametrize('name, kwargs, collectives, packed', [
    ('xla', {}, N_LARGE + 1, N_SMALL),
    ('bucketed', {'bucket_mb': 0.001}, N_LARGE + 5, N_SMALL - N_LARGE),
    ('xla', {'reduce_dtype': 'bfloat16'}, 1, N_LARGE + N_SMALL),
    ('flat', {}, None, None),
])
def test_allreduce_event_says_what_was_issued(name, kwargs, collectives,
                                              packed):
    """The trace-time `multi_node_optimizer:allreduce_grad` event
    carries the plan: `collectives` (large leaves + packed buckets),
    `leaves`, `packed_leaves`, `bytes` as reduced; a strategy with no
    per-leaf plan gives the two it knows."""
    from chainermn_tpu import telemetry
    comm = chainermn_tpu.create_communicator(name, mesh_shape=(2, 4),
                                             **kwargs)
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1), comm)
    params = jax.tree_util.tree_map(jnp.asarray, _wide_params())

    def f(p):
        state = opt.init(p)
        return opt.update(p, state, p)[0]

    telemetry.enable()
    try:
        jax.jit(jax.shard_map(f, mesh=comm.mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False)
                ).lower(params)
        (event,) = [e for e in telemetry.active().events
                    if e.get('name')
                    == 'multi_node_optimizer:allreduce_grad']
    finally:
        telemetry.disable()
    nbytes = sum(v.nbytes for v in params.values())
    assert event['kind'] == 'collective_trace'
    assert event['leaves'] == N_LARGE + N_SMALL
    assert event['bytes'] == (nbytes // 2 if kwargs.get('reduce_dtype')
                              else nbytes)
    assert event.get('collectives') == collectives
    assert event.get('packed_leaves') == packed


def test_step_is_compiled_under_the_strategys_options():
    """`StandardUpdater` compiles its step under what the communicator
    asks for (`step_compiler_options`): nothing on the CPU mesh, and a
    name the compiler does not know comes back in its refusal -- the
    proof that the options reach the compiler with no flag set."""
    upd, batch = _wide_updater()
    assert upd.comm.step_compiler_options() == {}
    assert chainermn_tpu.create_communicator(
        'flat', mesh_shape=(2, 4)).step_compiler_options() == {}

    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    comm.step_compiler_options = lambda: {'xla_option_of_this_test': 1}
    from chainermn_tpu import training
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    upd = training.StandardUpdater(iter([]), opt, _wide_loss,
                                   _wide_params(), comm)
    with pytest.raises(Exception, match='xla_option_of_this_test'):
        upd.update_core(upd.shard_batch(batch))
