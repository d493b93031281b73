"""Fused BN+relu(+add) kernel (``chainermn_tpu.ops.batch_norm_act``)
and its model wiring (``models._norm.norm_act`` / ``fused_norm=``).

Numerics are pinned against the flax ``nn.BatchNorm`` (+ relu
+ residual add) composition -- the oracle the fused path replaces --
on both the fallback and interpret (real Pallas kernels) paths, at
the acceptance tolerances: rtol 1e-5 f32, 5e-2 bf16.

The traffic tests assert the STRUCTURAL claim on the CPU backend:
the fused train step materializes zero f32 activation-sized
intermediates (the SL008 / memtraffic quantity -- a 100% drop of the
excess PERF.md diagnosed), and its XLA cost-analysis bytes-accessed
is no worse than the unfused step's.  The headline >=25% drop in
*post-fusion* bytes-accessed is a TPU claim: XLA's CPU fusion
re-fuses the unfused elementwise chain too, so the CPU delta is
small (~1-3% measured); the TPU A/B is banked by
``bench.py --fused-norm`` / ``ci/run_tpu_round.sh``
(``bench_resnet50_fused``) when a chip window opens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import flax.linen as nn

from chainermn_tpu import ops
from chainermn_tpu.models._norm import NormAct
from chainermn_tpu.ops import _common


@pytest.fixture(params=['fallback', 'interpret'])
def mode(request, monkeypatch):
    if request.param == 'interpret':
        monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    else:
        monkeypatch.delenv('CHAINERMN_TPU_PALLAS_INTERPRET',
                           raising=False)
    assert _common.pallas_mode() == request.param
    return request.param


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def _oracle(x, scale, bias, residual=None, relu=True, eps=1e-5):
    """flax BatchNorm (+ add) (+ relu): the composition the fused op
    replaces, returning (out, batch_mean, batch_var) like the op."""
    bn = nn.BatchNorm(use_running_average=False, epsilon=eps,
                      dtype=x.dtype, param_dtype=jnp.float32)
    variables = {
        'params': {'scale': scale, 'bias': bias},
        'batch_stats': {
            'mean': jnp.zeros(x.shape[-1], jnp.float32),
            'var': jnp.ones(x.shape[-1], jnp.float32)}}
    y, _ = bn.apply(variables, x, mutable=['batch_stats'])
    if residual is not None:
        y = y + residual
    if relu:
        y = jax.nn.relu(y)
    c = x.shape[-1]
    xf = x.reshape(-1, c).astype(jnp.float32)
    mean = xf.mean(axis=0)
    var = jnp.maximum((xf * xf).mean(axis=0) - mean * mean, 0.0)
    return y, mean, var


TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


class TestForward:
    @pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize('residual', [False, True])
    def test_matches_flax_oracle(self, mode, dtype, residual):
        x = _rand((4, 6, 6, 16), 0, dtype)
        res = _rand((4, 6, 6, 16), 1, dtype) if residual else None
        scale = _rand((16,), 2) * 0.5 + 1.0
        bias = _rand((16,), 3)
        out, mean, var = ops.batch_norm_act(x, scale, bias,
                                            residual=res)
        ref, rmean, rvar = _oracle(x, scale, bias, residual=res)
        tol = TOL[dtype]
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **tol)
        # statistics are f32 over the (possibly bf16) activation
        np.testing.assert_allclose(mean, rmean, rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(var, rvar, rtol=2e-2, atol=2e-2)

    def test_no_relu_variant(self, mode):
        x = _rand((4, 8, 16), 4)
        scale, bias = jnp.ones((16,)), jnp.zeros((16,))
        out, _, _ = ops.batch_norm_act(x, scale, bias, relu=False)
        ref, _, _ = _oracle(x, scale, bias, relu=False)
        np.testing.assert_allclose(out, ref, **TOL[jnp.float32])
        assert (np.asarray(out) < 0).any()  # relu really off

    def test_dtype_pins(self, mode):
        # bf16 compute in, bf16 out; f32 statistics -- the
        # mixed-precision contract (f32 masters, bf16 activations)
        x = _rand((4, 4, 4, 8), 5, jnp.bfloat16)
        out, mean, var = ops.batch_norm_act(x, jnp.ones((8,)),
                                            jnp.zeros((8,)))
        assert out.dtype == jnp.bfloat16
        assert mean.dtype == jnp.float32 and var.dtype == jnp.float32

    def test_row_padding(self, mode):
        # 4*5*5 = 100 rows: not a multiple of the kernel row block;
        # pad rows must not perturb the statistics
        x = _rand((4, 5, 5, 8), 6)
        out, mean, var = ops.batch_norm_act(x, jnp.ones((8,)),
                                            jnp.zeros((8,)))
        ref, rmean, rvar = _oracle(x, jnp.ones((8,)), jnp.zeros((8,)))
        np.testing.assert_allclose(out, ref, **TOL[jnp.float32])
        np.testing.assert_allclose(var, rvar, rtol=1e-5, atol=1e-5)


class TestBackward:
    @pytest.mark.parametrize('residual', [False, True])
    def test_grads_match_flax_oracle(self, mode, residual):
        x = _rand((4, 6, 6, 16), 7)
        res = _rand((4, 6, 6, 16), 8) if residual else None
        scale = _rand((16,), 9) * 0.5 + 1.0
        bias = _rand((16,), 10)

        def loss(op):
            def f(x, scale, bias, res):
                out = op(x, scale, bias, res)[0]
                return jnp.sum(out * out)
            return f

        fused = loss(lambda x, s, b, r: ops.batch_norm_act(
            x, s, b, residual=r))
        oracle = loss(lambda x, s, b, r: _oracle(x, s, b, residual=r))
        g = jax.grad(fused, argnums=(0, 1, 2, 3))(x, scale, bias, res)
        g_ref = jax.grad(oracle, argnums=(0, 1, 2, 3))(
            x, scale, bias, res)
        names = ('x', 'scale', 'bias', 'residual')
        for a, b, name in zip(g, g_ref, names):
            if a is None or b is None:
                assert not residual and name == 'residual'
                continue
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                       err_msg='grad %s' % name)

    def test_relu_mask_from_output_sign(self, mode):
        # backward must gate on the OUTPUT's sign (no mask tensor is
        # saved); a shifted bias makes both branches non-trivial
        x = _rand((8, 16), 11)
        bias = jnp.full((16,), 0.3)

        def f(x):
            out, _, _ = ops.batch_norm_act(x, jnp.ones((16,)), bias)
            return out.sum()

        def f_ref(x):
            out, _, _ = ops.batch_norm_act_reference(
                x, jnp.ones((16,)), bias)
            return out.sum()

        np.testing.assert_allclose(jax.grad(f)(x), jax.grad(f_ref)(x),
                                   rtol=1e-5, atol=1e-5)


class TestNormActModule:
    def _mods(self):
        fused = NormAct(use_running_average=False, momentum=0.9)
        oracle = nn.BatchNorm(use_running_average=False, momentum=0.9,
                              param_dtype=jnp.float32)
        return fused, oracle

    def test_variable_tree_matches_flax_batchnorm(self, mode):
        # init once, apply under either flag: same params/batch_stats
        fused, oracle = self._mods()
        x = _rand((4, 4, 4, 8), 12)
        vf = fused.init(jax.random.PRNGKey(0), x)
        vo = oracle.init(jax.random.PRNGKey(0), x)
        tf = jax.tree_util.tree_structure(vf)
        to = jax.tree_util.tree_structure(vo)
        assert tf == to
        for a, b in zip(jax.tree_util.tree_leaves(vf),
                        jax.tree_util.tree_leaves(vo)):
            assert a.shape == b.shape and a.dtype == b.dtype

    def test_running_statistics_update(self, mode):
        # one train-mode application advances the running average
        # exactly like nn.BatchNorm's momentum rule
        fused, oracle = self._mods()
        x = _rand((8, 6, 8), 13)
        variables = oracle.init(jax.random.PRNGKey(0), x)
        out_f, upd_f = fused.apply(variables, x,
                                   mutable=['batch_stats'])
        out_o, upd_o = oracle.apply(variables, x,
                                    mutable=['batch_stats'])
        np.testing.assert_allclose(out_f, jax.nn.relu(out_o),
                                   rtol=1e-5, atol=1e-5)
        for key in ('mean', 'var'):
            np.testing.assert_allclose(
                upd_f['batch_stats'][key],
                np.ravel(upd_o['batch_stats'][key]),
                rtol=1e-5, atol=1e-5, err_msg=key)

    def test_inference_uses_running_stats(self, mode):
        x = _rand((4, 4, 8), 14)
        stats = {'mean': jnp.full((8,), 0.5),
                 'var': jnp.full((8,), 2.0)}
        variables = {'params': {'scale': jnp.ones((8,)),
                                'bias': jnp.zeros((8,))},
                     'batch_stats': stats}
        out = NormAct(use_running_average=True).apply(variables, x)
        oracle = nn.BatchNorm(use_running_average=True)
        ref = jax.nn.relu(oracle.apply(
            {'params': variables['params'],
             'batch_stats': {k: v for k, v in stats.items()}}, x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def _mini_resnet_step(fused, dtype=jnp.bfloat16):
    """Bare fwd+bwd train step of a small ResNet -- the fast-set
    vehicle for jaxpr and value A/B assertions (the full resnet50 lint
    target is the slow-set twin in test_analysis.py)."""
    from chainermn_tpu.models.resnet50 import ResNet

    model = ResNet(stage_sizes=[1, 1], width=8, num_classes=4,
                   dtype=dtype, fused_norm=fused)
    x0 = jnp.zeros((1, 24, 24, 3), jnp.float32)
    variables = model.init({'params': jax.random.PRNGKey(0)}, x0,
                           train=False)
    x = jnp.zeros((4, 24, 24, 3), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)

    def loss_fn(params, stats, x, y):
        logits, upd = model.apply(
            {'params': params, 'batch_stats': stats}, x,
            train=True, mutable=['batch_stats'])
        onehot = jax.nn.one_hot(y, logits.shape[-1])
        l = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))
        return l, upd

    def step(params, stats, x, y):
        (l, upd), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, stats, x, y)
        return l, g, upd

    args = (variables['params'], variables['batch_stats'], x, y)
    return step, args


def test_fused_step_materializes_no_f32_activations():
    # THE structural claim, asserted on the traced step: the unfused
    # (flax-oracle) step upcasts activation-sized tensors to f32; the
    # fused step's count is zero -- a 100% (>= the 25% target) drop
    # of the SL008 / memtraffic excess
    from chainermn_tpu.analysis import memtraffic

    sizes = {}
    for fused in (False, True):
        step, args = _mini_resnet_step(fused)
        jaxpr = jax.make_jaxpr(step)(*args)
        t = memtraffic.jaxpr_traffic(jaxpr)
        sizes[fused] = t
    assert sizes[False]['f32_materialized_bytes'] > 0
    assert sizes[True]['f32_materialized_count'] == 0
    drop = 1.0 - (sizes[True]['f32_materialized_bytes']
                  / sizes[False]['f32_materialized_bytes'])
    assert drop >= 0.25, sizes


def test_fused_step_loss_grads_stats_match_unfused():
    # what the count above cannot say: the COMPILED train step through
    # every fused norm, residual join and custom VJP of a model hands
    # back the unfused step's loss, gradients and running statistics
    # (float32: the gaps read 1e-5 to 3e-5 of a leaf's norm; the
    # op-level gradient pin is TestFusedNormAct's, the forward's the
    # test below).  It took the place of a comparison of XLA-CPU's
    # `bytes accessed`, which the TPU decides and the CPU cannot.
    x = _rand((4, 24, 24, 3), 17)
    y = jnp.arange(4, dtype=jnp.int32)
    outs = {}
    for fused in (False, True):
        step, (params, stats, _, _) = _mini_resnet_step(
            fused, dtype=jnp.float32)
        outs[fused] = jax.jit(step)(params, stats, x, y)
    (loss_o, grads_o, upd_o), (loss_f, grads_f, upd_f) = (
        outs[False], outs[True])
    np.testing.assert_allclose(loss_f, loss_o, rtol=1e-4)
    leaves_o, tree_o = jax.tree_util.tree_flatten(grads_o)
    leaves_f, tree_f = jax.tree_util.tree_flatten(grads_f)
    assert tree_f == tree_o and leaves_o
    for a, b in zip(leaves_f, leaves_o):
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
        assert gap < 1e-3, (a.shape, gap)
    for a, b in zip(jax.tree_util.tree_leaves(upd_f),
                    jax.tree_util.tree_leaves(upd_o)):
        np.testing.assert_allclose(np.ravel(a), np.ravel(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_fused_model_matches_unfused(dtype):
    # end-to-end model pin at the acceptance tolerances: same
    # variables, same input, fused vs flax-oracle forward
    from chainermn_tpu.models.resnet50 import ResNet

    kw = dict(stage_sizes=[1, 1], width=8, num_classes=4, dtype=dtype)
    x = _rand((2, 24, 24, 3), 15)
    oracle = ResNet(fused_norm=False, **kw)
    fused = ResNet(fused_norm=True, **kw)
    variables = oracle.init({'params': jax.random.PRNGKey(0)},
                            x, train=False)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=5e-2)
    # train mode (batch statistics + running-average update)
    out_o, upd_o = oracle.apply(variables, x, train=True,
                                mutable=['batch_stats'])
    out_f, upd_f = fused.apply(variables, x, train=True,
                               mutable=['batch_stats'])
    np.testing.assert_allclose(out_f, out_o, **tol)
    for a, b in zip(jax.tree_util.tree_leaves(upd_f),
                    jax.tree_util.tree_leaves(upd_o)):
        np.testing.assert_allclose(np.ravel(a), np.ravel(b), **tol)
    # eval mode (running statistics)
    np.testing.assert_allclose(
        fused.apply(variables, x, train=False),
        oracle.apply(variables, x, train=False), **tol)


@pytest.mark.slow
def test_googlenetbn_fused_matches_unfused():
    # the inception zoo's explicit BatchNorm_N naming must replay
    # flax's auto-numbering exactly: same variable tree, and applying
    # the UNFUSED init through the fused model reproduces the oracle
    from chainermn_tpu.models import GoogLeNetBN

    x = _rand((2, 64, 64, 3), 16)
    oracle = GoogLeNetBN(num_classes=4, dtype=jnp.float32)
    fused = GoogLeNetBN(num_classes=4, dtype=jnp.float32,
                        fused_norm=True)
    variables = oracle.init({'params': jax.random.PRNGKey(0)}, x,
                            train=False)
    assert (jax.tree_util.tree_structure(variables)
            == jax.tree_util.tree_structure(
                fused.init({'params': jax.random.PRNGKey(0)}, x,
                           train=False)))
    out_o, upd_o = oracle.apply(variables, x, train=True,
                                mutable=['batch_stats'])
    out_f, upd_f = fused.apply(variables, x, train=True,
                               mutable=['batch_stats'])
    # 1e-4: f32 numerics accumulated through 10 inception stages
    np.testing.assert_allclose(out_f, out_o, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(upd_f),
                    jax.tree_util.tree_leaves(upd_o)):
        np.testing.assert_allclose(np.ravel(a), np.ravel(b),
                                   rtol=1e-4, atol=1e-4)


def test_zoo_models_accept_fused_norm_flag():
    # API parity across the conv zoo: every model constructor takes
    # fused_norm (a no-op for the norm-free VGG/NIN)
    from chainermn_tpu.models import (
        GoogLeNetBN, NIN, ResNet50, VGG16)

    for builder in (ResNet50, VGG16, NIN, GoogLeNetBN):
        model = builder(num_classes=4, fused_norm=True)
        assert model.fused_norm is True
