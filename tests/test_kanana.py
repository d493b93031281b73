"""The ``deepseek_v3`` family (Kanana-2-30B-A3B's layer) as it is
TRAINED: the program against the plain float32 reference
(``chipbench/reference/deepseek_v3.py``) on seeded random weights at a
small size -- logits, the loss and every leaf's gradient, three Adam
steps through ``StandardUpdater``; the share of the experts a layer is
told it holds against the uncut layer; the rotary's pairing; the
shared MLA module and the edited expert body against what ``xing4`` and
``afmoe`` computed before the move; the step's counters on the
trainer's span; what a layer's checkpoint keeps (the flash kernel's
residuals: its forward once a layer, no number moved)."""

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

import chainermn_tpu
from chainermn_tpu import ops, telemetry, training
from chainermn_tpu.analysis import walker
from chainermn_tpu.models import DeepseekV3LM, _experts, _mla
from chipbench.reference import common as ref_common
from chipbench.reference import deepseek_v3 as ref

CFG = {
    'family': 'deepseek_v3', 'vocab_size': 96, 'hidden_size': 32,
    'intermediate_size': 64, 'moe_intermediate_size': 16,
    'num_hidden_layers': 3, 'first_k_dense_replace': 1,
    'num_attention_heads': 2, 'q_lora_rank': None, 'kv_lora_rank': 16,
    'qk_nope_head_dim': 8, 'qk_rope_head_dim': 4, 'v_head_dim': 8,
    'n_routed_experts': 4, 'n_shared_experts': 2,
    'num_experts_per_tok': 3, 'n_group': 1, 'topk_group': 1,
    'norm_topk_prob': True, 'routed_scaling_factor': 2.448,
    'scoring_func': 'sigmoid', 'topk_method': 'noaux_tc',
    'rms_norm_eps': 1e-6, 'rope_theta': 1e6, 'rope_scaling': None,
    'rope_interleave': True, 'router_experts': 16, 'first_expert': 4,
    'train': {'optimizer': 'adam', 'lr': 3e-4, 'policy': None,
              'recompute': 'layer'}}
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


def _model(**overrides):
    return DeepseekV3LM.from_config(dict(CFG, **overrides),
                                    dtype=jnp.float32)


def _batch(seed=0, rows=2, t=24):
    rng = np.random.RandomState(seed)
    return tuple(rng.randint(0, CFG['vocab_size'], (rows, t)).astype(
        np.int32) for _ in range(2))


def _paths(tree):
    return ['/'.join(str(k.key) for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope='module')
def params():
    return ref.init_params(CFG, 7)


@pytest.fixture(params=['fallback', 'interpret'])
def mode(request, monkeypatch):
    """The flash and grouped kernels as jnp twins, or the Pallas
    kernels in the interpreter."""
    if request.param == 'interpret':
        monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    else:
        monkeypatch.delenv('CHAINERMN_TPU_PALLAS_INTERPRET',
                           raising=False)
    return request.param


@pytest.fixture(scope='module')
def grads(params):
    """``(program loss, aux, program grads, reference loss, reference
    grads)`` of one batch, float32 at ``highest``."""
    tokens, targets = _batch()
    with jax.default_matmul_precision('highest'):
        (loss, aux), got = jax.value_and_grad(
            _model().loss_fn(), has_aux=True)(
                params, jnp.asarray(tokens), jnp.asarray(targets))
    want_loss, want = ref.make_grad_fn(CFG)(params, (tokens, targets))
    return loss, aux, got, want_loss, want


def test_parameter_tree_is_the_references(params):
    shapes = _model().param_shapes()
    flat = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    assert [p.shape for p in jax.tree_util.tree_leaves(params)] == flat
    made = _model().init(jax.random.key(0))
    assert _paths(made) == _paths(params)
    assert float(jnp.mean(made['final_norm'])) == pytest.approx(1, abs=0.1)


@pytest.mark.parametrize('recompute', [None, 'layer'])
@pytest.mark.parametrize('first,held', [(4, 4), (0, 16)])
def test_full_forward_logits(recompute, first, held):
    cfg = dict(CFG, first_expert=first, n_routed_experts=held)
    params = ref.init_params(cfg, 5)
    tokens, _ = _batch(1)
    model = DeepseekV3LM.from_config(cfg, dtype=jnp.float32,
                                     train_recompute=recompute)
    with jax.default_matmul_precision('highest'):
        got = model.apply(params, jnp.asarray(tokens))
    want = jnp.stack([ref.forward(params, jnp.asarray(row), cfg)
                      for row in tokens])
    assert got.shape == (2, 24, CFG['vocab_size'])
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-5)


def test_loss_and_counters(grads, params):
    loss, aux, _, want_loss, _ = grads
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    # the counters against the reference's routing, a layer at a time
    tokens, _ = _batch()
    held = 0
    prec = ref_common.Precision('float32')
    for row in tokens:
        x = jnp.take(params['embed']['embedding'], row, axis=0)
        for i in range(CFG['num_hidden_layers']):
            x, chosen = ref._layer(x, params['layer_%d' % i], CFG, prec)
            if chosen is not None:
                local = np.asarray(chosen) - CFG['first_expert']
                held += int(((local >= 0) & (local < 4)).sum())
    assert float(aux['held_assignments']) == held
    assert float(aux['assignments']) == 2 * 2 * 24 * 3
    assert 1 <= float(aux['experts_with_row']) <= 4
    assert float(aux['expert_load_max_over_mean']) >= 1.0


def _leaf_names():
    spec = ref.param_spec(CFG)
    return _paths(jax.tree_util.tree_map(
        lambda s: 0, spec, is_leaf=lambda x: isinstance(x, tuple)))


@pytest.mark.parametrize('leaf', _leaf_names())
def test_gradient_of_every_leaf(grads, leaf):
    _, _, got, _, want = grads
    a = dict(zip(_paths(got), jax.tree_util.tree_leaves(got)))[leaf]
    b = dict(zip(_paths(want), jax.tree_util.tree_leaves(want)))[leaf]
    scale = float(jnp.max(jnp.abs(b)))
    if leaf.endswith('expert_bias'):
        # the bias chooses and is never differentiated
        assert scale == 0.0 and not np.asarray(a).any()
        return
    assert scale > 0, 'a dead gradient path'
    np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=2e-4)


def test_gradients_through_the_kernels_in_the_interpreter(
        grads, params, monkeypatch):
    """The same gradients with the flash and grouped kernels run by the
    Pallas interpreter (two-width backward, ``grouped_swiglu_bwd_dx`` /
    ``_bwd_dw``)."""
    monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    tokens, targets = _batch()
    with jax.default_matmul_precision('highest'):
        got = jax.grad(lambda p: _model().loss_fn()(
            p, jnp.asarray(tokens), jnp.asarray(targets))[0])(params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(grads[4])):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-12)
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, rtol=5e-4)


def _updater(params, policy, examples, batch=2, recompute='layer'):
    comm = chainermn_tpu.create_communicator(
        'xla', devices=jax.devices()[:1])
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(CFG['train']['lr']), comm)
    model = DeepseekV3LM.from_config(
        CFG, dtype=jnp.bfloat16 if policy else jnp.float32,
        train_recompute=recompute)
    return training.StandardUpdater(
        training.SerialIterator(examples, batch, shuffle=False), opt,
        model.loss_fn(), params, comm, has_aux=True, policy=policy)


def _examples(n=8, t=24):
    tokens, targets = _batch(3, rows=n, t=t)
    return list(zip(tokens, targets))


@pytest.mark.parametrize('policy', [None, 'bf16'])
def test_three_adam_steps_through_the_updater(params, policy):
    examples = _examples()
    with jax.default_matmul_precision('highest'):
        upd = _updater(jax.tree_util.tree_map(jnp.array, params),
                       chainermn_tpu.Policy.bf16() if policy else None,
                       examples)
        losses = [upd.update()['loss'] for _ in range(4)]
    rows = [ref.batch_of(examples[2 * i:2 * i + 2]) for i in range(4)]
    want = ref_common.follow_training(ref.make_grad_fn(CFG), params, rows,
                                      CFG['train'])
    tol = 2e-3 if policy else 1e-5
    np.testing.assert_allclose(losses, want['losses'], rtol=tol)
    change = np.asarray(ref_common.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, upd.params, params)))
    gaps = ref_common.leaf_gaps(change, want['change_norms'])
    assert gaps.mean() < (0.2 if policy else 1e-3), gaps
    # expert_bias: no gradient, and Adam leaves it where it is
    for i in (1, 2):
        np.testing.assert_array_equal(
            upd.params['layer_%d' % i]['expert_bias'],
            params['layer_%d' % i]['expert_bias'])


@pytest.mark.parametrize('recompute', ['layer', None])
def test_the_counters_ride_the_train_update_span(params, recompute):
    """With a recorder live, ``update()`` hangs the aux values the loss
    marks as counters on its ``train_update`` span; without one it
    costs a tuple lookup."""
    examples = _examples()
    upd = _updater(jax.tree_util.tree_map(jnp.array, params), None,
                   examples, recompute=recompute)
    assert upd._span_counters == DeepseekV3LM.span_counters
    telemetry.disable()
    out = upd.update()
    assert set(DeepseekV3LM.span_counters) <= set(out)
    rec = telemetry.enable(outdir=None)
    try:
        upd.update()
        spans = [r for r in rec.events if r.get('type') == 'span'
                 and r['name'] == 'train_update']
    finally:
        telemetry.disable()
    assert len(spans) == 1
    assert spans[0]['assignments'] == 2 * 2 * 24 * 3
    assert 0 <= spans[0]['held_assignments'] <= spans[0]['assignments']
    assert spans[0]['expert_load_max_over_mean'] >= 1.0
    # three layers' kept q, k (2 rows x 2 heads x 24 x 12, float32),
    # v, output (x 8) and statistics (one float32 a row), or nothing
    assert spans[0]['checkpoint_kept_bytes'] == (
        3 * 4 * 24 * (12 + 12 + 8 + 8 + 1) * 4 if recompute else 0)
    # a loss that marks nothing: nothing hung, nothing looked up
    plain = training.StandardUpdater(
        training.SerialIterator(examples, 2, shuffle=False),
        upd.optimizer, lambda p, x, y: _model().loss_fn()(p, x, y)[0],
        upd.params, upd.comm, donate=False)
    assert plain._span_counters == ()


# -- what a layer's checkpoint keeps -----------------------------------

def _flash_calls(jaxpr):
    """The kernels of a jaxpr by name: a Pallas kernel's own (the
    interpreter), the fallback's two scans of ``ops/flash_attention.py``
    told apart by what they carry (``m, l, acc`` forward, ``dq``
    backward)."""
    found = collections.Counter()
    for eqn, _ in walker.iter_eqns(jaxpr):
        if eqn.primitive.name == 'pallas_call':
            found[eqn.params['name']] += 1
        elif (eqn.primitive.name == 'scan' and 'flash_attention.py' in
              eqn.params['jaxpr'].jaxpr.debug_info.func_src_info):
            found['flash_attention_fwd' if eqn.params['num_carry'] == 3
                  else 'flash_attention_bwd'] += 1
    return found


def _loss_of(recompute, tokens, targets, dtype=jnp.float32):
    model = DeepseekV3LM.from_config(CFG, dtype=dtype,
                                     train_recompute=recompute)
    return lambda p: model.loss_fn()(p, jnp.asarray(tokens),
                                     jnp.asarray(targets))


@pytest.mark.parametrize('how,forwards', [
    ('layer', 1), (None, 1), ('bare checkpoint', 2)])
def test_flash_forward_runs_once_a_layer(params, mode, how, forwards):
    """Under ``train_recompute='layer'`` the gradient's jaxpr holds the
    flash forward ONCE a layer, as with no checkpoint at all, while the
    rest of a layer is still made again (the expert kernels twice);
    under a checkpoint with no policy the walker sees it twice."""
    layers = CFG['num_hidden_layers']
    loss = _loss_of('layer' if how == 'layer' else None, *_batch())
    scalar = (lambda p: loss(p)[0]) if how != 'bare checkpoint' else \
        jax.checkpoint(lambda p: loss(p)[0])
    calls = _flash_calls(jax.make_jaxpr(jax.grad(scalar))(params))
    assert calls['flash_attention_fwd'] == forwards * layers
    if mode == 'interpret':
        assert calls['flash_attention_bwd_dq'] == layers
        assert calls['flash_attention_bwd_dkv'] == layers
        assert calls['grouped_swiglu'] == (layers - 1) * (
            1 if how is None else 2)
    else:
        assert calls['flash_attention_bwd'] == layers


@pytest.mark.parametrize('t', [24, 200])
def test_a_layer_keeps_the_flash_kernels_residuals(params, mode, t,
                                                   capsys):
    """What ``jax.ad_checkpoint.print_saved_residuals`` lists from
    ``ops/flash_attention.py`` under ``'layer'``: the kernel's output
    and statistics and its merged ``q`` / ``k`` / ``v`` once a layer,
    at the padded length, each kept by its name (one the forward also
    hands on is listed as the ``reduce_precision`` JAX puts on it);
    their bytes are the step's ``checkpoint_kept_bytes``."""
    layers, rows = CFG['num_hidden_layers'], 2 * CFG['num_attention_heads']
    padded = {24: 24, 200: 256}[t]
    key = CFG['qk_nope_head_dim'] + CFG['qk_rope_head_dim']
    loss = _loss_of('layer', *_batch(t=t))
    jax.ad_checkpoint.print_saved_residuals(lambda p: loss(p)[0], params)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if 'flash_attention.py' in line]
    assert len(kept) == 5 * layers, kept
    assert all('named' in line or 'reduce_precision' in line
               for line in kept)
    assert sum("named 'flash_lse'" in line for line in kept) == layers
    shapes = collections.Counter(
        tuple(map(int, re.match(r'f32\[([\d,]+)\]', line).group(
            1).split(','))) for line in kept)
    assert shapes == {
        (rows, padded, key): 2 * layers,                    # q, k
        (rows, padded, CFG['v_head_dim']): 2 * layers,      # v, out
        ((rows, 1, padded) if mode == 'interpret'
         else (rows, padded)): layers}                      # lse
    aux = jax.eval_shape(loss, params)[1]
    assert set(DeepseekV3LM.span_counters) == set(aux)
    kept_bytes = float(jax.jit(lambda p: loss(p)[1][
        'checkpoint_kept_bytes'])(params))
    assert kept_bytes == sum(4 * int(np.prod(shape)) * n
                             for shape, n in shapes.items())
    none = _loss_of(None, *_batch(t=t))
    assert float(jax.jit(lambda p: none(p)[1]['checkpoint_kept_bytes'])(
        params)) == 0.0


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_what_is_kept_moves_no_number(params, mode, dtype):
    """A kept tensor is the tensor the recomputation would have made:
    the loss and every leaf's gradient under ``'layer'`` are those
    with no checkpoint, bit for bit."""
    batch = _batch(5)
    (kept_loss, _), kept = jax.value_and_grad(
        _loss_of('layer', *batch, dtype=dtype), has_aux=True)(params)
    (loss, _), grads = jax.value_and_grad(
        _loss_of(None, *batch, dtype=dtype), has_aux=True)(params)
    assert float(kept_loss) == float(loss)
    for name, a, b in zip(_paths(grads), jax.tree_util.tree_leaves(kept),
                          jax.tree_util.tree_leaves(grads)):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_all_the_shares_add_up_to_the_uncut_layer():
    """THE TEST THAT TIES THE SHARE TO THE MODEL: the routed parts all
    the shares give, and the shared expert counted once, are the
    reference's layer with every expert held."""
    whole = dict(CFG, n_routed_experts=16, first_expert=0)
    lp = ref.init_params(whole, 3)['layer_1']
    m = jax.random.normal(jax.random.key(0), (40, 32), jnp.float32)
    prec = ref_common.Precision('float32')
    want, _ = ref._experts(m, lp, whole, prec)
    shared = ref._swiglu(m, lp['shared'], prec)
    total, held_sum = shared, 0.0
    with jax.default_matmul_precision('highest'):
        for first in range(0, 16, 4):
            part = dict(lp, experts={k: v[first:first + 4]
                                     for k, v in lp['experts'].items()})
            out, (_, _, held) = _experts.sigmoid_routed_experts(
                m, part, 3, True, 2.448, jnp.float32, first=first)
            total = total + (out - shared)
            held_sum += float(held)
    assert held_sum == 40 * 3          # every assignment held by one
    np.testing.assert_allclose(total, want, atol=2e-6, rtol=2e-5)


def test_rope_interleave_is_the_adjacent_pair_rotation():
    """HF's even-then-odd reordering + rotate-half gives the dot
    products of rotating the ADJACENT dims (2i, 2i+1) in place."""
    freq = _mla.inv_freq(8, 1e6)
    np.testing.assert_allclose(freq, ref.inv_freq({
        'qk_rope_head_dim': 8, 'rope_theta': 1e6}), rtol=1e-6)
    q = jax.random.normal(jax.random.key(1), (12, 3, 8), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (12, 8), jnp.float32)
    positions = jnp.arange(12)

    def explicit(x, pos):
        out = np.array(x, np.float64)
        for i, f in enumerate(np.asarray(freq, np.float64)):
            c, s = np.cos(pos * f), np.sin(pos * f)
            a, b = np.array(x[..., 2 * i]), np.array(x[..., 2 * i + 1])
            out[..., 2 * i], out[..., 2 * i + 1] = a * c - b * s, \
                b * c + a * s
        return out

    want_q = np.stack([explicit(np.asarray(q[t]), t) for t in range(12)])
    want_k = np.stack([explicit(np.asarray(k[t]), t) for t in range(12)])
    got_q = _mla.rope(q, positions[:, None], freq, interleave=True)
    got_k = _mla.rope(k, positions, freq, interleave=True)
    np.testing.assert_allclose(
        jnp.einsum('qhd,kd->hqk', got_q, got_k),
        np.einsum('qhd,kd->hqk', want_q, want_k), atol=1e-5)
    # and the reference turns the pairs in place
    np.testing.assert_allclose(ref.rope_adjacent(k, freq), want_k,
                               atol=1e-5)
    # rotate-half pairing is another rotation
    assert not np.allclose(
        jnp.einsum('qhd,kd->hqk', _mla.rope(q, positions[:, None], freq),
                   _mla.rope(k, positions, freq)),
        np.einsum('qhd,kd->hqk', want_q, want_k), atol=1e-3)


def test_the_published_defaults_are_the_catalog_rows():
    if not os.path.exists(CATALOG):
        pytest.skip('no catalog beside the guides here')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'kanana-2-30b-a3b-instruct-2601']
    model = DeepseekV3LM()
    for key, value in row['config'].items():
        if hasattr(model, key):
            assert getattr(model, key) == value, key
    assert model.qk_head_dim == row['config']['qk_head_dim']
    assert model.router_width == 128 and model.first_expert == 0
    assert DeepseekV3LM.from_config(row['config']) == model


# -- what ``xing4`` and ``afmoe`` computed before the move -------------

def _old_rope(x, positions, inv_freq):
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], -1).astype(x.dtype)


def _old_expanded(w_k, w_v, h, scale, q_nope, q_rope, c, k_r):
    k = jnp.concatenate([
        jnp.einsum('tc,chn->thn', c, w_k),
        jnp.broadcast_to(k_r[:, None, :], (k_r.shape[0], h,
                                           k_r.shape[1]))], -1)
    v = jnp.einsum('tc,chv->thv', c, w_v)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = ops.flash_attention(q[None], k[None], v[None], causal=True,
                              scale=scale)[0]
    return out.reshape(out.shape[0], -1)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_shared_mla_module_is_bit_identical_to_xing4s_own(dtype):
    """``Xing4LM``'s rotary and expanded attention, as they stood in
    ``models/xing4.py`` before they moved to ``models/_mla.py``."""
    from chainermn_tpu.models import Xing4LM
    model = Xing4LM(vocab_size=64, hidden_size=32, intermediate_size=48,
                    moe_intermediate_size=16, num_hidden_layers=2,
                    first_k_dense_replace=1, num_attention_heads=2,
                    q_lora_rank=16, kv_lora_rank=128, qk_nope_head_dim=8,
                    qk_rope_head_dim=4, v_head_dim=8, n_routed_experts=4,
                    num_experts_per_tok=2, hc_mult=2, dtype=dtype,
                    rope_scaling={'type': 'yarn', 'factor': 8,
                                  'beta_fast': 32, 'beta_slow': 1,
                                  'mscale': 1, 'mscale_all_dim': 1,
                                  'original_max_position_embeddings': 16})
    lp = model.init(jax.random.key(0), dtype)['layer_1']
    a = jax.random.normal(jax.random.key(1), (20, 32)).astype(dtype)
    positions = jnp.arange(20, dtype=jnp.int32)
    latent = model._latent(lp, a, positions)
    np.testing.assert_array_equal(
        model._rope(latent[1], positions[:, None]),
        _old_rope(latent[1], positions[:, None], model._inv_freq()))
    got = model._expanded(lp, *latent)
    want = _old_expanded(*model._kvb(lp), 2, model.softmax_scale, *latent)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _old_dropless_experts(x, experts, selected, weights):
    """``ops.dropless_experts`` as it was before a layer could hold a
    share (PR 34's body)."""
    tokens, k = selected.shape
    n_experts = experts['w1'].shape[0]
    flat = selected.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    y = ops.grouped_swiglu(jnp.take(x, order // k, axis=0),
                           experts['w1'], experts['w3'], experts['w2'],
                           sizes)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(tokens, k, -1)
    return jnp.einsum('tkd,tk->td', y.astype(jnp.float32),
                      weights.astype(jnp.float32)), sizes


def _old_sigmoid_routed_experts(m, lp, k, route_norm, route_scale, dtype):
    e = lp['router'].shape[1]
    score = jax.nn.sigmoid(jnp.dot(
        m.astype(jnp.float32), lp['router'].astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(
        score + lp['expert_bias'].astype(jnp.float32), k)
    gate = jnp.take_along_axis(score, chosen, axis=1)
    if route_norm:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    gate = gate * route_scale
    experts = {name: w.astype(dtype)
               for name, w in lp['experts'].items()}
    routed, sizes = _old_dropless_experts(m, experts, chosen, gate)
    out = routed + _experts.swiglu(m, lp['shared'], dtype).astype(
        jnp.float32)
    counters = (jnp.sum(sizes > 0).astype(jnp.float32),
                jnp.max(sizes).astype(jnp.float32)
                * (e / (m.shape[0] * k)))
    return out.astype(dtype), counters


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_default_expert_body_is_bit_identical_to_before(dtype):
    """``afmoe`` and ``xing4`` hold every expert: their outputs bit
    for bit and both their counters as the body before the edit gave
    them, the third counter every assignment; and ``dropless_experts``
    told that ALL the experts are a share gives the same layer."""
    whole = dict(CFG, n_routed_experts=16, first_expert=0)
    lp = jax.tree_util.tree_map(
        lambda x: x.astype(dtype), ref.init_params(whole, 9)['layer_2'])
    m = jax.random.normal(jax.random.key(4), (48, 32)).astype(dtype)
    got, counters = _experts.sigmoid_routed_experts(
        m, lp, 3, True, 2.448, dtype)
    want, old = _old_sigmoid_routed_experts(m, lp, 3, True, 2.448, dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    touched, load, held = (float(c) for c in counters)
    assert touched == float(old[0]) and held == 48 * 3
    assert load == pytest.approx(float(old[1]), rel=1e-6)
    chosen = lax.top_k(jax.nn.sigmoid(jnp.dot(
        m.astype(jnp.float32), lp['router'].astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
        + lp['expert_bias'].astype(jnp.float32), 3)[1]
    gates = jax.random.uniform(jax.random.key(5), chosen.shape)
    experts = {name: w.astype(dtype) for name, w in lp['experts'].items()}
    whole, sizes = ops.dropless_experts(m, experts, chosen, gates)
    share, share_sizes = ops.dropless_experts(m, experts, chosen, gates,
                                              first=0)
    np.testing.assert_array_equal(np.asarray(share), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(share_sizes),
                                  np.asarray(sizes))


# -- what the family refuses, by name ------------------------------------

@pytest.mark.parametrize('entry', [
    'check_serving', 'init_kv_cache', 'init_paged_kv_cache', 'prefill',
    'prefill_paged', 'decode_step', 'decode_step_paged'])
def test_serving_entry_points_raise_by_name(entry):
    with pytest.raises(NotImplementedError, match='trained, not served'):
        getattr(_model(), entry)()


@pytest.mark.parametrize('bad,error', [
    ({'q_lora_rank': 16}, NotImplementedError),
    ({'n_group': 2}, NotImplementedError),
    ({'scoring_func': 'softmax'}, NotImplementedError),
    ({'rope_scaling': {'type': 'yarn'}}, NotImplementedError),
    ({'first_expert': 13}, ValueError),
    ({'train': {'recompute': 'attention'}}, ValueError)])
def test_what_the_family_cannot_do_it_says(bad, error):
    with pytest.raises(error):
        _model(**bad)
