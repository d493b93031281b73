"""The ``xing4`` family on the CPU at tiny widths (the published
SHAPE: a 128-lane latent beside a short rotary part, four streams, one
dense layer before the expert layers), float32, seeded weights:
``models.Xing4LM`` against the benchmark's plain reference
(``chipbench.reference.xing4``, which imports nothing of the program
and computes the EXPANDED attention and a Sinkhorn loop), the absorbed
form against the expanded one, the residual path's coefficients, the
router against ``afmoe``'s, and the model through ``GenerationEngine``.

``mode`` runs a case on the jnp twins (``fallback``, what the CPU takes
by default) and on the Pallas kernels in the interpreter.

Tolerance: everything here is float32; the absorbed form reorders two
products, which moves logits of order 1 by a few 1e-7."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import ops, serving
from chainermn_tpu.models import AfmoeLM, Xing4LM, _experts
from chipbench.reference import common
from chipbench.reference import xing4 as ref

CFG = dict(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=2.0, scoring_func='sigmoid',
    topk_method='noaux_tc', hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling={'beta_fast': 32, 'beta_slow': 1, 'factor': 64,
                  'mscale': 1, 'mscale_all_dim': 1,
                  'original_max_position_embeddings': 16, 'type': 'yarn'},
    max_position_embeddings=256)
PAGE = 8
ATOL = 3e-5
F32 = common.Precision('float32')


@pytest.fixture(params=['fallback', 'interpret'])
def mode(request, monkeypatch):
    monkeypatch.delenv('CHAINERMN_TPU_PALLAS', raising=False)
    if request.param == 'interpret':
        monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    else:
        monkeypatch.delenv('CHAINERMN_TPU_PALLAS_INTERPRET',
                           raising=False)
    return request.param


@pytest.fixture(scope='module')
def model():
    return Xing4LM.from_config(CFG, dtype=jnp.float32)


@pytest.fixture(scope='module')
def params():
    return ref.init_params(CFG, 3, jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG['vocab_size'], size=n).astype(np.int32)


def _reference_logits(params, tokens):
    return np.asarray(ref.forward(params, jnp.asarray(tokens), CFG, F32))


# -- the model against the plain reference ---------------------------

def test_parameter_tree_is_the_references(model, params):
    assert jax.tree_util.tree_map(lambda x: x.shape, params) \
        == model.param_shapes()
    mine = model.init(jax.random.PRNGKey(0), jnp.bfloat16)
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    # the residual path's coefficients stay float32 in both
    low = ref.init_params(CFG, 3, jnp.bfloat16)
    for tree in (mine, low):
        layer = tree['layer_1']
        assert {x.dtype for x in jax.tree_util.tree_leaves(
            [layer['hc_attn'], layer['hc_mlp']])} \
            == {jnp.dtype(jnp.float32)}
        assert layer['wq_a'].dtype == layer['router'].dtype \
            == jnp.bfloat16


def test_published_defaults_are_the_catalog_row():
    lm = Xing4LM()
    assert (lm.hidden_size, lm.num_hidden_layers, lm.kv_lora_rank,
            lm.q_lora_rank, lm.qk_head_dim, lm.v_head_dim) \
        == (3584, 40, 512, 768, 192, 128)
    assert (lm.latent_dim, lm.latent_lanes) == (576, 640)
    assert lm.window_ring(64) == 0 and not lm.has_state_row()
    shapes = lm.param_shapes()
    count = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert 28.9e9 < count < 29.9e9        # "29B"
    cut = Xing4LM(num_hidden_layers=6, first_k_dense_replace=1)
    count = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        cut.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)))
    assert round(count / 1e9, 2) == 4.79  # the benchmark's cut
    assert 'mlp' in shapes['layer_1'] and 'experts' in shapes['layer_2']


def test_yarn_frequencies_and_scale_are_the_references(model):
    np.testing.assert_allclose(np.asarray(model._inv_freq()),
                               np.asarray(ref.yarn_inv_freq(CFG)),
                               rtol=1e-6)
    assert model.softmax_scale == pytest.approx(ref.softmax_scale(CFG))
    full = Xing4LM(rope_scaling=dict(
        CFG['rope_scaling'], original_max_position_embeddings=4096))
    m = 0.1 * np.log(64.0) + 1.0
    assert full.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    freq = np.asarray(full._inv_freq())
    plain = 10000.0 ** -(np.arange(0, 64, 2) / 64.0)
    # the fastest dims keep their trained frequency, the slowest are
    # interpolated by the whole factor
    assert freq[0] == pytest.approx(plain[0])
    assert freq[-1] == pytest.approx(plain[-1] / 64, rel=1e-5)


@pytest.mark.parametrize('rejected', [
    dict(scoring_func='softmax'), dict(n_group=8, topk_group=4),
    dict(rope_scaling={'type': 'linear', 'factor': 2}),
    dict(kv_lora_rank=96)])
def test_what_the_family_cannot_compute_is_refused_at_construction(
        rejected):
    with pytest.raises((NotImplementedError, ValueError)):
        Xing4LM.from_config(dict(CFG, **rejected))


@pytest.mark.parametrize('n', [5, 70, 130])
def test_full_forward_matches_the_reference(model, params, mode, n):
    tokens = _tokens(n)
    got = np.asarray(model.apply(params, jnp.asarray(tokens)[None]))[0]
    np.testing.assert_allclose(got, _reference_logits(params, tokens),
                               atol=ATOL)


def _prefill(model, params, cache, tokens, bucket, table):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(tokens)] = tokens
    return model.prefill_paged(params, cache, jnp.asarray(padded),
                               len(tokens), jnp.asarray(table), 0)


@pytest.mark.parametrize('n_prompt, bucket', [
    (1, 8), (8, 8), (13, 16), (16, 16), (19, 32)])
def test_prefill_then_decode_through_the_latent_cache(
        model, params, mode, n_prompt, bucket):
    """Logits, not tokens: the prefill's at the prompt's last position,
    then 30 decode steps (two rows, one of them an idle pad row) across
    page boundaries, each against the reference's full forward."""
    tokens = _tokens(n_prompt + 30, seed=n_prompt)
    want = _reference_logits(params, tokens)
    cache = model.init_paged_kv_cache(24, PAGE)
    assert [leaf.shape for leaf in cache['latent']] \
        == [(24, 1, PAGE, 256)] * 3
    table = np.asarray([3, 5, 7, 9, 11, 13, 15, 0], np.int32)
    logits, cache, counters = _prefill(model, params, cache,
                                       tokens[:n_prompt], bucket, table)
    np.testing.assert_allclose(np.asarray(logits), want[n_prompt - 1],
                               atol=ATOL)
    assert float(counters[2]) == 0.0
    # pages past the prompt's last stay untouched (page 0 takes the
    # bucket's pad pages)
    last = (n_prompt - 1) // PAGE
    for leaf in cache['latent']:
        assert not np.asarray(leaf[table[last + 1]]).any()
        # the row's pad lanes stay zero: they enter the scores
        assert not np.asarray(leaf[..., 136:]).any()
    step = jax.jit(model.decode_step_paged)
    tables = jnp.asarray(np.stack([table, np.zeros_like(table)]))
    for pos in range(n_prompt, n_prompt + 30):
        logits, cache, counters = step(
            params, cache, jnp.asarray([tokens[pos], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables)
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos],
                                   atol=ATOL)
        # positions the kernel reads: the row's, the pad row's one,
        # in every layer
        assert float(counters[2]) == 3 * (pos + 1 + 1)


def test_absorbed_attention_is_the_expanded_one(model, params, mode):
    """One layer, one sequence: the last position's output through the
    latent cache and the absorbed products, against the expanded
    causal attention over the same rows."""
    lp = params['layer_1']
    t = 21
    a = jax.random.normal(jax.random.PRNGKey(4), (t, 32), jnp.float32)
    latent = model._latent(lp, a, jnp.arange(t, dtype=jnp.int32))
    want = np.asarray(model._expanded(lp, *latent))[-1]
    q_nope, q_rope, c, k_r = latent
    rows = model._latent_rows(c, k_r)
    leaf = jnp.zeros((6, 1, PAGE, 256), jnp.float32).at[
        jnp.asarray([2, 4, 5])].set(jnp.pad(
            rows, ((0, 3), (0, 0))).reshape(3, 1, PAGE, 256))
    w_k, w_v = model._kvb(lp)
    q = jnp.concatenate([jnp.einsum('thn,chn->thc', q_nope[-1:], w_k),
                         q_rope[-1:]], -1)
    ctx = ops.flash_attention_decode_paged(
        jnp.pad(q, ((0, 0), (0, 0), (0, 120))), leaf, None,
        jnp.asarray([[2, 4, 5]], jnp.int32), jnp.asarray([t], jnp.int32),
        scale=model.softmax_scale, group=4, head_major=True,
        value_lanes=128)
    got = jnp.einsum('thc,chv->thv', ctx, w_v).reshape(-1)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


# -- the residual path -------------------------------------------------

def test_mixing_matrix_is_doubly_stochastic_and_differs_by_token(
        model, params, mode):
    x = model._embed(params, jnp.asarray(_tokens(50)))
    # streams that differ, as they do after a layer
    x = x * (1.0 + 0.5 * jnp.arange(4.0))[None, :, None]
    pre, post, res = model._coefficients(x, params['layer_1']['hc_mlp'])
    res = np.asarray(res)
    assert res.shape == (50, 4, 4) and (res > 0).all()
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-3)
    assert np.abs(res - res[:1]).max() > 1e-3          # token-dependent
    assert np.abs(res - 0.25).max() > 1e-3             # and not uniform
    assert ((np.asarray(pre) > 0) & (np.asarray(pre) < 1)).all()
    assert ((np.asarray(post) > 0) & (np.asarray(post) < 2)).all()
    want = ref.coefficients(x, params['layer_1']['hc_mlp'], CFG, F32)
    for got, ref_value in zip((pre, post, res), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_value),
                                   atol=2e-6)


@pytest.mark.parametrize('dtype, wide', [
    (jnp.float32, False), (jnp.bfloat16, False), (jnp.bfloat16, True)])
@pytest.mark.parametrize('tokens', [1, 48, 128, 300])
def test_coefficient_kernel_is_its_reference(monkeypatch, dtype, wide,
                                             tokens):
    """The Pallas kernel in the interpreter against the jnp twin;
    ``wide``: logits of the spread the published widths give (std ~2.4),
    where 20 rounds leave the row sums short of 1 by up to a percent
    and both must agree on by how much."""
    monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    key = jax.random.PRNGKey(tokens)
    x = jax.random.normal(key, (tokens, 4 * 64), jnp.float32).astype(dtype)
    phi = (0.15 if wide else 0.02) * jax.random.normal(
        jax.random.fold_in(key, 1), (24, 4 * 64), jnp.float32)
    alpha = 1.0 + 0.02 * jax.random.normal(jax.random.fold_in(key, 2),
                                           (3,), jnp.float32)
    b = 0.02 * jax.random.normal(jax.random.fold_in(key, 3), (24,),
                                 jnp.float32)
    got = ops.mhc_coefficients(x, phi, alpha, b)
    want = ops.mhc_coefficients_reference(
        x, phi, alpha, b, 4, 20, 1e-6, (-30.0, 30.0), 1e-6)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=3e-6)
    np.testing.assert_allclose(np.asarray(got[2]).sum(-2), 1.0, atol=1e-4)


def test_identity_mixing_with_one_hot_gates_is_the_plain_residual(
        model, params, monkeypatch):
    """``H_res = I``, ``H_pre`` and ``H_post`` one-hot on stream 0: the
    stream is a pre-norm residual transformer's ``x + F(x)`` and the
    other three never move."""
    def fixed(self, x, hp):
        t = x.shape[0]
        one_hot = jnp.broadcast_to(jnp.asarray([1.0, 0, 0, 0]), (t, 4))
        return one_hot, one_hot, jnp.broadcast_to(jnp.eye(4), (t, 4, 4))

    monkeypatch.setattr(Xing4LM, '_coefficients', fixed)
    tokens = jnp.asarray(_tokens(33))
    got = np.asarray(model.apply(params, tokens[None]))[0]
    eps = CFG['rms_norm_eps']
    emb = jnp.take(params['embed']['embedding'], tokens, axis=0)
    x = emb
    for i in range(CFG['num_hidden_layers']):
        lp = params['layer_%d' % i]
        a = ref._rms(x, lp['attn_norm'], eps)
        x = x + F32.einsum('tf,fd->td', ref._attention(a, lp, CFG, F32),
                           lp['wo'])
        m = ref._rms(x, lp['mlp_norm'], eps)
        x = x + (ref._swiglu(m, lp['mlp'], F32) if 'mlp' in lp
                 else ref._experts(m, lp, CFG, F32)[0])
    want = ref.head(params, ref._rms(x + 3.0 * emb, params['final_norm'],
                                     eps), F32)
    np.testing.assert_allclose(got, np.asarray(want), atol=ATOL)


# -- the experts: one body, two families ------------------------------

def test_router_and_dispatch_are_afmoes(params, mode):
    lp = params['layer_2']
    m = jax.random.normal(jax.random.PRNGKey(2), (37, 32), jnp.float32)
    afmoe = AfmoeLM(
        vocab_size=32, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2, num_dense_layers=1,
        num_attention_heads=2, num_key_value_heads=1, head_dim=8,
        num_experts=8, num_experts_per_tok=2, route_scale=2.0,
        layer_types=['sliding_attention', 'full_attention'],
        dtype=jnp.float32)
    theirs, their_counters = afmoe._experts(m, lp)
    mine, counters = _experts.sigmoid_routed_experts(
        m, lp, 2, True, 2.0, jnp.float32)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert [float(c) for c in counters[:2]] \
        == [float(c) for c in their_counters]
    want, chosen = ref._experts(m, lp, CFG, F32)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(want),
                               atol=2e-6)
    assert float(counters[0]) == len(np.unique(np.asarray(chosen)))
    # one body: neither class carries a router of its own
    for cls in (AfmoeLM, Xing4LM):
        assert 'top_k' not in inspect.getsource(cls)


# -- through the engine ------------------------------------------------

def _engine(model, params, **kw):
    return serving.GenerationEngine(
        model, params, n_slots=3, max_prompt_len=24, max_len=64,
        paged=True, page_size=PAGE, prefix_sharing=False, eos_id=None,
        **kw)


def test_engine_serves_mixed_lengths_reusing_slots_and_pages(
        model, params, mode):
    """Seven requests over three slots through the protocol every
    family serves by: every served token is the float32 reference's own
    best, nothing compiles after warm-up, every page comes back."""
    engine = _engine(model, params)
    engine.warmup()
    assert engine._table_width == engine.pages_per_seq == 8
    assert engine.window_pool is None and engine.state_pool is None
    queue = serving.GenerationQueue(max_prompt_len=24, max_queue=64,
                                    page_size=PAGE)
    rng = np.random.default_rng(1)
    requests = []
    for n_prompt, n_out in [(5, 20), (24, 24), (13, 7), (1, 30),
                            (9, 12), (20, 3), (17, 28)]:
        prompt = rng.integers(0, 97, size=n_prompt).astype(np.int32)
        requests.append((prompt, n_out, queue.submit(prompt, n_out)))
    compiled = engine.compile_count
    while not all(r.done() for _, _, r in requests):
        engine.step(queue)
    assert engine.compile_count == compiled
    assert engine.stats()['pages_in_use'] == 0
    for prompt, n_out, request in requests:
        out = np.asarray(request.result(timeout=0))
        assert out.shape == (n_out,)
        seq = np.concatenate([prompt, out])
        logits = _reference_logits(params, seq)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        gap = logits[at].max(-1) - logits[at, seq[at + 1]]
        assert gap.max() < 1e-5


def test_spans_carry_the_latent_counters(model, params):
    from chainermn_tpu import telemetry
    telemetry.disable()
    recorder = telemetry.enable()
    try:
        engine = _engine(model, params)
        queue = serving.GenerationQueue(max_prompt_len=24, max_queue=8,
                                        page_size=PAGE)
        request = queue.submit(_tokens(9), 6)
        while not request.done():
            engine.step(queue)
        spans = [r for r in recorder.events if r.get('type') == 'span']
    finally:
        telemetry.disable()
    # one span a launched call says what the call was (the span that
    # READ its vector, a tick after the one that dispatched it)
    decode = [r for r in spans if r['name'] == 'serve_decode'
              and 'bucket' in r]
    prefill, = [r for r in spans if r['name'] == 'serve_prefill']
    ticks = [r for r in spans if r['name'] == 'serve_tick']
    assert len(decode) == 5
    assert prefill['tokens'] == 9 and prefill['latent_positions'] == 0
    first = decode[0]
    pad = first['bucket'] - 1
    assert first['kv_positions'] == 10
    # every layer's kernel reads the row's 10 positions and a pad row's 1
    assert first['latent_positions'] == 3 * (10 + pad)
    assert first['kv_pages_read'] == 3 * (2 + pad)
    assert 0 < first['experts_touched'] <= 8
    assert first['expert_load_max'] >= 1.0
    page_bytes, row_bytes = model.paged_cache_bytes(engine._cache_struct)
    assert (page_bytes, row_bytes) == (3 * PAGE * 256 * 4, 0)
    busy = [r for r in ticks if r['latent_pages_in_use']]
    assert busy and all(
        r['cache_bytes_in_use'] == r['latent_pages_in_use'] * page_bytes
        for r in busy)
    assert max(r['latent_pages_in_use'] for r in busy) == 2
    assert ticks[-1]['latent_pages_in_use'] == 0
    assert ticks[-1]['cache_bytes_in_use'] == 0


@pytest.mark.parametrize('asked, named', [
    (dict(prefix_sharing=True), 'prefix_sharing'),
    (dict(paged=False), 'paged=False'),
    (dict(prefill_chunk=8), 'prefill_chunk'),
    (dict(int8_kv=True), 'int8_kv'),
    (dict(plan=object()), 'plan'),
    (dict(draft_model=True), 'draft_model')])
def test_engine_refuses_what_the_family_has_no_path_for(
        model, params, asked, named):
    kw = dict(n_slots=2, max_prompt_len=8, max_len=16, paged=True,
              page_size=PAGE, prefix_sharing=False)
    kw.update(asked)
    if 'draft_model' in asked:
        kw.update(draft_model=model, draft_params=params)
    with pytest.raises(ValueError, match='xing4.*' + named):
        serving.GenerationEngine(model, params, **kw)


@pytest.mark.parametrize('method', [
    'init_kv_cache', 'prefill', 'decode_step', 'spec_verify',
    'spec_verify_paged', 'kv_cache_specs'])
def test_what_is_not_in_the_family_yet_raises_by_name(model, method):
    with pytest.raises(NotImplementedError, match=method):
        getattr(model, method)()
    with pytest.raises(NotImplementedError, match='int8 latent'):
        model.init_paged_kv_cache(4, PAGE, int8_kv=True)


def test_the_engine_names_no_family():
    from chainermn_tpu.serving import generate, paged
    for module in (generate, paged):
        source = inspect.getsource(module)
        for word in ('xing', 'Xing', 'mhc', 'kv_lora', 'latent'):
            assert word not in source
