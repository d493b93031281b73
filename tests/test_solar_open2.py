"""The ``solar_open2`` family on the CPU at tiny widths with the
published RATIOS (one ``gqa`` layer to three ``kda`` layers, grouped
K/V heads, four taps, top-k of a router wider than the experts held),
float32, seeded weights: ``models.SolarOpen2LM`` against the
benchmark's plain reference (``chipbench.reference.solar_open2``, which
imports nothing of the program: a scan over positions, no chunks, every
held expert on every token), the shares of a layer adding up to the
uncut layer, and the model through ``GenerationEngine``.

``mode`` runs a case on the jnp twins (``fallback``, what the CPU takes
by default) and on the Pallas kernels in the interpreter.

Tolerance: everything here is float32.  The chunked rule reorders the
recurrence's sums and the experts' rows are sorted before their
products, which moves logits of order 1 by a few 1e-6; 3e-5 holds that
with room and is 1,000 times under what bfloat16 activations move them
by."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import ops, serving
from chainermn_tpu.models import SolarOpen2LM, _experts, solar_open2
from chipbench.reference import common
from chipbench.reference import solar_open2 as ref

LINEAR = dict(short_conv_kernel_size=4, head_dim=16, num_heads=4,
              num_kv_heads=None)
#: one period; this chip's share: experts 4-7 of a router of 16
CFG = dict(
    vocab_size=97, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=4, gqa_layers=[0],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_attn_config=LINEAR, n_routed_experts=4, router_experts=16,
    first_expert=4, n_shared_experts=1, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=1.0, rms_norm_eps=1e-5,
    kda_allow_neg_eigval=True, max_position_embeddings=256)
PAGE = 4
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
    return SolarOpen2LM.from_config(CFG, dtype=jnp.float32)


@pytest.fixture(scope='module')
def params():
    return ref.init_params(CFG, 3, jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG['vocab_size'], size=n).astype(np.int32)


def _reference_logits(params, tokens, cfg=CFG):
    return np.asarray(ref.forward(params, jnp.asarray(tokens), cfg, F32))


def _count(tree):
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


# -- the model against the plain reference ---------------------------

def test_parameter_tree_is_the_references(model, params):
    assert jax.tree_util.tree_map(lambda x: x.shape, params) \
        == model.param_shapes()
    mine = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    assert float(jnp.mean(mine['final_norm'])) == pytest.approx(1, abs=0.05)
    # the decay's parameters are drawn where the reference draws them:
    # per-channel decays spread over about (0.3, 1)
    for tree in (mine, params):
        lp = tree['layer_1']
        g = -np.exp(np.asarray(lp['A_log']))[:, None] * np.log1p(np.exp(
            np.asarray(lp['dt_bias']).reshape(4, 16)))
        decay = np.exp(g)
        assert 0.05 < decay.min() < 0.5 and 0.85 < decay.max() <= 1
    assert 'wg' in mine['layer_0'] and 'conv' not in mine['layer_0']
    assert mine['layer_2']['conv'].shape == (4, 3 * 4 * 16)


def test_published_defaults_and_the_count_the_issue_states():
    full = SolarOpen2LM()
    assert (full.hidden_size, full.num_attention_heads,
            full.num_key_value_heads, full.head_dim, full.group,
            full.moe_intermediate_size, full.vocab_size,
            full.n_routed_experts, full.router_width,
            full.num_experts_per_tok, full.linear_heads,
            full.linear_head_dim, full.conv_taps, full.conv_channels) == (
        4096, 64, 8, 128, 8, 1280, 196608, 320, 320, 8, 64, 128, 4, 24576)
    assert full.gqa_layers == tuple(range(0, 48, 4))
    assert full.has_state_row() and full.window_ring(64) == 0
    assert [full.kda(i) for i in range(5)] == [False, True, True, True,
                                               False]
    assert not SolarOpen2LM(num_hidden_layers=1).has_state_row()
    shapes = full.param_shapes()
    mixer = lambda lp: _count({k: v for k, v in lp.items() if k not in (  # noqa
        'input_norm', 'pre_mlp_norm', 'router', 'expert_bias', 'experts',
        'shared')})
    # a KDA mixer 137.7 M, a gated GQA mixer 109.1 M, one routed expert
    # 15.73 M, router + shared expert + norms 17.0 M
    assert round(mixer(shapes['layer_1']) / 1e6, 1) == 137.7
    assert round(mixer(shapes['layer_0']) / 1e6, 1) == 109.1
    assert round(_count(shapes['layer_0']['experts']) / 320 / 1e6,
                 2) == 15.73
    rest = {k: shapes['layer_0'][k] for k in (
        'input_norm', 'pre_mlp_norm', 'router', 'expert_bias', 'shared')}
    assert round(_count(rest) / 1e6, 1) == 17.0
    # the whole model: the published "250B"
    assert round(_count(shapes) / 1e9, 2) == 250.29
    # the cell's share: 3.31 B parameters
    share = SolarOpen2LM(num_hidden_layers=4, gqa_layers=(0,),
                         n_routed_experts=40, router_experts=320,
                         vocab_size=24576)
    assert round(_count(share.param_shapes()) / 1e9, 2) == 3.31


@pytest.mark.parametrize('n', [5, 70, 130])
def test_full_forward_matches_the_reference(model, params, mode, n):
    """Lengths under one chunk, off the chunk size and over two."""
    tokens = _tokens(n)
    got = jax.jit(model.apply)(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, _reference_logits(params, tokens),
                               atol=ATOL, rtol=0)


@pytest.mark.parametrize('n', [33, 100])
def test_a_prompt_in_segments_is_the_prompt(model, params, monkeypatch,
                                            n):
    """Over ``SEGMENT`` positions a ``kda`` layer carries its state and
    the convolutions' last inputs from segment to segment: at 32 a
    segment, two and four segments, the last one mostly pad."""
    monkeypatch.setattr(solar_open2, 'SEGMENT', 32)
    tokens = _tokens(n, seed=2)
    got = jax.jit(model.apply)(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, _reference_logits(params, tokens),
                               atol=ATOL, rtol=0)


@pytest.mark.parametrize('n_prompt', [9, 16, 21])
def test_a_prefill_whose_last_segments_are_all_pad(
        model, params, monkeypatch, n_prompt):
    """A bucket of 64 in segments of 16: the segments past the prompt
    are identity steps, and the logits, the state row and the decode
    that follows are the reference's."""
    monkeypatch.setattr(solar_open2, 'SEGMENT', 16)
    tokens = _tokens(n_prompt + 6, seed=3)
    want = _reference_logits(params, tokens)
    cache, pages = _paged_setup(model)
    table = np.concatenate([pages, [1]]).astype(np.int32)
    logits, cache, _ = _prefill(model, params, cache, tokens[:n_prompt],
                                64, table)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=ATOL,
                               rtol=0)
    decode = jax.jit(model.decode_step_paged)
    for p in range(n_prompt, n_prompt + 6):
        logits, cache, _ = decode(
            params, cache, jnp.asarray(tokens[p:p + 1]),
            jnp.asarray([p], jnp.int32), jnp.asarray(table[None]))
        np.testing.assert_allclose(logits[0], want[p], atol=ATOL, rtol=0)


def _paged_setup(model, n_full=32, rows=4):
    cache = model.init_paged_kv_cache(1 + n_full, PAGE, n_state_rows=rows)
    return cache, np.arange(1, n_full + 1).astype(np.int32)


def _prefill(model, params, cache, tokens, bucket, table):
    row = np.zeros((1, bucket), np.int32)
    row[0, :len(tokens)] = tokens
    return jax.jit(model.prefill_paged)(
        params, cache, jnp.asarray(row), jnp.asarray(len(tokens)),
        jnp.asarray(table), jnp.asarray(0))


@pytest.mark.parametrize('n_prompt, bucket', [
    (21, 32), (3, 4), (1, 1), (70, 128), (64, 64)])
def test_prefill_then_24_decoded_tokens_match_the_reference(
        model, params, mode, n_prompt, bucket):
    """Logits, not tokens: the prompt in one call padded to its bucket,
    then 24 tokens one at a time through the cache, every one against
    the reference's full forward.  Prompts that fill their bucket and
    prompts that leave it mostly pad (were a pad position to touch the
    state, the tail or a page, every decoded token would be off),
    shorter than the convolutions and longer than a chunk."""
    n_total = n_prompt + 24
    tokens = _tokens(n_total, seed=n_prompt)
    want = _reference_logits(params, tokens)
    cache, pages = _paged_setup(model)
    table = np.concatenate([pages, [2]]).astype(np.int32)
    logits, cache, counters = _prefill(model, params, cache,
                                       tokens[:n_prompt], bucket, table)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=ATOL,
                               rtol=0)
    touched, fullest, held, rows, scanned = (float(c) for c in counters)
    assert (rows, scanned) == (1.0, float(n_prompt))
    # the mean over four layers of what falls on 4 of 16 experts
    assert 0 <= touched <= 4 and 0 <= held <= bucket * 3
    decode = jax.jit(model.decode_step_paged)
    for p in range(n_prompt, n_total):
        logits, cache, counters = decode(
            params, cache, jnp.asarray(tokens[p:p + 1]),
            jnp.asarray([p], jnp.int32), jnp.asarray(table[None]))
        np.testing.assert_allclose(logits[0], want[p], atol=ATOL,
                                   rtol=0, err_msg='position %d' % p)
    assert [float(c) for c in counters[3:]] == [1.0, 0.0]
    assert float(counters[2]) <= 3.0        # one row's three picks


def test_pad_positions_leave_state_and_tail_untouched(model, params,
                                                      mode):
    """One prompt at two buckets: the rows the two prefills write are
    the same to rounding, whatever follows the prompt in the bucket."""
    tokens = _tokens(21)
    cache, pages = _paged_setup(model)
    table = lambda row: np.concatenate([pages, [row]]).astype(  # noqa
        np.int32)
    _, cache, _ = _prefill(model, params, cache, tokens, 32, table(1))
    noisy = np.concatenate([tokens, _tokens(43, seed=9)])
    _, cache, _ = jax.jit(model.prefill_paged)(
        params, cache, jnp.asarray(noisy[None].astype(np.int32)),
        jnp.asarray(21), jnp.asarray(table(3)), jnp.asarray(0))
    for name in ('state', 'tail'):
        for leaf in cache[name]:
            assert float(jnp.max(jnp.abs(leaf[1]))) > 0
            np.testing.assert_allclose(leaf[1], leaf[3], atol=2e-5,
                                       rtol=0)
            assert not np.any(np.asarray(leaf[2]))     # nobody's row


def test_the_cache_leaves(model):
    cache = model.init_paged_kv_cache(9, PAGE, n_state_rows=3)
    assert len(cache['k']) == len(cache['v']) == 1      # the gqa layer
    assert len(cache['state']) == len(cache['tail']) == 3
    assert {leaf.shape for leaf in cache['k']} == {(9, 2, PAGE, 16)}
    # eight heads of dv 16 would fill the 128 lanes; four do not pack
    assert {leaf.shape for leaf in cache['state']} == {(3, 4, 16, 16)}
    assert all(leaf.dtype == jnp.float32 for leaf in cache['state'])
    page, row = model.paged_cache_bytes(cache)
    assert page == 2 * 2 * PAGE * 16 * 4
    assert row == 3 * (4 * 16 * 16 * 4 + 3 * 8 * 128 * 4)
    assert model.decode_paged_grid(cache, [5, 1], 8) == (3, 3)
    assert model.kv_lanes(cache) == ()
    with pytest.raises(ValueError, match='state rows'):
        model.init_paged_kv_cache(9, PAGE)
    # the published widths: a head a lane tile, 4,194,304 B a row a
    # layer, and the three convolutions' 24,576 channels
    assert ops.state_shape(65, 64, 128, 128) == (65, 64, 128, 128)
    assert ops.tail_shape(65, 4, 24576, jnp.bfloat16) == (65, 576, 128)


# -- the shares add up -------------------------------------------------

WHOLE = dict(CFG, n_routed_experts=16, router_experts=16, first_expert=0,
             vocab_size=96)


def _share(tree, first, held):
    """What the chip that holds experts ``first .. first + held - 1``
    has of a whole model's tree: every leaf but its slice of the
    experts."""
    out = dict(tree)
    for name, lp in tree.items():
        if name.startswith('layer_'):
            out[name] = dict(lp, experts={
                k: w[first:first + held]
                for k, w in lp['experts'].items()})
    return out


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(mode):
    """16 experts in 4 shares of 4: the routed parts the four chips
    compute (the program's layer, told which experts it holds), plus
    the shared expert counted ONCE, equal the reference's uncut layer.
    No share holds all of a token's experts here, and one token in
    three has none on a given chip."""
    whole = ref.init_params(WHOLE, 5, jnp.float32)
    lp = whole['layer_1']
    m = jax.random.normal(jax.random.PRNGKey(1), (50, 64), jnp.float32)
    want = ref.moe(m, lp, WHOLE, F32)
    shared = ref._swiglu(m, lp['shared'], F32)
    total, assignments = shared, 0.0
    for first in range(0, 16, 4):
        part = dict(lp, experts={k: w[first:first + 4]
                                 for k, w in lp['experts'].items()})
        out, (_, _, held) = _experts.sigmoid_routed_experts(
            m, part, 3, True, 1.0, jnp.float32, first=first)
        # each chip adds the shared expert too: counted once
        total = total + (out - shared)
        assignments += float(held)
        # the reference, given the same share, says the same
        np.testing.assert_allclose(
            out, ref.moe(m, part, WHOLE, F32, first=first, held=4),
            atol=ATOL, rtol=0)
    assert assignments == 50 * 3        # every pick is on some chip
    np.testing.assert_allclose(total, want, atol=ATOL, rtol=0)
    # and the uncut program layer is the uncut reference layer
    out, _ = _experts.sigmoid_routed_experts(
        m, lp, 3, True, 1.0, jnp.float32)
    np.testing.assert_allclose(out, want, atol=ATOL, rtol=0)


def test_a_vocabulary_slice_is_the_whole_heads_rows():
    """An eighth of the vocabulary: the share's logits are the whole
    head's logits at those rows, from a forward over the same ids."""
    whole = ref.init_params(WHOLE, 5, jnp.float32)
    part = dict(whole, embed={
        'embedding': whole['embed']['embedding'][:12]},
        lm_head=whole['lm_head'][:, :12])
    tokens = np.random.default_rng(0).integers(0, 12, size=20).astype(
        np.int32)
    cut = SolarOpen2LM.from_config(dict(WHOLE, vocab_size=12),
                                   dtype=jnp.float32)
    got = jax.jit(cut.apply)(part, jnp.asarray(tokens)[None])[0]
    want = _reference_logits(whole, tokens, WHOLE)
    assert got.shape == (20, 12)
    np.testing.assert_allclose(got, want[:, :12], atol=ATOL, rtol=0)


def test_a_whole_models_forward_is_the_shares_with_every_expert():
    """The model told it holds all 16 takes the body without masks
    (``first`` is not passed on) and matches the uncut reference."""
    whole = ref.init_params(WHOLE, 5, jnp.float32)
    lm = SolarOpen2LM.from_config(WHOLE, dtype=jnp.float32)
    tokens = np.random.default_rng(1).integers(0, 96, size=40).astype(
        np.int32)
    np.testing.assert_allclose(
        jax.jit(lm.apply)(whole, jnp.asarray(tokens)[None])[0],
        _reference_logits(whole, tokens, WHOLE), atol=ATOL, rtol=0)
    # the share's model on the share's slice of the same tree: the
    # reference given that share
    cfg = dict(WHOLE, n_routed_experts=4, first_expert=8)
    np.testing.assert_allclose(
        jax.jit(SolarOpen2LM.from_config(cfg, dtype=jnp.float32).apply)(
            _share(whole, 8, 4), jnp.asarray(tokens)[None])[0],
        _reference_logits(_share(whole, 8, 4), tokens, cfg), atol=ATOL,
        rtol=0)


# -- through the engine ----------------------------------------------

def _engine(model, params, **kw):
    return serving.GenerationEngine(
        model, params, n_slots=3, max_prompt_len=24, max_len=64,
        paged=True, page_size=PAGE, prefix_sharing=False, eos_id=None,
        **kw)


def test_engine_serves_mixed_lengths_reusing_slots_and_state_rows(
        model, params, mode):
    """Seven requests over three slots through the engine every family
    is served by: slots, pages and state rows are reused and all come
    back, nothing compiles after the warm-up, and every served token is
    the float32 reference's own best."""
    engine = _engine(model, params)
    engine.warmup()
    assert engine._table_width == engine.pages_per_seq + 1
    assert engine.state_pool.n_pages == 1 + 3
    assert engine.window_pool is None
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
    stats = engine.stats()
    assert stats['peak_state_rows_in_use'] == 3
    assert stats['state_rows_in_use'] == stats['pages_in_use'] == 0
    sequences = [np.concatenate([p, np.asarray(r.result(timeout=0))])
                 for p, _, r in requests]
    assert [len(s) - len(p) for s, (p, _, _) in zip(
        sequences, requests)] == [n for _, n, _ in requests]
    gaps = ref.served_token_gaps(
        params, CFG, sequences, [len(p) for p, _, _ in requests], 64)
    assert max(g.max() for g in gaps) < 1e-5


def test_spans_carry_the_families_counters(model, params):
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
    decode = [r for r in spans if r['name'] == 'serve_decode'
              and 'bucket' in r]
    prefill, = [r for r in spans if r['name'] == 'serve_prefill']
    ticks = [r for r in spans if r['name'] == 'serve_tick']
    assert len(decode) == 5
    for r in decode + [prefill]:
        assert set(SolarOpen2LM.serve_counters) <= set(r)
        assert 0 <= r['experts_touched'] <= 4       # of the 4 held
    assert [r['state_rows'] for r in decode] == [r['bucket']
                                                 for r in decode]
    assert all(r['scan_tokens'] == 0 for r in decode)
    # at most every pick of every row of the call on the held experts
    assert all(r['held_assignments'] <= 3 * r['bucket'] for r in decode)
    assert (prefill['state_rows'], prefill['scan_tokens'],
            prefill['tokens']) == (1, 9, 9)
    assert prefill['held_assignments'] <= 3 * prefill['bucket']
    assert decode[0]['kv_positions'] == 10
    # the one gqa layer alone reads pages: 3 live pages of 4
    assert decode[0]['kv_pages_read'] == 3 + decode[0]['bucket'] - 1
    page_bytes, row_bytes = model.paged_cache_bytes(engine._cache_struct)
    busy = [r for r in ticks if r['state_rows_in_use']]
    assert busy and all(
        r['state_bytes_in_use'] == r['state_rows_in_use'] * row_bytes
        and (r['cache_bytes_in_use'] - r['state_bytes_in_use'])
        % page_bytes == 0 for r in busy)
    assert ticks[-1]['cache_bytes_in_use'] == 0


# -- the family's refusals, by name ------------------------------------

@pytest.mark.parametrize('asked, named', [
    (dict(prefix_sharing=True), 'prefix_sharing'),
    (dict(paged=False), 'paged=False'),
    (dict(prefill_chunk=8), 'prefill_chunk'),
    (dict(int8_kv=True), 'int8_kv')])
def test_engine_refuses_what_the_family_has_no_path_for(
        model, params, asked, named):
    kw = dict(n_slots=2, max_prompt_len=8, max_len=16, paged=True,
              page_size=PAGE, prefix_sharing=False)
    kw.update(asked)
    with pytest.raises(ValueError, match='solar_open2.*' + named):
        serving.GenerationEngine(model, params, **kw)


@pytest.mark.parametrize('method', [
    'init_kv_cache', 'prefill', 'decode_step', 'spec_verify',
    'spec_verify_paged', 'kv_cache_specs'])
def test_what_is_not_in_the_family_yet_raises_by_name(model, method):
    with pytest.raises(NotImplementedError, match=method):
        getattr(model, method)()


@pytest.mark.parametrize('changed, error, named', [
    (dict(use_rope=True), NotImplementedError, 'use_rope'),
    (dict(use_gqa_gate=False), NotImplementedError, 'use_gqa_gate'),
    (dict(kda_use_full_proj=True), NotImplementedError,
     'kda_use_full_proj'),
    (dict(first_k_dense_replace=1), NotImplementedError,
     'first_k_dense_replace'),
    (dict(linear_attn_config=dict(LINEAR, num_kv_heads=2)),
     NotImplementedError, 'key / value heads'),
    (dict(gqa_layers=[0, 4]), ValueError, 'gqa_layers'),
    (dict(num_key_value_heads=3), ValueError, 'K/V heads'),
    (dict(first_expert=13), ValueError, "not among the router's 16")])
def test_a_configuration_the_family_cannot_run_is_refused_by_key(
        changed, error, named):
    with pytest.raises(error, match=named):
        SolarOpen2LM.from_config(dict(CFG, **changed))


def test_the_engine_names_no_family():
    from chainermn_tpu.serving import generate, paged
    for module in (generate, paged):
        source = inspect.getsource(module)
        for word in ('solar', 'Solar', 'kda', 'gqa_layers'):
            assert word not in source
