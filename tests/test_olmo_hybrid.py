"""The ``olmo_hybrid`` family on the CPU at tiny widths with the
published RATIOS (``dk`` half of ``dv``, three linear layers to a full
one, four taps), float32, seeded weights: ``models.OlmoHybridLM``
against the benchmark's plain reference
(``chipbench.reference.olmo_hybrid``, which imports nothing of the
program: a scan over positions, no chunks), the chunked gated delta
rule and the one-step kernels against the recurrence as written, and
the model through ``GenerationEngine``.

``mode`` runs a case on the jnp twins (``fallback``, what the CPU takes
by default) and on the Pallas kernels in the interpreter.

Tolerance: everything here is float32.  The chunked rule reorders the
recurrence's sums (a triangular solve a chunk instead of 32 rank-one
updates), which moves logits of order 1 by a few 1e-6; 3e-5 holds that
with room and is 1,000 times under what bfloat16 activations move them
by."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import ops, serving
from chainermn_tpu.models import AfmoeLM, OlmoHybridLM, TransformerLM
from chipbench.reference import common
from chipbench.reference import olmo_hybrid as ref

CFG = dict(
    vocab_size=97, hidden_size=64, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=4,
    layer_types=(['linear_attention'] * 3 + ['full_attention']) * 2,
    linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=32, linear_value_head_dim=64,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rms_norm_eps=1e-6, max_position_embeddings=256)
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
    return OlmoHybridLM.from_config(CFG, dtype=jnp.float32)


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
    mine = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(params)
    assert float(jnp.mean(mine['final_norm'])) == pytest.approx(1, abs=0.05)
    # the decay's parameters are drawn where the reference draws them
    a_log = np.asarray(mine['layer_0']['A_log'])
    assert -2.5 < a_log.min() and a_log.max() < 0


def test_published_defaults_and_derived_layer_types():
    full = OlmoHybridLM()
    assert (full.hidden_size, full.num_attention_heads, full.head_dim,
            full.intermediate_size, full.vocab_size,
            full.linear_key_head_dim, full.linear_value_head_dim,
            full.linear_conv_kernel_dim, full.conv_channels) == (
        3840, 30, 128, 11008, 100352, 96, 192, 4, 11520)
    assert full.layer_types[:4] == ('linear_attention',) * 3 + (
        'full_attention',)
    assert full.layer_types.count('full_attention') == 8
    assert full.has_state_row() and full.window_ring(64) == 0
    assert not OlmoHybridLM(
        num_hidden_layers=1,
        layer_types=['full_attention']).has_state_row()
    with pytest.raises(ValueError, match='layer_types'):
        OlmoHybridLM(num_hidden_layers=2, layer_types=['full_attention'])
    # the parameter count the configuration's file states: 215.6 M a
    # linear layer, 185.8 M a full one, 770.7 M of embedding and head
    shapes = full.param_shapes()
    count = lambda tree: sum(                          # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert round(count(shapes["layer_0"]) / 1e6, 1) == 215.6
    assert round(count(shapes['layer_3']) / 1e6, 1) == 185.8
    assert round(count(shapes) / 1e9, 2) == 7.43


@pytest.mark.parametrize('n', [5, 70, 130])
def test_full_forward_matches_the_reference(model, params, mode, n):
    """Lengths under one chunk, off the chunk size and over two."""
    tokens = _tokens(n)
    got = model.apply(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, _reference_logits(params, tokens),
                               atol=ATOL, rtol=0)


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
    (21, 32), (3, 4), (8, 8), (1, 1), (70, 128), (64, 64)])
def test_prefill_then_40_decoded_tokens_match_the_reference(
        model, params, mode, n_prompt, bucket):
    """Logits, not tokens: the prompt in one call padded to its bucket,
    then 40 tokens one at a time through the cache, every one against
    the reference's full forward.  Prompts that fill their bucket and
    prompts that leave it mostly pad (the pad-is-identity rule: were a
    pad position to touch the state or the tail, every decoded token
    would be off), shorter than the convolution and longer than a
    chunk."""
    n_total = n_prompt + 40
    tokens = _tokens(n_total, seed=n_prompt)
    want = _reference_logits(params, tokens)
    cache, pages = _paged_setup(model)
    table = np.concatenate([pages, [2]]).astype(np.int32)
    logits, cache, counters = _prefill(model, params, cache,
                                       tokens[:n_prompt], bucket, table)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=ATOL,
                               rtol=0)
    assert [float(c) for c in counters] == [1.0, float(n_prompt)]
    decode = jax.jit(model.decode_step_paged)
    for p in range(n_prompt, n_total):
        logits, cache, counters = decode(
            params, cache, jnp.asarray(tokens[p:p + 1]),
            jnp.asarray([p], jnp.int32), jnp.asarray(table[None]))
        np.testing.assert_allclose(logits[0], want[p], atol=ATOL,
                                   rtol=0, err_msg='position %d' % p)
    assert [float(c) for c in counters] == [1.0, 0.0]


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
    row = noisy[None].astype(np.int32)
    _, cache, _ = jax.jit(model.prefill_paged)(
        params, cache, jnp.asarray(row), jnp.asarray(21),
        jnp.asarray(table(3)), jnp.asarray(0))
    for name in ('state', 'tail'):
        for leaf in cache[name]:
            assert float(jnp.max(jnp.abs(leaf[1]))) > 0
            np.testing.assert_allclose(leaf[1], leaf[3], atol=2e-5,
                                       rtol=0)
            assert not np.any(np.asarray(leaf[2]))     # nobody's row


def test_a_reused_state_row_carries_nothing_over(model, params, mode):
    """A second sequence prefilled into the row a first one left: its
    logits are those of a fresh cache."""
    first, second = _tokens(30, seed=4), _tokens(11, seed=5)
    cache, pages = _paged_setup(model)
    table = np.concatenate([pages, [1]]).astype(np.int32)
    _, cache, _ = _prefill(model, params, cache, first, 32, table)
    decode = jax.jit(model.decode_step_paged)
    _, cache, _ = decode(params, cache, jnp.asarray([7]),
                         jnp.asarray([30], jnp.int32),
                         jnp.asarray(table[None]))
    logits, cache, _ = _prefill(model, params, cache, second, 16, table)
    want = _reference_logits(params, np.concatenate([second, [5]]))
    np.testing.assert_allclose(logits, want[10], atol=ATOL, rtol=0)
    logits, _, _ = decode(params, cache, jnp.asarray([5]),
                          jnp.asarray([11], jnp.int32),
                          jnp.asarray(table[None]))
    np.testing.assert_allclose(logits[0], want[11], atol=ATOL, rtol=0)


def test_a_linear_layer_owns_no_page(model):
    cache = model.init_paged_kv_cache(9, PAGE, n_state_rows=3)
    assert len(cache['k']) == len(cache['v']) == 2      # full layers
    assert len(cache['state']) == len(cache['tail']) == 6
    assert {leaf.shape for leaf in cache['k']} == {(9, 4, PAGE, 16)}
    # two heads of dv 64 side by side fill the 128 lanes
    assert {leaf.shape for leaf in cache['state']} == {(3, 2, 32, 128)}
    assert all(leaf.dtype == jnp.float32 for leaf in cache['state'])
    page, row = model.paged_cache_bytes(cache)
    assert page == 2 * 2 * 4 * PAGE * 16 * 4
    assert row == 6 * (4 * 32 * 64 * 4 + 3 * 8 * 128 * 4)
    assert model.decode_paged_grid(cache, [5, 1], 8) == (2 * 3, 2 * 3)
    with pytest.raises(ValueError, match='state rows'):
        model.init_paged_kv_cache(9, PAGE)
    # the published widths: 30 heads of 96 x 192 as 15 x (96, 384)
    assert ops.state_shape(49, 30, 96, 192) == (49, 15, 96, 384)
    assert ops.tail_shape(49, 4, 11520, jnp.bfloat16) == (49, 288, 128)


# -- the ops against the recurrence as written ------------------------

def _rule_inputs(t, heads=4, dk=32, dv=64, seed=0, decay=(0.45, 0.55),
                 beta=(1.9, 2.0)):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True))  # noqa
    q = unit(rng.normal(size=(t, heads, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(t, heads, dk)))
    v = rng.normal(size=(t, heads, dv))
    g = np.log(rng.uniform(*decay, size=(t, heads)))
    b = rng.uniform(*beta, size=(t, heads))
    s0 = rng.normal(size=(heads, dk, dv))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b, s0))


@pytest.mark.parametrize('t', [1, 5, 31, 32, 33, 63, 64, 65, 150])
def test_chunked_rule_is_the_per_token_recurrence(t):
    """``beta`` near 2 and the decay near 0.5: the hardest corner the
    configuration allows (a write that overshoots, a memory that
    halves), at lengths under one chunk (32 positions), on its edge,
    one past it, and over several."""
    q, k, v, g, b, s0 = _rule_inputs(t)
    want_o, want_s = ops.gated_delta_reference(q, k, v, g, b, s0)
    got_o, got_s = jax.jit(ops.gated_delta_rule)(q, k, v, g, b, s0)
    np.testing.assert_allclose(got_o, want_o, atol=5e-6, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6, rtol=0)


@pytest.mark.parametrize('c', [1, 2, 16, 32, 64, 128])
def test_unit_lower_inverse_is_the_inverse(c):
    from chainermn_tpu.ops import gated_delta
    a = 0.3 * np.tril(np.random.default_rng(c).normal(size=(3, 5, c, c)),
                      -1).astype(np.float32)
    got = gated_delta._unit_lower_inverse(jnp.asarray(a))
    want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * np.abs(want).max())


def test_chunked_rule_on_one_repeated_key_at_beta_two():
    """A run of one token is a run of one key: ``beta k k^T`` is then 2
    in every entry under the diagonal, whose power series reaches 1e30
    before it cancels.  The substitution inside the chunk holds it."""
    rng = np.random.default_rng(7)
    key = rng.normal(size=(32,))
    key = np.tile(key / np.linalg.norm(key), (200, 2, 1))
    q, k = jnp.asarray(key * 32 ** -0.5, jnp.float32), jnp.asarray(
        key, jnp.float32)
    v = jnp.asarray(rng.normal(size=(200, 2, 64)), jnp.float32)
    g, b = jnp.zeros((200, 2)), jnp.full((200, 2), 2.0)
    want_o, want_s = ops.gated_delta_reference(q, k, v, g, b)
    got_o, got_s = jax.jit(ops.gated_delta_rule)(q, k, v, g, b)
    scale = float(jnp.max(jnp.abs(want_o)))
    # 200 reflections, none of them damped: float32 drifts by 1e-4
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=2e-4 * scale)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=2e-4 * scale)


@pytest.mark.parametrize('decay', [(0.45, 0.55), (0.97, 1.0)])
@pytest.mark.parametrize('length', [1, 40, 97, 128])
def test_chunked_rule_stops_at_length(length, decay):
    q, k, v, g, b, s0 = _rule_inputs(128, seed=1, decay=decay)
    want_o, want_s = ops.gated_delta_reference(
        *(x[:length] for x in (q, k, v, g, b)), s0)
    got_o, got_s = jax.jit(ops.gated_delta_rule)(
        q, k, v, g, b, s0, jnp.asarray(length))
    np.testing.assert_allclose(got_o[:length], want_o, atol=1e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5, rtol=0)


@pytest.mark.parametrize('heads, dk, dv', [(4, 32, 64), (3, 8, 24),
                                           (2, 96, 192)])
def test_one_step_updates_its_rows_where_they_lie(mode, heads, dk, dv):
    """Five rows of a nine-row leaf, two of them idle on the scratch
    row: each live row takes its own update, every other row is as it
    was.  Head counts the lanes pack by two, not at all (three heads of
    24), and the published 96 x 192."""
    q, k, v, g, b, _ = _rule_inputs(5, heads, dk, dv, seed=2)
    state = jax.random.normal(jax.random.PRNGKey(3), (9, heads, dk, dv))
    leaf = ops.pack_state(state)
    assert leaf.shape == ops.state_shape(9, heads, dk, dv)
    assert np.array_equal(ops.unpack_state(leaf, heads), state)
    rows = jnp.asarray([3, 1, 8, 0, 0], jnp.int32)
    want_o, want_s = ops.gated_delta_reference(
        q[:1], k[:1], v[:1], g[:1], b[:1], state[3])
    got_o, got = jax.jit(ops.gated_delta_step)(leaf, rows, q, k, v, g, b)
    got = np.asarray(ops.unpack_state(got, heads))
    np.testing.assert_allclose(got_o[0], want_o[0], atol=2e-6, rtol=0)
    np.testing.assert_allclose(got[3], want_s, atol=2e-6, rtol=0)
    for row in (2, 4, 5, 6, 7):
        assert np.array_equal(got[row], np.asarray(state[row]))
    for i, row in enumerate([3, 1, 8]):
        _, s = ops.gated_delta_reference(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1], b[i:i + 1],
            state[row])
        np.testing.assert_allclose(got[row], s, atol=2e-6, rtol=0)


def test_convolution_step_continues_the_convolution(mode):
    """The whole convolution over 11 positions is the convolution over
    7, its tail, and four single steps through the tail leaf."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(11, 160)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 160)), jnp.float32)
    whole = ops.causal_conv(x, w)
    leaf = jnp.zeros(ops.tail_shape(3, 4, 160, jnp.float32), jnp.float32)
    assert leaf.shape == (3, 3 * 8, 128)
    leaf = leaf.at[2].set(ops.pack_tail(ops.conv_tail(x, 7, 4),
                                        jnp.float32))
    for t in range(7, 11):
        y, leaf = ops.causal_conv_step(
            leaf, jnp.asarray([2, 0], jnp.int32),
            jnp.stack([x[t], x[0]]), w)
        np.testing.assert_allclose(y[0], whole[t], atol=1e-5, rtol=0)
    assert not np.any(np.asarray(leaf[1]))
    # before the sequence there are zeros
    np.testing.assert_allclose(ops.conv_tail(x, 2, 4)[0], 0)
    np.testing.assert_allclose(ops.conv_tail(x, 2, 4)[1:], x[:2])


# -- through the engine ----------------------------------------------

def _engine(model, params, **kw):
    return serving.GenerationEngine(
        model, params, n_slots=3, max_prompt_len=24, max_len=64,
        paged=True, page_size=PAGE, prefix_sharing=False, eos_id=None,
        **kw)


def test_engine_serves_mixed_lengths_reusing_slots_and_state_rows(
        model, params, mode):
    """Seven requests over three slots: slots and state rows are
    reused, no sequence holds more than its one row, the rows all come
    back, and every served token is the float32 reference's own
    best."""
    engine = _engine(model, params)
    engine.warmup()
    assert engine._table_width == engine.pages_per_seq + 1
    assert engine.state_pool.n_pages == 1 + 3
    queue = serving.GenerationQueue(max_prompt_len=24, max_queue=64,
                                    page_size=PAGE)
    rng = np.random.default_rng(1)
    requests = []
    for n_prompt, n_out in [(5, 20), (24, 24), (13, 7), (1, 30),
                            (9, 12), (20, 3), (17, 28)]:
        prompt = rng.integers(0, 97, size=n_prompt).astype(np.int32)
        requests.append((prompt, n_out, queue.submit(prompt, n_out)))
    compiled = engine.compile_count
    rows_seen = set()
    while not all(r.done() for _, _, r in requests):
        engine.step(queue)
        live = [s.state_row for s in engine._slots.values()]
        assert all(row >= 1 for row in live)
        assert len(set(live)) == len(live)
        rows_seen.update(live)
        assert engine.stats()['state_rows_in_use'] == len(live) + len(
            engine._prefilling)
    assert rows_seen == {1, 2, 3}
    assert engine.compile_count == compiled     # nothing new compiled
    stats = engine.stats()
    assert stats['peak_state_rows_in_use'] == 3
    assert stats['state_rows_in_use'] == 0      # every row came back
    assert stats['pages_in_use'] == 0
    for prompt, n_out, request in requests:
        out = np.asarray(request.result(timeout=0))
        assert out.shape == (n_out,)
        seq = np.concatenate([prompt, out])
        logits = _reference_logits(params, seq)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        gap = logits[at].max(-1) - logits[at, seq[at + 1]]
        assert gap.max() < 1e-5


def test_spans_carry_the_state_counters(model, params):
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
    # a decode call moves the state of every row of its bucket (a pad
    # row moves the scratch row); a prefill its one row, through the
    # chunked rule over the prompt's real tokens
    assert [r['state_rows'] for r in decode] == [r['bucket']
                                                 for r in decode]
    assert all(r['scan_tokens'] == 0 for r in decode)
    assert (prefill['state_rows'], prefill['scan_tokens'],
            prefill['tokens']) == (1, 9, 9)
    assert decode[0]['kv_positions'] == 10
    # the two full layers alone read pages: 3 live pages of 4 a layer
    pad = decode[0]['bucket'] - 1
    assert decode[0]['kv_pages_read'] == 2 * (3 + pad)
    page_bytes, row_bytes = model.paged_cache_bytes(engine._cache_struct)
    busy = [r for r in ticks if r['state_rows_in_use']]
    assert busy and all(
        r['state_bytes_in_use'] == r['state_rows_in_use'] * row_bytes
        and r['cache_bytes_in_use'] > r['state_bytes_in_use']
        and (r['cache_bytes_in_use'] - r['state_bytes_in_use'])
        % page_bytes == 0 for r in busy)
    assert ticks[-1]['state_rows_in_use'] == 0
    assert ticks[-1]['cache_bytes_in_use'] == 0


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
    with pytest.raises(ValueError, match='olmo_hybrid.*' + named):
        serving.GenerationEngine(model, params, **kw)


@pytest.mark.parametrize('method', [
    'init_kv_cache', 'prefill', 'decode_step', 'spec_verify',
    'spec_verify_paged', 'kv_cache_specs'])
def test_what_is_not_in_the_family_yet_raises_by_name(model, method):
    with pytest.raises(NotImplementedError, match=method):
        getattr(model, method)()


def test_the_engine_names_no_family():
    from chainermn_tpu.serving import generate, paged
    for module in (generate, paged):
        source = inspect.getsource(module) \
            .replace(':class:`~chainermn_tpu.models', '') \
            .replace(':func:`chainermn_tpu.models', '')
        assert 'chainermn_tpu.models' not in source
        for word in ('olmo', 'Olmo', 'gated_delta', 'linear_attention'):
            assert word not in source.replace('OlmoHybridLM` all have',
                                              '')


@pytest.mark.parametrize('family', ['gpt2', 'afmoe'])
def test_other_families_go_through_the_same_protocol(family):
    """``TransformerLM`` and ``AfmoeLM`` answer ``has_state_row()`` no:
    their engines build no state pool, their tables are as wide as
    before and their stats read zero rows."""
    if family == 'gpt2':
        lm = TransformerLM(vocab_size=32, d_model=16, n_heads=2,
                           n_layers=1, d_ff=32, max_len=16)
        weights = lm.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))['params']
        kw, ring = {}, 0
    else:
        lm = AfmoeLM(
            vocab_size=32, hidden_size=16, intermediate_size=24,
            moe_intermediate_size=8, num_hidden_layers=2,
            num_dense_layers=1, num_attention_heads=2,
            num_key_value_heads=1, head_dim=8, num_experts=4,
            num_experts_per_tok=2,
            layer_types=['sliding_attention', 'full_attention'],
            sliding_window=8, max_position_embeddings=16,
            dtype=jnp.float32)
        weights = lm.init(jax.random.PRNGKey(0))
        kw, ring = dict(prefix_sharing=False), 2
    assert not lm.has_state_row()
    engine = serving.GenerationEngine(
        lm, weights, n_slots=2, max_prompt_len=4, max_len=16,
        paged=True, page_size=8, **kw)
    assert engine.state_pool is None
    assert engine._table_width == engine.pages_per_seq + ring == 2 + ring
    queue = serving.GenerationQueue(max_prompt_len=4, max_queue=4,
                                    page_size=8)
    request = queue.submit(np.asarray([1, 2, 3], np.int32), 4)
    while not request.done():
        engine.step(queue)
    assert len(request.result(timeout=0)) == 4
    assert all(slot.state_row == 0
               for slot in engine._slots.values())
    stats = engine.stats()
    assert stats['state_rows_in_use'] == 0
    assert stats['peak_state_rows_in_use'] == 0
