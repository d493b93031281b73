"""The ``afmoe`` family on the CPU at tiny widths, float32, seeded
weights: ``models.AfmoeLM`` against the benchmark's plain reference
(``chipbench.reference.afmoe``, which imports nothing of the program),
the dropless expert layer against a loop over experts, the grouped and
windowed attention kernels against ``mha_reference`` with repeated K/V
and an explicit mask, and the model through ``GenerationEngine``.

``mode`` runs a case on the jnp twins (``fallback``, what the CPU takes
by default) and on the Pallas kernels in the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import ops, serving
from chainermn_tpu.models import AfmoeLM, TransformerLM
from chipbench.reference import afmoe as ref
from chipbench.reference import common

CFG = dict(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=5, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
    layer_types=['sliding_attention'] * 4 + ['full_attention'],
    sliding_window=8, rms_norm_eps=1e-5, rope_theta=10000.0,
    score_func='sigmoid', route_norm=True, route_scale=2.826,
    mup_enabled=True, max_position_embeddings=256)
PAGE = 4            # a window of 8 over pages of 4: a ring of 3
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
    return AfmoeLM.from_config(CFG, dtype=jnp.float32)


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


def test_published_defaults_and_derived_layer_types():
    full = AfmoeLM()
    assert (full.hidden_size, full.num_attention_heads,
            full.num_key_value_heads, full.head_dim, full.num_experts,
            full.num_experts_per_tok, full.sliding_window,
            full.vocab_size, full.route_scale) == (
        2048, 32, 4, 128, 128, 8, 2048, 200192, 2.826)
    assert full.layer_types[:4] == ('sliding_attention',) * 3 + (
        'full_attention',)
    assert full.layer_types.count('full_attention') == 8
    assert full.window_ring(64) == 33 and full.group == 8
    assert AfmoeLM(num_hidden_layers=1, layer_types=['full_attention']
                   ).window_ring(64) == 0
    with pytest.raises(ValueError, match='layer_types'):
        AfmoeLM(num_hidden_layers=2, layer_types=['full_attention'])


@pytest.mark.parametrize('n', [5, 40])
def test_full_forward_matches_the_reference(model, params, mode, n):
    tokens = _tokens(n)
    got = model.apply(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, _reference_logits(params, tokens),
                               atol=2e-5, rtol=0)


def _paged_setup(model, n_full=16):
    ring = model.window_ring(PAGE)
    cache = model.init_paged_kv_cache(1 + n_full, PAGE,
                                      n_window_pages=1 + ring)
    table = np.concatenate([np.arange(1, n_full + 1),
                            np.arange(1, ring + 1)]).astype(np.int32)
    return cache, table, ring


@pytest.mark.parametrize('n_prompt, bucket', [(21, 32), (3, 4), (8, 8)])
def test_prefill_then_40_decoded_tokens_match_the_reference(
        model, params, mode, n_prompt, bucket):
    """Logits, not tokens: the prompt in one call, then 40 tokens one
    at a time through the paged cache, every one against the
    reference's full forward.  Three windows and more of positions
    over a ring of three pages: every column is overwritten several
    times."""
    n_total = n_prompt + 40
    assert n_total > 3 * CFG['sliding_window']
    tokens = _tokens(n_total, seed=n_prompt)
    want = _reference_logits(params, tokens)
    cache, table, ring = _paged_setup(model)
    assert ring == 3
    row = np.zeros((1, bucket), np.int32)
    row[0, :n_prompt] = tokens[:n_prompt]
    logits, cache, counters = jax.jit(model.prefill_paged)(
        params, cache, jnp.asarray(row), jnp.asarray(n_prompt),
        jnp.asarray(table), jnp.asarray(0))
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=2e-5,
                               rtol=0)
    decode = jax.jit(model.decode_step_paged)
    for p in range(n_prompt, n_total):
        logits, cache, counters = decode(
            params, cache, jnp.asarray(tokens[p:p + 1]),
            jnp.asarray([p], jnp.int32), jnp.asarray(table[None]))
        np.testing.assert_allclose(logits[0], want[p], atol=2e-5,
                                   rtol=0, err_msg='position %d' % p)
    touched, load_max = (float(c) for c in counters)
    # one row, two assignments: two experts, each with its fair share
    # times four
    assert touched == 2.0 and load_max == 4.0


def test_window_layers_write_only_their_ring(model, params, mode):
    """A prompt of 21 over pages of 4 has pages 0..5; a window layer
    banks pages 3, 4, 5 into ring columns 0, 1, 2 and nothing else,
    the full layer banks all six."""
    cache, table, ring = _paged_setup(model)
    row = np.zeros((1, 32), np.int32)
    row[0, :21] = _tokens(21)
    _, cache, _ = jax.jit(model.prefill_paged)(
        params, cache, jnp.asarray(row), jnp.asarray(21),
        jnp.asarray(table), jnp.asarray(0))
    window_leaf = np.asarray(cache['k'][0])
    full_leaf = np.asarray(cache['k'][4])
    assert window_leaf.shape == (1 + ring, 2, PAGE, 8)
    assert full_leaf.shape == (17, 2, PAGE, 8)
    assert all(np.abs(window_leaf[p]).sum() > 0 for p in (1, 2, 3))
    assert all(np.abs(full_leaf[p]).sum() > 0 for p in range(1, 7))
    assert np.abs(full_leaf[7:]).sum() == 0


@pytest.mark.parametrize('what', ['init_kv_cache', 'prefill',
                                  'decode_step', 'spec_verify',
                                  'spec_verify_paged', 'kv_cache_specs'])
def test_missing_twins_raise_by_name(model, what):
    with pytest.raises(NotImplementedError, match=what):
        getattr(model, what)()


def test_int8_cache_raises_by_name(model):
    with pytest.raises(NotImplementedError, match='int8'):
        model.init_paged_kv_cache(9, PAGE, n_window_pages=4,
                                  int8_kv=True)


# -- the dropless expert layer ---------------------------------------

def _experts(key, n_experts=8, d=16, f=8):
    return {name: 0.3 * jax.random.normal(
        jax.random.fold_in(key, i), shape)
        for i, (name, shape) in enumerate(
            (('w1', (n_experts, d, f)), ('w3', (n_experts, d, f)),
             ('w2', (n_experts, f, d))))}


def _loop_over_experts(x, experts, chosen, gates):
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for e, g in zip(np.asarray(chosen[t]), np.asarray(gates[t])):
            a = x[t] @ experts['w1'][e]
            h = a * jax.nn.sigmoid(a) * (x[t] @ experts['w3'][e])
            out[t] += g * np.asarray(h @ experts['w2'][e])
    return out


@pytest.mark.parametrize('tile_m', [None, 8, 32])
def test_uneven_routing_loses_no_row(mode, tile_m):
    """One expert takes half the rows, four take none: every
    assignment is computed (a capacity layer would drop most of expert
    0's)."""
    key = jax.random.PRNGKey(1)
    experts = _experts(key)
    tokens, k = 24, 2
    x = jax.random.normal(jax.random.fold_in(key, 9), (tokens, 16))
    rng = np.random.default_rng(0)
    chosen = np.stack([np.zeros(tokens, np.int64),
                       rng.choice([2, 5, 7], size=tokens)], 1)
    gates = rng.random((tokens, k)).astype(np.float32) + 0.5
    out, sizes = ops.dropless_experts(
        x, experts, jnp.asarray(chosen, jnp.int32), jnp.asarray(gates),
        tile_m=tile_m)
    sizes = np.asarray(sizes)
    assert sizes.sum() == tokens * k            # no row lost
    assert sizes[0] == tokens and sizes[[1, 3, 4, 6]].sum() == 0
    np.testing.assert_allclose(
        out, _loop_over_experts(x, experts, chosen, gates), atol=2e-5,
        rtol=0)


@pytest.mark.parametrize('sizes', [
    [20, 0, 0, 5, 1, 0, 13, 1], [0, 0, 40, 0, 0, 0, 0, 0],
    [1, 1, 1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 0, 0, 0, 3]])
def test_grouped_swiglu_against_the_ragged_reference(mode, sizes):
    key = jax.random.PRNGKey(2)
    experts = _experts(key)
    sizes = jnp.asarray(sizes, jnp.int32)
    x = jax.random.normal(jax.random.fold_in(key, 5),
                          (int(sizes.sum()), 16))
    args = (x, experts['w1'], experts['w3'], experts['w2'], sizes)
    np.testing.assert_allclose(
        ops.grouped_swiglu(*args, tile_m=8),
        ops.grouped_swiglu_reference(*args), atol=2e-5, rtol=0)


def test_an_untouched_expert_is_not_read(monkeypatch):
    """NaN weights in the experts no row chose: the kernel (in the
    interpreter) never fetches them, so nothing of them reaches the
    output."""
    monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    key = jax.random.PRNGKey(3)
    experts = _experts(key)
    sizes = np.asarray([6, 0, 0, 9, 0, 0, 0, 1])
    poisoned = {name: w.at[np.flatnonzero(sizes == 0)].set(jnp.nan)
                for name, w in experts.items()}
    x = jax.random.normal(jax.random.fold_in(key, 5), (16, 16))
    got = ops.grouped_swiglu(x, poisoned['w1'], poisoned['w3'],
                             poisoned['w2'], jnp.asarray(sizes), tile_m=8)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, ops.grouped_swiglu_reference(
        x, experts['w1'], experts['w3'], experts['w2'],
        jnp.asarray(sizes)), atol=2e-5, rtol=0)


def test_router_bias_chooses_and_never_weighs(model, params):
    """A large bias on one expert puts it in every token's top-k; its
    gate is still its own (normalised) score."""
    lp = dict(params['layer_1'])
    lp['expert_bias'] = lp['expert_bias'].at[5].set(10.0)
    m = jax.random.normal(jax.random.PRNGKey(4), (6, 32))
    _, (touched, _) = model._experts(m, lp)
    gates, chosen = ref.route(m, lp, CFG)
    assert np.all(np.any(np.asarray(chosen) == 5, axis=1))
    np.testing.assert_allclose(gates.sum(-1), CFG['route_scale'],
                               rtol=1e-6)
    assert 1 <= float(touched) <= 7


# -- grouped and windowed attention ----------------------------------

def _qkv(t, h, h_kv, d, seed=0):
    key = jax.random.PRNGKey(seed)
    return (jax.random.normal(jax.random.fold_in(key, 1), (1, t, h, d)),
            jax.random.normal(jax.random.fold_in(key, 2),
                              (1, t, h_kv, d)),
            jax.random.normal(jax.random.fold_in(key, 3),
                              (1, t, h_kv, d)))


def _masked_reference(q, k, v, window):
    """``mha_reference``'s arithmetic with K/V repeated per group and
    the window written out as a mask."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if window is None:
        return ops.mha_reference(q, k, v, causal=True)
    t = q.shape[1]
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * q.shape[-1] ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -1e30)
    return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize('h_kv, window', [(2, None), (8, 20), (2, 20),
                                          (1, 7), (4, 96)])
def test_flash_attention_grouped_and_windowed(mode, h_kv, window):
    q, k, v = _qkv(96, 8, h_kv, 16)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=16)
    np.testing.assert_allclose(got, _masked_reference(q, k, v, window),
                               atol=2e-5, rtol=0)


def test_flash_attention_without_the_new_arguments_is_unchanged(mode):
    """Group 1 and no window take the path they took before: the same
    bits as the call that names neither."""
    q, k, v = _qkv(64, 4, 4, 16)
    want = ops.flash_attention(q, k, v, causal=True)
    got = ops.flash_attention(q, k, v, causal=True, window=None)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        want, ops.mha_reference(q, k, v, causal=True), atol=2e-5, rtol=0)
    with pytest.raises(ValueError, match='causal'):
        ops.flash_attention(q, k, v, window=8)


def _pool(n_pages, h_kv, ps, d, seed=4):
    key = jax.random.PRNGKey(seed)
    return (jax.random.normal(jax.random.fold_in(key, 1),
                              (n_pages, h_kv, ps, d)),
            jax.random.normal(jax.random.fold_in(key, 2),
                              (n_pages, h_kv, ps, d)))


def _decode_reference(q, k, v, tables, lengths, window):
    """Each row's live keys gathered through its table (a ring where
    there is a window), K/V repeated, plain softmax."""
    group, ps = q.shape[1] // k.shape[1], k.shape[2]
    out = []
    for b, length in enumerate(lengths):
        first = 0 if window is None else max(length - window, 0)
        pos = np.arange(first, length)
        column = pos // ps
        if window is not None:
            column = column % tables.shape[1]
        pages = tables[b, column]
        kk = jnp.repeat(k[pages, :, pos % ps], group, axis=1)
        vv = jnp.repeat(v[pages, :, pos % ps], group, axis=1)
        s = jnp.einsum('hd,khd->hk', q[b], kk) * q.shape[-1] ** -0.5
        out.append(jnp.einsum('hk,khd->hd', jax.nn.softmax(s, -1), vv))
    return jnp.stack(out)


@pytest.mark.parametrize('group, window, lengths', [
    (4, 20, [5, 23, 61]), (4, None, [5, 23, 61]), (1, 20, [1, 20, 44]),
    (8, 9, [64, 2, 33])])
def test_paged_decode_grouped_and_windowed(mode, group, window, lengths):
    h, d, ps = 8, 16, 4
    k, v = _pool(40, h // group, ps, d)
    q = jax.random.normal(jax.random.PRNGKey(6), (3, h, d))
    width = 16 if window is None else -(-window // ps) + 1
    rng = np.random.default_rng(0)
    tables = np.stack([rng.permutation(np.arange(1, 40))[:width]
                       for _ in range(3)])
    got = ops.flash_attention_decode_paged(
        q, k, v, jnp.asarray(tables), jnp.asarray(lengths), group=group,
        window=window, head_major=True)
    np.testing.assert_allclose(
        got, _decode_reference(q, k, v, tables, lengths, window),
        atol=2e-5, rtol=0)


def test_paged_decode_without_the_new_arguments_is_unchanged(mode):
    """Group 1, no window, the page-major pool: what
    ``gpt2m-serve-closed32`` runs.  Bit-equal to the call that names
    none of the three, and right by the oracle."""
    key = jax.random.PRNGKey(7)
    k = jax.random.normal(jax.random.fold_in(key, 1), (20, 4, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (20, 4, 2, 16))
    q = jax.random.normal(jax.random.fold_in(key, 3), (3, 2, 16))
    tables = jnp.asarray(np.arange(1, 16).reshape(3, 5), jnp.int32)
    lengths = jnp.asarray([3, 20, 9], jnp.int32)
    want = ops.flash_attention_decode_paged(q, k, v, tables, lengths)
    got = ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, group=1, window=None, head_major=False)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        want, ops.decode_attention_paged_reference(q, k, v, tables,
                                                   lengths),
        atol=2e-5, rtol=0)


def test_paged_decode_refuses_what_it_cannot_address():
    k, v = _pool(9, 2, 4, 16)
    q = jnp.zeros((1, 8, 16))
    tables, lengths = jnp.zeros((1, 3), jnp.int32), jnp.ones((1,))
    with pytest.raises(ValueError, match='head_major'):
        ops.flash_attention_decode_paged(q, k, v, tables, lengths,
                                         group=4)
    with pytest.raises(ValueError, match='ring of 3'):
        ops.flash_attention_decode_paged(q, k, v, tables, lengths,
                                         group=4, window=12,
                                         head_major=True)
    with pytest.raises(ValueError, match='groups'):
        ops.flash_attention_decode_paged(q, k, v, tables, lengths,
                                         group=2, head_major=True)


def test_paged_kv_append_writes_one_row_a_sequence(mode):
    k, v = _pool(9, 2, 4, 16)
    new_k = jax.random.normal(jax.random.PRNGKey(8), (3, 2, 16))
    new_v = 2.0 * new_k
    pages, offsets = jnp.asarray([5, 2, 7]), jnp.asarray([0, 3, 1])
    got_k, got_v = ops.paged_kv_append(k, v, new_k, new_v, pages,
                                       offsets)
    want_k = np.asarray(k).copy()
    want_v = np.asarray(v).copy()
    for b, (p, o) in enumerate(zip([5, 2, 7], [0, 3, 1])):
        want_k[p, :, o] = new_k[b]
        want_v[p, :, o] = new_v[b]
    assert np.array_equal(np.asarray(got_k), want_k)
    assert np.array_equal(np.asarray(got_v), want_v)


# -- through the engine ----------------------------------------------

def _engine(model, params, **kw):
    return serving.GenerationEngine(
        model, params, n_slots=3, max_prompt_len=24, max_len=48,
        paged=True, page_size=PAGE, prefix_sharing=False, eos_id=None,
        **kw)


def test_engine_serves_mixed_lengths_reusing_slots_and_rings(
        model, params, mode):
    """Seven requests over three slots: slots and rings are reused,
    sequences grow to six windows, no sequence ever holds more window
    pages than its ring, and every served token is the float32
    reference's own best."""
    engine = _engine(model, params)
    engine.warmup()
    ring = engine.stats()['window_ring']
    assert ring == 3
    queue = serving.GenerationQueue(max_prompt_len=24, max_queue=64,
                                    page_size=PAGE)
    rng = np.random.default_rng(1)
    requests = []
    for n_prompt, n_out in [(5, 20), (24, 24), (13, 7), (1, 30),
                            (9, 12), (20, 3), (17, 28)]:
        prompt = rng.integers(0, 97, size=n_prompt).astype(np.int32)
        requests.append((prompt, n_out, queue.submit(prompt, n_out)))
    compiled = engine.compile_count
    widest_ring = 0
    while not all(r.done() for _, _, r in requests):
        engine.step(queue)
        for slot in engine._slots.values():
            widest_ring = max(widest_ring, len(slot.ring))
            assert len(slot.pages) == -(-slot.position // PAGE)
    assert widest_ring == ring
    assert engine.compile_count == compiled     # nothing new compiled
    stats = engine.stats()
    assert stats['peak_window_pages_in_use'] == 3 * ring
    assert stats['peak_full_pages_in_use'] > 3 * ring
    assert stats['window_pages_in_use'] == 0    # every ring came back
    assert stats['full_pages_in_use'] == 0
    for prompt, n_out, request in requests:
        out = np.asarray(request.result(timeout=0))
        assert out.shape == (n_out,)
        seq = np.concatenate([prompt, out])
        logits = _reference_logits(params, seq)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        gap = logits[at].max(-1) - logits[at, seq[at + 1]]
        assert gap.max() < 1e-5


def test_a_sequence_three_windows_long_holds_exactly_its_ring(
        model, params):
    engine = _engine(model, params)
    queue = serving.GenerationQueue(max_prompt_len=24, max_queue=8,
                                    page_size=PAGE)
    request = queue.submit(_tokens(4), 3 * CFG['sliding_window'])
    held = []
    while not request.done():
        engine.step(queue)
        held += [(s.position, len(s.ring), len(s.pages))
                 for s in engine._slots.values()]
    position, ring, pages = held[-1]
    assert position >= 3 * CFG['sliding_window']
    assert ring == engine.stats()['window_ring'] == 3
    assert pages == -(-position // PAGE) > 2 * ring
    assert max(r for _, r, _ in held) == 3


def test_spans_carry_the_expert_counters_and_page_counts(model, params):
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
    for r in decode + [prefill]:
        assert 1 <= r['experts_touched'] <= CFG['num_experts']
        assert r['expert_load_max'] >= 1
    assert prefill['tokens'] == 9
    # positions attended: 10 live at the first decode step, of which a
    # window layer sees 8
    assert decode[0]['kv_positions'] == 10
    assert decode[0]['kv_window_positions'] == 8
    # the paged decode kernel's grid: 3 live pages of 4 in the full
    # layer and in each of the 4 window layers (a pad row reads the
    # scratch page), one page a step (a head-major page of 4 rows is
    # under a sublane tile) and no dead step
    pad = decode[0]['bucket'] - 1
    assert decode[0]['kv_pages_read'] == 5 * (3 + pad)
    assert decode[0]['kv_grid_steps'] == decode[0]['kv_pages_read']
    assert all(r['window_pages_in_use'] <= r['full_pages_in_use']
               for r in ticks)
    assert ticks[-1]['window_pages_in_use'] == 0


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
    with pytest.raises(ValueError, match='afmoe.*' + named):
        serving.GenerationEngine(model, params, **kw)


def test_gpt2_family_goes_through_the_same_protocol():
    """``generate.py`` names no family: ``TransformerLM`` has the
    protocol's methods, with no counters and no ring, and a paged
    engine of it sizes its tables as before."""
    import inspect
    from chainermn_tpu.serving import generate
    assert 'chainermn_tpu.models' not in inspect.getsource(generate) \
        .replace(':class:`~chainermn_tpu.models', '') \
        .replace(':func:`chainermn_tpu.models', '')
    lm = TransformerLM(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                       d_ff=32, max_len=16)
    assert lm.serve_counters == () and lm.window_ring(16) == 0
    variables = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 4),
                                                         jnp.int32))
    engine = serving.GenerationEngine(
        lm, variables['params'], n_slots=2, max_prompt_len=4,
        max_len=16, paged=True, page_size=8)
    assert engine._table_width == engine.pages_per_seq == 2
    assert engine.window_pool is None
    logits, cache, counters = lm.prefill_paged(
        variables['params'], engine._cache, jnp.zeros((1, 4), jnp.int32),
        jnp.asarray(2), jnp.asarray([1, 0]), jnp.asarray(0))
    assert counters == () and logits.shape == (32,)
    stats = engine.stats()
    assert stats['window_pages_in_use'] == 0
    assert stats['full_pages_in_use'] == 0
