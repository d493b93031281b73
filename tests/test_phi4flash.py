"""The ``phi4flash`` family on the CPU at tiny widths with the published
RATIOS (twice as many query as K/V heads, ``d_inner`` twice the hidden
size, four taps, 16 state values), float32, seeded weights:
``models.Phi4FlashLM`` against the benchmark's plain reference
(``chipbench.reference.phi4flash``, which imports nothing of the
program: a scan over positions, two softmax maps a head pair, every
layer at every position), the chunked selective scan and the one-step
kernel against the recurrence as written, and the model through
``GenerationEngine`` over full pages, ring pages and a state row.

``mode`` runs a case on the jnp twins (``fallback``, what the CPU takes
by default) and on the Pallas kernels in the interpreter.

Tolerance: everything here is float32.  The chunked scan and the packed
attention reorder sums (an associative scan a chunk; one softmax over a
128-lane row whose other half is zeros), which moves logits of order 1
by a few 1e-6; 3e-5 holds that with room and is 1,000 times under what
bfloat16 activations move them by."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import ops, serving
from chainermn_tpu.models import Phi4FlashLM
from chipbench.reference import common
from chipbench.reference import phi4flash as ref

CFG = dict(
    vocab_size=97, hidden_size=64, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    sliding_window=8, mb_per_layer=2, layer_norm_eps=1e-5,
    max_position_embeddings=256, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False)
#: two cross layers and two gated memory units behind the K/V layer
DEEP = dict(CFG, num_hidden_layers=12)
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
    return Phi4FlashLM.from_config(CFG, dtype=jnp.float32)


@pytest.fixture(scope='module')
def params():
    return ref.init_params(CFG, 3, jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG['vocab_size'], size=n).astype(np.int32)


def _reference_logits(params, tokens, cfg=CFG):
    return np.asarray(ref.forward(params, jnp.asarray(tokens), cfg, F32))


def _shapes(spec):
    return jax.tree_util.tree_map(
        lambda leaf: leaf[0], spec,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


# -- the model against the plain reference ---------------------------

@pytest.mark.parametrize('cfg', [CFG, DEEP], ids=['L8', 'L12'])
def test_parameter_tree_is_the_references(cfg):
    model = Phi4FlashLM.from_config(cfg)
    assert _shapes(ref.param_spec(cfg)) == model.param_shapes()
    # no head of its own: the embedding, transposed
    assert 'lm_head' not in model.param_shapes()
    # the float32 leaves stay float32 in a bfloat16 tree, in both
    tree = ref.init_params(cfg, 1, jnp.bfloat16)
    mine = model.init(jax.random.PRNGKey(0), jnp.bfloat16)
    for params in (tree, mine):
        mamba, attn = params['layer_0'], params['layer_1']
        assert {mamba[k].dtype for k in ('A_log', 'D', 'dt_bias')} \
            == {jnp.dtype(jnp.float32)}
        assert attn['lambda_q1'].dtype == jnp.float32
        assert mamba['in_proj'].dtype == attn['wqkv'].dtype == jnp.bfloat16
    # the seeded step spreads log-uniformly over [0.001, 0.1]
    step = np.asarray(jax.nn.softplus(mine['layer_0']['dt_bias']))
    assert 0.00099 < step.min() < 0.002 and 0.05 < step.max() < 0.1001
    np.testing.assert_allclose(
        np.exp(np.asarray(mine['layer_0']['A_log'][0])),
        np.arange(1, 17), rtol=1e-6)


def test_published_defaults_and_the_index_rule():
    model = Phi4FlashLM()
    assert (model.vocab_size, model.hidden_size, model.intermediate_size,
            model.num_hidden_layers, model.num_attention_heads,
            model.num_key_value_heads, model.sliding_window,
            model.mb_per_layer, model.layer_norm_eps,
            model.max_position_embeddings, model.tie_word_embeddings,
            model.mlp_bias, model.lm_head_bias) == (
        200064, 2560, 10240, 32, 40, 20, 512, 2, 1e-5, 262144, True,
        False, False)
    assert (model.d_inner, model.mamba_d_state, model.mamba_d_conv,
            model.mamba_dt_rank, model.head_dim) == (5120, 16, 4, 160, 64)
    kinds = model.kinds
    assert kinds[:16] == ('mamba', 'window') * 8
    assert kinds[16:18] == ('memory', 'full')
    assert kinds[18:] == ('gmu', 'cross') * 7
    assert list(kinds) == ref.layer_kinds(dict(num_hidden_layers=32))
    assert model.lambda_init(3) == pytest.approx(ref.lambda_init(3))
    assert model.window_ring(64) == 9 and model.has_state_row()
    # 3.85 B parameters, the embedding counted once
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)))
    assert 3.84e9 < n < 3.86e9
    with pytest.raises(ValueError, match='do not split'):
        Phi4FlashLM(num_hidden_layers=7)
    with pytest.raises(NotImplementedError, match='untied'):
        Phi4FlashLM(tie_word_embeddings=False)


@pytest.mark.parametrize('n', [5, 21])
def test_full_forward_matches_the_reference(model, params, mode, n):
    """Under the window (8) and far past it."""
    tokens = np.stack([_tokens(n, 1), _tokens(n, 2)])
    got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for row, want in zip(got, (_reference_logits(params, t)
                               for t in tokens)):
        np.testing.assert_allclose(row, want, atol=ATOL)


def test_deep_forward_matches_the_reference():
    """Two gated memory units and two cross layers read ONE memory and
    ONE layer's K/V."""
    model = Phi4FlashLM.from_config(DEEP, dtype=jnp.float32)
    params = ref.init_params(DEEP, 5, jnp.float32)
    tokens = _tokens(17, 4)
    np.testing.assert_allclose(
        np.asarray(model.apply(params, jnp.asarray(tokens[None])))[0],
        _reference_logits(params, tokens, DEEP), atol=ATOL)


def _paged_setup(model, n_full=16, rows=3):
    ring = model.window_ring(PAGE)
    cache = model.init_paged_kv_cache(
        1 + rows * n_full, PAGE, n_window_pages=1 + rows * ring,
        n_state_rows=1 + rows)
    return cache, n_full, ring


def _table(n_full, ring, seat):
    """The table of the sequence in seat ``seat`` (0-based)."""
    return np.concatenate([
        1 + seat * n_full + np.arange(n_full),
        1 + seat * ring + np.arange(ring), [1 + seat]]).astype(np.int32)


def _prefill(model, params, cache, tokens, bucket, table):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(tokens)] = tokens
    return model.prefill_paged(params, cache, jnp.asarray(padded),
                               len(tokens), jnp.asarray(table), 0)


@pytest.mark.parametrize('n_prompt', [5, 13, 24])
def test_prefill_then_decode_through_pool_ring_and_state_row(
        model, params, mode, n_prompt):
    """A prompt under the window (5 of 8), past it (13) and past the
    ring (24 positions on a ring of 3 pages of 4), then decode to 40:
    every ring wraps.  Every logit against the reference's full
    forward."""
    cache, n_full, ring = _paged_setup(model)
    assert ring == 3
    seq = _tokens(40, 7)
    want = _reference_logits(params, seq)
    table = _table(n_full, ring, 1)
    logits, cache, counters = _prefill(
        model, params, cache, seq[:n_prompt], 32, table)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=ATOL)
    # one state row, the prompt's tokens scanned, its positions read
    # by the K/V layer and the one cross layer
    assert [float(c) for c in counters] == [1, n_prompt, 2 * n_prompt]
    idle = np.zeros_like(table)
    step = jax.jit(model.decode_step_paged)
    for pos in range(n_prompt, 40):
        logits, cache, counters = step(
            params, cache, jnp.asarray([0, seq[pos]]),
            jnp.asarray([0, pos]), jnp.asarray(np.stack([idle, table])))
        np.testing.assert_allclose(logits[1], want[pos], atol=ATOL)
    assert [float(c) for c in counters] == [2, 0, 2 * (40 + 1)]


def test_prefill_runs_the_cross_decoder_for_one_position(model, params):
    """The logits of the prefill that runs layers ``N/2 + 1 ..`` for
    the last position alone are those of the forward that runs every
    layer at every position; and it does run them for one: of the
    feed-forwards' first products, five take the bucket's 32 rows (the
    self-decoder and the memory layer) and three take ONE."""
    cache, n_full, ring = _paged_setup(model)
    tokens = _tokens(19, 9)
    logits, _, _ = _prefill(model, params, cache, tokens, 32,
                            _table(n_full, ring, 0))
    full = model.apply(params, jnp.asarray(tokens[None]))[0, -1]
    np.testing.assert_allclose(logits, full, atol=ATOL)
    np.testing.assert_allclose(logits, _reference_logits(params, tokens)[-1],
                               atol=ATOL)

    rows = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            if eqn.primitive.name == 'dot_general' and \
                    eqn.invars[1].aval.shape == (64, 2 * 96):
                rows.append(int(np.prod(eqn.outvars[0].aval.shape[:-1])))

    walk(jax.make_jaxpr(lambda p, c: model.prefill_paged(
        p, c, jnp.zeros((1, 32), jnp.int32), 19,
        jnp.asarray(_table(n_full, ring, 0)), 0))(params, cache).jaxpr)
    assert sorted(rows) == [1, 1, 1, 32, 32, 32, 32, 32]


def test_pad_positions_leave_state_tail_and_rings_untouched(model, params,
                                                           mode):
    """The same prompt in a bucket it fills and in one four times as
    wide: the same state rows, tails, pages and logits."""
    tokens = _tokens(8, 11)
    out = []
    for bucket in (8, 32):
        cache, n_full, ring = _paged_setup(model)
        logits, cache, _ = _prefill(model, params, cache, tokens, bucket,
                                    _table(n_full, ring, 2))
        out.append((logits, cache))
    np.testing.assert_allclose(out[0][0], out[1][0], atol=ATOL)
    for name in ('state', 'tail'):
        for tight, wide in zip(out[0][1][name], out[1][1][name]):
            np.testing.assert_allclose(tight[3], wide[3], atol=ATOL)
            np.testing.assert_array_equal(wide[1], 0)   # another's row
    ring_pages = _table(16, 3, 2)[16:19]
    for tight, wide in zip(out[0][1]['k'][:-1], out[1][1]['k'][:-1]):
        np.testing.assert_allclose(tight[ring_pages[:2]],
                                   wide[ring_pages[:2]], atol=ATOL)


def test_the_cache_holds_one_full_leaf_and_nothing_for_the_cross_decoder():
    model = Phi4FlashLM.from_config(DEEP)
    cache = model.init_paged_kv_cache(33, PAGE, n_window_pages=10,
                                      n_state_rows=4)
    # L12: 3 window layers' rings, then THE full leaf; 4 Mamba layers
    assert [leaf.shape for leaf in cache['k']] == \
        [(10, 1, PAGE, 32)] * 3 + [(33, 1, PAGE, 32)]
    assert [leaf.shape for leaf in cache['state']] == [(4, 1, 16, 128)] * 4
    assert cache['state'][0].dtype == jnp.float32
    assert [leaf.shape for leaf in cache['tail']] == \
        [ops.tail_shape(4, 4, 128, jnp.bfloat16)] * 4
    page, row, ring = model.paged_cache_bytes(cache)
    assert page == 2 * PAGE * 32 * 2            # ONE leaf pair
    assert ring == 3 * page
    assert row == 4 * (16 * 128 * 4 + cache['tail'][0][0].size * 2)
    # the decode grid: the full leaf's pages 3 times over (the K/V
    # layer and two cross layers), the rings' once a window layer
    read, steps = model.decode_paged_grid(cache, [9, 30], 16, 3)
    one = ops.decode_paged_grid([9, 30], (1, PAGE, 32), jnp.bfloat16, 16,
                                head_major=True)
    ringed = ops.decode_paged_grid([9, 30], (1, PAGE, 32), jnp.bfloat16,
                                   3, window=8, head_major=True)
    assert (read, steps) == (3 * one[0] + 3 * ringed[0],
                             3 * one[1] + 3 * ringed[1])
    with pytest.raises(ValueError, match='n_window_pages'):
        model.init_paged_kv_cache(33, PAGE)


@pytest.mark.parametrize('only', [7, 9, 11, 3])
def test_every_cross_layer_reads_the_kv_layers_leaf(only):
    """L12: the K/V layer is 7, the cross layers 9 and 11, 3 a window
    layer.  With every attention layer's output projection zeroed but
    ``only``'s, that layer alone speaks for attention.  Perturb a
    position of the SHARED leaf and the K/V layer and both cross layers
    move; perturb a ring and none of them moves (the window layer
    does)."""
    model = Phi4FlashLM.from_config(DEEP, dtype=jnp.float32)
    params = ref.init_params(DEEP, 5, jnp.float32)
    for i, kind in enumerate(model.kinds):
        if kind in ('window', 'full', 'cross') and i != only:
            lp = params['layer_%d' % i]
            lp['wo'], lp['bo'] = jnp.zeros_like(lp['wo']), \
                jnp.zeros_like(lp['bo'])
    cache, n_full, ring = _paged_setup(model)
    table = _table(n_full, ring, 0)
    seq = _tokens(11, 13)
    _, cache, _ = _prefill(model, params, cache, seq[:10], 16, table)

    def decode(cache):
        return np.asarray(model.decode_step_paged(
            params, cache, jnp.asarray([seq[10]]), jnp.asarray([10]),
            jnp.asarray(table[None]))[0][0])

    base = decode(cache)
    # position 5: page 1 of the sequence, offset 1; inside the window
    # of position 10 too (8), and in ring column 1
    shared = dict(cache, v=cache['v'][:-1] + (
        cache['v'][-1].at[table[1], :, 1].add(1.0),))
    ringed = dict(cache, v=tuple(
        leaf.at[table[n_full + 1], :, 1].add(1.0)
        for leaf in cache['v'][:-1]) + cache['v'][-1:])
    moved_by_shared = np.abs(decode(shared) - base).max()
    moved_by_ring = np.abs(decode(ringed) - base).max()
    if model.kinds[only] == 'window':
        assert moved_by_shared == 0.0 and moved_by_ring > 1e-4
    else:
        assert moved_by_shared > 1e-4 and moved_by_ring == 0.0


@pytest.mark.parametrize('window', [None, 8])
def test_packed_pairs_are_the_literal_two_softmax_form(model, params, mode,
                                                      window):
    """``[q1 | 0]`` and ``[0 | q2]`` over ``[k1 | k2]`` rows, one softmax
    a padded head, against the reference's two maps a pair."""
    t, h, hkv, dh = 21, 4, 2, 16
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(t, heads, dh)), jnp.float32)
               for heads in (h, hkv, hkv))
    lp = dict(params['layer_5'], wo=jnp.eye(h * dh), bo=jnp.zeros(h * dh))
    want = ref._diff_attention(q, k, v, lp, ref.lambda_init(5), CFG, F32,
                               window)
    packed = model._pairs(q[None], k[None], v[None])
    assert [a.shape for a in packed] == [(1, t, h, 2 * dh),
                                         (1, t, 1, 2 * dh)] * 1 \
        + [(1, t, 1, 2 * dh)]
    a = ops.flash_attention(*packed, causal=True, scale=dh ** -0.5,
                            window=window)
    got = model._differ(5, a, lp)[0]
    np.testing.assert_allclose(got, want, atol=ATOL)


# -- the selective scan ------------------------------------------------

def _scan_inputs(t, di=128, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    return (jnp.asarray(rng.normal(size=(t, di)), f32),
            jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                           size=(t, di))), f32),
            -jnp.asarray(np.tile(np.arange(1, n + 1), (di, 1)), f32),
            jnp.asarray(rng.normal(size=(t, n)), f32),
            jnp.asarray(rng.normal(size=(t, n)), f32),
            jnp.asarray(rng.normal(size=(di,)), f32))


@pytest.mark.parametrize('t', [3, 32, 45, 130])
def test_chunked_scan_is_the_per_token_recurrence(mode, t):
    """Under a chunk, a whole one, over the jnp form's boundary (32)
    and over the kernel's (128)."""
    inputs = _scan_inputs(t)
    m, state = ops.selective_scan(*inputs)
    want_m, want_state = ops.selective_scan_reference(*inputs)
    assert m.shape == (t, 128) and state.shape == (16, 128)
    np.testing.assert_allclose(m, want_m, atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=ATOL, rtol=1e-5)


@pytest.mark.parametrize('length', [1, 31, 33, 44])
def test_chunked_scan_stops_at_length(mode, length):
    x, delta, a, b, c, d = _scan_inputs(45, seed=2)
    m, state = ops.selective_scan(x, delta, a, b, c, d, length=length)
    want_m, want_state = ops.selective_scan_reference(
        x[:length], delta[:length], a, b[:length], c[:length], d)
    np.testing.assert_allclose(m[:length], want_m, atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=ATOL, rtol=1e-5)


def test_steps_iterated_are_the_scan(mode):
    """Twelve single steps of three rows in a five-row leaf: each row's
    outputs and final state are the scan's over its own sequence."""
    di, n, t = 128, 16, 12
    runs = [_scan_inputs(t, seed=s) for s in (4, 5, 6)]
    a, d = runs[0][2], runs[0][5]
    leaf = jnp.zeros(ops.state_shape(5, 1, n, di), jnp.float32)
    rows = jnp.asarray([3, 1, 4], jnp.int32)
    outs = []
    for i in range(t):
        m, leaf = ops.selective_scan_step(
            leaf, rows, *(jnp.stack([r[j][i] for r in runs])
                          for j in (0, 1)), a,
            *(jnp.stack([r[j][i] for r in runs]) for j in (3, 4)), d)
        outs.append(m)
    outs = jnp.stack(outs, axis=1)                     # (rows, T, Di)
    for i, (row, run) in enumerate(zip([3, 1, 4], runs)):
        want_m, want_state = ops.selective_scan_reference(
            run[0], run[1], a, run[3], run[4], d)
        np.testing.assert_allclose(outs[i], want_m, atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(leaf[row, 0], want_state, atol=ATOL,
                                   rtol=1e-5)
    np.testing.assert_array_equal(leaf[0], 0)
    np.testing.assert_array_equal(leaf[2], 0)


# -- through the engine ----------------------------------------------

def _engine(model, params, **kw):
    return serving.GenerationEngine(
        model, params, n_slots=3, max_prompt_len=24, max_len=64,
        paged=True, page_size=PAGE, prefix_sharing=False, eos_id=None,
        **kw)


def test_engine_serves_mixed_lengths_reusing_slots_pages_rings_and_rows(
        model, params, mode):
    """Seven requests over three slots: slots, pages, ring pages and
    state rows are reused, no sequence holds more than its ring and its
    one row, everything comes back, and every served token is the
    float32 reference's own best."""
    engine = _engine(model, params)
    engine.warmup()
    ring = model.window_ring(PAGE)
    assert engine._table_width == engine.pages_per_seq + ring + 1
    assert engine.state_pool.n_pages == 1 + 3
    assert engine.window_pool.n_pages == 1 + 3 * ring
    queue = serving.GenerationQueue(max_prompt_len=24, max_queue=64,
                                    page_size=PAGE)
    rng = np.random.default_rng(1)
    requests = []
    for n_prompt, n_out in [(5, 20), (24, 24), (13, 7), (1, 30),
                            (9, 12), (20, 3), (17, 28)]:
        prompt = rng.integers(0, 97, size=n_prompt).astype(np.int32)
        requests.append((prompt, n_out, queue.submit(prompt, n_out)))
    compiled = engine.compile_count
    rows_seen, ring_pages_seen = set(), set()
    while not all(r.done() for _, _, r in requests):
        engine.step(queue)
        live = [s.state_row for s in engine._slots.values()]
        assert all(row >= 1 for row in live)
        assert len(set(live)) == len(live)
        rows_seen.update(live)
        for slot in engine._slots.values():
            assert len(slot.ring) <= ring
            ring_pages_seen.update(slot.ring)
        assert engine.stats()['state_rows_in_use'] == len(live) + len(
            engine._prefilling)
    assert rows_seen == {1, 2, 3}
    assert ring_pages_seen <= set(range(1, 1 + 3 * ring))
    assert engine.compile_count == compiled     # nothing new compiled
    stats = engine.stats()
    assert stats['window_ring'] == ring
    assert stats['peak_state_rows_in_use'] == 3
    assert 0 < stats['peak_window_pages_in_use'] <= 3 * ring
    assert stats['state_rows_in_use'] == 0      # everything came back
    assert stats['window_pages_in_use'] == 0
    assert stats['pages_in_use'] == 0
    for prompt, n_out, request in requests:
        out = np.asarray(request.result(timeout=0))
        assert out.shape == (n_out,)
        seq = np.concatenate([prompt, out])
        logits = _reference_logits(params, seq)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        gap = logits[at].max(-1) - logits[at, seq[at + 1]]
        assert gap.max() < 1e-5


def test_spans_carry_the_state_and_shared_leaf_counters(model, params):
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
    assert [r['state_rows'] for r in decode] == [r['bucket']
                                                 for r in decode]
    assert all(r['scan_tokens'] == 0 for r in decode)
    assert (prefill['state_rows'], prefill['scan_tokens'],
            prefill['shared_kv_positions'], prefill['tokens']) == (
        1, 9, 2 * 9, 9)
    # the first decode call: one live row of 10 positions and pad rows
    # of one, each read by the K/V layer and the one cross layer
    pad = decode[0]['bucket'] - 1
    assert decode[0]['shared_kv_positions'] == 2 * (10 + pad)
    assert decode[0]['kv_positions'] == 10
    assert decode[0]['kv_window_positions'] == 8
    # 3 live pages of 4 in the full leaf twice over, 3 ring pages
    # (positions 2..9 lie in pages 0, 1, 2) in each of two window layers
    assert decode[0]['kv_pages_read'] == 2 * (3 + pad) + 2 * (3 + pad)
    page, row, ring = model.paged_cache_bytes(engine._cache_struct)
    busy = [r for r in ticks if r['state_rows_in_use']]
    assert busy and all(
        r['state_bytes_in_use'] == r['state_rows_in_use'] * row
        and r['cache_bytes_in_use'] == r['state_bytes_in_use']
        + r['full_pages_in_use'] * page + r['window_pages_in_use'] * ring
        and r['full_pages_in_use'] and r['window_pages_in_use']
        for r in busy)
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
    with pytest.raises(ValueError, match='phi4flash.*' + named):
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
        source = inspect.getsource(module)
        for word in ('phi4', 'Phi4', 'mamba', 'Mamba', 'selective_scan',
                     'lambda_', 'shared_kv'):
            assert word not in source
