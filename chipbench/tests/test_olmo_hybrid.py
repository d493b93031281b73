"""The ``olmo-hybrid-7b`` configuration and its cell: the files as
published, the plain reference against cases small enough to check by
hand, the byte and operation counts, the reader's way of finding the
chunked rule in a trace, and a rehearsal of a tiny ``olmo_hybrid`` cell
through the real harness on the CPU."""

import json
import os

import numpy as np
import pytest

import tiny
from chipbench import harness, hybrid_rooflines, traffic

ROOT = harness.ROOT
CELL = 'olmo-hybrid-serve-closed48'
PERIOD = ['linear_attention'] * 3 + ['full_attention']
#: the catalog row's ``config``, as published
PUBLISHED = {
    'model_type': 'olmo_hybrid', 'vocab_size': 100352,
    'hidden_size': 3840, 'intermediate_size': 11008,
    'num_attention_heads': 30, 'num_key_value_heads': 30,
    'hidden_act': 'silu', 'max_position_embeddings': 65536,
    'attention_bias': False, 'rms_norm_eps': 1e-06,
    'tie_word_embeddings': False, 'linear_num_key_heads': 30,
    'linear_num_value_heads': 30, 'linear_key_head_dim': 96,
    'linear_value_head_dim': 192, 'linear_conv_kernel_dim': 4,
    'linear_allow_neg_eigval': True,
    'rope_parameters': {'rope_theta': None}}
TINY = {
    'family': 'olmo_hybrid', 'vocab_size': 97, 'hidden_size': 64,
    'intermediate_size': 96, 'num_hidden_layers': 8,
    'num_attention_heads': 4, 'num_key_value_heads': 4,
    'layer_types': PERIOD * 2, 'linear_num_key_heads': 4,
    'linear_num_value_heads': 4, 'linear_key_head_dim': 32,
    'linear_value_head_dim': 64, 'linear_conv_kernel_dim': 4,
    'linear_allow_neg_eigval': True, 'rms_norm_eps': 1e-6,
    'max_position_embeddings': 256}
TINY_MIX = {
    'kind': 'serve_closed', 'n_clients': 4, 'warm_seconds': 0.3,
    'engine': {'n_slots': 4, 'max_prompt_len': 16, 'max_len': 48,
               'paged': True, 'page_size': 4},
    'check_requests': 3, 'check_pad_to': 48,
    'pairs': [[4, 20], [7, 9], [9, 30], [12, 12], [16, 32], [5, 16]]}


def _json(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def bench():
    return _json('BENCHMARK.json')


@pytest.fixture(scope='module')
def cfg():
    return _json('chipbench/configs/olmo-hybrid-7b.json')


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_published_key_is_as_published(cfg, key):
    assert cfg[key] == PUBLISHED[key]


def test_only_depth_is_cut_and_each_cut_states_its_published_value(
        bench, cfg):
    entry, = [c for c in bench['configs'] if c['name'] == 'olmo-hybrid-7b']
    assert entry['file'] == 'chipbench/configs/olmo-hybrid-7b.json'
    assert entry['source'] == cfg['source'] == (
        'https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/'
        'config.json')
    assert entry['reduced'] == ['num_hidden_layers', 'layer_types']
    assert set(cfg['published']) == set(entry['reduced'])
    assert cfg['published']['num_hidden_layers'] == 32
    # the first two whole 3 : 1 periods
    assert cfg['num_hidden_layers'] == len(cfg['layer_types']) == 8
    assert cfg['layer_types'] == PERIOD * 2
    assert 'four pipeline stages' in cfg['deployment'] and cfg['assumed']
    assert cfg['family'] == 'olmo_hybrid' and 'train' not in cfg
    # every key the file holds beside the published ones is the
    # benchmark's own
    assert set(cfg) - set(PUBLISHED) == {
        'family', 'source', 'num_hidden_layers', 'layer_types',
        'published', 'deployment', 'precision', 'assumed'}


def test_the_cell_and_its_traffic(bench):
    cell, = [w for w in bench['workloads'] if w['name'] == CELL]
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'olmo-hybrid-7b', 'closed48-reason', 1)
    assert bench['workloads'][-1] is cell       # appended, not inserted
    mix = _json('chipbench/traffic/closed48-reason.json')
    other = _json('chipbench/traffic/closed64-reason.json')
    # the same 256 pairs as the other reasoning cell: the two differ in
    # the model and the slot count, not in what is asked
    assert mix['pairs'] == other['pairs']
    assert mix['generated_from'] == other['generated_from']
    gen = mix['generated_from']
    assert mix['pairs'] == traffic.paired_lengths(
        gen['prompt'], gen['output'], gen['n'], gen['pair_seed'])
    e = mix['engine']
    assert (mix['n_clients'], e['n_slots'], e['max_prompt_len'],
            e['max_len'], e['paged'], mix['warm_seconds']) == (
        48, 48, 3072, 4096, True, 15)
    assert (mix['check_requests'], mix['check_pad_to']) == (4, 4096)
    assert max(p + o for p, o in mix['pairs']) <= e['max_len']
    spec = harness.Spec(CELL)       # every name leads to its file
    assert [m['name'] for m in spec.end_to_end] == [
        'serve_tokens_per_s', 'tpot_p90_ms', 'setup_s']
    reported = {m['name'] for m in spec.end_to_end}
    assert all(m['moves'] in reported for m in spec.per_layer)
    mine = {'state_decode_roofline_share', 'scan_prefill_roofline_share',
            'attn_decode_roofline_share.hybrid', 'state_cache_share'}
    assert {m['name'] for m in spec.per_layer} == mine | {
        'decode_tick_ms', 'decode_occupancy', 'decode_exec_device_ms',
        'pallas_share.serve', 'itl_p99_ms', 'client_resubmit_p99_ms',
        'device_idle_share.serve', 'sched_host_ms',
        'window_compiles.serve', 'decode_pages_per_grid_step',
        'prefill_exec_device_ms.tokens', 'admit_tick_ms.tokens'}
    # the new metrics are this cell's alone, at the end of the list
    assert [m['name'] for m in bench['per_layer'][-4:]] == [
        'state_decode_roofline_share', 'scan_prefill_roofline_share',
        'attn_decode_roofline_share.hybrid', 'state_cache_share']
    assert all(m['workloads'] == [CELL] for m in bench['per_layer'][-4:])
    assert set(spec.limits) == {
        'served_logit_gap_widest', 'served_logit_gap_mean',
        'failed_requests', 'compiles_in_window'}
    assert spec.limits['failed_requests'] == 0
    assert spec.limits['compiles_in_window'] == 0


def test_byte_and_operation_counts(cfg):
    h = hybrid_rooflines
    assert h.layer_kinds(cfg) == (6, 2)
    assert h.state_row_bytes(cfg) == 30 * 96 * 192 * 4 == 2211840
    # 48 rows: each row's state read and written in six layers
    assert h.state_decode_bytes(cfg, 48) == 48 * 6 * 2 * 2211840
    # one row at position 3000: 3,001 keys in each of two full layers,
    # 30 heads x 128 x (K and V) x 2 bytes = 15,360 a key a layer
    assert h.attn_decode_bytes(cfg, 3001) == 3001 * 2 * 15360
    # 1,000 tokens: three products of 2 x 96 x 192 a head
    assert h.scan_prefill_flops(cfg, 1000) == (
        1000 * 3 * 2 * 96 * 192 * 30 * 6)
    # q, k of 30 x 96 and v, z, o of 30 x 192, two bytes each
    assert h.scan_prefill_bytes(cfg, 1000) == (
        1000 * (2 * 2880 + 3 * 5760) * 2 * 6)
    # the bytes bound it: 0.34 ms against 0.10 ms of products
    least = h.scan_prefill_least_seconds(cfg, 1000, 197e12, 819e9)
    assert least == h.scan_prefill_bytes(cfg, 1000) / 819e9
    assert least > h.scan_prefill_flops(cfg, 1000) / 197e12
    # 8.19 GB in 10 ms is the whole of 819 GB/s
    assert h.share(8.19e9, 819e9, 0.010) == pytest.approx(100.0)


def test_the_reader_finds_the_rule_and_the_kernels_by_type(cfg):
    spec = harness.Spec(CELL)
    reader = spec.reader('roofline_hybrid')
    module = reader.__globals__
    assert module['state_leaf'](cfg, 49) == (49, 15, 96, 384)
    scan = module['scan_operation']
    for label in ('fusion f32[30,96,32,32]', 'fusion f32[30,32,32,288]',
                  'fusion bf16[30,96,32,96]', 'fusion f32[30,96,192]',
                  'fusion f32[32,30,32,192]', 'copy f32[64,32,30,192]',
                  'fusion f32[30,64,2,16,16]', 'fusion f32[30,96,2,16]',
                  'reduce-window reduce_window_sum f32[30,64,32]',
                  'while (s32[], f32[30,96,192], f32[96,30,32,192])'):
        assert scan(label, cfg), label
    for label in ('fusion bf16[1,3072,11520]', 'fusion bf16[3072,3840]',
                  'pallas custom-call bf16[30,3072,128]',
                  'fusion bf16[1,32,30,128]', 'fusion f32[3072,30]',
                  'fusion f32[48,30,96]', 'fusion f32[16,30,192]',
                  'broadcast f32[2048,30,192]', 'fusion f32[30,16]',
                  'fusion bf16[6145,30,32,128]', 'fusion f32[100352]'):
        assert not scan(label, cfg), label

    class Run:
        trace = None
    Run.spec = spec
    assert reader(Run, 'state_decode') is None      # no device trace


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rms(x):
    return x / np.sqrt(np.mean(x * x))


def _hand_params(layer):
    eye = np.eye(2, dtype=np.float32)
    ones = np.ones((2,), np.float32)
    layer = dict(layer, post_attn_norm=ones, post_mlp_norm=ones,
                 mlp={'w1': eye, 'w3': eye, 'w2': eye})
    return {'embed': {'embedding': eye}, 'layer_0': layer,
            'final_norm': ones, 'lm_head': eye}


def _hand_cfg(kind):
    return {'hidden_size': 2, 'intermediate_size': 2, 'vocab_size': 2,
            'num_hidden_layers': 1, 'layer_types': [kind],
            'num_attention_heads': 1, 'num_key_value_heads': 1,
            'linear_num_key_heads': 1, 'linear_num_value_heads': 1,
            'linear_key_head_dim': 2, 'linear_value_head_dim': 2,
            'linear_conv_kernel_dim': 4, 'linear_allow_neg_eigval': True,
            'rms_norm_eps': 0.0}


def _after_mixer(x, out):
    """The rest of a layer and the head once the mixer's output is
    known: every matrix is the identity."""
    h = x + _rms(out)
    h = h + _rms(_silu(h) * h)
    return _rms(h)


def test_reference_linear_layer_against_a_two_token_case_by_hand():
    """One linear layer, one head of 2 x 2, two tokens, every
    projection the identity (the gate all ones), taps (0, 0, 1/2, 1),
    decay 1/2 and beta 1: small enough to follow with a pencil."""
    import jax.numpy as jnp
    from chipbench.reference import common, olmo_hybrid as ref

    eye = np.eye(2, dtype=np.float32)
    taps = np.repeat(np.asarray([[0.0], [0.0], [0.5], [1.0]],
                                np.float32), 6, axis=1)
    params = _hand_params({
        'wq': eye, 'wk': eye, 'wv': eye,
        'wz': np.ones((2, 2), np.float32),
        'wa': np.zeros((2, 1), np.float32),
        'wb': np.zeros((2, 1), np.float32), 'conv': taps,
        # g = -exp(0) * softplus(0) = -ln 2: the state halves a token;
        # beta = 2 * sigmoid(0) = 1
        'A_log': np.zeros((1,), np.float32),
        'dt_bias': np.zeros((1,), np.float32),
        'o_norm': np.ones((2,), np.float32), 'wo': eye})
    logits = np.asarray(ref.forward(
        params, jnp.asarray([0, 1]), _hand_cfg('linear_attention'),
        common.Precision('float32')))

    x0, x1 = np.asarray([1.0, 0.0]), np.asarray([0.0, 1.0])
    gate = _silu(np.asarray([1.0, 1.0]))            # z = x @ ones
    # token 0: the convolution sees zeros before it, y = x0; q = k = v
    c0 = _silu(x0)                                  # (0.7311, 0)
    k0 = c0 / np.linalg.norm(c0)                    # (1, 0)
    state = np.outer(k0, c0)                        # from zero: k v^T
    o0 = state.T @ (k0 / np.sqrt(2.0))
    want0 = _after_mixer(x0, _rms(o0) * gate)
    np.testing.assert_allclose(logits[0], want0, rtol=1e-5, atol=1e-6)
    # token 1: y = x1 + x0 / 2 = (1/2, 1)
    c1 = _silu(x1 + 0.5 * x0)                       # (0.3112, 0.7311)
    k1 = c1 / np.linalg.norm(c1)
    state = 0.5 * state                             # the decay
    u = c1 - state.T @ k1                           # what is new in v
    state = state + np.outer(k1, u)
    o1 = state.T @ (k1 / np.sqrt(2.0))
    assert o1 == pytest.approx([0.22008, 0.51694], abs=2e-5)
    want1 = _after_mixer(x1, _rms(o1) * gate)
    np.testing.assert_allclose(logits[1], want1, rtol=1e-5, atol=1e-6)


def test_reference_full_layer_against_a_two_token_case_by_hand():
    """One full layer, one head of 2, identities: the norm over the
    whole of q and k, no positions, scale 2 ** -1/2."""
    import jax.numpy as jnp
    from chipbench.reference import common, olmo_hybrid as ref

    eye = np.eye(2, dtype=np.float32)
    ones = np.ones((2,), np.float32)
    params = _hand_params({'wq': eye, 'wk': eye, 'wv': eye, 'wo': eye,
                           'q_norm': ones, 'k_norm': ones})
    logits = np.asarray(ref.forward(
        params, jnp.asarray([0, 1]), _hand_cfg('full_attention'),
        common.Precision('float32')))
    x0, x1 = np.asarray([1.0, 0.0]), np.asarray([0.0, 1.0])
    # token 0 sees itself alone: attn = v = x0
    np.testing.assert_allclose(logits[0], _after_mixer(x0, x0),
                               rtol=1e-5, atol=1e-6)
    # token 1: q = rms(x1) = (0, sqrt 2); q.k over sqrt 2 is 0 for
    # token 0 and sqrt 2 for itself
    w = np.exp([0.0, np.sqrt(2.0)])
    w = w / w.sum()
    np.testing.assert_allclose(
        logits[1], _after_mixer(x1, w[0] * x0 + w[1] * x1),
        rtol=1e-5, atol=1e-6)


def test_head_readings_a_block_of_the_vocabulary_at_a_time():
    import jax
    import jax.numpy as jnp
    from chipbench.reference import olmo_hybrid as ref

    key = jax.random.PRNGKey(0)
    lm_head = jax.random.normal(key, (16, 96))     # 8 blocks of 12
    x = jax.random.normal(jax.random.fold_in(key, 1), (5, 16))
    chosen = jnp.asarray([0, 11, 12, 95, 50], jnp.int32)
    best, token, picked = ref._head_readings(lm_head, x, chosen,
                                             'float32')
    logits = np.asarray(jnp.dot(x, lm_head, precision='highest'))
    np.testing.assert_allclose(best, logits.max(-1), rtol=1e-6)
    assert np.array_equal(token, logits.argmax(-1))
    np.testing.assert_allclose(
        picked, logits[np.arange(5), np.asarray(chosen)], rtol=1e-6)


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """``tiny.make_root``'s checkout with a tiny ``olmo_hybrid``
    configuration, mix and cell ADDED beside the others."""
    root = tiny.make_root(tmp_path_factory.mktemp('olmo_hybrid'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'hybrid', 'source': 'test', 'why': 'tiny',
         'reduced': [], 'file': 'chipbench/configs/hybrid.json'})
    bench['workloads'].append(
        {'name': 'hybrid-serve', 'config': 'hybrid', 'chips': 1,
         'traffic': 'closed4-state', 'why': 'tiny'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'lm-serve' in m.get('workloads', ()):
            m['workloads'].append('hybrid-serve')
    for relative, obj in (
            ('BENCHMARK.json', bench),
            ('chipbench/configs/hybrid.json', TINY),
            ('chipbench/traffic/closed4-state.json', TINY_MIX),
            # bfloat16 against float32 at toy widths on a CPU (read:
            # 0.083 widest, 0.0087 in the mean; the same engine in
            # float32 reads under 1e-5, ``tests/test_olmo_hybrid.py``)
            ('chipbench/limits/hybrid-serve.json',
             {'served_logit_gap_widest': 0.2,
              'served_logit_gap_mean': 0.02, 'failed_requests': 0,
              'compiles_in_window': 0})):
        tiny._dump(os.path.join(root, relative), obj)
    return root


@pytest.mark.parametrize('trace', [0, 1])
def test_tiny_hybrid_cell_through_the_harness(root, trace):
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'hybrid-serve', trace=trace, seconds=0.6)
    finally:
        telemetry.disable()
    assert result['correct'] is True, result['checks']
    assert result['failed'] == 0 and result['attempted'] > 0
    metrics = result['metrics']
    if not trace:
        assert set(metrics) == {'serve_tokens_per_s', 'ttft_p75_ms',
                                'tpot_p90_ms', 'setup_s'}
        return
    # no chip, so no device trace: the roofline shares are absent; the
    # counters the program hangs on its spans are read.  A sequence's
    # state is 6 x 33 KB however long it is, its K/V 4 KB a page of 4
    assert not any('roofline' in k for k in metrics)
    assert 50 < metrics['state_cache_share']['value'] < 100
    assert metrics['decode_pages_per_grid_step']['value'] == 1.0
    assert metrics['decode_occupancy']['value'] > 0


def test_the_tiny_cell_leaves_other_families_metrics_alone(root):
    """The new metrics read nothing in a cell of another family: the
    line leaves them out and nothing raises."""
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'lm-serve', trace=1, seconds=0.4)
    finally:
        telemetry.disable()
    assert result['correct'] is True, result['checks']
    assert not {'state_cache_share', 'state_decode_roofline_share',
                'scan_prefill_roofline_share',
                'attn_decode_roofline_share.hybrid'} & set(
        result['metrics'])
