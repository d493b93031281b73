"""The ``solar-open2-250b`` configuration and its cell: the files as
published, the share's arithmetic, the plain reference against a case
small enough to follow by hand, the byte and operation counts (none of
which can read over 100), the reader's way of finding the chunked
per-channel rule in a trace, and a rehearsal of a tiny ``solar_open2``
cell through the real harness on the CPU."""

import json
import os

import numpy as np
import pytest

import tiny
from chipbench import harness, solar_rooflines, traffic

ROOT = harness.ROOT
CELL = 'solar-open2-serve-closed64-doc'
NAME = 'solar-open2-250b'
SOURCE = ('https://huggingface.co/upstage/Solar-Open2-250B/blob/main/'
          'config.json')
#: the catalog row's ``config``, as published
PUBLISHED = {
    'model_type': 'solar_open2', 'partial_rotary_factor': 1,
    'linear_attn_config': {'short_conv_kernel_size': 4, 'head_dim': 128,
                           'num_heads': 64, 'num_kv_heads': None},
    'hidden_size': 4096, 'num_hidden_layers': 48,
    'num_attention_heads': 64, 'head_dim': 128, 'num_key_value_heads': 8,
    'vocab_size': 196608, 'intermediate_size': 10240,
    'moe_intermediate_size': 1280, 'rms_norm_eps': 1e-05,
    'rope_theta': 10000, 'tie_word_embeddings': False,
    'max_position_embeddings': 1048576, 'first_k_dense_replace': 0,
    'use_rope': False, 'gqa_interval': 3,
    'gqa_layers': list(range(0, 48, 4)), 'use_gqa_gate': True,
    'kda_use_full_proj': False, 'kda_allow_neg_eigval': True,
    'n_routed_experts': 320, 'n_shared_experts': 1,
    'norm_topk_prob': True, 'routed_scaling_factor': 1,
    'num_experts_per_tok': 8}
REDUCED = {'num_hidden_layers': 4, 'gqa_layers': [0],
           'n_routed_experts': 40, 'vocab_size': 24576}
TINY = {
    'family': 'solar_open2', 'vocab_size': 97, 'hidden_size': 64,
    'intermediate_size': 96, 'moe_intermediate_size': 32,
    'num_hidden_layers': 4, 'gqa_layers': [0], 'num_attention_heads': 4,
    'num_key_value_heads': 2, 'head_dim': 16,
    'linear_attn_config': {'short_conv_kernel_size': 4, 'head_dim': 16,
                           'num_heads': 4, 'num_kv_heads': None},
    'n_routed_experts': 4, 'router_experts': 16, 'first_expert': 4,
    'n_shared_experts': 1, 'num_experts_per_tok': 3,
    'norm_topk_prob': True, 'routed_scaling_factor': 1.0,
    'rms_norm_eps': 1e-5, 'kda_allow_neg_eigval': True,
    'max_position_embeddings': 256}
TINY_MIX = {
    'kind': 'serve_closed', 'n_clients': 4, 'warm_seconds': 0.3,
    'engine': {'n_slots': 4, 'max_prompt_len': 16, 'max_len': 48,
               'paged': True, 'page_size': 4},
    'check_requests': 3, 'check_pad_to': 48,
    'pairs': [[4, 20], [7, 9], [9, 30], [12, 12], [16, 32], [5, 16]]}
MINE = ['state_decode_roofline_share.solar',
        'scan_prefill_roofline_share.solar',
        'moe_decode_roofline_share.solar',
        'attn_decode_roofline_share.solar',
        'experts_touched_share.solar', 'held_assignments_share.solar',
        'expert_load_max_over_mean.solar',
        'decode_exec_device_ms.tokens', 'decode_tick_ms.tokens']
#: what the other serving cells report for ``tpot_p90_ms``, which this
#: cell does not report end to end (the order a seed deals the 256
#: pairs in moves it by more than half its bound: PERF.md section 7)
MOVES_TPOT = {'decode_tick_ms', 'decode_exec_device_ms',
              'pallas_share.serve', 'itl_p99_ms', 'sched_host_ms',
              'decode_pages_per_grid_step', 'tick_uncovered_ms',
              'decode_wait_ms', 'decode_dispatch_ms'}


def _json(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def bench():
    return _json('BENCHMARK.json')


@pytest.fixture(scope='module')
def cfg():
    return _json('chipbench/configs/%s.json' % NAME)


@pytest.mark.parametrize('key', sorted(set(PUBLISHED) - set(REDUCED)))
def test_published_key_is_as_published(cfg, key):
    assert cfg[key] == PUBLISHED[key]


def test_the_four_cuts_and_what_the_file_states_beside_them(bench, cfg):
    entry, = [c for c in bench['configs'] if c['name'] == NAME]
    assert entry['file'] == 'chipbench/configs/%s.json' % NAME
    assert entry['source'] == cfg['source'] == SOURCE
    assert entry['reduced'] == ['num_hidden_layers', 'gqa_layers',
                                'n_routed_experts', 'vocab_size']
    for key, value in REDUCED.items():
        assert cfg[key] == value
        assert cfg['published'][key] == PUBLISHED[key]
    assert set(cfg['published']) == set(REDUCED) | {'kept'}
    # one whole period of the 1 : 3 pattern; the share named as
    # deepseek_v3 names it; an eighth of the vocabulary
    assert (cfg['router_experts'], cfg['first_expert']) == (320, 0)
    assert cfg['n_routed_experts'] * 8 == cfg['router_experts']
    assert cfg['vocab_size'] * 8 == PUBLISHED['vocab_size']
    assert 'EIGHT chips to a layer' in cfg['deployment']
    assert 'float32' in cfg['precision'] and 'train' not in cfg
    assert {'norms', 'kda', 'kda_convolution', 'kda_qk_norm', 'kda_decay',
            'kda_output', 'gqa', 'router', 'head', 'unused_keys',
            'weights'} == set(cfg['assumed'])
    assert set(cfg) - set(PUBLISHED) == {
        'family', 'source', 'router_experts', 'first_expert',
        'published', 'deployment', 'precision', 'assumed'}
    # no width is among the cuts
    assert not [k for k in entry['reduced']
                if k.endswith(('_dim', '_rank', '_size'))
                and k != 'vocab_size']


def test_the_shares_arithmetic(cfg):
    """3.31 B parameters here, 250.29 B in the whole model, from the
    reference's own parameter tree."""
    from chipbench.reference import solar_open2 as ref

    def count(c):
        import jax
        return sum(int(np.prod(shape)) for shape, _, _ in
                   jax.tree_util.tree_leaves(
                       ref.param_spec(c),
                       is_leaf=lambda x: isinstance(x, tuple)))

    assert round(count(cfg) / 1e9, 2) == 3.31
    whole = dict(cfg, **{k: PUBLISHED[k] for k in REDUCED})
    whole.pop('router_experts')
    assert round(count(whole) / 1e9, 2) == 250.29
    # active a token: everything but the routed experts, + 8 of them
    layer = 3 * 4096 * 1280
    active = count(whole) - 48 * (320 - 8) * layer
    assert round(active / 1e9, 1) == 14.7


def test_the_cell_and_its_traffic(bench):
    cell, = [w for w in bench['workloads'] if w['name'] == CELL]
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        NAME, 'closed64-doc', 1)
    assert sum(1 for w in bench['workloads'] if w['chips'] == 4) == 1
    mix = _json('chipbench/traffic/closed64-doc.json')
    gen = mix['generated_from']
    # ISSUE 46's traffic, letter for letter
    assert gen == {'n': 256, 'pair_seed': 20261004,
                   'prompt': {'median': 4096, 'sigma': 0.8, 'lo': 1024,
                              'hi': 12288},
                   'output': {'median': 512, 'sigma': 0.6, 'lo': 128,
                              'hi': 1536}}
    assert mix['pairs'] == traffic.paired_lengths(
        gen['prompt'], gen['output'], gen['n'], gen['pair_seed'])
    prompts, outputs = zip(*mix['pairs'])
    assert round(np.mean(prompts)) == 5096 and round(np.mean(outputs)) == 598
    assert max(p + o for p, o in mix['pairs']) == 13586
    e = mix['engine']
    assert (mix['kind'], mix['n_clients'], mix['warm_seconds']) == (
        'serve_closed', 64, 20)
    assert e == {'n_slots': 64, 'max_prompt_len': 12288, 'max_len': 13824,
                 'paged': True, 'page_size': 64}
    assert (mix['check_requests'], mix['check_pad_to']) == (4, 13824)
    spec = harness.Spec(CELL)       # every name leads to its file
    assert [m['name'] for m in spec.end_to_end] == [
        'serve_tokens_per_s', 'setup_s']
    reported = {m['name'] for m in spec.end_to_end}
    assert all(m['moves'] in reported for m in spec.per_layer)
    names = {m['name'] for m in spec.per_layer}
    # what every serving cell reports, this cell reports
    olmo = {m['name'] for m in harness.Spec(
        'olmo-hybrid-serve-closed48').per_layer}
    assert names - set(MINE) == olmo - MOVES_TPOT - {
        'state_decode_roofline_share', 'scan_prefill_roofline_share',
        'attn_decode_roofline_share.hybrid'}
    assert set(MINE) <= names and 'state_cache_share' in names
    # the new metrics are this cell's alone, appended together (a
    # later PR's come behind them)
    names = [m['name'] for m in bench['per_layer']]
    at = names.index(MINE[0])
    assert names[at:at + 9] == MINE and at >= 70
    assert all(m['workloads'][0] == CELL
               for m in bench['per_layer'][at:at + 9])
    for m in spec.per_layer:
        assert callable(spec.reader(m['reader']))
    assert set(spec.limits) <= {
        'served_logit_gap_widest', 'served_logit_gap_mean',
        'failed_requests', 'compiles_in_window'}
    assert spec.limits['failed_requests'] == 0
    assert spec.limits['compiles_in_window'] == 0


def test_nothing_the_benchmark_had_is_changed(bench):
    """Entries are appended: the nine cells and seven configurations
    before this one keep their places, this one is the tenth and the
    eighth (a later PR's come behind it), and a list this cell joined
    holds it behind the cells it had."""
    cells = [w['name'] for w in bench['workloads']]
    assert cells[:10] == [
        'gpt2m-train-1k', 'gpt2m-serve-closed32', 'resnet50-train-b256',
        'gpt2m-train-dp4', 'trinity-mini-serve-closed64',
        'olmo-hybrid-serve-closed48', 'xing4-serve-closed48-long',
        'phi4flash-serve-closed96-think', 'kanana-train-8k-ep8share',
        CELL]
    assert [c['name'] for c in bench['configs']].index(NAME) == 7
    for m in bench['end_to_end'] + bench['per_layer']:
        listed = m.get('workloads', [])
        if CELL in listed:
            assert not set(listed[listed.index(CELL):]) & set(cells[:9])


def test_byte_and_operation_counts(cfg):
    s = solar_rooflines
    assert s.layer_kinds(cfg) == (3, 1)
    assert s.state_row_bytes(cfg) == 64 * 128 * 128 * 4 == 4194304
    # 64 rows: each row's state read and written in three layers
    assert s.state_decode_bytes(cfg, 64) == 64 * 3 * 2 * 4194304
    # one row at position 5000: 5,001 keys in the one gqa layer, 8
    # heads x 128 x (K and V) x 2 bytes = 4,096 a key
    assert s.attn_decode_bytes(cfg, 5001) == 5001 * 4096
    assert s.expert_bytes(cfg) == 31457280
    # 32 of the 40 held experts touched in each of four layers
    assert s.moe_decode_bytes(cfg, 32) == 32 * 4 * 31457280
    # 1,000 tokens: three products of 2 x 128 x 128 a head
    assert s.scan_prefill_flops(cfg, 1000) == (
        1000 * 3 * 2 * 128 * 128 * 64 * 3)
    # q, k, v, g, gate, o of 64 x 128, two bytes each
    assert s.scan_prefill_bytes(cfg, 1000) == 1000 * 6 * 8192 * 2 * 3
    # the bytes bound it: 0.36 ms against 0.10 ms of products
    least = s.scan_prefill_least_seconds(cfg, 1000, 197e12, 819e9)
    assert least == s.scan_prefill_bytes(cfg, 1000) / 819e9
    assert least > s.scan_prefill_flops(cfg, 1000) / 197e12


def test_no_share_reads_over_100_at_the_least_time(cfg):
    """A kernel that took exactly its least time reads 100; any real
    one takes longer.  The issue's reckoning of a tick: 1.6 GB of
    state in 2.0 ms, 4.0 GB of experts in 4.9 ms."""
    s = solar_rooflines
    for needed in (s.state_decode_bytes(cfg, 64),
                   s.moe_decode_bytes(cfg, 32),
                   s.attn_decode_bytes(cfg, 64 * 5500)):
        assert s.share(needed, 819e9, needed / 819e9) == pytest.approx(100)
        assert s.share(needed, 819e9, 1.5 * needed / 819e9) < 100
    assert s.state_decode_bytes(cfg, 64) / 819e9 == pytest.approx(
        1.97e-3, rel=0.01)
    assert s.moe_decode_bytes(cfg, 32) / 819e9 == pytest.approx(
        4.92e-3, rel=0.01)


def test_the_reader_finds_the_rule_and_the_kernels_by_type(cfg):
    """Labels read off the prefill executable compiled for a described
    v5e at the cell's shapes (bucket 2,048)."""
    spec = harness.Spec(CELL)
    reader = spec.reader('roofline_solar')
    sizes = reader.__globals__['rule_sizes']()
    assert sizes == (64, 1024)              # the program's own
    scan = lambda label, cfg: (                         # noqa: E731
        reader.__globals__['scan_operation'](label, cfg, *sizes))
    for label in (
            'fusion exponential_multiply_fusion (f32[64,16,64,128], '
            'f32[64,16,64,128], f32[64,16,64,128], f32[64,16,64,128])',
            'fusion multiply_reduce_fusion f32[64,16,4,16,16]',
            'fusion f32[64,16,64,256]', 'fusion bf16[64,16,16,48]',
            'fusion convolution_negate_fusion f32[64,16,2,16,16]',
            'fusion subtract_dynamic-update-slice_fusion '
            'f32[64,16,2,2,16,16]',
            'copy f32[16,64,64,128]', 'fusion bf16[16,64,64,64]',
            'fusion convolution_add_fusion f32[64,128,128]',
            'fusion bf16[64,64,128]', 'fusion f32[64,64,128]'):
        assert scan(label, cfg), label
    for label in (
            'fusion divide_multiply_fusion f32[1024,24576]',
            'fusion pad_slice_fusion (f32[1027,24576], f32[1027,24576])',
            'fusion convert_bitcast_fusion f32[128,8,64,128]',
            'fusion convolution_bitcast_fusion bf16[64,2048,128]',
            'pallas custom-call (bf16[64,2048,128], f32[64,1,2048])',
            'pallas custom-call bf16[16384,4096]',
            'fusion bf16[13825,8,64,128]', 'copy bf16[32,8,64,128]',
            'fusion bf16[2048,8192]', 'fusion f32[2,1024,64,128]',
            'fusion f32[65,64,128,128]', 'fusion add_rsqrt_fusion '
            'f32[2,1024,64]', 'fusion f32[64,128]', 'copy f32[64,64]'):
        assert not scan(label, cfg) or label.startswith('pallas'), label

    class Run:
        trace = None
    Run.spec = spec
    for what in ('state_decode', 'attn_decode', 'moe_decode',
                 'scan_prefill'):
        assert reader(Run, what) is None            # no device trace
    # a program that solves 32 positions at once in segments of 2,048
    # is still read: the sizes are the program's, not the reader's
    other = reader.__globals__['scan_operation']
    assert other('fusion f32[64,64,32,128]', cfg, 32, 2048)
    assert other('fusion bf16[64,32,128]', cfg, 32, 2048)
    assert not other('fusion f32[64,16,64,128]', cfg, 32, 2048)


def test_reference_kda_layer_against_a_two_token_case_by_hand():
    """One ``kda`` layer, one head of 2 x 2, two tokens, the wide
    projections the identity, taps (0, 0, 1/2, 1); the decay differs
    between the two key channels (1/2 and 1/4 a token), beta 1/2 (at 1
    the rule would write ``S^T k = v`` whatever the decay), the output
    gate sigmoid(0) = 1/2; the experts all zero: small enough to follow
    with a pencil."""
    import jax.numpy as jnp
    from chipbench.reference import common, solar_open2 as ref

    eye = np.eye(2, dtype=np.float32)
    ones = np.ones((2,), np.float32)
    zero = lambda *shape: np.zeros(shape, np.float32)   # noqa: E731
    taps = np.repeat(np.asarray([[0.0], [0.0], [0.5], [1.0]],
                                np.float32), 6, axis=1)
    swiglu = lambda lead=(): {                          # noqa: E731
        'w1': zero(*lead, 2, 2), 'w3': zero(*lead, 2, 2),
        'w2': zero(*lead, 2, 2)}
    # softplus(dt_bias) = ln 2 and 2 ln 2 with A_log 0: exp(g) = 1/2, 1/4
    dt_bias = np.log(np.exp(np.asarray([np.log(2), 2 * np.log(2)]))
                     - 1.0).astype(np.float32)
    layer = {
        'input_norm': ones, 'pre_mlp_norm': ones,
        'wq': eye, 'wk': eye, 'wv': eye, 'conv': taps,
        'wf1': zero(2, 2), 'wf2': zero(2, 2), 'dt_bias': dt_bias,
        'A_log': zero(1), 'wb': zero(2, 1),
        'wg1': zero(2, 2), 'wg2': zero(2, 2), 'b_g': zero(2),
        'o_norm': ones, 'wo': eye,
        'router': zero(2, 2), 'expert_bias': zero(2),
        'experts': swiglu((2,)), 'shared': swiglu()}
    params = {'embed': {'embedding': eye}, 'layer_0': layer,
              'final_norm': ones, 'lm_head': eye}
    cfg = {'hidden_size': 2, 'vocab_size': 2, 'num_hidden_layers': 1,
           'gqa_layers': [], 'num_attention_heads': 1,
           'num_key_value_heads': 1, 'head_dim': 2,
           'linear_attn_config': {'short_conv_kernel_size': 4,
                                  'head_dim': 2, 'num_heads': 1,
                                  'num_kv_heads': None},
           'moe_intermediate_size': 2, 'n_routed_experts': 2,
           'n_shared_experts': 1, 'num_experts_per_tok': 1,
           'norm_topk_prob': True, 'routed_scaling_factor': 1,
           'kda_allow_neg_eigval': False, 'rms_norm_eps': 0.0}
    logits = np.asarray(ref.forward(params, jnp.asarray([0, 1]), cfg,
                                    common.Precision('float32')))

    silu = lambda x: x / (1.0 + np.exp(-x))             # noqa: E731
    rms = lambda x: x / np.sqrt(np.mean(x * x))         # noqa: E731
    x0, x1 = np.asarray([1.0, 0.0]), np.asarray([0.0, 1.0])
    a0, a1 = rms(x0), rms(x1)               # (sqrt 2, 0), (0, sqrt 2)
    decay = np.asarray([0.5, 0.25])
    # token 0: the convolutions see zeros before it
    c0 = silu(a0)
    k0 = c0 / np.linalg.norm(c0)                        # (1, 0)
    state = np.outer(k0, 0.5 * c0)          # from zero, beta 1/2
    o0 = state.T @ (k0 / np.sqrt(2.0))
    want0 = rms(x0 + 0.5 * rms(o0))
    np.testing.assert_allclose(logits[0], want0, rtol=1e-5, atol=1e-6)
    # token 1: the convolutions give a1 + a0 / 2
    c1 = silu(a1 + 0.5 * a0)
    k1 = c1 / np.linalg.norm(c1)
    state = decay[:, None] * state          # a decay a KEY channel
    u = 0.5 * (c1 - state.T @ k1)
    state = state + np.outer(k1, u)
    o1 = state.T @ (k1 / np.sqrt(2.0))
    want1 = rms(x1 + 0.5 * rms(o1))
    np.testing.assert_allclose(logits[1], want1, rtol=1e-5, atol=1e-6)
    # with ONE decay for both channels the second token comes out
    # elsewhere: the vector is read
    other = decay.mean() * np.outer(k0, 0.5 * c0)
    other = other + np.outer(k1, 0.5 * (c1 - other.T @ k1))
    assert np.abs(other.T @ k1 - state.T @ k1).max() > 1e-3


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """``tiny.make_root``'s checkout with a tiny ``solar_open2``
    configuration, mix and cell ADDED beside the others."""
    root = tiny.make_root(tmp_path_factory.mktemp('solar_open2'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'solar', 'source': 'test', 'why': 'tiny',
         'reduced': [], 'file': 'chipbench/configs/solar.json'})
    bench['workloads'].append(
        {'name': 'solar-serve', 'config': 'solar', 'chips': 1,
         'traffic': 'closed4-doc', 'why': 'tiny'})
    # the tiny cell reports what the real one does
    real = _json('BENCHMARK.json')
    mine = {m['name'] for m in real['end_to_end'] + real['per_layer']
            if CELL in m.get('workloads', ())}
    for m in bench['end_to_end'] + bench['per_layer']:
        if m['name'] in mine:
            m['workloads'].append('solar-serve')
    for relative, obj in (
            ('BENCHMARK.json', bench),
            ('chipbench/configs/solar.json', TINY),
            ('chipbench/traffic/closed4-doc.json', TINY_MIX),
            # bfloat16 against float32 at toy widths on a CPU (the same
            # engine in float32 reads under 1e-5,
            # ``tests/test_solar_open2.py``)
            ('chipbench/limits/solar-serve.json',
             {'served_logit_gap_widest': 0.2,
              'served_logit_gap_mean': 0.02, 'failed_requests': 0,
              'compiles_in_window': 0})):
        tiny._dump(os.path.join(root, relative), obj)
    return root


@pytest.mark.parametrize('trace', [0, 1])
def test_tiny_solar_cell_through_the_harness(root, trace):
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'solar-serve', trace=trace, seconds=0.6)
    finally:
        telemetry.disable()
    assert result['correct'] is True, result['checks']
    assert result['failed'] == 0 and result['attempted'] > 0
    metrics = result['metrics']
    if not trace:
        assert set(metrics) == {'serve_tokens_per_s', 'setup_s'}
        return
    # no chip, so no device trace: the roofline shares are absent; the
    # counters the program hangs on its spans are read
    assert not any('roofline' in k for k in metrics)
    assert 0 < metrics['experts_touched_share.solar']['value'] <= 100
    # 4 of 16 experts held: 25 expected of a uniform router
    assert 0 < metrics['held_assignments_share.solar']['value'] < 100
    assert metrics['expert_load_max_over_mean.solar']['value'] >= 1.0
    assert 50 < metrics['state_cache_share']['value'] < 100
    assert metrics['decode_tick_ms.tokens']['value'] > 0
    assert metrics['decode_occupancy']['value'] > 0
    assert not MOVES_TPOT & set(metrics)


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
    assert not [k for k in result['metrics'] if k.endswith('.solar')]


def test_the_fp8_control_reads_wider_than_float32_at_tiny_widths():
    """The control rounds every product's operands to float8: on the
    same served tokens its own first choice lies further from the
    float32 forward's best than float32's own (0)."""
    import jax.numpy as jnp
    from chipbench.reference import solar_open2 as ref

    params = ref.init_params(TINY, 11, jnp.float32)
    seq = np.random.default_rng(0).integers(0, 97, size=40).astype(
        np.int32)
    sound, = ref.served_token_gaps(params, TINY, [seq], [20], 48)
    assert sound.shape == (20,) and np.all(sound >= 0)
    control, = ref.served_token_gaps(params, TINY, [seq], [20], 48,
                                     control='fp8')
    assert control.shape == (20,) and control.mean() > 0
