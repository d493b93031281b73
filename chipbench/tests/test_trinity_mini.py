"""The ``trinity-mini`` configuration and its cell: the files as
published, the plain reference against a case small enough to check by
hand, the byte and operation counts, and a rehearsal of a tiny ``afmoe``
cell through the real harness on the CPU."""

import json
import os

import numpy as np
import pytest

import tiny
from chipbench import harness, rooflines, traffic

ROOT = harness.ROOT
CELL = 'trinity-mini-serve-closed64'
#: the catalog row's ``config``, as published
PUBLISHED = {
    'global_attn_every_n_layers': 4, 'head_dim': 128,
    'hidden_act': 'silu', 'hidden_size': 2048,
    'intermediate_size': 6144, 'load_balance_coeff': 0.001,
    'max_position_embeddings': 131072, 'model_type': 'afmoe',
    'moe_intermediate_size': 1024, 'mup_enabled': True, 'n_group': 1,
    'num_attention_heads': 32, 'num_expert_groups': 1,
    'num_experts': 128, 'num_experts_per_tok': 8,
    'num_key_value_heads': 4, 'num_limited_groups': 1,
    'num_shared_experts': 1, 'rms_norm_eps': 1e-05,
    'rope_scaling': None, 'rope_theta': 10000, 'route_norm': True,
    'route_scale': 2.826, 'score_func': 'sigmoid',
    'sliding_window': 2048, 'tie_word_embeddings': False,
    'topk_group': 1, 'use_grouped_mm': True, 'vocab_size': 200192}
TINY = {
    'family': 'afmoe', 'vocab_size': 97, 'hidden_size': 32,
    'intermediate_size': 48, 'moe_intermediate_size': 16,
    'num_hidden_layers': 5, 'num_dense_layers': 1,
    'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 8,
    'num_experts': 8, 'num_experts_per_tok': 2,
    'num_shared_experts': 1,
    'layer_types': ['sliding_attention'] * 4 + ['full_attention'],
    'sliding_window': 8, 'rms_norm_eps': 1e-5, 'rope_theta': 10000.0,
    'score_func': 'sigmoid', 'route_norm': True, 'route_scale': 2.826,
    'mup_enabled': True, 'max_position_embeddings': 256}
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
    return _json('chipbench/configs/trinity-mini.json')


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_published_key_is_as_published(cfg, key):
    assert cfg[key] == PUBLISHED[key]


def test_only_depth_is_cut_and_each_cut_states_its_published_value(
        bench, cfg):
    entry, = [c for c in bench['configs'] if c['name'] == 'trinity-mini']
    assert entry['file'] == 'chipbench/configs/trinity-mini.json'
    assert entry['source'] == cfg['source']
    assert entry['reduced'] == ['num_hidden_layers', 'num_dense_layers',
                                'layer_types']
    assert set(cfg['published']) == set(entry['reduced'])
    assert cfg['published']['num_hidden_layers'] == 32
    assert cfg['published']['num_dense_layers'] == 2
    assert cfg['num_hidden_layers'] == len(cfg['layer_types']) == 5
    assert cfg['num_dense_layers'] == 1
    # one whole 3 : 1 period of expert layers after the dense one
    assert cfg['layer_types'] == ['sliding_attention'] * 4 + [
        'full_attention']
    assert 'eight' in cfg['deployment'] and cfg['assumed']
    assert 'train' not in cfg


def test_the_cell_and_its_traffic(bench):
    cell, = [w for w in bench['workloads'] if w['name'] == CELL]
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'trinity-mini', 'closed64-reason', 1)
    mix = _json('chipbench/traffic/closed64-reason.json')
    gen = mix['generated_from']
    assert mix['pairs'] == traffic.paired_lengths(
        gen['prompt'], gen['output'], gen['n'], gen['pair_seed'])
    assert gen['pair_seed'] == 20260928 and len(mix['pairs']) == 256
    e = mix['engine']
    assert (mix['n_clients'], e['n_slots'], e['page_size'],
            e['max_prompt_len'], e['max_len']) == (64, 64, 64, 3072,
                                                   4096)
    assert max(p + o for p, o in mix['pairs']) <= e['max_len']
    assert mix['check_pad_to'] == e['max_len']
    past = sum(1 for p, o in mix['pairs'] if p + o > 2048)
    assert 0.2 < past / 256 < 0.35      # "about a quarter"
    spec = harness.Spec(CELL)       # every name leads to its file
    # ttft_p75_ms is NOT this cell's: it sits on the step between the
    # 2,048 and the 3,072 prompt bucket (19% of prompts) and one run in
    # six reads 59 ms for 41 (PERF.md, PR 27)
    assert [m['name'] for m in spec.end_to_end] == [
        'serve_tokens_per_s', 'tpot_p90_ms', 'setup_s']
    reported = {m['name'] for m in spec.end_to_end}
    assert all(m['moves'] in reported for m in spec.per_layer)
    assert {m['name'] for m in spec.per_layer} >= {
        'moe_decode_roofline_share', 'attn_decode_roofline_share',
        'experts_touched_share', 'expert_load_max_over_mean',
        'window_pages_share', 'moe_prefill_mxu_share',
        'prefill_exec_device_ms.tokens', 'admit_tick_ms.tokens'}
    assert set(spec.limits) == {
        'served_logit_gap_widest', 'served_logit_gap_mean',
        'failed_requests', 'compiles_in_window'}


def test_byte_and_operation_counts(cfg):
    assert rooflines.layer_kinds(cfg) == (4, 1, 4)
    assert rooflines.expert_bytes(cfg) == 12582912
    # every expert of every expert layer, once
    assert rooflines.moe_decode_bytes(cfg, 128) == 4 * 128 * 12582912
    # one row at position 3000: 3,001 keys in the full layer, the
    # 2,048 of the window in each of four window layers, 2 KB a key
    assert rooflines.attn_decode_bytes(cfg, 3001, 2048) == (
        3001 + 4 * 2048) * 2048
    assert rooflines.moe_prefill_flops(cfg, 1000) == (
        1000 * 8 * 6 * 2048 * 1024 * 4)
    # 8.19 GB in 10 ms is the whole of 819 GB/s
    assert rooflines.share(8.19e9, 819e9, 0.010) == pytest.approx(100.0)


def test_reference_against_a_two_token_case_by_hand():
    """One window layer, one head, one expert beside the shared one,
    every matrix a multiple of the identity, two tokens: small enough
    to follow with a pencil."""
    import jax.numpy as jnp
    from chipbench.reference import afmoe as ref, common

    d = 2
    cfg = {'hidden_size': d, 'head_dim': d, 'num_attention_heads': 1,
           'num_key_value_heads': 1, 'num_hidden_layers': 1,
           'num_dense_layers': 0, 'num_experts': 1,
           'num_experts_per_tok': 1, 'num_shared_experts': 1,
           'moe_intermediate_size': d, 'intermediate_size': d,
           'layer_types': ['full_attention'], 'sliding_window': 2,
           'rms_norm_eps': 0.0, 'rope_theta': 10000.0,
           'route_norm': True, 'route_scale': 2.0,
           'mup_enabled': True, 'vocab_size': 2}
    eye = np.eye(d, dtype=np.float32)
    ones = np.ones((d,), np.float32)
    layer = {'input_norm': ones, 'post_attn_norm': ones,
             'pre_mlp_norm': ones, 'post_mlp_norm': ones,
             'q_norm': ones, 'k_norm': ones, 'wq': eye, 'wk': eye,
             'wv': eye, 'wg': 0 * eye, 'wo': eye,
             'router': np.zeros((d, 1), np.float32),
             'expert_bias': np.zeros((1,), np.float32),
             'experts': {'w1': eye[None], 'w3': eye[None],
                         'w2': eye[None]},
             'shared': {'w1': eye, 'w3': eye, 'w2': 0 * eye}}
    params = {'embed': {'embedding': np.asarray(
        [[1.0, 0.0], [0.0, 1.0]], np.float32)},
        'layer_0': layer, 'final_norm': ones, 'lm_head': eye}
    logits = np.asarray(ref.forward(
        params, jnp.asarray([0, 1]), cfg, common.Precision('float32')))

    def rms(x):
        return x / np.sqrt(np.mean(x * x))

    def silu(x):
        return x / (1.0 + np.exp(-x))

    # token 0: h0 = e0 * sqrt(2); a = rms(h0) = (sqrt 2, 0); it sees
    # only itself, so attn = v = a; the gate is sigmoid(0) = 1/2
    h0 = np.asarray([np.sqrt(2.0), 0.0])
    a = rms(h0)
    h = h0 + rms(0.5 * a)
    m = rms(h)
    # one expert, score sigmoid(0) = 1/2, normalised to 1, times 2;
    # the shared expert's w2 is 0
    ff = 2.0 * (silu(m) * m)
    want0 = rms(h + rms(ff))
    np.testing.assert_allclose(logits[0], want0, rtol=1e-6, atol=1e-6)
    # token 1 attends both: q.k over sqrt(2) is 0 for token 0 and
    # sqrt(2) for itself (q, k normed to (0, sqrt 2))
    h1 = np.asarray([0.0, np.sqrt(2.0)])
    a1 = rms(h1)
    w = np.exp([0.0, np.sqrt(2.0)])
    w = w / w.sum()
    attn = w[0] * a + w[1] * a1
    hb = h1 + rms(0.5 * attn)
    mb = rms(hb)
    want1 = rms(hb + rms(2.0 * (silu(mb) * mb)))
    np.testing.assert_allclose(logits[1], want1, rtol=1e-6, atol=1e-6)


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """``tiny.make_root``'s checkout with a tiny ``afmoe``
    configuration, mix and cell ADDED beside the others."""
    root = tiny.make_root(tmp_path_factory.mktemp('afmoe'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'afmoe', 'source': 'test', 'why': 'tiny',
         'reduced': [], 'file': 'chipbench/configs/afmoe.json'})
    bench['workloads'].append(
        {'name': 'afmoe-serve', 'config': 'afmoe', 'chips': 1,
         'traffic': 'closed4-ring', 'why': 'tiny'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'lm-serve' in m.get('workloads', ()):
            m['workloads'].append('afmoe-serve')
    for relative, obj in (
            ('BENCHMARK.json', bench),
            ('chipbench/configs/afmoe.json', TINY),
            ('chipbench/traffic/closed4-ring.json', TINY_MIX),
            # bfloat16 against float32 at toy widths on a CPU, where a
            # flipped expert moves a logit by more than rounding does
            ('chipbench/limits/afmoe-serve.json',
             {'served_logit_gap_widest': 0.2,
              'served_logit_gap_mean': 0.02, 'failed_requests': 0,
              'compiles_in_window': 0})):
        tiny._dump(os.path.join(root, relative), obj)
    return root


@pytest.mark.parametrize('trace', [0, 1])
def test_tiny_afmoe_cell_through_the_harness(root, trace):
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'afmoe-serve', trace=trace, seconds=0.6)
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
    # counters the program hangs on its spans are read
    assert not any('roofline' in k or 'mxu' in k for k in metrics)
    assert 0 < metrics['experts_touched_share']['value'] <= 100
    assert metrics['expert_load_max_over_mean']['value'] >= 1
    assert 0 < metrics['window_pages_share']['value'] <= 100
