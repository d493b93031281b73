"""The ``xing4-29b-a4b`` configuration and its cell: the files as
published, the plain reference against cases small enough to check by
hand, the byte and operation counts, the reader's way of finding the
family's kernels in a trace, the program against the reference at a
tiny size, and a rehearsal of a tiny ``xing4`` cell through the real
harness on the CPU."""

import collections
import json
import os

import numpy as np
import pytest

import tiny
from chipbench import harness, mla_rooflines, traffic

ROOT = harness.ROOT
CELL = 'xing4-serve-closed48-long'
YARN = {'beta_fast': 32, 'beta_slow': 1, 'factor': 64, 'mscale': 1,
        'mscale_all_dim': 1, 'original_max_position_embeddings': 4096,
        'type': 'yarn'}
#: the catalog row's ``config``, as published, less the three keys cut
PUBLISHED = {
    'attention_bias': False, 'ep_size': 1, 'hidden_act': 'silu',
    'hidden_size': 3584, 'intermediate_size': 9216, 'kv_lora_rank': 512,
    'max_position_embeddings': 262144, 'model_type': 'xing4_0',
    'moe_intermediate_size': 1024, 'moe_layer_freq': 1, 'n_group': 1,
    'n_routed_experts': 64, 'n_shared_experts': 1, 'norm_topk_prob': True,
    'num_attention_heads': 32, 'num_experts_per_tok': 4,
    'num_key_value_heads': 32, 'hc_mult': 4, 'hc_sinkhorn_iters': 20,
    'hc_eps': 1e-06, 'mhc_h_res_clamp_min': -30,
    'mhc_h_res_clamp_max': 30, 'q_lora_rank': 768,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64,
    'rms_norm_eps': 1e-06, 'rope_theta': 10000, 'rope_scaling': YARN,
    'routed_scaling_factor': 2, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_group': 1,
    'topk_method': 'noaux_tc', 'v_head_dim': 128, 'vocab_size': 131072}
CUT = {'num_hidden_layers': (6, 40), 'first_k_dense_replace': (1, 2),
       'num_nextn_predict_layers': (0, 1)}
TINY = {
    'family': 'xing4', 'vocab_size': 97, 'hidden_size': 32,
    'intermediate_size': 48, 'moe_intermediate_size': 16,
    'num_hidden_layers': 3, 'first_k_dense_replace': 1,
    'num_attention_heads': 4, 'q_lora_rank': 24, 'kv_lora_rank': 128,
    'qk_nope_head_dim': 16, 'qk_rope_head_dim': 8, 'v_head_dim': 16,
    'n_routed_experts': 8, 'n_shared_experts': 1,
    'num_experts_per_tok': 2, 'n_group': 1, 'topk_group': 1,
    'norm_topk_prob': True, 'routed_scaling_factor': 2.0,
    'scoring_func': 'sigmoid', 'topk_method': 'noaux_tc', 'hc_mult': 4,
    'hc_sinkhorn_iters': 20, 'hc_eps': 1e-6, 'mhc_h_res_clamp_min': -30,
    'mhc_h_res_clamp_max': 30, 'rms_norm_eps': 1e-6, 'rope_theta': 10000,
    'rope_scaling': dict(YARN, original_max_position_embeddings=16),
    'max_position_embeddings': 256}
TINY_MIX = {
    'kind': 'serve_closed', 'n_clients': 4, 'warm_seconds': 0.3,
    'engine': {'n_slots': 4, 'max_prompt_len': 16, 'max_len': 48,
               'paged': True, 'page_size': 8},
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
    return _json('chipbench/configs/xing4-29b-a4b.json')


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_published_key_is_as_published(cfg, key):
    assert cfg[key] == PUBLISHED[key]


def test_only_depth_is_cut_and_each_cut_states_its_published_value(
        bench, cfg):
    entry, = [c for c in bench['configs'] if c['name'] == 'xing4-29b-a4b']
    assert bench['configs'][-1] is entry        # appended, not inserted
    assert entry['file'] == 'chipbench/configs/xing4-29b-a4b.json'
    assert entry['source'] == cfg['source'] == (
        'https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/'
        'config.json')
    assert entry['reduced'] == list(CUT)
    for key, (here, published) in CUT.items():
        assert cfg[key] == here and cfg['published'][key] == published
    assert 'eight pipeline stages' in cfg['deployment'] and cfg['assumed']
    assert cfg['family'] == 'xing4' and 'train' not in cfg
    # every key the file holds beside the published ones is the
    # benchmark's own
    assert set(cfg) - set(PUBLISHED) == set(CUT) | {
        'family', 'source', 'published', 'deployment', 'precision',
        'assumed'}
    # no width among the cuts
    assert not any(key.endswith(('_dim', '_rank', '_size'))
                   for key in entry['reduced'])


def test_the_cell_and_its_traffic(bench):
    cell, = [w for w in bench['workloads'] if w['name'] == CELL]
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'xing4-29b-a4b', 'closed48-long', 1)
    assert bench['workloads'][-1] is cell       # appended, not inserted
    assert sum(w['chips'] == 4 for w in bench['workloads']) == 1
    mix = _json('chipbench/traffic/closed48-long.json')
    gen = mix['generated_from']
    assert gen == {'n': 256, 'pair_seed': 20260929,
                   'prompt': {'median': 3072, 'sigma': 0.7, 'lo': 1024,
                              'hi': 6144},
                   'output': {'median': 640, 'sigma': 0.6, 'lo': 128,
                              'hi': 1536}}
    assert mix['pairs'] == traffic.paired_lengths(
        gen['prompt'], gen['output'], gen['n'], gen['pair_seed'])
    prompts = np.asarray(mix['pairs'])[:, 0]
    outputs = np.asarray(mix['pairs'])[:, 1]
    assert 3300 < prompts.mean() < 3600 and 700 < outputs.mean() < 760
    e = mix['engine']
    assert (mix['n_clients'], e['n_slots'], e['max_prompt_len'],
            e['max_len'], e['paged'], e['page_size'],
            mix['warm_seconds']) == (48, 48, 6144, 7680, True, 64, 15)
    assert (mix['check_requests'], mix['check_pad_to']) == (4, 7680)
    assert max(p + o for p, o in mix['pairs']) <= e['max_len']
    spec = harness.Spec(CELL)       # every name leads to its file
    assert [m['name'] for m in spec.end_to_end] == [
        'serve_tokens_per_s', 'tpot_p90_ms', 'setup_s']
    reported = {m['name'] for m in spec.end_to_end}
    assert all(m['moves'] in reported for m in spec.per_layer)
    mine = ['mla_decode_roofline_share', 'mla_prefill_mxu_share',
            'moe_decode_roofline_share.mla', 'mhc_decode_ms',
            'mhc_coeff_roofline_share', 'experts_touched_share.mla',
            'expert_load_max_over_mean.mla', 'latent_positions_per_row']
    assert {m['name'] for m in spec.per_layer} == set(mine) | {
        'decode_tick_ms', 'decode_occupancy', 'decode_exec_device_ms',
        'pallas_share.serve', 'itl_p99_ms', 'client_resubmit_p99_ms',
        'device_idle_share.serve', 'sched_host_ms',
        'window_compiles.serve', 'decode_pages_per_grid_step',
        'prefill_exec_device_ms.tokens', 'admit_tick_ms.tokens'}
    # the new metrics are this cell's alone, at the end of the list
    assert [m['name'] for m in bench['per_layer'][-8:]] == mine
    assert all(m['workloads'] == [CELL] for m in bench['per_layer'][-8:])
    assert all(m['moves'] == ('serve_tokens_per_s'
                              if m['name'] == 'mla_prefill_mxu_share'
                              else 'tpot_p90_ms')
               for m in bench['per_layer'][-8:])
    # a list that gained the cell gained it at its end
    for m in bench['end_to_end'] + bench['per_layer']:
        if CELL in m.get('workloads', ()):
            assert m['workloads'][-1] == CELL
    # no limit on the widest gap: with discrete routing a sound seed
    # reads as far as the fp8 control does (PERF.md section 2), so the
    # number is printed and the mean carries the comparison
    assert set(spec.limits) == {
        'served_logit_gap_mean', 'failed_requests', 'compiles_in_window'}
    assert 0.06 < spec.limits['served_logit_gap_mean'] < 0.52
    assert spec.limits['failed_requests'] == 0
    assert spec.limits['compiles_in_window'] == 0


def test_byte_and_operation_counts(cfg):
    m = mla_rooflines
    assert m.expert_layers(cfg) == 5
    assert m.latent_row_bytes(cfg) == (512 + 64) * 2 == 1152
    assert m.latent_position_flops(cfg) == 32 * 2 * (576 + 512) == 69632
    # the bytes bound it at the MXU's peak: 1.41 ns against 0.35 ns a
    # position (the kernel fills a quarter of the MXU: even)
    least = m.mla_decode_least_seconds(cfg, 1e6, 197e12, 819e9)
    assert least == pytest.approx(1e6 * 1152 / 819e9)
    assert least > 1e6 * 69632 / 197e12
    # and the products would, on a chip four times as short of them
    assert m.mla_decode_least_seconds(cfg, 1e6, 197e12 / 5, 819e9) \
        == pytest.approx(1e6 * 69632 / (197e12 / 5))
    assert m.causal_entries(3) == 6 and m.causal_entries(1) == 1
    # one prompt of 3,000 tokens: 4.5 M live pairs x 32 x 2 x 320 x 6
    assert m.mla_prefill_flops(cfg, m.causal_entries(3000)) == (
        3000 * 3001 / 2 * 32 * 2 * 320 * 6)
    assert m.expert_bytes(cfg) == 3 * 3584 * 1024 * 2 == 22020096
    assert m.moe_decode_bytes(cfg, 60.9) == pytest.approx(
        60.9 * 5 * 22020096)
    assert m.mhc_solves(cfg) == 12
    assert m.mhc_coeff_bytes(cfg, 48) == 12 * (
        48 * 4 * 3584 * 2 + 24 * 4 * 3584 * 4)
    assert m.share(8.19e9, 819e9, 0.010) == pytest.approx(100.0)


def _fake_run(spec, ops, launches, spans):
    """A run that holds a reduced trace and span records, as the
    readers see them."""
    class Trace:
        op_seconds = collections.Counter(ops)

        @staticmethod
        def module(pattern):
            return launches.get(pattern, 0), 0.0

    class Device:
        device_kind = 'TPU v5 lite'

    class Run:
        trace = Trace
        devices = [Device]
        program_spans = [(r, 0.0, 1.0) for r in spans]
    Run.spec = spec
    return Run


def test_the_reader_finds_the_kernels_by_type(cfg):
    spec = harness.Spec(CELL)
    read = spec.reader('roofline_mla')
    ops = {
        'pallas custom-call bf16[48,1,32,512]': 0.010,    # latent decode
        'pallas custom-call bf16[32,1,32,512]': 0.002,    # bucket 32
        'pallas custom-call bf16[192,3584]': 0.040,       # experts, 48 x 4
        'pallas custom-call bf16[24576,3584]': 0.5,       # prefill's
        'pallas custom-call (bf16[32,6144,128], f32[32,1,6144])': 0.030,
        'pallas custom-call f32[24,48]': 0.0005,          # coefficients
        'pallas custom-call f32[24,6144]': 0.02,          # prefill's
        'fusion bf16[48,4,3584]': 0.0010,                 # the streams
        'fusion f32[48,4,4]': 0.0002,
        'fusion bf16[6144,4,3584]': 0.3,                  # prefill's
        'fusion f32[48,4]': 0.1,                          # the router's
        'fusion bf16[48,3584]': 0.2,
    }
    decode = {'name': 'serve_decode', 'bucket': 48,
              'latent_positions': 6 * 48 * 4000.0,
              'experts_touched': 60.0}
    prefill = {'name': 'serve_prefill', 'tokens': 3000}
    run = _fake_run(spec, ops, {'decode': 100, 'prefill': 4},
                    [decode, decode, prefill])
    positions = 6 * 48 * 4000.0 * 100
    assert read(run, 'mla_decode') == pytest.approx(
        100 * positions * 1152 / 819e9 / 0.012)
    assert read(run, 'moe_decode') == pytest.approx(
        100 * 60 * 5 * 22020096 * 100 / 819e9 / 0.040)
    assert read(run, 'mla_prefill') == pytest.approx(
        100 * 4 * (3000 * 3001 / 2) * 32 * 2 * 320 * 6 / 197e12 / 0.030)
    assert read(run, 'mhc_coeff') == pytest.approx(
        100 * 100 * mla_rooflines.mhc_coeff_bytes(cfg, 48) / 819e9
        / 0.0005)
    assert read(run, 'mhc_decode_ms') == pytest.approx(
        1e3 * (0.0005 + 0.0010 + 0.0002) / 100)
    # nothing to read: no number, and nothing raised
    empty = _fake_run(spec, {}, {}, [])
    for what in ('mla_decode', 'mla_prefill', 'moe_decode', 'mhc_coeff',
                 'mhc_decode_ms'):
        assert read(empty, what) is None
    # a cell of another family, and a run without a device trace
    other = _fake_run(harness.Spec('trinity-mini-serve-closed64'), ops,
                      {'decode': 100}, [decode])
    assert read(other, 'mla_decode') is None
    run.trace = None
    assert read(run, 'mla_decode') is None


# -- the plain reference against cases by hand -------------------------

def test_reference_coefficients_by_hand():
    """``phi = 0``: the coefficients are the biases' alone.  ``b_res``
    = ln of a matrix that is already doubly stochastic: Sinkhorn leaves
    it where it is."""
    import jax.numpy as jnp
    from chipbench.reference import common
    from chipbench.reference import xing4 as ref
    cfg = dict(TINY, hidden_size=8)
    target = np.asarray([[.7, .1, .1, .1], [.1, .7, .1, .1],
                         [.1, .1, .4, .4], [.1, .1, .4, .4]], np.float32)
    b = np.concatenate([[0.0, 1.0, -1.0, 30.0], [0.0, 0.0, 0.0, -30.0],
                        np.log(target).reshape(-1)]).astype(np.float32)
    hp = {'phi': jnp.zeros((24, 32)), 'alpha': jnp.ones((3,)),
          'b': jnp.asarray(b)}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 4, 8)),
                    jnp.float32)
    pre, post, res = ref.coefficients(x, hp, cfg,
                                      common.Precision('float32'))
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))            # noqa: E731
    np.testing.assert_allclose(pre[0], [0.5, sig(1.0), sig(-1.0), 1.0],
                               rtol=1e-6)
    np.testing.assert_allclose(post[0], [1.0, 1.0, 1.0, 0.0], atol=1e-6)
    np.testing.assert_allclose(res, np.broadcast_to(target, (5, 4, 4)),
                               atol=1e-5)


def test_reference_attention_on_two_tokens_by_hand():
    """Two positions, one head: the first attends itself, the second
    both by the softmax of its two scores at the published scale."""
    import jax.numpy as jnp
    from chipbench.reference import common
    from chipbench.reference import xing4 as ref
    cfg = dict(TINY, num_attention_heads=1, rope_scaling=None)
    rng = np.random.default_rng(1)
    lp = {'wq_a': rng.normal(size=(32, 24)), 'q_a_norm': np.ones(24),
          'wq_b': rng.normal(size=(24, 24)),
          'wkv_a': rng.normal(size=(32, 136)), 'kv_a_norm': np.ones(128),
          'wkv_b': rng.normal(size=(128, 32)) * 0.1}
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
    a = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
    got = np.asarray(ref._attention(a, lp, cfg,
                                    common.Precision('float32')))
    rms = lambda v: v / np.sqrt((v * v).mean(-1, keepdims=True)  # noqa
                                + 1e-6)
    a64 = np.asarray(a, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    q = rms(a64 @ w['wq_a']) @ w['wq_b']                 # (2, 24)
    ckv = a64 @ w['wkv_a']
    c, k_r = rms(ckv[:, :128]), ckv[:, 128:]
    inv = 10000.0 ** -(np.arange(0, 8, 2) / 8.0)

    def rope(v):
        out = v.copy()
        ang = inv                                        # position 1
        x1, x2 = v[1, :4], v[1, 4:]
        out[1] = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                 x2 * np.cos(ang) + x1 * np.sin(ang)])
        return out

    kv = c @ w['wkv_b']
    k = np.concatenate([kv[:, :16], rope(k_r)], -1)
    qq = np.concatenate([q[:, :16], rope(q[:, 16:])], -1)
    v = kv[:, 16:]
    s = qq[1] @ k.T * 24 ** -0.5
    p = np.exp(s - s.max())
    p /= p.sum()
    np.testing.assert_allclose(got[0], v[0], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], p @ v, rtol=2e-5, atol=1e-6)


def test_program_against_the_reference_at_a_tiny_size():
    """Seeded weights, float32, the CPU: ``Xing4LM.apply`` (absorbed
    nowhere, kernels' jnp twins) against the reference's forward."""
    import jax.numpy as jnp
    from chainermn_tpu.models import Xing4LM
    from chipbench.reference import common
    from chipbench.reference import xing4 as ref
    params = ref.init_params(TINY, 11, jnp.float32)
    model = Xing4LM.from_config(TINY, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 97, 40),
                         jnp.int32)
    want = ref.forward(params, tokens, TINY, common.Precision('float32'))
    got = model.apply(params, tokens[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5)
    # the fp8 control moves the same logits a thousand times further
    low = ref.forward(params, tokens, TINY, common.Precision('fp8'))
    assert np.abs(np.asarray(low) - np.asarray(want)).max() > 1e-2


# -- a tiny cell through the harness -----------------------------------

@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """``tiny.make_root``'s checkout with a tiny ``xing4``
    configuration, mix and cell ADDED beside the others."""
    root = tiny.make_root(tmp_path_factory.mktemp('xing4'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'latent', 'source': 'test', 'why': 'tiny',
         'reduced': [], 'file': 'chipbench/configs/latent.json'})
    bench['workloads'].append(
        {'name': 'latent-serve', 'config': 'latent', 'chips': 1,
         'traffic': 'closed4-latent', 'why': 'tiny'})
    # the tiny cell reports what the real one does
    real = _json('BENCHMARK.json')
    mine = {m['name'] for m in real['end_to_end'] + real['per_layer']
            if CELL in m.get('workloads', ())}
    for m in bench['end_to_end'] + bench['per_layer']:
        if m['name'] in mine:
            m['workloads'].append('latent-serve')
    for relative, obj in (
            ('BENCHMARK.json', bench),
            ('chipbench/configs/latent.json', TINY),
            ('chipbench/traffic/closed4-latent.json', TINY_MIX),
            # bfloat16 against float32 at toy widths on a CPU; the same
            # engine in float32 reads under 1e-5
            # (``tests/test_xing4.py``)
            ('chipbench/limits/latent-serve.json',
             {'served_logit_gap_widest': 0.2,
              'served_logit_gap_mean': 0.02, 'failed_requests': 0,
              'compiles_in_window': 0})):
        tiny._dump(os.path.join(root, relative), obj)
    return root


@pytest.mark.parametrize('trace', [0, 1])
def test_tiny_latent_cell_through_the_harness(root, trace):
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'latent-serve', trace=trace, seconds=0.6)
    finally:
        telemetry.disable()
    assert result['correct'] is True, result['checks']
    assert result['failed'] == 0 and result['attempted'] > 0
    metrics = result['metrics']
    if not trace:
        assert set(metrics) == {'serve_tokens_per_s', 'tpot_p90_ms',
                                'setup_s'}
        return
    # no chip, so no device trace: the shares and the device
    # milliseconds are absent; the counters the program hangs on its
    # spans are read
    assert not any('roofline' in k or 'mxu' in k or k == 'mhc_decode_ms'
                   for k in metrics)
    assert 0 < metrics['experts_touched_share.mla']['value'] <= 100
    assert metrics['expert_load_max_over_mean.mla']['value'] >= 1.0
    # prompts of 4-16 and outputs of 9-32: rows hold 5-48 positions
    # (the tiny model has 3 layers where the reader divides by the
    # cell's 6)
    assert 2 < metrics['latent_positions_per_row']['value'] < 24
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
    assert not {m for m in result['metrics']
                if m.endswith('.mla') or m.startswith(('mla_', 'mhc_',
                                                       'latent_'))}
