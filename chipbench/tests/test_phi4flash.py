"""The ``phi4-mini-flash`` configuration and its cell: the files as
published, the mix regenerated from its parameters, the plain reference
against itself and its fp8 control, the byte and operation counts at
the published sizes, the reader's way of finding the family's kernels
in a trace, and a rehearsal of a tiny ``phi4flash`` cell through the
real harness on the CPU."""

import collections
import json
import os

import numpy as np
import pytest

import tiny
from chipbench import harness, ssm_rooflines, traffic

ROOT = harness.ROOT
CELL = 'phi4flash-serve-closed96-think'
#: the catalog row's ``config``, as published
PUBLISHED = {
    'embd_pdrop': 0, 'hidden_act': 'silu', 'hidden_size': 2560,
    'intermediate_size': 10240, 'layer_norm_eps': 1e-05,
    'max_position_embeddings': 262144, 'mb_per_layer': 2,
    'model_type': 'phi4flash', 'num_attention_heads': 40,
    'num_hidden_layers': 32, 'num_key_value_heads': 20, 'resid_pdrop': 0,
    'sliding_window': 512, 'tie_word_embeddings': True, 'mlp_bias': False,
    'lm_head_bias': False, 'vocab_size': 200064}
TINY = {
    'family': 'phi4flash', 'vocab_size': 97, 'hidden_size': 64,
    'intermediate_size': 96, 'num_hidden_layers': 8,
    'num_attention_heads': 4, 'num_key_value_heads': 2,
    'sliding_window': 8, 'mb_per_layer': 2, 'layer_norm_eps': 1e-5,
    'max_position_embeddings': 256, 'tie_word_embeddings': True,
    'mlp_bias': False, 'lm_head_bias': False}
TINY_MIX = {
    'kind': 'serve_closed', 'n_clients': 4, 'warm_seconds': 0.3,
    'engine': {'n_slots': 4, 'max_prompt_len': 16, 'max_len': 48,
               'paged': True, 'page_size': 4},
    'check_requests': 3, 'check_pad_to': 48,
    'pairs': [[4, 20], [7, 9], [9, 30], [12, 12], [16, 32], [5, 16]]}
MINE = ['ssm_decode_roofline_share', 'ssm_prefill_roofline_share',
        'attn_decode_roofline_share.yoco', 'shared_kv_read_share']


def _json(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def bench():
    return _json('BENCHMARK.json')


@pytest.fixture(scope='module')
def cfg():
    return _json('chipbench/configs/phi4-mini-flash.json')


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_published_key_is_as_published(cfg, key):
    assert cfg[key] == PUBLISHED[key]


def test_nothing_is_cut_and_what_config_json_lacks_is_assumed(bench, cfg):
    entry, = [c for c in bench['configs'] if c['name'] == 'phi4-mini-flash']
    assert entry['file'] == 'chipbench/configs/phi4-mini-flash.json'
    assert entry['source'] == cfg['source'] == (
        'https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/'
        'blob/main/config.json')
    assert entry['reduced'] == cfg['reduced'] == []
    assert 'ONE v5e chip' in cfg['deployment']
    assert cfg['family'] == 'phi4flash' and 'train' not in cfg
    # the sizes config.json does not give are the family's defaults,
    # stated beside the sentence that assumes them
    assert (cfg['mamba_d_state'], cfg['mamba_d_conv'],
            cfg['mamba_expand'], cfg['mamba_dt_rank']) == (16, 4, 2, 160)
    assert set(cfg['assumed']) == {
        'source_of_these', 'layers', 'index_rule', 'mamba',
        'gated_memory_unit', 'differential_attention', 'cross_attention',
        'weights'}
    assert set(cfg) - set(PUBLISHED) == {
        'family', 'source', 'mamba_d_state', 'mamba_d_conv',
        'mamba_expand', 'mamba_dt_rank', 'reduced', 'published',
        'deployment', 'precision', 'assumed'}


def test_the_cell_and_its_traffic(bench):
    cell, = [w for w in bench['workloads'] if w['name'] == CELL]
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'phi4-mini-flash', 'closed96-think', 1)
    assert sum(w['chips'] == 4 for w in bench['workloads']) == 1
    mix = _json('chipbench/traffic/closed96-think.json')
    gen = mix['generated_from']
    assert gen == {'n': 256, 'pair_seed': 20261001,
                   'prompt': {'median': 256, 'sigma': 0.8, 'lo': 64,
                              'hi': 1024},
                   'output': {'median': 1024, 'sigma': 0.6, 'lo': 256,
                              'hi': 4096}}
    assert mix['pairs'] == traffic.paired_lengths(
        gen['prompt'], gen['output'], gen['n'], gen['pair_seed'])
    prompts = np.asarray(mix['pairs'])[:, 0]
    outputs = np.asarray(mix['pairs'])[:, 1]
    assert 300 < prompts.mean() < 360 and 1150 < outputs.mean() < 1280
    e = mix['engine']
    assert (mix['kind'], mix['n_clients'], e['n_slots'],
            e['max_prompt_len'], e['max_len'], e['paged'], e['page_size'],
            mix['warm_seconds']) == (
        'serve_closed', 96, 96, 1024, 5120, True, 64, 30)
    assert (mix['check_requests'], mix['check_pad_to']) == (4, 5120)
    assert max(p + o for p, o in mix['pairs']) <= e['max_len']
    spec = harness.Spec(CELL)       # every name leads to its file
    assert [m['name'] for m in spec.end_to_end] == [
        'serve_tokens_per_s', 'tpot_p90_ms', 'setup_s']
    reported = {m['name'] for m in spec.end_to_end}
    assert all(m['moves'] in reported for m in spec.per_layer)
    assert {m['name'] for m in spec.per_layer} == set(MINE) | {
        'decode_tick_ms', 'decode_occupancy', 'decode_exec_device_ms',
        'pallas_share.serve', 'itl_p99_ms', 'client_resubmit_p99_ms',
        'device_idle_share.serve', 'sched_host_ms',
        'window_compiles.serve', 'decode_pages_per_grid_step',
        'prefill_exec_device_ms.tokens', 'admit_tick_ms.tokens',
        'decode_runahead_share', 'tick_uncovered_ms', 'decode_wait_ms',
        'decode_dispatch_ms', 'device_starved_share',
        'device_starved_share.admission', 'admits_per_admit_tick',
        'window_pages_share', 'state_cache_share'}
    # the new metrics were this cell's alone when it was added (a later
    # cell of the family may join their lists, behind it)
    mine = [m for m in bench['per_layer'] if m['name'] in MINE]
    assert [m['name'] for m in mine] == MINE
    assert all(m['workloads'][0] == CELL for m in mine)
    assert set(spec.limits) == {
        'served_logit_gap_widest', 'served_logit_gap_mean',
        'failed_requests', 'compiles_in_window'}
    assert spec.limits['failed_requests'] == 0
    assert spec.limits['compiles_in_window'] == 0


def test_byte_and_operation_counts_at_the_published_sizes(cfg):
    s = ssm_rooflines
    assert s.layer_kinds(cfg) == (9, 8, 8)
    assert s.state_row_bytes(cfg) == 5120 * 16 * 4 == 327680
    # 96 rows: each row's state read and written in nine layers
    assert s.ssm_decode_bytes(cfg, 96) == 96 * 9 * 2 * 327680
    assert s.kv_position_bytes(cfg) == 2 * 20 * 64 * 2 == 5120
    # 96 rows at 1,100 live positions: the shared leaf 8 times over,
    # and 512 of them in each of 8 rings
    shared = 8 * 96 * 1100
    assert s.attn_decode_bytes(cfg, shared, 96 * 512) == (
        8 * 96 * 1100 + 8 * 96 * 512) * 5120
    # the table of the issue: 3.85 B parameters, 7.70 GB as served
    assert 7.69e9 < s.weight_bytes(cfg) < 7.71e9
    read = s.tick_read_bytes(cfg, 96, shared, 96 * 512)
    assert read == (s.weight_bytes(cfg) + 96 * 9 * 327680
                    + (shared + 8 * 96 * 512) * 5120)
    assert 14.2e9 < read < 14.4e9       # ~17.5 ms at 819 GB/s
    assert s.shared_kv_read_share(cfg, 96, shared, 96 * 512) == \
        pytest.approx(100.0 * shared * 5120 / read)
    assert 29 < s.shared_kv_read_share(cfg, 96, shared, 96 * 512) < 31
    # 1,000 tokens: three multiply-adds a state element
    assert s.scan_prefill_flops(cfg, 1000) == 1000 * 6 * 5120 * 16 * 9
    assert s.scan_prefill_bytes(cfg, 1000) == 1000 * 41024 * 9
    # the bytes bound it: 0.45 ms against 0.02 ms of multiply-adds
    least = s.scan_prefill_least_seconds(cfg, 1000, 197e12, 819e9)
    assert least == s.scan_prefill_bytes(cfg, 1000) / 819e9
    assert s.share(8.19e9, 819e9, 0.010) == pytest.approx(100.0)


def _fake_run(spec, ops, launches, spans, trace=True):
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
        devices = [Device]
        program_spans = [(r, 0.0, 1.0) for r in spans]
    Run.trace = Trace if trace else None
    Run.spec = spec
    return Run


def test_the_reader_finds_the_kernels_by_type(cfg):
    spec = harness.Spec(CELL)
    read = spec.reader('roofline_ssm')
    ops = {
        # the step: m beside the state leaf
        'pallas custom-call (f32[96,1,5120], f32[97,1,16,5120])': 0.090,
        'pallas custom-call (f32[64,1,5120], f32[97,1,16,5120])': 0.010,
        # the prompt kernel: m of the bucket beside a lone state
        'pallas custom-call (f32[1024,5120], f32[16,5120])': 0.040,
        'pallas custom-call (f32[256,5120], f32[16,5120])': 0.010,
        # the paged decode kernel, both buckets
        'pallas custom-call bf16[96,10,4,128]': 0.5,
        'pallas custom-call bf16[64,10,4,128]': 0.1,
        # not ours: the append, the convolution step, a window flash
        'pallas custom-call (bf16[7681,10,64,128], bf16[7681,10,64,128])':
            0.3,
        'pallas custom-call (f32[96,48,128], bf16[97,144,128])': 0.2,
        'pallas custom-call (bf16[40,1024,128], f32[40,1,1024])': 0.7,
        'fusion f32[97,1,16,5120]': 0.9,
    }
    decode = {'name': 'serve_decode', 'bucket': 96, 'state_rows': 96.0,
              'shared_kv_positions': 8 * 96 * 1000.0,
              'kv_window_positions': 96 * 500}
    prefill = {'name': 'serve_prefill', 'tokens': 300,
               'scan_tokens': 300.0}
    run = _fake_run(spec, ops, {'decode': 100, 'prefill': 4},
                    [decode, decode, prefill])
    assert read(run, 'ssm_decode') == pytest.approx(
        100 * 100 * 96 * 9 * 2 * 327680 / 819e9 / 0.100)
    assert read(run, 'ssm_prefill') == pytest.approx(
        100 * 4 * 300 * 41024 * 9 / 819e9 / 0.050)
    assert read(run, 'attn_decode') == pytest.approx(
        100 * 100 * (8 * 96 * 1000 + 8 * 96 * 500) * 5120 / 819e9 / 0.6)
    want = ssm_rooflines.shared_kv_read_share(
        cfg, 96, 8 * 96 * 1000, 96 * 500)
    assert read(run, 'shared_kv') == pytest.approx(want)
    # counters alone: read without a device trace too
    untraced = _fake_run(spec, {}, {}, [decode], trace=False)
    assert read(untraced, 'shared_kv') == pytest.approx(want)
    assert read(untraced, 'ssm_decode') is None
    # nothing to read: no number, and nothing raised
    empty = _fake_run(spec, {}, {}, [])
    for what in ('ssm_decode', 'ssm_prefill', 'attn_decode', 'shared_kv'):
        assert read(empty, what) is None
    # a cell of another family
    other = _fake_run(harness.Spec('olmo-hybrid-serve-closed48'), ops,
                      {'decode': 100}, [decode])
    for what in ('ssm_decode', 'attn_decode', 'shared_kv'):
        assert read(other, what) is None


def test_the_new_readers_on_the_recorded_trace():
    """``testdata``'s trace is of another program: every new reader
    finds nothing there, and says so with ``None``."""
    from chipbench import trace as trace_mod
    spec = harness.Spec(CELL)
    read = spec.reader('roofline_ssm')
    summary = trace_mod.reduce(trace_mod.load(os.path.join(
        ROOT, 'chipbench', 'testdata', 'v5e_small.xplane.pb')))
    assert summary is not None and summary.op_seconds

    class Device:
        device_kind = 'TPU v5 lite'

    class Run:
        trace = summary
        devices = [Device]
        program_spans = None
    Run.spec = spec
    for what in ('ssm_decode', 'ssm_prefill', 'attn_decode', 'shared_kv'):
        assert read(Run, what) is None


# -- the plain reference ----------------------------------------------

def test_the_index_rule_and_the_lambda_schedule():
    from chipbench.reference import phi4flash as ref
    kinds = ref.layer_kinds(PUBLISHED)
    assert kinds[:16] == ['mamba', 'window'] * 8
    assert kinds[16:18] == ['memory', 'full']
    assert kinds[18:] == ['gmu', 'cross'] * 7
    assert ref.widths(PUBLISHED) == (5120, 16, 4, 160)
    assert ref.lambda_init(0) == pytest.approx(0.2)
    assert ref.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    spec = ref.param_spec(PUBLISHED)
    assert 'lm_head' not in spec                # tied
    assert set(spec['layer_19']) >= {'wq', 'bq'} \
        and 'wqkv' not in spec['layer_19']      # no K/V projection
    assert set(spec['layer_18']) == {'norm1', 'norm2', 'mlp', 'in_proj',
                                     'out_proj'}


def test_reference_scan_on_two_tokens_by_hand():
    """One channel, two state values, two tokens: ``A = (-1, -2)``,
    ``delta = ln 2`` (so the decays are 1/2 and 1/4), ``B = C = (1,
    1)``, ``D = 1``."""
    import jax.numpy as jnp
    from chipbench.reference import phi4flash as ref
    dt = np.log(2.0)
    m = np.asarray(ref.scan(
        jnp.asarray([[1.0], [3.0]]), jnp.full((2, 1), dt),
        jnp.asarray([[-1.0, -2.0]]), jnp.ones((2, 2)), jnp.ones((2, 2)),
        jnp.ones((1,))))
    # h_0 = dt * 1 * (1, 1); m_0 = 2 dt + 1
    assert m[0, 0] == pytest.approx(2 * dt + 1.0)
    # h_1 = (dt / 2, dt / 4) + 3 dt (1, 1); m_1 = 6.75 dt + 3
    assert m[1, 0] == pytest.approx(6.75 * dt + 3.0)


def test_served_token_gaps_zero_against_itself_positive_under_fp8():
    """The reference's own best tokens as the served ones: every gap is
    0 in float32, and the fp8 control lies above it."""
    import jax.numpy as jnp
    from chipbench.reference import common, phi4flash as ref
    params = ref.init_params(TINY, 11)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, TINY['vocab_size'], size=6).astype(np.int32)
    seq = list(prompt)
    for _ in range(40):                     # greedy, by the reference
        row = np.zeros((64,), np.int32)
        row[:len(seq)] = seq
        logits = ref.forward(params, jnp.asarray(row), TINY,
                             common.Precision('float32'))
        seq.append(int(np.argmax(np.asarray(logits[len(seq) - 1]))))
    gaps, = ref.served_token_gaps(params, TINY, [np.asarray(seq)], [6], 64)
    assert gaps.shape == (40,) and np.all(gaps == 0.0)
    low, = ref.served_token_gaps(params, TINY, [np.asarray(seq)], [6], 64,
                                 control='fp8')
    assert low.shape == (40,) and low.mean() > 0.0


# -- a tiny cell through the real harness ------------------------------

@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """``tiny.make_root``'s checkout with a tiny ``phi4flash``
    configuration, mix and cell ADDED beside the others."""
    root = tiny.make_root(tmp_path_factory.mktemp('phi4flash'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'flash', 'source': 'test', 'why': 'tiny',
         'reduced': [], 'file': 'chipbench/configs/flash.json'})
    bench['workloads'].append(
        {'name': 'flash-serve', 'config': 'flash', 'chips': 1,
         'traffic': 'closed4-think', 'why': 'tiny'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'lm-serve' in m.get('workloads', ()):
            m['workloads'].append('flash-serve')
    for relative, obj in (
            ('BENCHMARK.json', bench),
            ('chipbench/configs/flash.json', TINY),
            ('chipbench/traffic/closed4-think.json', TINY_MIX),
            # bfloat16 against float32 at toy widths on a CPU (the same
            # engine in float32 reads under 1e-5,
            # ``tests/test_phi4flash.py``)
            ('chipbench/limits/flash-serve.json',
             {'served_logit_gap_widest': 0.2,
              'served_logit_gap_mean': 0.02, 'failed_requests': 0,
              'compiles_in_window': 0})):
        tiny._dump(os.path.join(root, relative), obj)
    return root


@pytest.mark.parametrize('trace', [0, 1])
def test_tiny_phi4flash_cell_through_the_harness(root, trace):
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'flash-serve', trace=trace, seconds=0.6)
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
    assert not any('roofline' in k for k in metrics)
    assert 0 < metrics['shared_kv_read_share']['value'] < 100
    assert 0 < metrics['state_cache_share']['value'] < 100
    assert 0 < metrics['window_pages_share']['value'] <= 300
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
    assert not set(MINE) & set(result['metrics'])
