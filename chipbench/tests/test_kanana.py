"""The ``kanana-2-30b-a3b`` configuration and its cell: the files as
published, the cut and its arithmetic, the plain reference against
itself and its fp8 control, the operation counts at the published
sizes, the reader's way of finding the family's kernels in a trace, and
a rehearsal of a tiny ``deepseek_v3`` training cell through the real
harness on the CPU."""

import collections
import json
import os

import numpy as np
import pytest

import tiny
from chipbench import harness, kanana_rooflines

ROOT = harness.ROOT
CELL = 'kanana-train-8k-ep8share'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
#: the catalog row's ``config``, as published
PUBLISHED = {
    'attention_bias': False, 'first_k_dense_replace': 1, 'head_dim': 64,
    'hidden_act': 'silu', 'hidden_size': 2048, 'intermediate_size': 6144,
    'kv_lora_rank': 512, 'max_position_embeddings': 32768,
    'model_type': 'deepseek_v3', 'moe_intermediate_size': 768,
    'moe_layer_freq': 1, 'n_group': 1, 'n_routed_experts': 128,
    'n_shared_experts': 2, 'norm_topk_prob': True,
    'num_attention_heads': 32, 'num_experts_per_tok': 6,
    'num_hidden_layers': 48, 'num_key_value_heads': 32,
    'q_lora_rank': None, 'qk_head_dim': 192, 'qk_nope_head_dim': 128,
    'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_interleave': True, 'rope_scaling': None, 'rope_theta': 1000000,
    'routed_scaling_factor': 2.448, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_group': 1,
    'topk_method': 'noaux_tc', 'v_head_dim': 128, 'vocab_size': 128256}
REDUCED = {'num_hidden_layers': 5, 'n_routed_experts': 16,
           'vocab_size': 16032}
TINY = {
    'family': 'deepseek_v3', 'vocab_size': 96, 'hidden_size': 32,
    'intermediate_size': 64, 'moe_intermediate_size': 16,
    'num_hidden_layers': 3, 'first_k_dense_replace': 1,
    'num_attention_heads': 2, 'q_lora_rank': None, 'kv_lora_rank': 16,
    'qk_nope_head_dim': 8, 'qk_rope_head_dim': 4, 'v_head_dim': 8,
    'n_routed_experts': 4, 'n_shared_experts': 2,
    'num_experts_per_tok': 3, 'n_group': 1, 'topk_group': 1,
    'norm_topk_prob': True, 'routed_scaling_factor': 2.448,
    'scoring_func': 'sigmoid', 'topk_method': 'noaux_tc',
    'rms_norm_eps': 1e-6, 'rope_theta': 1e6, 'rope_scaling': None,
    'rope_interleave': True, 'router_experts': 16, 'first_expert': 4,
    'train': {'optimizer': 'adam', 'lr': 3e-4, 'policy': 'bf16',
              'recompute': 'layer'}}
TINY_MIX = {'kind': 'train', 'dataset': 'lm_tokens',
            'dataset_examples': 16, 'seq_len': 32, 'batch': 2,
            'iterator': 'serial', 'device_prefetch': 0}
MINE = ['mla_train_mxu_share', 'moe_train_mxu_share',
        'held_assignments_share', 'expert_load_max_over_mean.train']


def _json(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def bench():
    return _json('BENCHMARK.json')


@pytest.fixture(scope='module')
def cfg():
    return _json('chipbench/configs/kanana-2-30b-a3b.json')


def test_the_published_dict_is_the_catalog_rows():
    if not os.path.exists(CATALOG):
        pytest.skip('no catalog beside the guides here')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'kanana-2-30b-a3b-instruct-2601']
    assert row['config'] == PUBLISHED


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_published_key_is_as_published(cfg, key):
    assert cfg[key] == REDUCED.get(key, PUBLISHED[key])


def test_the_cut_is_the_issues_and_says_so(bench, cfg):
    entry, = [c for c in bench['configs']
              if c['name'] == 'kanana-2-30b-a3b']
    assert entry['file'] == 'chipbench/configs/kanana-2-30b-a3b.json'
    assert entry['source'] == cfg['source']
    assert entry['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                                'vocab_size']
    assert {k: cfg['published'][k] for k in REDUCED} == {
        k: PUBLISHED[k] for k in REDUCED}
    # the router keeps its published width; this chip's experts are
    # the first 16; an eighth of the vocabulary
    assert cfg['router_experts'] == PUBLISHED['n_routed_experts']
    assert cfg['first_expert'] == 0
    assert cfg['vocab_size'] * 8 == PUBLISHED['vocab_size']
    assert cfg['n_routed_experts'] * 8 == PUBLISHED['n_routed_experts']
    assert 'EIGHT chips' in cfg['deployment']
    # ISSUE 41's rate: the other LM cells'
    assert cfg['train'] == {'optimizer': 'adam', 'lr': 3e-4,
                            'policy': 'bf16', 'recompute': 'layer'}
    assert {'norms', 'mla', 'rotary', 'router', 'balance', 'optimizer',
            'weights'} <= set(cfg['assumed'])


def test_parameters_here_are_the_issues_arithmetic(cfg):
    from chipbench.reference import deepseek_v3 as ref
    count = collections.Counter()
    for name, sub in ref.param_spec(cfg).items():
        for leaf in _leaves(sub):
            count[name.split('_')[0]] += int(np.prod(leaf[0]))
    total = sum(count.values())
    assert round(total / 1e6, 1) == 576.0     # 575.9 M + norms, biases
    # 16 bytes a parameter: float32 weight, gradient, Adam's two moments
    assert 9.2e9 < 16 * total < 9.23e9
    assert count['embed'] + count['lm'] == 2 * 16032 * 2048


def _leaves(spec):
    if isinstance(spec, dict):
        for sub in spec.values():
            yield from _leaves(sub)
    else:
        yield spec


def test_the_cell_and_its_traffic(bench):
    cell, = [w for w in bench['workloads'] if w['name'] == CELL]
    assert cell == dict(cell, config='kanana-2-30b-a3b',
                        traffic='lm-b1-s8192', chips=1)
    assert len(cell['why']) <= 200
    mix = _json('chipbench/traffic/lm-b1-s8192.json')
    assert {k: mix[k] for k in ('kind', 'dataset', 'dataset_examples',
                                'seq_len', 'batch', 'iterator',
                                'device_prefetch')} == {
        'kind': 'train', 'dataset': 'lm_tokens', 'dataset_examples': 256,
        'seq_len': 8192, 'batch': 1, 'iterator': 'serial',
        'device_prefetch': 0}
    spec = harness.Spec(CELL)
    assert [m['name'] for m in spec.end_to_end] == [
        'train_samples_per_s', 'setup_s']
    names = {m['name'] for m in spec.per_layer}
    assert set(MINE) <= names and 'mfu.train' in names
    # every trainer-step / input-path / device metric the GPT-2 cell has
    other = {m['name'] for m in harness.Spec('gpt2m-train-1k').per_layer}
    assert other <= names
    assert set(_json('chipbench/limits/%s.json' % CELL)) >= {
        'loss_gap', 'first_grad_norm_gap_mean', 'nonfinite_losses'}
    # at most a quarter of the cells, rounded down, on four chips
    assert sum(w['chips'] == 4 for w in bench['workloads']) == 1
    assert len(bench['workloads']) == 9 and len(bench['configs']) == 7


def test_operation_counts_at_the_published_sizes(cfg):
    from chipbench.reference import deepseek_v3 as ref
    mix = _json('chipbench/traffic/lm-b1-s8192.json')
    # the issue's table: 255 M weights a token meets, 84 MFLOP of
    # causal attention a token a layer
    assert round(ref.matmul_weights_per_token(cfg) / 1e6) == 255
    per_layer = ref.attention_flops_per_sample(cfg, 8192) / 8192 / 5
    assert round(per_layer / 1e6) == 84
    total = ref.train_flops_per_sample(cfg, mix)
    assert 22.7e12 < total < 22.9e12
    attention = 3 * ref.attention_flops_per_sample(cfg, 8192)
    assert 0.44 < attention / total < 0.46
    # the kernels' numerators add up to the same attention count, and
    # the experts' to the expectation in train_flops_per_sample
    assert kanana_rooflines.mla_train_flops(cfg, 8192, 1) == attention
    held = kanana_rooflines.held_assignments_expected(cfg, 8192)
    assert held == 8192 * 6 * 16 / 128 * 4
    assert kanana_rooflines.moe_train_flops(cfg, held) == (
        3 * 2 * 8192 * 4 * 0.75 * 3 * 2048 * 768)


class _Trace:
    def __init__(self, ops, launches):
        self.op_seconds = collections.Counter(ops)
        self._launches = launches

    def module(self, pattern):
        return (self._launches, 1.0) if pattern in 'jit_train_step' \
            else (0, 0.0)


def test_the_reader_finds_the_kernels_by_type(cfg):
    """Labels as ``chipbench/trace.py`` makes them from the step
    compiled for a described v5e (``tests/test_chip_compile.py`` holds
    the kernels' shapes)."""
    read = harness.Spec(CELL).reader('roofline_train_moe')
    p = 'pallas custom-call '
    ops = {
        p + '(bf16[32,8192,128], f32[32,1,8192])': 0.10,
        p + 'bf16[32,8192,192]': 0.06,
        p + '(bf16[32,8192,192], bf16[32,8192,128])': 0.08,
        p + 'bf16[49152,2048]': 0.004,
        p + '(bf16[49152,2048], bf16[49152,768], bf16[49152,768], '
            'bf16[49152,768])': 0.005,
        p + '(bf16[16,2048,768], bf16[16,2048,768], '
            'bf16[16,768,2048])': 0.003,
        p + '(f32[8192,1], f32[8192,1])': 0.5,      # the loss: not ours
        'fusion bf16[8192,2048]': 9.0}

    class Device:
        platform, device_kind = 'tpu', 'TPU v5 lite'

    run = harness.Run(harness.Spec(CELL), 7, 1.0, 1, 0.0, [Device()])
    run.trace = _Trace(ops, 2)
    run.program_spans = [
        ({'name': 'train_update', 'held_assignments': h,
          'assignments': 196608.0}, 0.0, 1.0) for h in (24000.0, 25152.0)]
    mla = read(run, what='mla_train')
    assert mla == pytest.approx(100 * 2 * kanana_rooflines.mla_train_flops(
        cfg, 8192, 1) / 197e12 / 0.24)
    moe = read(run, what='moe_train')
    assert moe == pytest.approx(100 * 2 * kanana_rooflines.moe_train_flops(
        cfg, 24576.0) / 197e12 / 0.012)
    assert 0 < mla <= 100 and 0 < moe <= 100
    # a program older than the counter, and a run without a trace
    run.program_spans = [({'name': 'train_update'}, 0.0, 1.0)]
    assert read(run, what='moe_train') is None
    run.trace = None
    assert read(run, what='mla_train') is None


def test_reference_share_sums_to_the_uncut_layer():
    """The routed parts all the shares give + the shared expert once =
    the layer with every expert held."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import common, deepseek_v3 as ref
    prec = common.Precision('float32')
    whole = dict(TINY, n_routed_experts=16, first_expert=0)
    lp = ref.init_params(whole, 3)['layer_1']
    m = jax.random.normal(jax.random.key(0), (24, 32), jnp.float32)
    want, _ = ref._experts(m, lp, whole, prec)
    shared = ref._swiglu(m, lp['shared'], prec)
    total = shared
    for first in range(0, 16, 4):
        part = dict(lp, experts={k: v[first:first + 4]
                                 for k, v in lp['experts'].items()})
        got, _ = ref._experts(m, part, dict(TINY, first_expert=first),
                              prec)
        total = total + (got - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_gradients_zero_gap_against_itself_positive_under_fp8():
    from chipbench.reference import common, deepseek_v3 as ref
    rng = np.random.RandomState(0)
    batch = tuple(rng.randint(0, 96, (2, 32)).astype(np.int32)
                  for _ in range(2))
    params = ref.init_params(TINY, 11)
    loss, grads = ref.make_grad_fn(TINY)(params, batch)
    low_loss, low = ref.make_grad_fn(TINY, precision='fp8')(params, batch)
    assert abs(float(loss) - np.log(96)) < 0.2
    gaps = common.leaf_gaps(np.asarray(common.leaf_norms(low)),
                            np.asarray(common.leaf_norms(grads)))
    assert gaps.mean() > 0.01 and float(low_loss) != float(loss)
    # the bias chooses and is never differentiated
    assert float(np.abs(np.asarray(
        grads['layer_1']['expert_bias'])).max()) == 0.0


# -- a tiny cell through the real harness ------------------------------

@pytest.fixture(scope='module')
def root(tmp_path_factory):
    """``tiny.make_root``'s checkout with a tiny ``deepseek_v3``
    configuration, mix and cell ADDED beside the others."""
    root = tiny.make_root(tmp_path_factory.mktemp('kanana'))
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['configs'].append(
        {'name': 'kanana', 'source': 'test', 'why': 'tiny',
         'reduced': [], 'file': 'chipbench/configs/kanana.json'})
    bench['workloads'].append(
        {'name': 'kanana-train', 'config': 'kanana', 'chips': 1,
         'traffic': 'lm-tiny-8', 'why': 'tiny'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'lm-train' in m.get('workloads', ()) or m['name'] in MINE:
            m['workloads'].append('kanana-train')
    for relative, obj in (
            ('BENCHMARK.json', bench),
            ('chipbench/configs/kanana.json', TINY),
            ('chipbench/traffic/lm-tiny-8.json', TINY_MIX),
            # bfloat16 against float32 at toy widths on a CPU
            ('chipbench/limits/kanana-train.json',
             {'loss_gap': 0.002, 'first_grad_norm_gap_mean': 0.05,
              'param_change_norm_gap_mean': 0.5,
              'nonfinite_losses': 0})):
        tiny._dump(os.path.join(root, relative), obj)
    return root


@pytest.mark.parametrize('trace', [0, 1])
def test_tiny_kanana_cell_through_the_harness(root, trace):
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'kanana-train', trace=trace, seconds=0.6)
    finally:
        telemetry.disable()
    assert result['correct'] is True, result['checks']
    assert result['failed'] == 0 and result['attempted'] > 0
    metrics = result['metrics']
    if not trace:
        assert set(metrics) == {'train_samples_per_s', 'setup_s'}
        return
    # no chip, so no device trace: the MXU shares and mfu are absent;
    # the counters the trainer hangs on its span are read
    assert not any('mxu' in k or 'mfu' in k for k in metrics)
    share = metrics['held_assignments_share']['value']
    assert 0 < share < 100          # 4 of 16 experts: 25 in expectation
    assert metrics['expert_load_max_over_mean.train']['value'] >= 1.0


def test_the_tiny_cell_leaves_other_families_metrics_alone(root):
    """The new metrics read nothing in a cell of another family: the
    line leaves them out and nothing raises."""
    from chainermn_tpu import telemetry
    telemetry.disable()
    try:
        result = tiny.run(root, 'lm-train', trace=1, seconds=0.4)
    finally:
        telemetry.disable()
    assert result['correct'] is True, result['checks']
    assert not set(MINE) & set(result['metrics'])
