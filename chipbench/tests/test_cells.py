"""Every kind of cell end to end on the CPU at a tiny size: the
data-driven add of a configuration, a mix, a cell and a per-layer
metric; the result line's keys; the plain references against the
program; the control and the broken timed paths coming out as not
correct; and no result without a chip."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny
from chipbench import harness

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp('bench'))


@pytest.mark.parametrize('cell, e2e', [
    ('lm-train', {'train_samples_per_s', 'setup_s'}),
    ('lm-train-dp4', {'train_samples_per_s', 'setup_s'}),
    ('resnet-train', {'train_samples_per_s', 'setup_s'}),
    ('lm-serve', {'serve_tokens_per_s', 'ttft_p75_ms', 'tpot_p90_ms',
                  'setup_s'}),
])
def test_rehearsal_prints_the_contracts_keys(root, cell, e2e):
    result = tiny.run(root, cell, seed=2147483999)
    assert KEYS <= set(result)
    json.dumps(result)
    assert result['correct'] is True, result['checks']
    assert result['attempted'] > 0 and result['failed'] == 0
    assert set(result['metrics']) == e2e
    for m in result['metrics'].values():
        assert m['value'] > 0 and isinstance(m['unit'], str)
    chips = 4 if cell.endswith('dp4') else 1
    assert result['device'] == {'platform': 'cpu', 'kind': 'cpu',
                                'count': chips,
                                'memory_peak_bytes': None}
    assert len(result['checks']) >= 4


@pytest.mark.parametrize('cell, some', [
    ('lm-train', {'data_wait_share', 'step_ms'}),
    ('lm-serve', {'decode_tick_ms', 'admit_tick_ms', 'decode_occupancy',
                  'ttft_p90_ms', 'itl_p99_ms',
                  'client_resubmit_p99_ms'}),
])
def test_traced_rehearsal_reads_the_host_side_layers(root, cell, some):
    """No chip, so no device plane: the readers of the device trace
    find nothing and their metrics are left out of the line."""
    result = tiny.run(root, cell, trace=1, seconds=0.5)
    assert result['correct'] is True
    assert some <= set(result['metrics'])
    assert not any('pallas' in k or 'idle' in k or 'device_ms' in k
                   or 'mfu' in k for k in result['metrics'])
    assert 'busy_s' not in result['device']


def test_a_new_per_layer_metric_is_a_file_and_an_entry(root):
    """A later PR's metric: one JSON file, one reader file, one entry
    of ``per_layer`` -- and no edit of a file that is there."""
    with open(os.path.join(root, 'chipbench/readers/steps.py'), 'w') as f:
        f.write('def read(run, scale):\n'
                '    return scale * run.counters["steps"]\n')
    with open(os.path.join(root, 'chipbench/layer_metrics/steps_x2.json'),
              'w') as f:
        json.dump({'name': 'steps_x2', 'unit': 'steps', 'reader': 'steps',
                   'layer': 'trainer step', 'moves': 'train_samples_per_s',
                   'args': {'scale': 2}}, f)
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        bench = json.load(f)
    bench['per_layer'].append({
        'name': 'steps_x2', 'unit': 'steps', 'better': 'higher',
        'source': 'program_counter', 'layer': 'trainer step',
        'moves': 'train_samples_per_s', 'workloads': ['lm-train']})
    with open(path, 'w') as f:
        json.dump(bench, f)
    result = tiny.run(root, 'lm-train', trace=1, seconds=0.3)
    assert result['metrics']['steps_x2']['value'] == \
        2 * result['attempted']


def test_unchanged_state_comes_out_as_not_correct(root, monkeypatch):
    """The timed path broken underneath: a step that returns its state
    unchanged.  The loss at seeded weights hardly notices; the norm of
    the parameters' change is there to catch it."""
    from chainermn_tpu.training import StandardUpdater
    real = StandardUpdater.update_core

    def frozen(self, arrays):
        before = (self.params, self.opt_state)
        # the real step donates its inputs: step on copies
        import jax
        self.params, self.opt_state = jax.tree_util.tree_map(
            lambda x: x + 0, before)
        metrics = real(self, arrays)
        self.params, self.opt_state = before
        return metrics

    monkeypatch.setattr(StandardUpdater, 'update_core', frozen)
    result = tiny.run(root, 'lm-train', seconds=0.2)
    checks = {n: (v, limit) for n, v, limit in result['checks']}
    assert result['correct'] is False
    value, limit = checks['param_change_norm_gap']
    assert value == pytest.approx(1.0) and value > limit


def test_part_of_the_batch_left_out_comes_out_as_not_correct(
        root, monkeypatch):
    """A shard_batch that feeds the first half of the rows twice: the
    loss is that of another batch."""
    from chainermn_tpu.training import StandardUpdater
    real = StandardUpdater.shard_batch

    def half(self, batch):
        n = len(batch) // 2
        return real(self, list(batch[:n]) + list(batch[:n]))

    monkeypatch.setattr(StandardUpdater, 'shard_batch', half)
    result = tiny.run(root, 'lm-train', seconds=0.2)
    assert result['correct'] is False
    checks = {n: (v, limit) for n, v, limit in result['checks']}
    assert checks['loss_gap'][0] > checks['loss_gap'][1]


def test_an_altered_token_comes_out_as_not_correct(root, monkeypatch):
    """A served token altered where it is produced (every 5th token the
    engine hands to ``on_token`` is shifted by one)."""
    from chainermn_tpu.serving.generate import GenRequest
    real = GenRequest.notify_tokens
    count = [0]

    def altered(self, tokens):
        out = []
        for tok in tokens:
            count[0] += 1
            out.append((tok + 1) % 256 if count[0] % 5 == 0 else tok)
        return real(self, out)

    monkeypatch.setattr(GenRequest, 'notify_tokens', altered)
    result = tiny.run(root, 'lm-serve', seconds=0.5)
    assert result['correct'] is False
    checks = {n: (v, limit) for n, v, limit in result['checks']}
    assert checks['served_logit_gap_widest'][0] > \
        checks['served_logit_gap_widest'][1]


@pytest.mark.parametrize('cell, number', [
    ('lm-train', 'first_grad_norm_gap_mean'),
    ('resnet-train', 'first_grad_norm_gap_mean'),
    ('lm-serve', 'served_logit_gap_mean'),
])
def test_the_control_comes_out_as_not_correct(root, cell, number):
    """The control -- the reference in fp8 in the program's place -- at a
    size a test can hold: it fails the limit the sound program keeps.
    (The chip's readings at the cells' own sizes are in PERF.md; they
    set the chip's limits.)"""
    import time
    spec = harness.Spec(cell, root=root)
    result = harness.run_cell(spec, 11, 0.3, 0, time.perf_counter(),
                              platform='cpu', control=True)
    sound = {n: v for n, v, _ in result['checks']}
    control = dict(result['control'])
    assert result['correct'] is True, result['checks']
    assert control[number] > spec.limits[number] >= sound[number]


def test_without_a_chip_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, 'run.py'),
         '--workload', 'gpt2m-train-1k', '--seed', '1', '--seconds', '1',
         '--trace', '0'], env=env, capture_output=True, text=True,
        timeout=300, cwd=harness.ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert 'need 1 tpu chip' in proc.stderr


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.Spec('no-such-cell')


def test_the_references_weights_are_seeded_and_large_seeds_differ():
    from chipbench.reference import transformer_lm as ref
    a = ref.init_params(tiny.LM, 2147483999)
    b = ref.init_params(tiny.LM, 2147483999)
    c = ref.init_params(tiny.LM, 2147483999 + 2 ** 31)
    ka, kb, kc = (np.asarray(t['lm_head']['kernel']) for t in (a, b, c))
    assert np.array_equal(ka, kb) and not np.array_equal(ka, kc)
    assert ka.std() == pytest.approx(0.02, rel=0.05)
