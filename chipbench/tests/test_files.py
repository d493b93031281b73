"""``BENCHMARK.json`` against the files its names lead to, and against
the letter of the benchmark's contract."""

import json
import os
import re

import pytest

from chipbench import harness

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter',
           'host_clock'}


@pytest.fixture(scope='module')
def bench():
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert bench['command'] == ['python3', 'chipbench/run.py']
    assert bench['paths'] == ['chipbench']
    assert 1 <= bench['run_seconds'] <= 51
    # a full check with all 24 cells has to fit into 43,200 s
    runs = 2 + 14 * 24
    assert runs * (bench['run_seconds'] + 60) + 24 * 180 + 1200 <= 43200
    size = os.path.getsize(os.path.join(harness.ROOT, 'BENCHMARK.json'))
    assert size <= 64 * 1024


def test_end_to_end_metrics_are_the_issues_five(bench):
    names = [m['name'] for m in bench['end_to_end']]
    assert sorted(names) == sorted([
        'train_samples_per_s', 'serve_tokens_per_s', 'ttft_p75_ms',
        'tpot_p90_ms', 'setup_s'])
    for m in bench['end_to_end']:
        assert set(m) <= {'name', 'unit', 'better', 'bound', 'source',
                          'workloads'}
        assert 0.01 <= m['bound'] <= 0.1
        assert m['source'] in ('host_clock', 'device_trace')
    layer_names = {m['name'] for m in bench['per_layer']}
    assert {'itl_p99_ms', 'ttft_p90_ms'} <= layer_names
    assert not layer_names & set(names)


def test_names_units_and_whys(bench):
    entries = (bench['configs'] + bench['workloads'] + bench['end_to_end']
               + bench['per_layer'])
    for e in entries:
        assert NAME.match(e['name']), e['name']
        if 'unit' in e:
            assert UNIT.match(e['unit']), e
            assert e['better'] in ('lower', 'higher')
            assert e['source'] in SOURCES
        for key in ('why', 'layer', 'source'):
            if key in e:
                assert 1 <= len(e[key]) <= 200, (e['name'], key)
                assert '\n' not in e[key] and '\t' not in e[key]
    for group in ('configs', 'workloads'):
        names = [e['name'] for e in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m['name'] for m in bench['end_to_end'] + bench['per_layer']]
    assert len(metrics) == len(set(metrics))
    pairs = [(w['config'], w['traffic']) for w in bench['workloads']]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench['workloads'] if w['chips'] == 4]
    assert len(four) <= max(1, len(bench['workloads']) // 4)


def test_every_name_leads_to_its_file(bench):
    cells = {w['name'] for w in bench['workloads']}
    configs = {c['name']: c for c in bench['configs']}
    for c in bench['configs']:
        assert c['file'].startswith('chipbench/')
        assert c['reduced'] == []
        with open(os.path.join(harness.ROOT, c['file'])) as f:
            cfg = json.load(f)
        assert 'family' in cfg and 'assumed' in cfg
    assert {w['config'] for w in bench['workloads']} == set(configs)
    for w in bench['workloads']:
        spec = harness.Spec(w['name'])
        assert spec.mix['kind'] in ('train', 'serve_closed')
        assert spec.limits
        assert any(m['name'] == 'setup_s' for m in spec.end_to_end)
        assert len(spec.end_to_end) >= 2 and spec.per_layer
        for m in spec.per_layer:
            assert callable(spec.reader(m['reader']))
    e2e = {m['name']: m for m in bench['end_to_end']}
    for m in bench['per_layer']:
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
        assert set(m['workloads']) <= cells
        moved = e2e[m['moves']]
        assert set(m['workloads']) <= set(moved.get('workloads', cells))
        with open(os.path.join(harness.HERE, 'layer_metrics',
                               m['name'] + '.json')) as f:
            own = json.load(f)
        for key in ('name', 'unit', 'layer', 'moves'):
            assert own[key] == m[key], (m['name'], key)


def test_one_layer_one_spelling(bench):
    layers = {m['layer'] for m in bench['per_layer']}
    assert len({x.lower() for x in layers}) == len(layers)


def test_gpt2_medium_is_as_published():
    with open(os.path.join(harness.HERE, 'configs',
                           'gpt2-medium.json')) as f:
        cfg = json.load(f)
    assert (cfg['n_embd'], cfg['n_layer'], cfg['n_head'],
            cfg['n_positions'], cfg['vocab_size'], cfg['n_inner']) == (
        1024, 24, 16, 1024, 50257, 4096)
