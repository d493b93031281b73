"""Round-5 perf tooling tests: scaling-projection input parsing and
math, the real-data digits builder, and the host-init helpers.

These are the chip-independent parts of the perf evidence chain
(VERDICT r4 next #5/#6/#8); the on-chip halves live in
``benchmarks/results/`` artifacts.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, 'ci'))


# ----------------------------------------------------------------------
# scaling projection

def _write_rows(path, rows):
    with open(path, 'w') as f:
        for r in rows:
            f.write(json.dumps(r) + '\n')


def test_measured_inputs_tracks_raw_min_and_skips_suspect(tmp_path,
                                                          monkeypatch):
    from benchmarks import scaling_projection as sp
    monkeypatch.setattr(sp, 'RES', str(tmp_path))
    _write_rows(
        os.path.join(str(tmp_path), 'allreduce_tpu_rX.out'),
        [
            {'metric': 'hbm_touch_bandwidth', 'measured_hbm_gbs': 600.0},
            # suspect rows must not contribute anything
            {'metric': 'allreduce_payload_sweep', 'payload_mb': 102.4,
             'strategy': 'naive', 'staging_overhead_ms': -9.0,
             'suspect': True},
            # raw minimum is the NEGATIVE xla row (noise) -> clamped
            # to 0 at use, but the recorded strategy must be xla, not
            # whichever negative row came last
            {'metric': 'allreduce_payload_sweep', 'payload_mb': 102.4,
             'strategy': 'xla', 'staging_overhead_ms': -0.006,
             'staging_below_noise': True},
            {'metric': 'allreduce_payload_sweep', 'payload_mb': 102.4,
             'strategy': 'bucketed', 'staging_overhead_ms': -0.002,
             'staging_below_noise': True},
            # small-payload rows are ignored (>50 MB filter)
            {'metric': 'allreduce_payload_sweep', 'payload_mb': 25.6,
             'strategy': 'flat', 'staging_overhead_ms': -7.0},
        ])
    _write_rows(
        os.path.join(str(tmp_path), 'bench_resnet50_rX.out'),
        [{'step_time_ms': 12.5}])
    got = sp.measured_inputs('rX')
    assert got['hbm_gbs'] == 600.0
    assert got['staging_ms'] == 0.0
    assert got['staging_strategy'] == 'xla'
    assert got['staging_below_noise'] is True
    assert got['step_time_ms'] == 12.5


def test_measured_inputs_positive_staging_beats_stale_noise(tmp_path,
                                                            monkeypatch):
    from benchmarks import scaling_projection as sp
    monkeypatch.setattr(sp, 'RES', str(tmp_path))
    _write_rows(
        os.path.join(str(tmp_path), 'allreduce_tpu_rX.out'),
        [{'metric': 'allreduce_payload_sweep', 'payload_mb': 102.4,
          'strategy': 'flat', 'staging_overhead_ms': 0.12},
         {'metric': 'allreduce_payload_sweep', 'payload_mb': 102.4,
          'strategy': 'hierarchical', 'staging_overhead_ms': 0.05}])
    got = sp.measured_inputs('rX')
    # a real positive minimum is kept as-is with its strategy
    assert got['staging_ms'] == 0.05
    assert got['staging_strategy'] == 'hierarchical'


def test_projection_rows_are_labeled_and_monotone(tmp_path):
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, 'benchmarks', 'scaling_projection.py'),
         '--tag', 'nonexistent_tag', '--results-dir', str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith('{')]
    assert all(r.get('projection') is True for r in rows)
    proj = [r for r in rows
            if r['metric'] == 'allreduce_scaling_projection']
    assert [r['devices'] for r in proj] == [8, 16, 32, 64, 128, 256]
    effs = [r['scaling_efficiency_vs_8'] for r in proj]
    # flat-payload scaling efficiency starts at 1 and degrades
    # monotonically as the (N-1)/N wire term grows
    assert effs[0] == 1.0
    assert all(a >= b for a, b in zip(effs, effs[1:]))
    assert all(0.5 < e <= 1.0 for e in effs)
    # fallback inputs must be LABELED as unmeasured
    assumptions = next(r for r in rows
                       if r['metric'] == 'scaling_projection_assumptions')
    assert assumptions['staging_ms_measured'] is False
    assert assumptions['resnet50_step_ms_measured'] is False


# ----------------------------------------------------------------------
# real-data digits npz

def test_digits_npz_build_shapes_and_determinism():
    pytest.importorskip('sklearn')
    import make_digits_npz
    a = make_digits_npz.build()
    b = make_digits_npz.build()
    assert a['x_train'].shape == (1437, 28, 28)
    assert a['x_test'].shape == (360, 28, 28)
    assert a['x_train'].dtype == np.uint8
    assert int(a['x_train'].max()) <= 255
    assert set(np.unique(a['y_train'])) == set(range(10))
    # deterministic split: the gate must see the same data every run
    assert np.array_equal(a['x_train'], b['x_train'])
    assert np.array_equal(a['y_test'], b['y_test'])
    # train/test must not overlap (split is a permutation)
    assert len(a['y_train']) + len(a['y_test']) == 1797


# ----------------------------------------------------------------------
# host-init helpers

def _rs_row(value, override=None, stem=None, **kw):
    row = {'metric': 'resnet50_train_images_per_sec_per_chip',
           'backend': 'tpu', 'value': value,
           'per_device_batch_override': override, 'stem': stem}
    row.update(kw)
    return row


def test_pick_tuned_resnet50_crowns_best_trustworthy_tuned_row():
    from bench import pick_tuned_resnet50
    flags, source, value = pick_tuned_resnet50([
        _rs_row(2588.0, _source='bench_resnet50_r5.out'),
        _rs_row(4100.0, override=128, _source='bench_resnet50_b128_r5.out'),
        # higher but suspect -> must not win
        _rs_row(9000.0, override=256, suspect=True,
                _source='bench_resnet50_b256_r5.out'),
        # higher but error row -> must not win
        _rs_row(9500.0, override=256, error='bench_timeout',
                _source='bench_resnet50_b256_r4.out'),
        # higher but CPU backend -> must not win
        dict(_rs_row(9999.0, override=256), backend='cpu'),
        _rs_row(3900.0, override=64, stem='space_to_depth',
                _source='bench_resnet50_s2d_r5.out'),
    ])
    assert flags == ['--batch', '128']
    assert source == 'bench_resnet50_b128_r5.out'
    assert value == 4100.0


def test_pick_tuned_resnet50_keeps_default_when_it_wins():
    from bench import pick_tuned_resnet50
    flags, source, value = pick_tuned_resnet50([
        _rs_row(2588.0),
        _rs_row(2100.0, override=64),
    ])
    assert flags is None and source is None and value is None


def test_pick_tuned_resnet50_stem_only_and_combined_flags():
    from bench import pick_tuned_resnet50
    flags, _, _ = pick_tuned_resnet50([
        _rs_row(2588.0),
        _rs_row(3000.0, stem='space_to_depth'),
    ])
    assert flags == ['--s2d']
    flags, _, _ = pick_tuned_resnet50([
        _rs_row(2588.0),
        _rs_row(3000.0, override=128, stem='space_to_depth'),
    ])
    assert flags == ['--batch', '128', '--s2d']


def test_pick_tuned_resnet50_no_rows_and_garbage_rows():
    from bench import pick_tuned_resnet50
    assert pick_tuned_resnet50([]) == (None, None, None)
    assert pick_tuned_resnet50(
        [{'metric': 'mlp_train_images_per_sec_per_chip',
          'backend': 'tpu', 'value': 1.0,
          'per_device_batch_override': 64},
         'not-a-dict', {'value': 'nan-ish'}]) == (None, None, None)


def test_adopt_tuned_config_reads_artifacts_and_sets_env(tmp_path,
                                                         monkeypatch):
    import bench
    res = tmp_path / 'benchmarks' / 'results'
    res.mkdir(parents=True)
    (res / 'bench_resnet50_r5.out').write_text(
        json.dumps(_rs_row(2588.0)) + '\n')
    (res / 'bench_resnet50_b128_r5.out').write_text(
        '[bench] stray log line\n' + json.dumps(_rs_row(4100.0,
                                                        override=128)))
    monkeypatch.setattr(
        bench.os.path, 'dirname',
        lambda p, _real=bench.os.path.dirname:
            str(tmp_path) if p == bench.os.path.abspath(bench.__file__)
            else _real(p))
    # setenv FIRST so monkeypatch records the pre-test state and
    # teardown restores it even though the code under test mutates
    # the variable (delenv(raising=False) on an absent var records
    # nothing and would leak fabricated provenance after the test)
    monkeypatch.setenv('CHAINERMN_TPU_ADOPTED_FROM', 'sentinel')
    os.environ.pop('CHAINERMN_TPU_ADOPTED_FROM')
    argv = bench.adopt_tuned_config(['--quick'], 'resnet50')
    assert argv == ['--quick', '--batch', '128']
    assert os.environ['CHAINERMN_TPU_ADOPTED_FROM'] == \
        'bench_resnet50_b128_r5.out'
    # explicit flags disable adoption AND clear inherited provenance
    # (a wrapper-exported stale value must not fabricate a row field)
    os.environ['CHAINERMN_TPU_ADOPTED_FROM'] = 'stale.out'
    assert bench.adopt_tuned_config(['--batch', '64'], 'resnet50') == \
        ['--batch', '64']
    assert 'CHAINERMN_TPU_ADOPTED_FROM' not in os.environ
    assert bench.adopt_tuned_config(['--no-adopt'], 'resnet50') == \
        ['--no-adopt']
    assert bench.adopt_tuned_config([], 'vgg16') == []
    # a stale tuned winner from an OLDER round is ignored once the
    # newest tag has any trustworthy row: r6's default-config row
    # becomes the deciding tag even though r5 crowned --batch 128
    (res / 'bench_resnet50_r6.out').write_text(
        json.dumps(_rs_row(2600.0)) + '\n')
    assert bench.adopt_tuned_config(['--quick'], 'resnet50') == \
        ['--quick']
    assert 'CHAINERMN_TPU_ADOPTED_FROM' not in os.environ
    # ...but a newest tag holding ONLY suspect rows defers to the
    # last tag that produced trustworthy data
    (res / 'bench_resnet50_r6.out').write_text(
        json.dumps(_rs_row(2600.0, suspect=True)) + '\n')
    argv = bench.adopt_tuned_config(['--quick'], 'resnet50')
    assert argv == ['--quick', '--batch', '128']
    # untagged artifacts (no _rN suffix) are ignored entirely
    (res / 'bench_resnet50_custom.out').write_text(
        json.dumps(_rs_row(99999.0, override=512)) + '\n')
    argv = bench.adopt_tuned_config(['--quick'], 'resnet50')
    assert argv == ['--quick', '--batch', '128']
    # multi-underscore sweep filenames must group into the SAME tag
    # as the plain headline artifact (a \w-style tag regex once
    # swallowed '..._b64_r5' whole, splitting every artifact into its
    # own tag and crowning a tuned row that LOSES to the incumbent)
    (res / 'bench_resnet50_s2d_b96_r6.out').write_text(
        json.dumps(_rs_row(1000.0, override=96,
                           stem='space_to_depth')) + '\n')
    (res / 'bench_resnet50_r6.out').write_text(
        json.dumps(_rs_row(2600.0)) + '\n')
    assert bench.adopt_tuned_config(['--quick'], 'resnet50') == \
        ['--quick']
    for f in ('bench_resnet50_s2d_b96_r6.out', 'bench_resnet50_r6.out'):
        (res / f).unlink()
    # a newest tag holding only value-less rows (no error field, but
    # value 0/NaN) must NOT terminate the tag search
    (res / 'bench_resnet50_r6.out').write_text(
        json.dumps(_rs_row(0.0)) + '\n'
        + json.dumps(_rs_row(float('nan'), override=256)))
    argv = bench.adopt_tuned_config(['--quick'], 'resnet50')
    assert argv == ['--quick', '--batch', '128']


# ----------------------------------------------------------------------
# series dead-backend circuit breaker (ci/run_tpu_round.sh)

def _drive_breaker(tmp_path, outcomes):
    """Source note_outcome from the series script and feed it a
    sequence of (rc, row-or-None); returns the shell's exit code and
    stdout (DEAD counter printed after each call)."""
    files = []
    for i, (_, row) in enumerate(outcomes):
        p = tmp_path / ('o%d.out' % i)
        p.write_text('' if row is None else json.dumps(row) + '\n')
        files.append(str(p))
    calls = '\n'.join(
        'note_outcome %d %s; echo "DEAD=$DEAD"' % (rc, f)
        for (rc, _), f in zip(outcomes, files))
    script = (
        'source <(sed -n "/^DEAD=0/,/^}/p" %s)\n%s\n'
        % (os.path.join(REPO, 'ci', 'run_tpu_round.sh'), calls))
    p = subprocess.run(['bash', '-c', script], capture_output=True,
                       text=True, cwd=REPO)
    return p.returncode, p.stdout


def test_series_breaker_trips_on_two_consecutive_dead_steps(tmp_path):
    dead = {'metric': 'x', 'value': 0.0, 'error': 'backend_unavailable'}
    rc, out = _drive_breaker(tmp_path, [(1, dead), (1, dead)])
    assert rc == 4
    assert out.splitlines() == ['DEAD=1']  # second call exits


def test_series_breaker_resets_on_success_and_live_failure(tmp_path):
    dead = {'metric': 'x', 'value': 0.0, 'error': 'bench_timeout'}
    ok = {'metric': 'x', 'value': 5.0}
    live = {'metric': 'x', 'value': 0.0, 'error': 'bench_failed'}
    rc, out = _drive_breaker(
        tmp_path,
        [(1, dead), (0, ok), (1, dead), (1, live), (124, None)])
    # success and a live (backend-answered) failure both break the
    # consecutive-dead run; the bare timeout then only reaches DEAD=1
    assert rc == 0
    assert out.splitlines() == ['DEAD=1', 'DEAD=0', 'DEAD=1',
                                'DEAD=0', 'DEAD=1']


# ----------------------------------------------------------------------
# trace report (benchmarks/trace_report.py)

def _datatable(cols, rows):
    return {'cols': [{'id': c} for c in cols],
            'rows': [{'c': [{'v': v} for v in r]} for r in rows]}


def test_trace_report_buckets_and_top_ops(tmp_path, monkeypatch):
    from benchmarks import trace_report as tr
    table = _datatable(
        ['category', 'hlo_op_name', 'occurrences', 'total_self_time',
         'model_flop_rate', 'measured_memory_bw', 'dma_stall_percent'],
        [
            ['convolution', '%conv.1', 3, 5000.0, 120.0, 300.0, 2.0],
            ['convolution fusion', '%conv.2', 3, 3000.0, 90.0, 250.0,
             0.0],
            ['loop fusion', '%fused.bn', 49, 2500.0, None, 400.0, 10.0],
            ['copy', '%copy.3', 7, 1000.0, None, 500.0, 0.0],
            ['all-reduce', '%ar.1', 1, 500.0, None, None, 0.0],
            ['weird-new-category', '%x.1', 1, 100.0, None, None, None],
            ['convolution', '%conv.zero', 1, 0.0, None, None, None],
        ])
    d = tmp_path / 'trace'
    d.mkdir()
    (d / 'host.xplane.pb').write_bytes(b'\x00')  # existence only
    overview = {'cols': [], 'rows': [],
                'p': {'device_duty_cycle_percent': '41.0%',
                      'mxu_utilization_percent': '18.2%',
                      'not_a_surfaced_key': 'x'}}
    monkeypatch.setattr(
        tr, '_tool_tables',
        lambda paths, tool: ([overview] if tool == 'overview_page'
                             else [table]))
    rep = tr.analyze_trace(str(d))
    assert rep['source'] == 'hlo_stats'
    assert rep['device_utilization'] == {
        'device_duty_cycle_percent': '41.0%',
        'mxu_utilization_percent': '18.2%'}
    assert rep['total_self_time_us'] == 12100.0
    b = rep['buckets']
    assert b['conv/matmul']['self_time_us'] == 8000.0
    assert b['conv/matmul']['pct'] == 66.1
    assert b['fusion/elementwise']['self_time_us'] == 2500.0
    assert b['copy/transpose']['self_time_us'] == 1000.0
    assert b['collective']['self_time_us'] == 500.0
    assert b['other']['self_time_us'] == 100.0
    # buckets ordered by descending self time
    assert list(b) == ['conv/matmul', 'fusion/elementwise',
                       'copy/transpose', 'collective', 'other']
    assert rep['top_ops'][0]['op'] == '%conv.1'
    # zero-self-time rows are dropped entirely
    assert all(o['op'] != '%conv.zero' for o in rep['top_ops'])
    text = tr.render(rep)
    assert 'conv/matmul' in text and '%fused.bn' in text


def test_trace_report_host_fallback_and_degradation(tmp_path,
                                                    monkeypatch):
    from benchmarks import trace_report as tr
    d = tmp_path / 'trace'
    d.mkdir()
    (d / 'host.xplane.pb').write_bytes(b'\x00')
    host = _datatable(
        ['host_or_device', 'type', 'operation', 'occurrences',
         'total_self_time'],
        [['Host', 'matmul', 'jit(f)/dot_general', 8, 900.0]])
    calls = []

    def fake_tables(paths, tool):
        calls.append(tool)
        return [] if tool == 'hlo_stats' else [host]

    monkeypatch.setattr(tr, '_tool_tables', fake_tables)
    rep = tr.analyze_trace(str(d))
    # hlo first, host fallback second; overview_page utilization is
    # queried only after ops were found
    assert calls[:2] == ['hlo_stats', 'framework_op_stats']
    assert rep['source'].startswith('framework_op_stats')
    assert rep['top_ops'][0]['op'] == 'jit(f)/dot_general'
    # missing traces and empty tables degrade to explanatory stubs
    assert 'error' in tr.analyze_trace(str(tmp_path / 'nope'))
    monkeypatch.setattr(tr, '_tool_tables', lambda p, t: [])
    # (the raw host-plane fallback is mocked empty too: the stub
    # bytes above are not a parseable XSpace)
    monkeypatch.setattr(tr, '_collect_host_events',
                        lambda p: ({}, []))
    assert 'rows' in tr.analyze_trace(str(d))['error']
    monkeypatch.setattr(
        tr, '_tool_tables',
        lambda p, t: (_ for _ in ()).throw(RuntimeError('boom')))
    assert 'conversion failed' in tr.analyze_trace(str(d))['error']


def test_trace_report_analyzes_only_newest_session(tmp_path,
                                                   monkeypatch):
    from benchmarks import trace_report as tr
    d = tmp_path / 'trace'
    old = d / 'plugins' / 'profile' / '2026_07_30_01_00_00'
    new = d / 'plugins' / 'profile' / '2026_07_31_02_00_00'
    for s in (old, new):
        s.mkdir(parents=True)
        (s / 'vm.xplane.pb').write_bytes(b'\x00')
    seen = []

    def fake_tables(paths, tool):
        seen.extend(paths)
        return [_datatable(['category', 'hlo_op_name',
                            'total_self_time'],
                           [['convolution', '%c', 10.0]])]

    monkeypatch.setattr(tr, '_tool_tables', fake_tables)
    rep = tr.analyze_trace(str(d))
    # only the newest timestamped session contributes (no
    # double-counting of prior rounds' captures left in the dir)
    assert all('2026_07_31_02_00_00' in p for p in seen) and seen
    assert rep['session'].endswith('2026_07_31_02_00_00')
    assert rep['older_sessions_ignored'] == 1
    assert rep['total_self_time_us'] == 10.0


def test_trace_report_main_writes_jsonl(tmp_path, monkeypatch,
                                        capsys):
    from benchmarks import trace_report as tr
    d = tmp_path / 'traces' / 'tpu' / 'xla'
    d.mkdir(parents=True)
    (d / 'vm.xplane.pb').write_bytes(b'\x00')
    monkeypatch.setattr(tr, 'RES', str(tmp_path))
    monkeypatch.setattr(tr, '_tool_tables', lambda paths, tool: [
        _datatable(['category', 'hlo_op_name', 'total_self_time'],
                   [['convolution', '%c', 10.0]])])
    assert tr.main(['--latest']) == 0
    out = capsys.readouterr().out
    assert 'conv/matmul' in out and 'wrote' in out
    rows = [json.loads(ln) for ln in
            open(str(tmp_path / 'trace_report.json'))]
    assert len(rows) == 1 and rows[0]['source'] == 'hlo_stats'
    # empty tree: says so, still exits 0 (safe to wire into CI)
    monkeypatch.setattr(tr, 'RES', str(tmp_path / 'empty'))
    assert tr.main(['--latest']) == 0
    assert 'no trace dirs' in capsys.readouterr().out


def test_trace_report_real_cpu_capture_produces_breakdown(tmp_path):
    """END-TO-END, nothing mocked: jax.profiler capture on the CPU
    backend -> the REAL xprof/tensorboard converter -> a non-stub
    per-op breakdown.  This is the VERDICT r5 trace-tooling gap
    ("never produced a real breakdown"): the converter's pybind entry
    point moved between TF generations and the old import path died
    on images like this one, so only a mocked parser was ever
    exercised.  A converter regression now fails tier-1 instead of
    surfacing as a silent stub after a paid TPU window."""
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_report as tr

    td = tmp_path / 'trace'
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()  # compile outside the capture
    with jax.profiler.trace(str(td)):
        for _ in range(3):
            r = f(x)
        r.block_until_ready()
    rep = tr.analyze_trace(str(td))
    assert 'error' not in rep, rep
    # a CPU trace has no device plane: the designed degradation is a
    # REAL host-side framework-op breakdown, not a stub
    assert rep['total_self_time_us'] > 0
    assert rep['buckets'] and rep['top_ops'], rep
    assert sum(b['ops'] for b in rep['buckets'].values()) > 0
    # and it renders without crashing on whatever cells came back
    assert rep['trace_dir'] in tr.render(rep)


# ----------------------------------------------------------------------
# banked-last-good lookup (the backend_unavailable degradation path)

def _fake_results(tmp_path, monkeypatch, files):
    import bench
    res = tmp_path / 'benchmarks' / 'results'
    res.mkdir(parents=True)
    for name, row in files.items():
        (res / name).write_text(
            '[bench] log line\n' + json.dumps(row) + '\n')
    monkeypatch.setattr(
        bench.os.path, 'dirname',
        lambda p, _real=bench.os.path.dirname:
            str(tmp_path) if p == bench.os.path.abspath(bench.__file__)
            else _real(p))
    return bench


def test_banked_last_good_picks_newest_trustworthy_round(
        tmp_path, monkeypatch):
    bench = _fake_results(tmp_path, monkeypatch, {
        'bench_resnet50_r4.out': _rs_row(2000.0),
        'bench_resnet50_r5.out': _rs_row(2588.0),
        # newest round exists but is untrustworthy: error, suspect
        # and retracted rows must all be skipped, falling back to r5
        'bench_resnet50_r6.out': _rs_row(0.0, error='bench_timeout'),
        'bench_resnet50_b64_r6.out': _rs_row(9999.0, suspect=True),
        'bench_resnet50_b128_r6.out': _rs_row(14011.0, retracted=True),
    })
    value, tag, src = bench.banked_last_good('resnet50')
    assert (value, tag, src) == (2588.0, 'r5', 'bench_resnet50_r5.out')


def test_banked_last_good_none_when_nothing_trustworthy(
        tmp_path, monkeypatch):
    bench = _fake_results(tmp_path, monkeypatch, {
        'bench_vgg16_r5.out': {'metric': 'vgg16_train_x', 'backend':
                               'tpu', 'value': 0.0, 'error': 'x'},
    })
    assert bench.banked_last_good('vgg16') == (None, None, None)
    # and a model with no artifacts at all
    assert bench.banked_last_good('transformer') == (None, None, None)


def test_banked_last_good_best_within_round(tmp_path, monkeypatch):
    bench = _fake_results(tmp_path, monkeypatch, {
        'bench_resnet50_r5.out': _rs_row(2588.0),
        'bench_resnet50_b128_r5.out': _rs_row(4100.0, override=128),
    })
    value, tag, src = bench.banked_last_good('resnet50')
    assert (value, tag, src) == (
        4100.0, 'r5', 'bench_resnet50_b128_r5.out')


def test_banked_last_good_row_carries_hbm_sidecars(tmp_path,
                                                   monkeypatch):
    # the backend_unavailable row surfaces the banked row's
    # HBM-traffic / MFU diagnostics, not just the bare value
    bench = _fake_results(tmp_path, monkeypatch, {
        'bench_resnet50_r5.out': _rs_row(
            2588.0, hbm_bytes_per_image=316.4e6, pct_of_hbm_peak=93.2,
            pct_of_bf16_peak=16.2, step_time_ms=12.37,
            fused_norm=False),
    })
    row, value, tag, src = bench.banked_last_good_row('resnet50')
    assert value == 2588.0 and tag == 'r5'
    for key in ('hbm_bytes_per_image', 'pct_of_hbm_peak',
                'pct_of_bf16_peak', 'step_time_ms', 'fused_norm'):
        assert key in bench.BANKED_SIDECAR_KEYS
        assert row.get(key) == _rs_row(
            2588.0, hbm_bytes_per_image=316.4e6, pct_of_hbm_peak=93.2,
            pct_of_bf16_peak=16.2, step_time_ms=12.37,
            fused_norm=False)[key]


def test_parse_fused_norm():
    from bench import parse_fused_norm
    assert parse_fused_norm([], 'resnet50') is False
    assert parse_fused_norm(['--fused-norm'], 'resnet50') is True
    assert parse_fused_norm(['--fused-norm'], 'googlenetbn') is True
    for model in ('vgg16', 'mlp', 'transformer'):
        with pytest.raises(SystemExit):
            parse_fused_norm(['--fused-norm'], model)


def test_trustworthy_value_rejects_retracted_rows():
    from bench import _trustworthy_value
    assert _trustworthy_value(_rs_row(100.0)) == 100.0
    assert _trustworthy_value(_rs_row(100.0, retracted=True)) is None
    mlp = {'metric': 'mlp_train_images_per_sec_per_chip',
           'backend': 'tpu', 'value': 5.0}
    assert _trustworthy_value(mlp, 'mlp') == 5.0
    assert _trustworthy_value(mlp) is None  # wrong model prefix


# ----------------------------------------------------------------------
# adoption fairness (ADVICE r5 #1/#2)

def test_row_quickness_recorded_and_inferred():
    from bench import _row_quickness
    assert _row_quickness(_rs_row(1.0, quick=True)) == 'quick'
    assert _row_quickness(_rs_row(1.0, quick=False)) == 'full'
    # legacy rows: inferred from scan lengths
    assert _row_quickness(_rs_row(1.0, scan_lengths=[2, 4, 6])) == \
        'quick'
    assert _row_quickness(_rs_row(1.0, scan_lengths=[4, 8, 12])) == \
        'full'
    assert _row_quickness(_rs_row(1.0)) is None


def test_pick_tuned_only_crowns_against_matching_quickness():
    from bench import _pick_tuned, pick_tuned_resnet50
    # quick tuned winner vs full incumbent only: DECLINED -- the
    # cross-quickness comparison is exactly the bias ADVICE r5 #1
    # forbids
    rows = [
        _rs_row(2588.0, quick=False, _source='full_default.out'),
        _rs_row(4100.0, override=128, quick=True,
                _source='quick_b128.out'),
    ]
    d = _pick_tuned(rows)
    assert d['flags'] is None and 'quickness' in d['declined']
    assert pick_tuned_resnet50(rows) == (None, None, None)
    # matching-quickness incumbent present: crowned, and the
    # comparison provenance is recorded
    rows.append(_rs_row(2500.0, quick=True,
                        _source='quick_default.out'))
    d = _pick_tuned(rows)
    assert d['flags'] == ['--batch', '128']
    assert d['incumbent_source'] == 'quick_default.out'
    assert d['winner_quick'] == 'quick'
    assert d['incumbent_quick'] == 'quick'
    # unknown quickness (legacy rows) still matches anything
    legacy = [_rs_row(2588.0), _rs_row(4100.0, override=128)]
    assert pick_tuned_resnet50(legacy)[0] == ['--batch', '128']


def test_pick_tuned_fallback_incumbent_and_decline():
    from bench import _pick_tuned
    tuned_only = [_rs_row(4100.0, override=128,
                          _source='quick_b128.out')]
    # no incumbent anywhere: DECLINE (the old behavior adopted
    # uncompared -- ADVICE r5 #2's bug)
    d = _pick_tuned(tuned_only)
    assert d['flags'] is None and d.get('declined')
    # fallback incumbent from an older tag: compared against it
    older_default = _rs_row(4500.0, _source='old_default.out')
    d = _pick_tuned(tuned_only, fallback_incumbent=older_default)
    assert d['flags'] is None  # tuned row LOSES to the old default
    assert d['incumbent_source'] == 'old_default.out'
    assert d.get('incumbent_fallback') is True
    slower_default = _rs_row(2500.0, _source='old_default.out')
    d = _pick_tuned(tuned_only, fallback_incumbent=slower_default)
    assert d['flags'] == ['--batch', '128']
    assert d.get('incumbent_fallback') is True


def test_adopt_declines_when_deciding_tag_has_no_incumbent(
        tmp_path, monkeypatch):
    import bench
    res = tmp_path / 'benchmarks' / 'results'
    res.mkdir(parents=True)
    # newest tag holds ONLY a tuned row; the older tag's default row
    # is the fallback incumbent and it BEATS the tuned value, so no
    # adoption happens
    (res / 'bench_resnet50_b128_r7.out').write_text(
        json.dumps(_rs_row(4100.0, override=128)) + '\n')
    (res / 'bench_resnet50_r6.out').write_text(
        json.dumps(_rs_row(4500.0)) + '\n')
    monkeypatch.setattr(
        bench.os.path, 'dirname',
        lambda p, _real=bench.os.path.dirname:
            str(tmp_path) if p == bench.os.path.abspath(bench.__file__)
            else _real(p))
    monkeypatch.setenv('CHAINERMN_TPU_ADOPTED_FROM', 'sentinel')
    monkeypatch.setenv('CHAINERMN_TPU_ADOPTED_COMPARISON', 'sentinel')
    os.environ.pop('CHAINERMN_TPU_ADOPTED_FROM')
    os.environ.pop('CHAINERMN_TPU_ADOPTED_COMPARISON')
    assert bench.adopt_tuned_config([], 'resnet50') == []
    assert 'CHAINERMN_TPU_ADOPTED_FROM' not in os.environ
    # flip the older default below the tuned value: now adopted, with
    # the fallback comparison recorded in the provenance env
    (res / 'bench_resnet50_r6.out').write_text(
        json.dumps(_rs_row(2500.0)) + '\n')
    assert bench.adopt_tuned_config([], 'resnet50') == \
        ['--batch', '128']
    comp = json.loads(os.environ['CHAINERMN_TPU_ADOPTED_COMPARISON'])
    assert comp['incumbent_fallback'] is True
    assert comp['incumbent_source'] == 'bench_resnet50_r6.out'
    assert comp['value'] == 4100.0


# ----------------------------------------------------------------------
# trace_report tolerant parsing + no-dirs stub (ADVICE r5 #3/#4)

def test_trace_report_cell_float_tolerates_formatted_strings():
    from benchmarks.trace_report import cell_float
    assert cell_float(1234.5) == 1234.5
    assert cell_float('1,234') == 1234.0
    assert cell_float('56.2%') == 56.2
    assert cell_float(' 7 ') == 7.0
    assert cell_float('n/a') is None
    assert cell_float(None) is None


def test_trace_report_formatted_cells_survive_render(tmp_path,
                                                     monkeypatch):
    from benchmarks import trace_report as tr
    table = _datatable(
        ['category', 'hlo_op_name', 'occurrences', 'total_self_time',
         'model_flop_rate', 'measured_memory_bw', 'dma_stall_percent'],
        [
            # formatted-string cells, exactly what crashed the
            # standalone CLI (ADVICE r5 #3)
            ['convolution', '%conv.1', 3, '5,000', '1,234', '300.5',
             '2.5%'],
            ['copy', '%copy.1', 1, '250', 'n/a', None, 'oops'],
        ])
    d = tmp_path / 'trace'
    d.mkdir()
    (d / 'vm.xplane.pb').write_bytes(b'\x00')
    monkeypatch.setattr(tr, '_tool_tables',
                        lambda paths, tool: [table])
    rep = tr.analyze_trace(str(d))
    assert rep['total_self_time_us'] == 5250.0
    text = tr.render(rep)  # must not raise
    assert '1234 GF/s' in text
    # unparseable cells fall back to the raw value, never crash
    assert "dma_stall_pct='oops'" in text


def test_trace_report_no_dirs_writes_explanatory_stub(tmp_path,
                                                      monkeypatch,
                                                      capsys):
    from benchmarks import trace_report as tr
    res = tmp_path / 'results'
    res.mkdir()
    # a stale committed breakdown from an earlier capture...
    (res / 'trace_report.json').write_text(
        json.dumps({'buckets': {'conv/matmul': {}}}) + '\n')
    monkeypatch.setattr(tr, 'RES', str(res))
    assert tr.main(['--latest']) == 0
    out = capsys.readouterr().out
    assert 'no trace dirs' in out and 'stub' in out
    # ...is REWRITTEN with the explanatory stub (ADVICE r5 #4)
    rows = [json.loads(ln)
            for ln in open(str(res / 'trace_report.json'))]
    assert len(rows) == 1
    assert rows[0]['error'] == 'no trace dirs found'
    assert 'superseded' in rows[0]['detail']


def test_donating_scan_maker_replays_from_fresh_buffers():
    # bench --donate measures with buffers donated at the outer jit
    # boundary; donation consumes them, so every timed call must
    # re-place fresh copies and reproduce the SAME loss trajectory
    # (a second call reading donated garbage would diverge or crash)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import bench
    import chainermn_tpu
    from chainermn_tpu import training
    from chainermn_tpu.models import MLP, classifier_loss

    comm = chainermn_tpu.create_communicator('xla')
    model = MLP(n_units=8, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 784), jnp.float32))['params']
    loss = classifier_loss(lambda p, x: model.apply({'params': p}, x))
    upd = training.StandardUpdater(
        iter([]), chainermn_tpu.create_multi_node_optimizer(
            optax.adam(1e-3), comm),
        loss, params, comm, has_aux=True, donate=True, remat=True)
    rng = np.random.RandomState(0)
    batch = [(rng.rand(784).astype(np.float32), np.int32(i % 10))
             for i in range(8)]
    arrays = upd.shard_batch(batch)
    make = bench._donating_scan_maker(upd, arrays)
    call = make(3)
    first = np.asarray(call())
    second = np.asarray(call())
    assert first.shape == (3,)
    np.testing.assert_allclose(first, second, rtol=1e-6)
    assert np.all(np.isfinite(first))


def test_pick_tuned_records_window_and_device_identity():
    # ISSUE 7 satellite (ADVICE r5 residual): a winner crowned across
    # two chip windows (round tags) or two device kinds must say so
    # in the comparison provenance
    from bench import _pick_tuned

    same = [
        _rs_row(2588.0, _source='bench_resnet50_r5.out',
                device_kind='TPU v5 lite'),
        _rs_row(4100.0, override=128,
                _source='bench_resnet50_b128_r5.out',
                device_kind='TPU v5 lite'),
    ]
    d = _pick_tuned(same)
    assert d['winner_round_tag'] == 'r5'
    assert d['incumbent_round_tag'] == 'r5'
    assert d['cross_window'] is False

    cross = [
        _rs_row(2588.0, _source='bench_resnet50_r4.out',
                device_kind='TPU v5 lite'),
        _rs_row(4100.0, override=128,
                _source='bench_resnet50_b128_r6.out',
                device_kind='TPU v6 lite'),
    ]
    d = _pick_tuned(cross)
    assert (d['winner_round_tag'], d['incumbent_round_tag']) == \
        ('r6', 'r4')
    assert d['cross_window'] is True

    # rows without artifact names (direct API use) stay well-defined
    bare = [_rs_row(2588.0), _rs_row(4100.0, override=128)]
    d = _pick_tuned(bare)
    assert d['winner_round_tag'] is None
    assert d['cross_window'] is False
