"""Unified runtime telemetry (``chainermn_tpu/telemetry/``): the
recorder/metrics core, the per-rank log merge + overlap fraction, the
Prometheus exporter, the instrumentation threaded through updaters /
communicators / recovery / chaos, and the disabled-by-default
overhead pin (ISSUE 6 acceptance: < 2% on the mlp step, measured by
``benchmark_op``)."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu import telemetry
from chainermn_tpu import training
from chainermn_tpu.models import MLP, Classifier
from chainermn_tpu.telemetry import recorder as rec_mod
from chainermn_tpu.telemetry import report as rep_mod
from chainermn_tpu.utils import profiling


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with telemetry OFF (the production
    default); tests that enable it do so explicitly."""
    telemetry.disable()
    yield
    telemetry.disable()


def _mlp_updater(n_units=16, batch=16, comm=None, donate=True):
    comm = comm or chainermn_tpu.create_communicator(
        'xla', mesh_shape=(2, 4))
    model = MLP(n_units=n_units, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 784), jnp.float32))
    clf = Classifier(model.apply)
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1), comm)
    upd = training.StandardUpdater(iter([]), opt, clf, params, comm,
                                   has_aux=True, donate=donate)
    rs = np.random.RandomState(0)
    batch_list = [(rs.randn(784).astype(np.float32), i % 10)
                  for i in range(batch)]
    return upd, batch_list


# ---------------------------------------------------------------------
# recorder core

def test_disabled_by_default_nullspan_and_noop_event():
    assert telemetry.active() is None and not telemetry.enabled()
    sp = telemetry.span('x', kind='compute')
    assert sp is rec_mod.NULL_SPAN
    with sp as handle:
        handle.set(anything=1)  # no-op, no crash
    telemetry.event('x')  # no-op, no crash
    assert telemetry.registry() is None
    assert telemetry.flush() is None


def test_recorder_spans_events_and_flush(tmp_path):
    rec = telemetry.enable(outdir=None)
    with telemetry.span('jitted_step', kind='compute', iteration=3):
        time.sleep(0.002)
    telemetry.event('chaos:drop_send', kind='chaos', occurrence=0)
    path = rec.flush(str(tmp_path))
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]['type'] == 'meta' and lines[0]['rank'] == 0
    span = next(ln for ln in lines if ln['type'] == 'span')
    assert span['name'] == 'jitted_step'
    assert span['iteration'] == 3
    assert span['t1'] - span['t0'] >= 0.002
    event = next(ln for ln in lines if ln['type'] == 'event')
    assert event['kind'] == 'chaos'
    # incremental: a second flush appends nothing new
    n0 = len(open(path).readlines())
    rec.flush(str(tmp_path))
    assert len(open(path).readlines()) == n0


def test_enable_is_idempotent_and_repoints_outdir(tmp_path):
    rec = telemetry.enable()
    assert telemetry.enable() is rec
    telemetry.enable(outdir=str(tmp_path))
    assert rec.outdir == str(tmp_path)


def test_maybe_enable_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.ENV_VAR, str(tmp_path))
    assert telemetry.maybe_enable_from_env() is not None
    assert telemetry.active().outdir == str(tmp_path)
    telemetry.disable()
    monkeypatch.delenv(telemetry.ENV_VAR)
    assert telemetry.maybe_enable_from_env() is None


def test_span_records_say_what_caused_them():
    """Every span record carries ``id``, ``parent`` (the innermost
    span open on the same thread) and ``thread``; a span entered on
    another thread has no parent here."""
    import threading
    rec = telemetry.enable()

    def elsewhere():
        with rec.span('elsewhere'):
            pass

    with rec.span('outer', kind='step', iteration=4) as outer:
        with rec.span('inner', kind='host'):
            pass
        worker = threading.Thread(target=elsewhere)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
    by_name = {e['name']: e for e in rec.events}
    assert by_name['inner']['parent'] == by_name['outer']['id'] \
        == outer.id
    assert by_name['outer']['parent'] is None
    assert by_name['elsewhere']['parent'] is None
    assert by_name['elsewhere']['thread'] != by_name['outer']['thread']
    assert by_name['outer']['thread'] == threading.get_native_id()
    # the recorder's clock, laid onto time.perf_counter()
    t = time.perf_counter()
    assert abs(rec.to_perf_counter(rec.now()) - t) < 0.05


# ---------------------------------------------------------------------
# metrics registry + Prometheus

def test_histogram_percentiles_and_summary():
    h = telemetry.Histogram('t')
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s['count'] == 100 and s['min'] == 1.0 and s['max'] == 100.0
    assert s['p50'] == 51.0 and s['p99'] == 100.0


def test_a_full_histogram_trims_an_eighth_at_a_time(monkeypatch):
    """A trim moves the whole sample list: one per ``observe`` cost a
    32-row serving tick 0.3 ms once the list was full (ISSUE 37).  The
    newest ``MAX_SAMPLES`` are always there; the count is exact."""
    from chainermn_tpu.telemetry import recorder as rec_mod
    monkeypatch.setattr(rec_mod, 'MAX_SAMPLES', 64)
    h = telemetry.Histogram('t')
    trims, longest = 0, 0
    for v in range(1000):
        before = len(h.samples)
        h.observe(float(v))
        trims += len(h.samples) <= before
        longest = max(longest, len(h.samples))
        assert h.samples[-min(v + 1, 64):] == [
            float(x) for x in range(max(v - 63, 0), v + 1)]
    assert longest == 64 + 8
    assert 0 < trims <= 1000 // 8
    assert h.count == 1000 and h.summary()['max'] == 999.0


def test_a_span_calls_its_at_exit_once_it_is_recorded():
    """``at_exit`` on a span's handle: one callable, called with the
    span after its record is written, its two ends readable; a span
    without one pays an attribute test."""
    rec = telemetry.enable()
    seen = []

    def ended(span):
        seen.append((span.name, span.recorder is rec,
                     rec.events[-1]['name'], span.t1 >= span.t0))

    with rec.span('outer', kind='serve') as outer:
        outer.at_exit = ended
        with rec.span('inner') as inner:
            pass
        assert inner.at_exit is None and not seen
    assert seen == [('outer', True, 'outer', True)]
    assert rec.events[-1]['t1'] == outer.t1


def test_registry_kind_clash_raises():
    reg = telemetry.Registry()
    reg.counter('a')
    with pytest.raises(TypeError):
        reg.gauge('a')


def test_prometheus_text_is_valid_and_sanitized():
    reg = telemetry.Registry()
    reg.counter('steps.total').inc(3)
    reg.gauge('loss-scale').set(1024)
    h = reg.histogram('step_time_seconds')
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    text = reg.to_prometheus()
    assert rep_mod.validate_prometheus(text) == []
    assert 'chainermn_tpu_steps_total 3.0' in text
    assert 'chainermn_tpu_step_time_seconds{quantile="0.50"}' in text


def test_validate_prometheus_catches_malformed():
    assert rep_mod.validate_prometheus('ok_metric 1.0\n') == []
    assert rep_mod.validate_prometheus('bad metric 1.0\n')
    assert rep_mod.validate_prometheus('no_value\n')


def test_prometheus_help_lines_emitted_and_escaped():
    reg = telemetry.Registry()
    reg.counter('retries_total',
                help='publish retries\nsecond line \\ tail').inc(2)
    h = reg.histogram('wait_seconds', help='bounded waits')
    h.observe(0.5)
    text = reg.to_prometheus()
    assert rep_mod.validate_prometheus(text) == []
    # newline and backslash escaped per the exposition format
    assert ('# HELP chainermn_tpu_retries_total publish '
            'retries\\nsecond line \\\\ tail') in text
    assert '# HELP chainermn_tpu_wait_seconds bounded waits' in text
    # HELP precedes TYPE for the same metric
    lines = text.splitlines()
    ih = lines.index('# HELP chainermn_tpu_wait_seconds bounded waits')
    assert lines[ih + 1] == '# TYPE chainermn_tpu_wait_seconds summary'


def test_prometheus_label_values_escaped():
    from chainermn_tpu.telemetry.recorder import (
        escape_label_value, snapshot_to_prometheus)
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    text = snapshot_to_prometheus({
        'g': {'type': 'gauge', 'value': 1.0,
              'labels': {'rank': 'a"b\\c\nd', 'host': 'n-1'}}})
    assert rep_mod.validate_prometheus(text) == []
    assert 'host="n-1",rank="a\\"b\\\\c\\nd"' in text


def test_validate_prometheus_rejects_unescaped_labels():
    # raw quote inside a value, raw backslash, bad escape sequence,
    # malformed HELP target -- all must be flagged
    assert rep_mod.validate_prometheus('m{k="a"b"} 1.0\n')
    assert rep_mod.validate_prometheus('m{k="a\\qb"} 1.0\n')
    assert rep_mod.validate_prometheus('# HELP 9bad text\n')
    assert rep_mod.validate_prometheus(
        'm{k="ok\\n",j="fi\\\\ne"} 2.0\n# HELP m fine\n') == []


def test_help_survives_rank_merge(tmp_path):
    for rank in (0, 1):
        with open(str(tmp_path / ('metrics-rank%d.json' % rank)),
                  'w') as f:
            json.dump({'rank': rank, 'metrics': {
                'steps_total': {'type': 'counter', 'value': 1.0,
                                'help': 'steps taken'}}}, f)
    merged = rep_mod.aggregate_metrics(
        rep_mod.load_rank_metrics(str(tmp_path)))
    assert merged['steps_total']['help'] == 'steps taken'
    text = telemetry.snapshot_to_prometheus(merged)
    assert '# HELP chainermn_tpu_steps_total steps taken' in text


# ---------------------------------------------------------------------
# interval arithmetic + overlap

def test_merge_intervals_and_exposed_time():
    merged = rep_mod.merge_intervals([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert merged == [(0, 3), (5, 6)]
    assert rep_mod.exposed_time((0, 4), merged) == 1.0   # [3,4)
    assert rep_mod.exposed_time((5, 6), merged) == 0.0


def test_overlap_from_intervals_half_hidden():
    st = rep_mod.overlap_from_intervals(
        collective=[(0.0, 10.0)], compute=[(0.0, 5.0)])
    assert st['total_collective_s'] == 10.0
    assert st['exposed_collective_s'] == 5.0
    assert st['overlap_fraction'] == 0.5


def test_overlap_nested_collectives_count_once():
    # an evaluator wrapper span around an inner allreduce span must
    # not double the collective wall time
    st = rep_mod.overlap_from_intervals(
        collective=[(0.0, 10.0), (2.0, 8.0)], compute=[])
    assert st['total_collective_s'] == 10.0
    assert st['overlap_fraction'] == 0.0


def test_overlap_without_collectives_is_none_not_fabricated():
    st = rep_mod.overlap_from_intervals([], [(0.0, 5.0)])
    assert st['overlap_fraction'] is None


def test_overlap_stats_is_per_rank():
    spans = [
        {'rank': 0, 'kind': 'collective', 't0': 0.0, 't1': 1.0},
        # rank 1's compute must NOT hide rank 0's collective
        {'rank': 1, 'kind': 'compute', 't0': 0.0, 't1': 1.0},
    ]
    st = rep_mod.overlap_stats(spans)
    assert st['overlap_fraction'] == 0.0
    spans.append(
        {'rank': 0, 'kind': 'compute', 't0': 0.0, 't1': 1.0})
    assert rep_mod.overlap_stats(spans)['overlap_fraction'] == 1.0


# ---------------------------------------------------------------------
# merge + report + CLI

def _write_rank_log(tmp_path, rank, records):
    path = tmp_path / ('events-rank%d.jsonl' % rank)
    with open(str(path), 'w') as f:
        f.write(json.dumps({'type': 'meta', 'rank': rank,
                            'wall0': 0.0}) + '\n')
        for r in records:
            f.write(json.dumps(dict(r, rank=rank)) + '\n')


def test_build_report_merges_ranks_and_steps(tmp_path):
    for rank in (0, 1):
        _write_rank_log(tmp_path, rank, [
            {'type': 'span', 'name': 'host_batch_prep', 'kind': 'host',
             't0': 0.0, 't1': 0.01, 'iteration': 0},
            {'type': 'span', 'name': 'jitted_step', 'kind': 'compute',
             't0': 0.02, 't1': 0.10, 'iteration': 0},
            {'type': 'span', 'name': 'allreduce_obj',
             'kind': 'collective', 't0': 0.04, 't1': 0.08},
            {'type': 'event', 'name': 'chaos:stall_kv',
             'kind': 'chaos', 't': 0.05},
        ])
    report = rep_mod.build_report(str(tmp_path))
    assert report['ranks'] == [0, 1]
    assert len(report['steps']) == 2  # (iter 0, rank 0), (iter 0, rank 1)
    assert report['steps'][0]['jitted_step_ms'] == 80.0
    # each rank's 40 ms collective sits fully inside its compute span
    assert report['overlap']['overlap_fraction'] == 1.0
    assert len(report['chaos_events']) == 2
    text = rep_mod.render_text(report)
    assert 'overlap fraction: 1.000' in text
    assert 'chaos events in timeline: 2' in text


def test_report_tolerates_torn_tail(tmp_path):
    _write_rank_log(tmp_path, 0, [
        {'type': 'span', 'name': 'jitted_step', 'kind': 'compute',
         't0': 0.0, 't1': 1.0}])
    with open(str(tmp_path / 'events-rank0.jsonl'), 'a') as f:
        f.write('{"type": "span", "name": "torn')  # crashed mid-write
    report = rep_mod.build_report(str(tmp_path))
    assert report['n_spans'] == 1
    assert report['n_unparseable_lines'] == 1


def test_aggregate_metrics_merges_histogram_samples(tmp_path):
    for rank, samples in ((0, [0.1, 0.2]), (1, [0.3, 0.4])):
        with open(str(tmp_path / ('metrics-rank%d.json' % rank)),
                  'w') as f:
            json.dump({'rank': rank, 'metrics': {
                'step_time_seconds': {
                    'type': 'histogram', 'count': 2,
                    'sum': sum(samples), 'samples': samples},
                'steps_total': {'type': 'counter', 'value': 2.0},
            }}, f)
    merged = rep_mod.aggregate_metrics(
        rep_mod.load_rank_metrics(str(tmp_path)))
    assert merged['steps_total']['value'] == 4.0
    h = merged['step_time_seconds']
    assert h['count'] == 4
    assert h['summary']['min'] == 0.1 and h['summary']['max'] == 0.4


def test_cli_report_empty_capture_exits_2(tmp_path, capsys):
    from chainermn_tpu.telemetry.__main__ import main
    assert main(['report', str(tmp_path)]) == 2


def test_cli_report_writes_artifacts(tmp_path, capsys):
    from chainermn_tpu.telemetry.__main__ import main
    _write_rank_log(tmp_path, 0, [
        {'type': 'span', 'name': 'jitted_step', 'kind': 'compute',
         't0': 0.0, 't1': 0.5, 'iteration': 0},
        {'type': 'span', 'name': 'allreduce_obj', 'kind': 'collective',
         't0': 0.1, 't1': 0.2}])
    assert main(['report', str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert 'overlap fraction' in out
    assert os.path.exists(str(tmp_path / 'merged_report.json'))
    assert os.path.exists(str(tmp_path / 'metrics.json'))
    assert rep_mod.validate_prometheus(
        open(str(tmp_path / 'metrics.prom')).read()) == []


# ---------------------------------------------------------------------
# instrumentation integration

def test_updater_emits_step_phase_spans(tmp_path):
    telemetry.enable(outdir=str(tmp_path))
    upd, batch = _mlp_updater()
    for _ in range(2):
        upd.update_core(upd.shard_batch(batch))
    rec = telemetry.active()
    names = [e['name'] for e in rec.events if e['type'] == 'span']
    for phase in ('host_batch_prep', 'h2d', 'jitted_step'):
        assert names.count(phase) == 2, (phase, names)
    # iteration attrs group the phases per step
    its = sorted(e['iteration'] for e in rec.events
                 if e.get('name') == 'jitted_step')
    assert its == [0, 1]
    # the strategy's trace-time collective-issue mark fired ONCE (one
    # compilation), as did the L4 wrapper's broadcast/allreduce marks
    marks = [e['name'] for e in rec.events
             if e.get('kind') == 'collective_trace']
    assert marks.count('XlaCommunicator:allreduce_grad') == 1
    assert marks.count('multi_node_optimizer:broadcast_data') == 1
    # the merged report computes a step table from the capture
    telemetry.flush()
    report = rep_mod.build_report(str(tmp_path))
    assert len(report['steps']) == 2
    assert report['step_time_ms']['count'] == 2


def test_pipeline_updater_emits_step_spans():
    from chainermn_tpu.training.pipeline_updater import (
        PipelineUpdater, pipeline_mesh)

    telemetry.enable()
    mesh = pipeline_mesh(2)
    d = 8

    def stage_fn(p, x):
        return jnp.tanh(x @ p['w'])

    def loss_on_last(outs, y):
        loss = jnp.mean((outs - y) ** 2)
        return loss, {'mse': loss}

    upd = PipelineUpdater(
        iter([]), optax.sgd(0.1), stage_fn, loss_on_last,
        {'w': jnp.zeros((2, d, d), jnp.float32)}, mesh, n_micro=2)
    n_data = mesh.shape['data']
    rs = np.random.RandomState(0)
    batch = [(rs.randn(d).astype(np.float32),
              rs.randn(d).astype(np.float32))
             for _ in range(4 * n_data)]
    upd.update_core(upd.shard_batch(batch))
    names = [e['name'] for e in telemetry.active().events
             if e['type'] == 'span']
    assert 'host_batch_prep' in names
    assert 'h2d' in names
    assert 'jitted_step' in names


def test_multi_node_optimizer_broadcast_appears_exactly_once():
    """Satellite regression (ISSUE 6): over several optimizer steps
    the first-call broadcast mark appears EXACTLY once in the
    timeline -- once because the wrapper traces the broadcast branch
    a single time (one compilation), and not more, which would be the
    footprint of a recompilation leak re-tracing the step."""
    telemetry.enable()
    upd, batch = _mlp_updater()
    arrays = upd.shard_batch(batch)
    for _ in range(3):
        upd.update_core(arrays)
    events = telemetry.active().events
    marks = [e['name'] for e in events
             if e.get('kind') == 'collective_trace']
    assert marks.count('multi_node_optimizer:broadcast_data') == 1
    assert marks.count('multi_node_optimizer:allreduce_grad') == 1


def test_evaluator_wrapper_emits_collective_span():
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    telemetry.enable()
    ev = chainermn_tpu.create_multi_node_evaluator(
        lambda: {'accuracy': 0.5, 'loss': 1.0}, comm)
    out = ev.evaluate()
    assert out['accuracy'] == 0.5
    spans = [e for e in telemetry.active().events
             if e['type'] == 'span']
    (span,) = [s for s in spans
               if s['name'] == 'multi_node_evaluator:allreduce']
    assert span['kind'] == 'collective'
    assert span['keys'] == 2


def test_chaos_faults_land_in_timeline():
    from chainermn_tpu.utils import chaos

    telemetry.enable()
    inj = chaos.install(chaos.FaultInjector('stall_kv=@0:0.0'))
    try:
        chaos.before_kv_wait()   # occurrence 0: fires
        chaos.before_kv_wait()   # occurrence 1: does not
    finally:
        chaos.uninstall()
    events = [e for e in telemetry.active().events
              if e.get('kind') == 'chaos']
    assert [e['name'] for e in events] == ['chaos:stall_kv']
    assert events[0]['occurrence'] == 0
    assert inj.counts()['stall_kv'] == 2


def test_recovery_checkpoint_spans(tmp_path):
    from chainermn_tpu.training import recovery

    telemetry.enable()
    upd, batch = _mlp_updater(donate=False)
    upd.update_core(upd.shard_batch(batch))
    handler = recovery.PreemptionHandler(upd, out=str(tmp_path),
                                         signals=())
    path = handler.checkpoint()
    assert path and os.path.exists(path)
    upd2, _ = _mlp_updater(donate=False)
    it = recovery.auto_resume(upd2, str(tmp_path))
    assert it == 1
    names = [e['name'] for e in telemetry.active().events
             if e['type'] == 'span' and e['kind'] == 'checkpoint']
    assert 'checkpoint_write' in names
    assert 'checkpoint_resume' in names


def test_step_timer_records_into_active_registry_and_timeline():
    telemetry.enable()
    t = profiling.StepTimer(items_per_step=8, warmup=0)
    for _ in range(3):
        t.tick()
        time.sleep(0.002)
    s = t.summary()
    assert s['steps'] == 2 and s['p50_step_s'] >= 0.001
    # one timing source of truth: the session registry holds the
    # histogram and the timeline holds one 'step' span per interval
    reg = telemetry.registry()
    assert reg.histogram('step_time_seconds').count == 2
    steps = [e for e in telemetry.active().events
             if e.get('name') == 'step']
    assert len(steps) == 2


def test_step_timer_standalone_without_telemetry():
    t = profiling.StepTimer(items_per_step=8, warmup=0)
    for _ in range(3):
        t.tick()
        time.sleep(0.002)
    s = t.summary()
    assert s['steps'] == 2 and s['items_per_sec'] > 0


def test_benchmark_op_records_metric_when_enabled():
    telemetry.enable()
    f = jax.jit(lambda x: x * 2 + 1)
    dt = profiling.benchmark_op(f, jnp.ones(64), n_steps=2, warmup=1)
    assert dt > 0
    assert telemetry.registry().histogram(
        'benchmark_op_seconds').count == 1


# ---------------------------------------------------------------------
# the acceptance pin: telemetry disabled-by-default costs a step
# nothing -- as a count of what the step reaches, not a clock

def test_step_reaches_no_recorder_disabled_and_records_its_spans_enabled(
        monkeypatch):
    """ISSUE 6 acceptance ("disabled by default adds no per-step
    overhead"), decided by what a step REACHES and no longer by a
    wall-clock ratio, which six xdist workers on one CPU cannot hold
    to 2%.  Disabled: a whole ``update()`` -- input wait, collation,
    placement, the jitted step, the metrics read-back -- calls no
    method of ``Recorder`` and builds no span handle (every one of
    them is patched to raise), and a span asked for is the one shared
    ``NULL_SPAN``.  Enabled: the same step records exactly the spans
    ``docs/observability.md`` lists for a step, each under its
    documented parent, and nothing else."""
    import types

    from chainermn_tpu.training.iterators import SerialIterator

    assert not telemetry.enabled()
    upd, batch = _mlp_updater()
    upd.iterator = SerialIterator(batch, len(batch))
    upd.update()                         # compile; the first broadcast

    def refuse(*args, **kwargs):
        raise AssertionError('telemetry is off: nothing may reach '
                             'the recorder')

    with monkeypatch.context() as patch:
        for cls in (rec_mod.Recorder, rec_mod._SpanHandle):
            for name, attr in vars(cls).items():
                if isinstance(attr, types.FunctionType):
                    patch.setattr(cls, name, refuse)
        upd.update()
        assert telemetry.span('x', kind='compute') is rec_mod.NULL_SPAN
        assert telemetry.active() is None

    rec = telemetry.enable()             # in memory, no directory
    upd.update()
    telemetry.disable()
    assert {e['type'] for e in rec.events} == {'span'}
    names = {e['id']: e['name'] for e in rec.events}
    step = sorted((e['name'], names.get(e['parent']))
                  for e in rec.events)
    assert step == sorted([
        ('train_update', None),
        ('input_wait', 'train_update'),
        ('host_batch_prep', 'train_update'),
        ('h2d', 'train_update'),
        ('shard_batch', 'h2d'),          # the communicator's own
        ('jitted_step', 'train_update'),
        ('metrics_sync', 'train_update')]), step


# ---------------------------------------------------------------------
# degenerate captures: the shapes a killed or half-started rank
# leaves behind (ISSUE 8 satellite)

def test_rank_dir_with_metrics_but_no_events(tmp_path):
    # a rank that died before its first event flush still leaves a
    # metrics snapshot; the merge must produce a report, not raise
    with open(str(tmp_path / 'metrics-rank0.json'), 'w') as f:
        json.dump({'rank': 0, 'metrics': {
            'steps_total': {'type': 'counter', 'value': 3.0}}}, f)
    report = rep_mod.build_report(str(tmp_path))
    assert report['n_spans'] == 0 and report['steps'] == []
    assert report['metrics']['steps_total']['value'] == 3.0
    assert report['overlap']['overlap_fraction'] is None


def test_loader_skips_torn_tail_and_binary_garbage(tmp_path):
    # the exact footprint of a killed rank: valid lines, then a line
    # cut mid-JSON with no trailing newline -- plus a line of raw
    # bytes from a torn buffered write.  Loader must keep every
    # intact record and count (not raise on) the rest.
    path = str(tmp_path / 'events-rank0.jsonl')
    with open(path, 'w') as f:
        f.write(json.dumps({'type': 'meta', 'rank': 0,
                            'wall0': 0.0}) + '\n')
        f.write(json.dumps({'type': 'span', 'name': 'jitted_step',
                            'kind': 'compute', 't0': 0.0, 't1': 1.0,
                            'iteration': 0, 'rank': 0}) + '\n')
        f.write('\x00\x01\xff garbled {{{\n')
        f.write('{"type": "span", "name": "allreduce_obj", "kin')
    metas, spans, events, bad = rep_mod.load_rank_logs(str(tmp_path))
    assert len(metas) == 1 and len(spans) == 1
    assert bad == 2
    report = rep_mod.build_report(str(tmp_path))
    assert report['n_spans'] == 1
    assert report['n_unparseable_lines'] == 2


def test_truncated_metrics_snapshot_is_skipped(tmp_path):
    with open(str(tmp_path / 'metrics-rank0.json'), 'w') as f:
        f.write('{"rank": 0, "metrics": {"steps_tot')  # torn write
    with open(str(tmp_path / 'metrics-rank1.json'), 'w') as f:
        json.dump({'rank': 1, 'metrics': {
            'steps_total': {'type': 'counter', 'value': 2.0}}}, f)
    merged = rep_mod.aggregate_metrics(
        rep_mod.load_rank_metrics(str(tmp_path)))
    assert merged['steps_total']['value'] == 2.0


def test_aggregate_metrics_empty_and_malformed_snapshots():
    assert rep_mod.aggregate_metrics([]) == {}
    # snapshots without 'metrics', or entries without 'type', are
    # ignored rather than fatal
    merged = rep_mod.aggregate_metrics([
        {'rank': 0},
        {'rank': 1, 'metrics': {'x': {'no_type': True}}},
        {'rank': 2, 'metrics': {'ok': {'type': 'counter',
                                       'value': 1.0}}},
    ])
    assert list(merged) == ['ok']


# ---------------------------------------------------------------------
# chaos kill sites flush the timeline AND the flight record across
# os._exit (ISSUE 8 satellite; subprocess-based like ckpt_kill_worker)

@pytest.mark.parametrize('site,rc', [('kill_step', 42),
                                     ('kill_recv', 42),
                                     ('ckpt_kill', 43)])
def test_chaos_kill_site_flushes_telemetry_and_flight(tmp_path, site,
                                                      rc):
    import subprocess
    import sys
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'telemetry_kill_worker.py')
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS',
                        'CHAINERMN_TPU_CHAOS',
                        'CHAINERMN_TPU_TELEMETRY')}
    env['PYTHONPATH'] = root + os.pathsep + env.get('PYTHONPATH', '')
    env['CHAINERMN_TPU_TELEMETRY'] = str(tmp_path)
    proc = subprocess.run([sys.executable, worker, site], env=env,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True,
                          timeout=240)
    assert proc.returncode == rc, proc.stdout  # died AT the site
    # the event log made it out before os._exit, chaos event included
    lines = [json.loads(ln) for ln in
             open(str(tmp_path / 'events-rank0.jsonl'))]
    names = [ln.get('name') for ln in lines]
    assert ('chaos:' + site) in names
    assert 'jitted_step' in names
    # ... and so did the crash-safe flight record
    with open(str(tmp_path / 'flight-rank0.json')) as f:
        flight = json.load(f)
    assert flight['complete'] is True
    assert flight['reason'] == 'chaos:' + site
    assert flight['last_collective']['name'] == 'allreduce_obj'
    assert flight['last_collective']['seq'] == 4
    assert any(r.get('name') == 'chaos:' + site
               for r in flight['ring'])
    # the doctor reads the same artifacts and declares the death
    from chainermn_tpu.telemetry import diagnosis
    diag = diagnosis.diagnose(str(tmp_path))
    assert diag['crash']['dead_ranks'] == [0]


def test_overlap_stats_splits_per_axis():
    # ISSUE 7 satellite: collective spans carry the mesh axis name,
    # so the overlap column splits dp vs tp communication.  One
    # 'data' span fully hidden behind compute, one 'model' span fully
    # exposed; the aggregate blends them, the per-axis split does not.
    from chainermn_tpu.telemetry.report import overlap_stats

    spans = [
        {'kind': 'compute', 't0': 0.0, 't1': 1.0, 'rank': 0},
        {'kind': 'collective', 't0': 0.2, 't1': 0.4, 'rank': 0,
         'axes': ['data']},
        {'kind': 'collective', 't0': 2.0, 't1': 2.4, 'rank': 0,
         'axes': ['model']},
        {'kind': 'collective', 't0': 3.0, 't1': 3.1, 'rank': 0},
    ]
    st = overlap_stats(spans)
    per = st['per_axis']
    assert per['data']['overlap_fraction'] == 1.0
    assert per['model']['overlap_fraction'] == 0.0
    assert abs(per['model']['exposed_collective_s'] - 0.4) < 1e-9
    assert 'untagged' in per  # pre-tagging spans stay visible
    assert 0.0 < st['overlap_fraction'] < 1.0


# ---------------------------------------------------------------------
# per-request tracing primitives (ISSUE 12)

class TestRequestTracePrimitives:
    def test_child_span_records_kind_request(self):
        rec = telemetry.enable()
        t0 = rec.now()
        rec.child_span('r1', 'queue_wait', t0, t0 + 0.01, seq=3)
        telemetry.request_stage('r1', 'prefill', t0 + 0.01,
                                t0 + 0.02, slot=0)
        telemetry.request_event('r1', 'complete', tokens=5)
        spans = [e for e in rec.events if e['type'] == 'span']
        events = [e for e in rec.events if e['type'] == 'event']
        assert all(s['kind'] == 'request' for s in spans)
        assert spans[0]['request_id'] == 'r1'
        assert spans[0]['seq'] == 3
        assert events[-1]['name'] == 'complete'
        assert events[-1]['tokens'] == 5

    def test_request_api_noop_when_disabled(self):
        # zero-cost-off contract: no recorder, no records, no error
        telemetry.request_stage('r1', 'decode', 0.0, 1.0)
        telemetry.request_event('r1', 'complete')
        assert telemetry.active() is None

    def test_request_traces_and_summary(self):
        records = [
            {'type': 'span', 'kind': 'request', 'name': 'queue_wait',
             'request_id': 'a', 't0': 0.0, 't1': 0.010},
            {'type': 'span', 'kind': 'request', 'name': 'bucket_pack',
             'request_id': 'a', 't0': 0.010, 't1': 0.011,
             'bucket': 4, 'pad_fraction': 0.5},
            {'type': 'span', 'kind': 'request', 'name': 'prefill',
             'request_id': 'a', 't0': 0.011, 't1': 0.020},
            {'type': 'span', 'kind': 'request', 'name': 'decode',
             'request_id': 'a', 't0': 0.020, 't1': 0.030, 'step': 0},
            {'type': 'span', 'kind': 'request', 'name': 'decode',
             'request_id': 'a', 't0': 0.030, 't1': 0.045, 'step': 1},
            {'type': 'event', 'kind': 'request', 'name': 'complete',
             'request_id': 'a', 't': 0.045, 'tokens': 3},
            {'type': 'span', 'kind': 'request', 'name': 'queue_wait',
             'request_id': 'b', 't0': 0.0, 't1': 0.005},
            {'type': 'event', 'kind': 'request', 'name': 'shed',
             'request_id': 'b', 't': 0.005, 'reason': 'deadline',
             'queue_depth': 7},
            {'type': 'span', 'kind': 'compute', 'name': 'jitted_step',
             't0': 0.0, 't1': 1.0, 'iteration': 0},   # ignored
        ]
        traces = rep_mod.request_traces(records)
        assert set(traces) == {'a', 'b'}
        a = traces['a']
        assert a['stage_ms'] == {'bucket_pack': 1.0, 'decode': 25.0,
                                 'prefill': 9.0, 'queue_wait': 10.0}
        assert a['e2e_ms'] == 45.0
        assert a['n_decode'] == 2
        assert a['outcome'] == 'complete'
        assert traces['b']['outcome'] == 'shed'
        assert traces['b']['outcome_attrs']['reason'] == 'deadline'
        summary = rep_mod.request_summary(records)
        assert summary['count'] == 2
        assert summary['completed'] == 1 and summary['shed'] == 1
        worst = summary['worst']
        assert worst['request_id'] == 'a'
        assert worst['stage_sum_ms'] == worst['e2e_ms'] == 45.0
        # stage tiling property: budgets telescope exactly
        assert sum(a['stage_ms'].values()) == a['e2e_ms']
        text = rep_mod.render_request_text(a)
        assert 'queue_wait' in text and 'decode' in text
        assert 'outcome complete' in text

    def test_request_summary_none_without_request_records(self):
        assert rep_mod.request_summary(
            [{'type': 'span', 'kind': 'compute', 't0': 0, 't1': 1,
              'name': 'jitted_step'}]) is None

    def test_report_renders_worst_request_line(self, tmp_path):
        rec = telemetry.enable(str(tmp_path))
        t0 = rec.now()
        rec.child_span('r9', 'queue_wait', t0, t0 + 0.001)
        rec.child_span('r9', 'prefill', t0 + 0.001, t0 + 0.004)
        rec.event('complete', kind='request', request_id='r9')
        rec.flush()
        telemetry.disable()
        report = rep_mod.build_report(str(tmp_path))
        assert report['requests']['count'] == 1
        text = rep_mod.render_text(report)
        assert 'request traces: 1' in text
        assert 'worst request r9' in text


# ---------------------------------------------------------------------
# pipeline bubble fraction (ISSUE 14): the pipe-axis row of the
# per-axis story -- schedule events stamped at trace time turn into
# per-stage bubble fractions in the merged report, and "more
# microbatches -> smaller bubble" is a pinned property, not a slide.

class TestPipelineBubble:
    def test_bubble_fraction_bounds_and_monotonicity(self):
        from chainermn_tpu.parallel.pipeline import bubble_fraction
        for schedule in ('gpipe', '1f1b'):
            prev = None
            for m in (1, 2, 4, 8, 16, 64):
                b = bubble_fraction(m, 4, schedule)
                assert 0.0 <= b < 1.0
                if prev is not None:
                    assert b < prev, (schedule, m, b, prev)
                prev = b
        # one stage: gpipe has no bubble; the combined 1f1b scan
        # still pays its single turnaround tick (1 / (M + 1))
        assert bubble_fraction(8, 1, 'gpipe') == 0.0
        assert abs(bubble_fraction(8, 1, '1f1b') - 1.0 / 9.0) < 1e-12

    def test_pipeline_summary_from_events(self):
        events = [
            {'type': 'event', 'kind': 'pipeline',
             'name': 'pipeline:schedule', 'schedule': '1f1b',
             'n_micro': 2, 'n_stages': 2, 'total_ticks': 5,
             'axes': ['pipe']},
            # duplicate compile of the same config: deduped
            {'type': 'event', 'kind': 'pipeline',
             'name': 'pipeline:schedule', 'schedule': '1f1b',
             'n_micro': 2, 'n_stages': 2, 'total_ticks': 5,
             'axes': ['pipe']},
            # torn/garbage record: skipped, not fatal
            {'type': 'event', 'kind': 'pipeline',
             'name': 'pipeline:schedule', 'n_micro': 'x'},
        ]
        rows = rep_mod.pipeline_summary(events)
        assert len(rows) == 1
        row = rows[0]
        assert row['axis'] == 'pipe' and row['n_stages'] == 2
        per_stage = row['bubble_fraction_per_stage']
        assert len(per_stage) == row['n_stages']
        assert all(0.0 <= b <= 1.0 for b in per_stage)
        assert rep_mod.pipeline_summary([]) is None

    def test_capture_bubble_strictly_decreases_2_to_8(self, tmp_path):
        # the acceptance pin: REAL captures of the unified pipeline
        # step at n_micro 2 and 8 over the SAME global batch -- the
        # reported bubble fraction must strictly shrink
        from chainermn_tpu.parallel.pipeline import stack_stage_params
        from chainermn_tpu.parallel.meshplan import MeshPlan
        from chainermn_tpu.training import MeshPipelineUpdater

        dim = 8
        rs = np.random.RandomState(0)
        stacked = stack_stage_params(
            [{'w': jnp.asarray(rs.randn(dim, dim) * 0.5,
                               jnp.float32)} for _ in range(2)])

        def stage_fn(p, x):
            return jnp.tanh(x @ p['w'])

        def loss_on_last(outs, y_micro):
            return jnp.mean((outs - y_micro) ** 2), {}

        batch = [(rs.randn(dim).astype(np.float32),
                  rs.randn(dim).astype(np.float32))
                 for _ in range(16)]
        bubbles = {}
        for n_micro in (2, 8):
            out = tmp_path / ('m%d' % n_micro)
            rec = telemetry.enable(str(out))
            plan = MeshPlan.create(tp=1, pp=2,
                                   devices=jax.devices()[:4])
            upd = MeshPipelineUpdater(
                iter([]), optax.sgd(0.1), stage_fn, loss_on_last,
                stacked, plan, n_micro=n_micro, donate=False)
            upd.update_core(upd.shard_batch(batch))
            rec.flush()
            telemetry.disable()
            report = rep_mod.build_report(str(out))
            (row,) = report['pipeline']
            assert row['schedule'] == '1f1b'
            assert row['axis'] == 'pipe'
            assert row['n_micro'] == n_micro
            assert all(0.0 <= b <= 1.0
                       for b in row['bubble_fraction_per_stage'])
            bubbles[n_micro] = row['bubble_fraction']
            text = rep_mod.render_text(report)
            assert 'bubble fraction' in text
        assert bubbles[8] < bubbles[2], bubbles
