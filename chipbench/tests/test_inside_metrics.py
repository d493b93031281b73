"""The program's own spans and its compile log, read by the benchmark:
a traced rehearsal of every tiny cell on the CPU.  Nothing here enables
telemetry: the harness opens the profiler (at once, the window being
shorter than the traced part) and the program switches its spans on
when it finds the profiler open."""

import pytest

import tiny

TRAIN = {'input_batch_ms', 'step_dispatch_ms', 'metrics_sync_ms',
         'window_compiles.train', 'input_wait_ms',
         'train_update_self_ms'}
SERVE = {'prefill_ms', 'queue_wait_p75_ms', 'sched_host_ms',
         'window_compiles.serve'}


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp('bench'))


@pytest.fixture(autouse=True)
def _telemetry_off():
    from chainermn_tpu import telemetry
    telemetry.disable()
    yield
    telemetry.disable()


@pytest.mark.parametrize('cell, new', [
    ('lm-train', TRAIN), ('lm-train-dp4', TRAIN),
    ('resnet-train', TRAIN), ('lm-serve', SERVE)])
def test_traced_rehearsal_reads_the_programs_spans(root, cell, new):
    from chainermn_tpu import telemetry
    result = tiny.run(root, cell, trace=1, seconds=0.5)
    assert result['correct'] is True, result['checks']
    metrics = result['metrics']
    assert new <= set(metrics), sorted(new - set(metrics))
    for name in new:
        assert metrics[name]['value'] >= 0
        assert metrics[name]['unit'] in ('ms', 'count')
    compiles, = [n for n in new if n.startswith('window_compiles')]
    assert metrics[compiles]['value'] == 0
    # no chip, so no device plane: the two module_ms metrics are absent
    assert not any('device_ms' in k for k in metrics)
    # the recorder was installed by the program, for the profiler
    assert telemetry.active().follows_profiler
    assert telemetry.live() is None   # the session is closed


def test_untraced_run_leaves_the_recorder_off(root):
    from chainermn_tpu import telemetry
    tiny.run(root, 'lm-train', trace=0, seconds=0.2)
    assert telemetry.active() is None
    assert len(telemetry.compile_log) > 0   # the one always-on counter
