"""The trace reduction on a small trace recorded on a v5e: three
launches of a four-matmul step (~50.5 us each), 11 ms sleeps between
them under ``chipbench:data_wait``, all under ``chipbench:window``.
The device's clock runs ~1 ms ahead of the host's in it, so the first
launch lies before the host's window span and is clipped away."""

import os

import pytest

from chipbench import harness, trace

RECORDED = os.path.join(harness.HERE, 'testdata', 'v5e_small.xplane.pb')


@pytest.fixture(scope='module')
def summary():
    return trace.reduce(trace.load(RECORDED),
                        window_span='chipbench:window')


def test_busy_is_inside_the_window(summary):
    assert summary.n_devices == 1
    assert 0.0 < summary.busy_s <= summary.window_s
    assert summary.window_s == pytest.approx(0.0355464, rel=1e-4)
    # two launches of ~50.5 us inside the 35.5 ms window
    assert summary.busy_s == pytest.approx(101.03e-6, rel=1e-3)
    assert summary.idle_share == pytest.approx(99.716, abs=0.001)


def test_named_ops_are_summed_over_their_launches(summary):
    ops = dict(summary.breakdown()['device_ops'])
    fusion = 'fusion convolution_tanh_fusion bf16[1024,1024]'
    assert ops[fusion] == pytest.approx(101.0e-6, rel=1e-3)
    assert max(ops, key=ops.get) == fusion
    assert summary.pallas_s == 0.0 and summary.collective_s == 0.0
    assert len(ops) <= 10


def test_modules_count_launches(summary):
    launches, seconds = summary.module('small_step')
    assert launches == 2
    assert seconds == pytest.approx(101.055e-6, rel=1e-4)
    assert summary.module('no_such_executable') == (0, 0)


def test_gaps_are_attributed_to_the_host_span_over_them(summary):
    gaps = dict(summary.breakdown()['idle_gaps'])
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)
    # the device waits while the host sleeps under data_wait
    assert gaps['chipbench:data_wait'] > 0.9 * sum(gaps.values())


def test_without_a_window_span_the_device_extent_is_the_window():
    s = trace.reduce(trace.load(RECORDED), window_span='absent')
    assert s.busy_s <= s.window_s < 0.03
    assert s.module('small_step')[0] == 3
    assert s.busy_s == pytest.approx(151.5e-6, rel=1e-2)


@pytest.mark.parametrize('hlo, kind, label', [
    ('%fusion.12 = bf16[8,4]{1,0:T(8,128)} fusion(bf16[8,4]{1,0} %p), '
     'kind=kLoop', 'fusion', 'fusion bf16[8,4]'),
    ('%add_add_fusion.3 = (bf16[2]{0}, f32[2]{0}) fusion(%a)', 'fusion',
     'fusion add_add_fusion (bf16[2], f32[2])'),
    ('%custom-call.7 = bf16[32,16,64]{2,1,0} custom-call(%q), '
     'custom_call_target="tpu_custom_call"', 'custom-call',
     'pallas custom-call bf16[32,16,64]'),
    ('%all-reduce.1 = f32[1024]{0} all-reduce(f32[1024]{0} %g), '
     'replica_groups={}', 'all-reduce', 'all-reduce f32[1024]'),
])
def test_op_labels(hlo, kind, label):
    assert trace.op_label(hlo) == (kind, label)
