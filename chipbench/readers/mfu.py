"""Model-FLOP utilisation: samples/s x the FLOPs one sample requires
(the family's shape function; no recomputation) over chips x the bf16
peak of the device kind.  Over 100% is a bug in the count, not a
result."""

import importlib

from chipbench import peaks


def read(run):
    rate = run.e2e.get('train_samples_per_s')
    if rate is None or run.devices[0].platform != 'tpu':
        return None   # a CPU rehearsal's rate is not a device metric
    cfg = run.spec.cfg
    ref = importlib.import_module('chipbench.reference.' + cfg['family'])
    per_sample = ref.train_flops_per_sample(cfg, run.spec.mix)
    peak = peaks.peak(run.devices[0].device_kind, 'bf16_tflops') * 1e12
    value = 100.0 * rate * per_sample / (len(run.devices) * peak)
    if value > 100.0:
        raise AssertionError('mfu %.1f%% is over 100%%' % value)
    return value
