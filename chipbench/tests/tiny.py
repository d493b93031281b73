"""A benchmark in a temporary directory, at sizes a CPU test can hold:
the real harness, readers and per-layer metric files, with tiny
configurations, mixes, cells and limits ADDED as files and entries.
Reachable only from the tests: ``run.py`` has no way to name it."""

import json
import os
import shutil

from chipbench import harness

LM = {
    'family': 'transformer_lm', 'n_embd': 64, 'n_layer': 2, 'n_head': 2,
    'n_positions': 64, 'vocab_size': 256, 'n_inner': 128,
    'train': {'optimizer': 'adam', 'lr': 0.001, 'policy': None},
}
RESNET = {
    'family': 'resnet', 'stage_sizes': [1, 1], 'width': 8,
    'num_classes': 10, 'image_size': 32,
    'train': {'optimizer': 'sgd_momentum', 'lr': 0.1, 'momentum': 0.9,
              'policy': 'bf16'},
}
MIXES = {
    'lm-tiny': {'kind': 'train', 'dataset': 'lm_tokens',
                'dataset_examples': 32, 'seq_len': 32, 'batch': 4,
                'iterator': 'serial', 'device_prefetch': 0},
    'lm-tiny-dp4': {'kind': 'train', 'dataset': 'lm_tokens',
                    'dataset_examples': 64, 'seq_len': 32, 'batch': 8,
                    'iterator': 'serial', 'device_prefetch': 0},
    'images-tiny': {'kind': 'train', 'dataset': 'images',
                    'dataset_examples': 32, 'batch': 8,
                    'iterator': 'prefetch_thread', 'device_prefetch': 2},
    'closed4-tiny': {
        'kind': 'serve_closed', 'n_clients': 4, 'warm_seconds': 0.3,
        'engine': {'n_slots': 4, 'max_prompt_len': 16, 'max_len': 64,
                   'paged': True, 'page_size': 8},
        'check_requests': 3, 'check_pad_to': 32,
        'pairs': [[4, 6], [7, 9], [9, 5], [12, 12], [16, 8], [5, 16]]},
}
CELLS = [
    ('lm-train', 'lm', 'lm-tiny', 1),
    ('lm-train-dp4', 'lm', 'lm-tiny-dp4', 4),
    ('resnet-train', 'resnet', 'images-tiny', 1),
    ('lm-serve', 'lm', 'closed4-tiny', 1),
]
#: bfloat16 against float32 at these toy sizes on a CPU, between what
#: the sound program reads there (mean first-gradient gap 3.6e-4 for
#: the LM, 0.022 for the net; served gaps 0) and what the fp8 control
#: reads (3.8e-3, 0.058, mean served gap 3.7e-4).  The chip's limits are in
#: chipbench/limits/ and come from chip readings
LIMITS = {
    'lm-train': {'loss_gap': 7e-5, 'first_grad_norm_gap': 0.008,
                 'first_grad_norm_gap_mean': 0.0015,
                 'param_change_norm_gap': 0.9, 'nonfinite_losses': 0},
    'resnet-train': {'loss_gap': 7e-4, 'first_grad_norm_gap_mean': 0.04,
                     'param_change_norm_gap_mean': 0.5,
                     'nonfinite_losses': 0},
    'lm-serve': {'served_logit_gap_widest': 0.004,
                 'served_logit_gap_mean': 1e-4, 'failed_requests': 0,
                 'compiles_in_window': 0},
}
LIMITS['lm-train-dp4'] = LIMITS['lm-train']


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        json.dump(obj, f)


def make_root(tmp):
    """``tmp`` becomes a checkout that holds only a ``BENCHMARK.json``
    and ``chipbench/`` data: the real per-layer files and readers,
    copied, and the tiny cells, added."""
    tmp = str(tmp)
    real = harness.ROOT
    for sub in ('layer_metrics', 'readers'):
        shutil.copytree(os.path.join(real, 'chipbench', sub),
                        os.path.join(tmp, 'chipbench', sub))
    with open(os.path.join(real, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    bench['configs'] = [
        {'name': name, 'source': 'test', 'reduced': [], 'why': 'tiny',
         'file': 'chipbench/configs/%s.json' % name}
        for name in ('lm', 'resnet')]
    _dump(os.path.join(tmp, 'chipbench/configs/lm.json'), LM)
    _dump(os.path.join(tmp, 'chipbench/configs/resnet.json'), RESNET)
    for name, mix in MIXES.items():
        _dump(os.path.join(tmp, 'chipbench/traffic/%s.json' % name), mix)
    bench['workloads'] = [
        {'name': n, 'config': c, 'traffic': t, 'chips': chips,
         'why': 'tiny'} for n, c, t, chips in CELLS]
    for n, _, _, _ in CELLS:
        _dump(os.path.join(tmp, 'chipbench/limits/%s.json' % n),
              LIMITS[n])
    train = [n for n, _, t, _ in CELLS if MIXES[t]['kind'] == 'train']
    serve = [n for n, _, t, _ in CELLS if MIXES[t]['kind'] != 'train']
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'workloads' not in m:
            continue
        was_serve = any('serve' in w for w in m['workloads'])
        m['workloads'] = (serve if was_serve
                          else ['lm-train-dp4'] if 'collective' in m['name']
                          else train)
    _dump(os.path.join(tmp, 'BENCHMARK.json'), bench)
    return tmp


def run(root, workload, seed=7, seconds=0.5, trace=0):
    import time
    return harness.run_cell(harness.Spec(workload, root=root), seed,
                            seconds, trace, time.perf_counter(),
                            platform='cpu')
