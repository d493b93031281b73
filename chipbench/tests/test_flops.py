"""The FLOP functions against hand counts, and the table of peaks."""

import json
import os

import pytest

from chipbench import flops, harness, peaks


def config(name):
    with open(os.path.join(harness.HERE, 'configs', name + '.json')) as f:
        return json.load(f)


def test_gpt2_medium_forward_flops_by_hand():
    cfg = config('gpt2-medium')
    d, layers, vocab, t = 1024, 24, 50257, 1024
    # per layer: qkv 3 d^2, proj d^2, two feed-forward matmuls 8 d^2
    matmul_params = layers * 12 * d * d + d * vocab
    assert flops.transformer_lm_matmul_params(cfg) == matmul_params \
        == 353453056
    attention = layers * 2 * t * t * d        # causal QK^T and PV
    assert flops.transformer_lm_forward_flops(cfg, t) == \
        2 * matmul_params * t + attention
    # one training sample: ~2.33 TFLOP, so 19.1 sequences/s is ~22.6%
    # of a v5e's 197 TFLOP/s -- and 19.6 k "samples/s" would be 231x it
    per_sample = 3 * flops.transformer_lm_forward_flops(cfg, t)
    assert per_sample == pytest.approx(2.326e12, rel=1e-3)
    assert 19.1 * per_sample / 197e12 == pytest.approx(0.2255, rel=1e-2)


def test_resnet50_macs_by_hand():
    cfg = config('resnet50-imagenet')
    stem = 112 * 112 * 49 * 3 * 64
    # stage 1 at 56 px: first block 64->64->64->256 with a projection
    first = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    other = 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    stage1 = first + 2 * other
    assert stage1 == 56 * 56 * (
        64 * 64 + 2 * 256 * 64 + 3 * 9 * 64 * 64 + 4 * 64 * 256)
    total = flops.resnet_forward_macs(cfg)
    assert total == pytest.approx(4.09e9, rel=5e-3)
    assert total > stem + stage1 + 2048 * 1000


def test_peaks_know_the_v5e_and_nothing_else():
    assert peaks.peak('TPU v5 lite', 'bf16_tflops') == 197.0
    assert peaks.peak('TPU v5 lite', 'hbm_gbs') == 819.0
    with pytest.raises(KeyError):
        peaks.peak('some new chip', 'bf16_tflops')
