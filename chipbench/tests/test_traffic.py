"""The fixed-multiset length generator and the seeded datasets."""

import collections
import json
import os

import numpy as np
import pytest

from chipbench import harness, traffic

MIX = os.path.join(harness.HERE, 'traffic', 'closed32-chat.json')


@pytest.fixture(scope='module')
def mix():
    with open(MIX) as f:
        return json.load(f)


def dealt(mix, seed, n):
    stream = traffic.RequestStream(mix, 50257, seed)
    return [stream.lengths(i) for i in range(n)]


def test_the_files_pairs_are_what_its_parameters_generate(mix):
    g = mix['generated_from']
    assert mix['pairs'] == traffic.paired_lengths(
        g['prompt'], g['output'], g['n'], g['pair_seed'])


def test_lengths_fit_the_engine(mix):
    e = mix['engine']
    pairs = np.asarray(mix['pairs'])
    assert len(pairs) == 256
    assert pairs[:, 0].min() >= 16 and pairs[:, 0].max() == 512
    assert pairs[:, 0].max() <= e['max_prompt_len']
    assert pairs[:, 1].min() >= 16 and pairs[:, 1].max() <= 256
    assert pairs.sum(1).max() <= mix['check_pad_to'] < e['max_len']
    assert np.median(pairs[:, 0]) == 128 and np.median(pairs[:, 1]) == 96


@pytest.mark.parametrize('seed', [0, 7, 2147483999, 3000000011])
def test_every_seed_deals_the_same_multiset_in_another_order(mix, seed):
    n = len(mix['pairs'])
    want = collections.Counter(tuple(p) for p in mix['pairs'])
    two_passes = dealt(mix, seed, 2 * n)
    assert collections.Counter(two_passes[:n]) == want
    assert collections.Counter(two_passes[n:]) == want
    assert two_passes[:n] != two_passes[n:]
    assert two_passes[:n] != dealt(mix, seed + 1, n)
    assert two_passes[:n] == dealt(mix, seed, n)


def test_requests_are_seeded_and_share_no_prefix(mix):
    a = traffic.RequestStream(mix, 50257, 5)
    b = traffic.RequestStream(mix, 50257, 5)
    p0, n0 = a.request(3)
    p1, n1 = b.request(3)
    assert n0 == n1 and np.array_equal(p0, p1)
    assert p0.dtype == np.int32 and len(p0) == a.lengths(3)[0]
    assert 0 <= p0.min() and p0.max() < 50257
    firsts = {tuple(a.request(i)[0][:4]) for i in range(64)}
    assert len(firsts) == 64


def test_lm_examples_are_shifted_rows_that_all_differ():
    mix = {'dataset_examples': 16, 'seq_len': 32}
    ex = traffic.lm_examples(mix, 1000, 2147483999)
    assert len(ex) == 16 and ex[0][0].shape == (32,)
    assert np.array_equal(ex[0][0][1:], ex[0][1][:-1])
    assert len({e[0].tobytes() for e in ex}) == 16
    again = traffic.lm_examples(mix, 1000, 2147483999)
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(ex, again))


def test_image_examples_are_float32_and_seeded():
    mix = {'dataset_examples': 6}
    ex = traffic.image_examples(mix, 16, 10, 3)
    x, y = ex[0]
    assert x.shape == (16, 16, 3) and x.dtype == np.float32
    assert y.dtype == np.int32 and 0 <= y < 10
    assert len({e[0].tobytes() for e in ex}) == 6
