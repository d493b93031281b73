"""The one general traffic generator.  A traffic mix is a data file
under ``chipbench/traffic/``; this module turns it and ``--seed`` into
the work a run offers.  ``--seed`` never changes HOW MUCH work there
is: the multiset of sizes is the file's, the seed sets the order and
the contents."""

import statistics

import numpy as np


def lognormal_quantile_lengths(median, sigma, n, lo, hi):
    """The ``n`` mid-quantile points of a log-normal length
    distribution, rounded and clipped to ``[lo, hi]``: a deterministic
    sample with the distribution's shape and no draw."""
    nd = statistics.NormalDist()
    mu = np.log(float(median))
    return [int(min(hi, max(lo, round(float(np.exp(
        mu + sigma * nd.inv_cdf((i + 0.5) / n)))))))
        for i in range(n)]


def paired_lengths(prompt, output, n, pair_seed):
    """``n`` (prompt, output) pairs: the quantile points of the two
    distributions, paired by ONE fixed permutation (``pair_seed`` is a
    constant of the mix, not the run's seed)."""
    p = lognormal_quantile_lengths(n=n, **prompt)
    o = lognormal_quantile_lengths(n=n, **output)
    perm = np.random.RandomState(pair_seed).permutation(n)
    return [[p[i], o[int(perm[i])]] for i in range(n)]


def _rng(seed, *stream):
    return np.random.default_rng([int(seed)] + [int(s) for s in stream])


def deal_order(n_pairs, seed, epoch):
    """The order in which pass ``epoch`` over the multiset is dealt."""
    return _rng(seed, 1, epoch).permutation(n_pairs)


class RequestStream:
    """Request ``i`` of a serving mix: ``(prompt token ids, output
    length)``.  Every pass over ``pairs`` deals the whole multiset
    once, in an order drawn from the seed; token ids are drawn per
    request, so no two prompts share a prefix by construction."""

    def __init__(self, mix, vocab_size, seed):
        self.pairs = [tuple(p) for p in mix['pairs']]
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        self._orders = {}

    def lengths(self, i):
        epoch, k = divmod(i, len(self.pairs))
        if epoch not in self._orders:
            self._orders[epoch] = deal_order(len(self.pairs), self.seed,
                                             epoch)
        return self.pairs[int(self._orders[epoch][k])]

    def request(self, i):
        n_prompt, n_out = self.lengths(i)
        prompt = _rng(self.seed, 2, i).integers(
            0, self.vocab_size, size=n_prompt, dtype=np.int64)
        return prompt.astype(np.int32), int(n_out)


def lm_examples(mix, vocab_size, seed):
    """The seeded host dataset of an LM training mix: ``examples``
    rows of ``seq_len + 1`` token ids, every row different; an example
    is ``(tokens[:-1], tokens[1:])``."""
    rows = _rng(seed, 3).integers(
        0, vocab_size, size=(mix['dataset_examples'], mix['seq_len'] + 1),
        dtype=np.int64).astype(np.int32)
    return [(r[:-1], r[1:]) for r in rows]


def image_examples(mix, image_size, num_classes, seed):
    """The seeded host dataset of an image training mix: float32
    ``(size, size, 3)`` examples in [0, 1) with a label each, every
    example different."""
    rng = _rng(seed, 4)
    n = mix['dataset_examples']
    x = rng.random((n, image_size, image_size, 3), dtype=np.float32)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    return [(x[i], y[i]) for i in range(n)]
