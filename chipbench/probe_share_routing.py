#!/usr/bin/env python3
"""How long a chip's share of the experts keeps its rows, step by step,
in the program and in the plain reference:

    python3 chipbench/probe_share_routing.py --side reference \\
        --workload kanana-train-8k-ep8share --seed 2147501199 --steps 26
    python3 chipbench/probe_share_routing.py --side program ... --lr 1e-5

``--side program`` builds the cell's trainer as the cell does and prints
``update()``'s counters at every call; ``--side reference`` follows the
same batches in float32 at ``highest`` with the reference's own Adam
(nothing of the program imported) and counts, before each call, the
assignments its router puts on the held experts.  The first call only
synchronises the weights on both sides.  The reference side runs
wherever JAX runs (the CPU too, a few minutes a step at the cell's
size).  A probe: the benchmark's own runs never run this."""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def say(side, call, held, total, with_row, max_over_mean, loss, t0):
    print('probe %s call %d held_assignments %d of %d (%.2f%%) '
          'experts_with_row %.2f load_max_over_mean %.2f loss %.4f '
          '%.0f s' % (side, call, held, total, 100.0 * held / total,
                      with_row, max_over_mean, loss,
                      time.perf_counter() - t0), flush=True)


def follow_reference(cfg, mix, examples, seed, steps):
    import jax
    import numpy as np

    from chipbench.reference import common
    from chipbench.reference import deepseek_v3 as ref

    prec = common.Precision('float32')
    lo, n = ref.first_expert(cfg), cfg['n_routed_experts']
    routing = jax.jit(lambda params, tokens: ref.hidden(
        params, tokens, cfg, prec, with_routing=True)[1])
    grad_fn = ref.make_grad_fn(cfg)
    init, step = common.OPTIMIZERS[cfg['train']['optimizer']]
    params = ref.init_params(cfg, seed)
    state = init(params)
    t0 = time.perf_counter()
    for call in range(steps):
        at = call % len(examples)
        batch = ref.batch_of(examples[at:at + 1])
        chosen = np.asarray(routing(params, batch[0][0])) - lo
        held = (chosen >= 0) & (chosen < n)      # (layers, T, k)
        sizes = np.stack([np.bincount(c[h], minlength=n)
                          for c, h in zip(chosen, held)])
        loss, grads = grad_fn(params, batch)
        say('reference', call, int(held.sum()), held.size,
            float((sizes > 0).sum(1).mean()),
            float((sizes.max(1) * n / np.maximum(sizes.sum(1), 1)).mean()),
            float(loss), t0)
        if call:
            params, state = step(params, state, grads,
                                 lr=cfg['train']['lr'])


def follow_program(cfg, mix, examples, seed, steps):
    import importlib

    import jax

    from chipbench.reference import deepseek_v3 as ref
    adapter = importlib.import_module('chipbench.adapters.' + cfg['family'])
    params = jax.block_until_ready(ref.init_params(
        cfg, seed, adapter.PARAM_DTYPE['train']))
    upd = adapter.build_trainer(cfg, mix, examples, params,
                                jax.devices()[:1])
    del params
    t0 = time.perf_counter()
    for call in range(steps):
        out = upd.update()
        say('program', call, int(out['held_assignments']),
            int(out['assignments']), out['experts_with_row'],
            out['expert_load_max_over_mean'], out['loss'], t0)


def main(argv=None):
    from chipbench import harness
    from chipbench.drivers import train
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--side', choices=('reference', 'program'),
                        required=True)
    parser.add_argument('--workload', default='kanana-train-8k-ep8share')
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--steps', type=int, default=26)
    parser.add_argument('--lr', type=float,
                        help="in place of the configuration's")
    args = parser.parse_args(argv)
    spec = harness.Spec(args.workload)
    cfg, mix = spec.cfg, spec.mix
    assert mix['batch'] == 1, 'the probe follows one sequence a call'
    if args.lr is not None:
        cfg['train'] = dict(cfg['train'], lr=args.lr)
    harness.place_compile_cache()
    examples = train.make_examples(cfg, mix, args.seed)
    follow = follow_reference if args.side == 'reference' else follow_program
    follow(cfg, mix, examples, args.seed, args.steps)
    return 0


if __name__ == '__main__':
    sys.exit(main())
