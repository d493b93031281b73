"""Training cells: ``StandardUpdater.update()`` in a loop, input path
running, for ``--seconds``; then the plain reference follows the
trainer's first calls and the two are compared."""

import importlib
import math
import time

from chipbench import traffic
from chipbench.adapters import common as adapter_common
from chipbench.reference import common as ref_common

#: calls the comparison covers: the first only synchronises the
#: weights (``create_multi_node_optimizer``), three optimizer steps
#: follow
FIRST_CALLS = 4


class _TimedIterator:
    """The trainer's iterator, each ``next()`` a span."""

    def __init__(self, inner, span):
        self._inner, self._span = inner, span

    def __iter__(self):
        return self

    def __next__(self):
        with self._span('chipbench:next_batch'):
            return next(self._inner)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _spanned(fn, span, name):
    def call(*args, **kwargs):
        with span(name):
            return fn(*args, **kwargs)
    return call


def instrument(upd, span):
    """Spans around the calls ``update()`` makes into each layer:
    the iterator, the collation + placement, the jitted step."""
    upd.iterator = _TimedIterator(upd.iterator, span)
    upd.shard_batch = _spanned(upd.shard_batch, span,
                               'chipbench:shard_batch')
    upd.update_core = _spanned(upd.update_core, span,
                               'chipbench:update_core')


def make_examples(cfg, mix, seed):
    if mix['dataset'] == 'lm_tokens':
        return traffic.lm_examples(mix, cfg['vocab_size'], seed)
    if mix['dataset'] == 'images':
        return traffic.image_examples(mix, cfg['image_size'],
                                      cfg['num_classes'], seed)
    raise KeyError('no dataset %r' % mix['dataset'])


def first_calls(upd, start_params_fn, say):
    """Drive the trainer through its first calls, by the window's own
    call and feed, and read what the comparison needs."""
    import jax
    losses, grad_norms = [], None
    for i in range(FIRST_CALLS):
        losses.append(upd.update()['loss'])
        say('call %d done' % i)
        if i == 1:
            grad_norms = adapter_common.first_gradient_norms(upd)
    start = jax.device_put(start_params_fn(), jax.tree_util.tree_map(
        lambda x: x.sharding, upd.params))
    change = adapter_common.change_norms(upd, start)
    return {'losses': losses, 'first_grad_norms': grad_norms,
            'change_norms': change}


def compare(got, want):
    """The numbers compared, each a gap of the program from the
    reference: every call's loss (the worst); the first gradient and
    the parameters' change, each by its worst leaf and by the mean over
    its leaves (steadier from seed to seed).  A cell's limits file
    says which of them decide ``correct``; the rest are printed."""
    grad = ref_common.leaf_gaps(got['first_grad_norms'],
                                want['first_grad_norms'])
    change = ref_common.leaf_gaps(got['change_norms'],
                                  want['change_norms'])
    return {
        'loss_gap': max(abs(a - b) / abs(b) for a, b in
                        zip(got['losses'], want['losses'])),
        'first_grad_norm_gap': float(grad.max()),
        'first_grad_norm_gap_mean': float(grad.mean()),
        'param_change_norm_gap': float(change.max()),
        'param_change_norm_gap_mean': float(change.mean()),
    }


def run(run):
    import jax

    cfg, mix, seed = run.spec.cfg, run.spec.mix, run.seed
    family = cfg['family']
    ref = importlib.import_module('chipbench.reference.' + family)
    adapter = importlib.import_module('chipbench.adapters.' + family)
    devices = run.devices
    batch = mix['batch']

    def seeded_params():
        return ref.init_params(cfg, seed, adapter.PARAM_DTYPE['train'])

    examples = make_examples(cfg, mix, seed)
    params = jax.block_until_ready(seeded_params())
    run.say('seeded dataset and weights made')
    upd = adapter.build_trainer(cfg, mix, examples, params, devices)
    del params
    instrument(upd, run.span)
    run.say('trainer built')
    got = first_calls(upd, seeded_params, run.say)
    run.say('first calls: losses %s' % ['%.5f' % v for v in got['losses']])

    # ---- the measured window -----------------------------------------
    run.setup_done()
    steps = failed = 0
    t0 = time.perf_counter()
    while True:
        loss = upd.update()['loss']
        steps += 1
        failed += not math.isfinite(loss)
        now = time.perf_counter()
        run.maybe_start_trace(now - t0)
        if now - t0 >= run.seconds:
            break
    jax.block_until_ready(upd.params)
    t1 = time.perf_counter()
    run.stop_trace()
    run.window = (t0, t1)
    run.read_memory_peak()
    finalize = getattr(upd.iterator, 'finalize', None)
    if finalize is not None:
        finalize()
    del upd

    # ---- the plain reference, after the program's state is freed ------
    t_ref = time.perf_counter()
    rows = [ref.batch_of(examples[i * batch:(i + 1) * batch])
            for i in range(FIRST_CALLS)]
    want = ref_common.follow_training(
        ref.make_grad_fn(cfg), ref.init_params(cfg, seed), rows,
        cfg['train'])
    run.say('reference: losses %s, %.1f s'
            % (['%.5f' % v for v in want['losses']],
               time.perf_counter() - t_ref))
    for name, value in compare(got, want).items():
        run.check(name, value)
    run.check('nonfinite_losses', failed)
    if run.control:
        # the reference put in the program's place, in the precision
        # below the configuration's: it has to come out as not correct
        low = ref_common.follow_training(
            ref.make_grad_fn(cfg, precision='fp8'),
            ref.init_params(cfg, seed), rows, cfg['train'])
        for name, value in compare(low, want).items():
            run.control_reading(name, value)

    run.attempted, run.failed = steps, failed
    run.counters.update(steps=steps, samples=steps * batch)
    run.e2e['train_samples_per_s'] = steps * batch / (t1 - t0)
