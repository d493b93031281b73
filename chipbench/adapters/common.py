"""Building the program's trainer from a configuration and a mix, and
reading what the comparison needs back out of it."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import leaf_norms


def build_updater(cfg, mix, examples, params, loss_fn, devices,
                  model_state=None, has_aux=False):
    """``create_communicator('xla')`` -> ``create_multi_node_optimizer``
    -> ``StandardUpdater`` over the mix's input path, as
    ``chip_smoke.py`` and ``examples/imagenet/train_imagenet.py`` do."""
    import optax

    import chainermn_tpu
    from chainermn_tpu import training

    train = cfg['train']
    comm = chainermn_tpu.create_communicator('xla', devices=devices)
    if train['optimizer'] == 'adam':
        inner = optax.adam(train['lr'])
    elif train['optimizer'] == 'sgd_momentum':
        inner = optax.sgd(train['lr'], momentum=train['momentum'])
    else:
        raise KeyError('no optimizer %r' % train['optimizer'])
    optimizer = chainermn_tpu.create_multi_node_optimizer(inner, comm)
    if mix['iterator'] == 'serial':
        iterator = training.SerialIterator(examples, mix['batch'],
                                           shuffle=False)
    elif mix['iterator'] == 'prefetch_thread':
        iterator = training.iterators.MultiprocessIterator(
            examples, mix['batch'], shuffle=False)
    else:
        raise KeyError('no iterator %r' % mix['iterator'])
    policy = (chainermn_tpu.Policy.bf16()
              if train.get('policy') == 'bf16' else None)
    return training.StandardUpdater(
        iterator, optimizer, loss_fn, params, comm, has_aux=has_aux,
        model_state=model_state, policy=policy,
        device_prefetch=mix.get('device_prefetch', 0))


def _moment(opt_state):
    """The optimizer's first moment (Adam's ``mu``, momentum's
    ``trace``): after ONE step from zero it is the gradient the
    optimizer was given, times a known factor."""
    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = [str(getattr(k, 'name', getattr(k, 'key', k)))
                 for k in path]
        for which in ('mu', 'trace'):
            if which in names:
                found.setdefault(which, []).append(leaf)
    if len(found) != 1:
        raise ValueError('expected one first moment in the optimizer '
                         'state, found %r' % sorted(found))
    return next(iter(found.items()))


def first_gradient_norms(upd):
    """Per-leaf norms of the gradient the optimizer got in its first
    step, worked out from its state after that step."""
    which, leaves = _moment(upd.opt_state)
    factor = (1.0 - 0.9) if which == 'mu' else 1.0   # optax.adam's b1
    return np.asarray(jax.jit(leaf_norms)(leaves)) / factor


def change_norms(upd, start_params):
    """Per-leaf norms of (the trainer's parameters - ``start_params``),
    computed on the trainer's devices."""
    return np.asarray(jax.jit(lambda now, then: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, now, then)))(
            upd.params, start_params))
