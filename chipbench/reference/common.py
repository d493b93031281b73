"""What the plain references share: seeded weights, the precision
switch, hand-written optimizers and per-leaf norms.  Nothing here
imports the program."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def seed_key(seed):
    """A PRNG key from any whole number a little over 2**31 (more than
    32 signed bits hold): low bits seed, high bits folded in.  ``rbg``
    generates a gigabyte of weights in one cheap device op."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7fffffff, impl='rbg')
    return jax.random.fold_in(key, seed >> 31)


def init_from_spec(spec, key, dtype):
    """Weights for ``spec`` (a nested dict whose leaves are
    ``(shape, mean, std)``) as ONE normal draw carved into leaves:
    ``mean + std * n``.  Jit it and every leaf is made on the device
    in one call."""
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))
    sizes = [int(np.prod(shape)) for shape, _, _ in leaves]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, at = [], 0
    for (shape, mean, std), size in zip(leaves, sizes):
        out.append((mean + std * flat[at:at + size].reshape(shape))
                   .astype(dtype))
        at += size
    return jax.tree_util.tree_unflatten(treedef, out)


def _round_to(x, dtype):
    """``x`` as a tensor of the fp8 ``dtype`` with one scale holds it."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) \
        / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8_round(x):
    """An operand as fp8 training holds it: float8_e4m3fn on the way
    forward, and the cotangent that comes back through it rounded to
    float8_e5m2 (each with one scale per tensor)."""
    return _round_to(x, jnp.float8_e4m3fn)


fp8_round.defvjp(lambda x: (fp8_round(x), None),
                 lambda _, g: (_round_to(g, jnp.float8_e5m2),))


class Precision:
    """How a reference multiplies.  ``float32``: full-precision
    products (``highest``; a TPU otherwise rounds float32 operands to
    bfloat16).  ``fp8``: both operands of every product rounded to
    float8_e4m3fn first -- the precision below bfloat16, the control
    that has to come out as not correct."""

    def __init__(self, name='float32'):
        if name not in ('float32', 'fp8'):
            raise ValueError('unknown reference precision %r' % name)
        self.name = name

    def operand(self, x):
        x = x.astype(jnp.float32)
        return fp8_round(x) if self.name == 'fp8' else x

    def store(self, x):
        """An activation where the program keeps one in its compute
        dtype between layers: float32 here, fp8 in the control."""
        return self.operand(x)

    def einsum(self, expr, a, b):
        return jnp.einsum(expr, self.operand(a), self.operand(b),
                          precision=HIGHEST)

    def conv(self, x, w, stride, padding):
        return lax.conv_general_dilated(
            self.operand(x), self.operand(w), (stride, stride), padding,
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            precision=HIGHEST)


def leaf_norms(tree):
    """The 2-norm of every leaf, as one float32 vector in
    ``tree_leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(tree)])


def leaf_paths(tree):
    return ['/'.join(str(getattr(k, 'key', k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {'count': jnp.zeros((), jnp.int32), 'mu': zeros, 'nu': zeros}


def adam_step(params, state, grads, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam (Kingma & Ba), bias-corrected, as ``optax.adam`` has it."""
    count = state['count'] + 1
    t = count.astype(jnp.float32)
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state['mu'], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                state['nu'], grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t))
        / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
    return params, {'count': count, 'mu': mu, 'nu': nu}


def momentum_init(params):
    return {'trace': jax.tree_util.tree_map(jnp.zeros_like, params)}


def momentum_step(params, state, grads, lr, momentum=0.9):
    """SGD with (heavy-ball) momentum, as ``optax.sgd`` has it."""
    trace = jax.tree_util.tree_map(lambda t, g: g + momentum * t,
                                   state['trace'], grads)
    params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params,
                                    trace)
    return params, {'trace': trace}


OPTIMIZERS = {'adam': (adam_init, adam_step),
              'sgd_momentum': (momentum_init, momentum_step)}


def follow_training(grad_fn, params, batches, train,
                    first_call_steps=False):
    """What the trainer's first ``len(batches)`` calls produce, in the
    reference's arithmetic.  ``grad_fn(params, batch) -> (loss,
    grads)``.  The program's first call only synchronises the weights
    (``first_call_steps=False``): its loss is reported and no step is
    taken.  Returns the losses, the per-leaf norms of the first
    gradient an optimizer step consumed, and the per-leaf norms of the
    parameters' change over all the calls."""
    init, step = OPTIMIZERS[train['optimizer']]
    hyper = {k: train[k] for k in ('lr', 'momentum') if k in train}
    state = init(params)
    start = params
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        loss, grads = grad_fn(params, batch)
        losses.append(float(loss))
        if i == 0 and not first_call_steps:
            continue
        if first_grad is None:
            first_grad = np.asarray(leaf_norms(grads))
        params, state = step(params, state, grads, **hyper)
    change = np.asarray(leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, start)))
    return {'losses': losses, 'first_grad_norms': first_grad,
            'change_norms': change}


def leaf_gaps(got, want):
    """Per leaf, the gap between a program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(want, float(np.median(want)))
