"""Fused momentum-SGD update.

The reference's optimizer step is Chainer's per-param Python loop
(`multi_node_optimizer.py:29` delegating to MomentumSGD).  Here the
whole elementwise sweep is one Pallas pass per tensor -- velocity
update and parameter delta computed together so each gradient leaf is
read from HBM exactly once.  Exposed two ways:

- :func:`momentum_sgd` -- functional kernel over a pytree
- :func:`fused_momentum_sgd` -- drop-in ``optax.GradientTransformation``
  (same signature as ``optax.sgd(lr, momentum)``)
"""

import functools

import jax
import jax.numpy as jnp
import optax

from chainermn_tpu.ops._common import interpret_flag, pallas_mode

_LANES = 128
_BLOCK_ROWS = 512


def _sgd_kernel(g_ref, v_ref, vout_ref, dout_ref, *, lr, momentum):
    g = g_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    v_new = momentum * v + g
    vout_ref[:] = v_new.astype(vout_ref.dtype)
    dout_ref[:] = (-lr * v_new).astype(dout_ref.dtype)


def _leaf_update_pallas(g, v, lr, momentum):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape, dtype = g.shape, g.dtype
    n = g.size
    rows = -(-n // _LANES)
    pad = rows * _LANES - n
    block = min(_BLOCK_ROWS, rows)
    rpad = (-rows) % block

    def to2d(x):
        flat = x.reshape(-1).astype(jnp.float32)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        out = flat.reshape(rows, _LANES)
        if rpad:
            out = jnp.pad(out, ((0, rpad), (0, 0)))
        return out

    g2, v2 = to2d(g), to2d(v)
    total_rows = rows + rpad
    v_new, delta = pl.pallas_call(
        functools.partial(_sgd_kernel, lr=lr, momentum=momentum),
        grid=(total_rows // block,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((total_rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((total_rows, _LANES), jnp.float32),
        ],
        interpret=interpret_flag(),
        name='sgd_momentum_update',
    )(g2, v2)

    def from2d(x, out_dtype):
        return x.reshape(-1)[:n].reshape(shape).astype(out_dtype)

    # velocity keeps its own (float32) state dtype -- casting it to
    # g.dtype would silently carry bf16 momentum state on the native
    # path and diverge from the jnp/optax trajectory
    return from2d(v_new, v.dtype), from2d(delta, dtype)


def _leaf_update_jnp(g, v, lr, momentum):
    gf = g.astype(jnp.float32)
    v_new = momentum * v.astype(jnp.float32) + gf
    return v_new.astype(v.dtype), (-lr * v_new).astype(g.dtype)


def momentum_sgd(params, grads, velocity, lr, momentum=0.9):
    """One fused update over a pytree: returns (new_params,
    new_velocity).  Matches ``optax.sgd(lr, momentum)`` (heavy-ball,
    v = mu*v + g; p -= lr*v)."""
    leaf = (_leaf_update_jnp if pallas_mode() == 'fallback'
            else _leaf_update_pallas)

    def upd(p, g, v):
        v_new, delta = leaf(g, v, lr, momentum)
        return p + delta.astype(p.dtype), v_new

    flat = jax.tree_util.tree_map(upd, params, grads, velocity)
    new_params = jax.tree_util.tree_map(
        lambda pv: pv[0], flat, is_leaf=lambda x: isinstance(x, tuple))
    new_velocity = jax.tree_util.tree_map(
        lambda pv: pv[1], flat, is_leaf=lambda x: isinstance(x, tuple))
    return new_params, new_velocity


def fused_momentum_sgd(learning_rate, momentum=0.9):
    """optax-compatible fused momentum SGD (one HBM pass per leaf)."""
    leaf = (_leaf_update_jnp if pallas_mode() == 'fallback'
            else _leaf_update_pallas)

    def init(params):
        return {'velocity': jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)}

    def update(grads, state, params=None):
        del params
        pairs = jax.tree_util.tree_map(
            lambda g, v: leaf(g, v, learning_rate, momentum),
            grads, state['velocity'])
        velocity = jax.tree_util.tree_map(
            lambda pv: pv[0], pairs,
            is_leaf=lambda x: isinstance(x, tuple))
        updates = jax.tree_util.tree_map(
            lambda pv: pv[1], pairs,
            is_leaf=lambda x: isinstance(x, tuple))
        return updates, {'velocity': velocity}

    return optax.GradientTransformation(init, update)
