"""Fused LayerNorm.

One VMEM pass computing mean/variance/normalize/affine per row --
the transformer-side normalization used by
``chainermn_tpu.models.transformer``.  Backward uses the standard
closed-form layernorm gradient in jnp (XLA fuses it into two passes).
"""

import functools

import jax
import jax.numpy as jnp

from chainermn_tpu.ops._common import interpret_flag, pallas_mode


def layer_norm_reference(x, gamma, beta, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                 # (block_b, D)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_pallas(x2d, gamma, beta, eps, block_b):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, d = x2d.shape
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, d), x2d.dtype),
        interpret=interpret_flag(),
        name='layer_norm_fwd',
    )(x2d, gamma[None, :], beta[None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x, gamma, beta, eps):
    out, _ = _ln_fwd(x, gamma, beta, eps)
    return out


def _ln_fwd(x, gamma, beta, eps):
    shape = x.shape
    d = shape[-1]
    x2d = x.reshape(-1, d)
    if pallas_mode() == 'fallback':
        out2d = layer_norm_reference(x2d, gamma, beta, eps)
    else:
        b = x2d.shape[0]
        block_b = 8
        pad = (-b) % block_b
        xp = jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d
        out2d = _ln_pallas(xp, gamma, beta, eps, block_b)[:b]
    return out2d.reshape(shape), (x, gamma)


def _ln_bwd(eps, res, g):
    x, gamma = res
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d).astype(jnp.float32)
    gf = g.reshape(-1, d).astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dgamma = jnp.sum(gf * xhat, axis=0)
    dbeta = jnp.sum(gf, axis=0)
    gy = gf * gamma.astype(jnp.float32)
    dx = rstd * (gy - jnp.mean(gy, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    return (dx.reshape(shape).astype(x.dtype),
            dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype))


_ln.defvjp(_ln_fwd, _ln_bwd)


def layer_norm(x, gamma, beta, eps=1e-6):
    """LayerNorm over the last axis. x (..., D), gamma/beta (D,)."""
    return _ln(x, gamma, beta, eps)
