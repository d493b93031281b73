"""Fused LayerNorm.

The forward is a row-tiled Pallas kernel (``layer_norm_fwd``), one pass
over HBM: a grid step reads a tile of rows, computes mean / variance /
normalize / affine in float32 and writes the tile in ``x``'s dtype.  A
tile is sized in BYTES (``_rows_tile``): a grid step costs a fraction of
a microsecond before it moves a byte, so eight rows a step left the
kernel at a sixth of the HBM's rate at ``(8192, 1024)``.  The grid is
``cdiv(rows, tile)``: the last tile may hang over the end, where reads
are unspecified and writes are dropped (a row's result depends on that
row alone).

The backward is the closed-form gradient in jnp on every platform: XLA
hangs its passes on the neighbouring products as epilogues, and a
kernel of its own moved the step by 0.3% (``PERF.md`` section 6, PR 47).
"""

import functools

import jax
import jax.numpy as jnp

from chainermn_tpu.ops._common import interpret_flag, pallas_mode

#: bytes of a tile's float32 working copy
_TILE_BYTES = 2 * 1024 * 1024
#: what the kernel may hold of VMEM: the compiler's own default, stated
#: (a tile of float32 rows takes 10 MB).  The serving executables run
#: this kernel, and one that states more moves XLA's memory-space
#: assignment around it (at 32 MiB the 96-row decode executable of
#: ``phi4flash`` began to prefetch a whole 32 MB state leaf into VMEM
#: and copy it back).
_VMEM_LIMIT = 16 * 1024 * 1024


def layer_norm_reference(x, gamma, beta, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


def _rows_tile(rows, d, dtype):
    """Rows a grid step takes: all of them where their float32 copy
    fits ``_TILE_BYTES`` (a decode call's 32 or 96 rows are one step),
    else the largest multiple of the dtype's sublane packing (8 rows of
    float32, 16 of bfloat16) that does."""
    sub = 32 // jnp.dtype(dtype).itemsize
    lanes = -(-d // 128) * 128          # a row in VMEM, padded
    cap = max(sub, _TILE_BYTES // (4 * lanes) // sub * sub)
    return rows if rows <= cap else cap


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                 # (tile, D)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_pallas(x2d, gamma, beta, eps):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, d = x2d.shape
    tile = _rows_tile(b, d, x2d.dtype)
    rows = pl.BlockSpec((tile, d), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    row = pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(pl.cdiv(b, tile),),
        in_specs=[rows, row, row],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((b, d), x2d.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',),
            vmem_limit_bytes=_VMEM_LIMIT),
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
        out2d = _ln_pallas(x2d, gamma, beta, eps)
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
