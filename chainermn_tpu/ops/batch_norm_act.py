"""Fused BatchNorm + activation (+ residual add): one HBM pass.

The HBM-bandwidth evidence (PERF.md "What the batch sweep's first
point says"): ResNet-50 per-image step time is flat in batch size --
the signature of a bandwidth-bound step -- with ~316 MB of HBM
traffic per image, ~4x the ideal activation footprint.  The excess is
materialized intermediates around the BN/relu/residual-add interludes
between convs: the stock ``flax.linen.BatchNorm`` + ``relu`` + ``+``
chain upcasts the bf16 activation to f32 for statistics, materializes
the normalized value for the backward pass, and makes the relu mask
and the residual sum separate activation-sized tensors.

This op fuses the whole interlude:

  normalize (f32 statistics over bf16 activations) -> scale/shift ->
  optional residual add -> optional relu

into one Pallas pass over the activation per direction, with a
``custom_vjp`` whose backward RECOMPUTES the normalized value from
the saved ``(x, mean, rstd)`` instead of materializing it across the
forward/backward boundary -- the saved set is the bf16 activation the
next conv consumes anyway plus two ``(C,)`` vectors.

Layer conventions (``chainermn_tpu.ops`` docstring): a pure-``jnp``
reference (:func:`batch_norm_act_reference`) is the numerics oracle
in tests and the fallback on non-TPU backends; the Pallas path runs
natively on TPU and in interpret mode when
``CHAINERMN_TPU_PALLAS_INTERPRET=1``.  Statistics math matches
``flax.linen.BatchNorm`` (f32, fast variance ``E[x^2] - E[x]^2``
clipped at zero) so the flax path stays a drop-in oracle.
"""

import functools

import jax
import jax.numpy as jnp

from chainermn_tpu.ops._common import interpret_flag, pallas_mode


def _batch_stats(x2d, eps):
    """flax-parity batch statistics: f32, fast variance, clipped."""
    xf = x2d.astype(jnp.float32)
    mean = jnp.mean(xf, axis=0)
    mean2 = jnp.mean(xf * xf, axis=0)
    var = jnp.maximum(mean2 - mean * mean, 0.0)
    return mean, var, jax.lax.rsqrt(var + eps)


def _apply_ref(x, mean, rstd, scale, bias, residual, relu):
    """Normalize + affine (+ add) (+ relu) in f32; output in x.dtype."""
    y = (x.astype(jnp.float32) - mean) * (rstd * scale) + bias
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def batch_norm_act_reference(x, scale, bias, eps=1e-5, residual=None,
                             relu=True):
    """Pure-jnp oracle.  ``x`` (..., C) any float dtype, ``scale`` /
    ``bias`` (C,) f32; returns ``(out, batch_mean, batch_var)`` with
    f32 statistics (the running-average update inputs)."""
    c = x.shape[-1]
    mean, var, rstd = _batch_stats(x.reshape(-1, c), eps)
    out = _apply_ref(x, mean, rstd, scale.astype(jnp.float32),
                     bias.astype(jnp.float32), residual, relu)
    return out, mean, var


def batch_norm_act_inference(x, scale, bias, mean, var, eps=1e-5,
                             residual=None, relu=True):
    """Inference-mode normalize with RUNNING statistics: a pure
    elementwise chain XLA fuses on its own (no bespoke kernel
    needed); f32 math, output in ``x.dtype``."""
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    return _apply_ref(x, mean.astype(jnp.float32), rstd,
                      scale.astype(jnp.float32),
                      bias.astype(jnp.float32), residual, relu)


# ---------------------------------------------------------------------
# Pallas kernels.  Layout: the (..., C) activation is flattened to
# (M, C) rows; statistics reduce over rows (axis 0), so the kernels
# grid over row blocks with the channel axis on the TPU lane
# dimension.  The stats kernel accumulates partial sums into its
# (1, C) outputs across the sequential TPU grid; the apply kernel is
# one read of x (+ residual) and one write of out per row block.

def _stats_kernel(x_ref, s_ref, q_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        s_ref[:] = jnp.zeros_like(s_ref)
        q_ref[:] = jnp.zeros_like(q_ref)

    xf = x_ref[:].astype(jnp.float32)
    s_ref[:] += jnp.sum(xf, axis=0, keepdims=True)
    q_ref[:] += jnp.sum(xf * xf, axis=0, keepdims=True)


def _stats_pallas(x2d, block_m):
    """(sum, sumsq) over rows, each (1, C) f32, in one HBM pass."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = x2d.shape
    return pl.pallas_call(
        _stats_kernel,
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((block_m, c), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, c), lambda i: (0, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        interpret=interpret_flag(),
        name='batch_norm_stats',
    )(x2d)


def _apply_kernel(x_ref, mu_ref, rs_ref, g_ref, b_ref, o_ref, *, relu):
    xf = x_ref[:].astype(jnp.float32)
    y = (xf - mu_ref[:]) * (rs_ref[:] * g_ref[:]) + b_ref[:]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.astype(o_ref.dtype)


def _apply_res_kernel(x_ref, r_ref, mu_ref, rs_ref, g_ref, b_ref,
                      o_ref, *, relu):
    xf = x_ref[:].astype(jnp.float32)
    y = (xf - mu_ref[:]) * (rs_ref[:] * g_ref[:]) + b_ref[:]
    y = y + r_ref[:].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.astype(o_ref.dtype)


def _apply_pallas(x2d, res2d, mean, rstd, scale, bias, relu, block_m):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = x2d.shape
    row = pl.BlockSpec((block_m, c), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, c), lambda i: (0, 0),
                       memory_space=pltpu.VMEM)
    vecs = (mean[None, :], rstd[None, :],
            scale.astype(jnp.float32)[None, :],
            bias.astype(jnp.float32)[None, :])
    if res2d is None:
        kernel = functools.partial(_apply_kernel, relu=relu)
        in_specs, args = [row] + [vec] * 4, (x2d,) + vecs
    else:
        kernel = functools.partial(_apply_res_kernel, relu=relu)
        in_specs, args = [row, row] + [vec] * 4, (x2d, res2d) + vecs
    return pl.pallas_call(
        kernel,
        grid=(m // block_m,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, c), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, c), x2d.dtype),
        interpret=interpret_flag(),
        name='batch_norm_apply',
    )(*args)


_BLOCK_M = 256


def _pad_rows(x2d, block_m):
    m = x2d.shape[0]
    pad = (-m) % block_m
    return (jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d), m


# ---------------------------------------------------------------------
# custom_vjp: the differentiable training-mode op

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_act(x, scale, bias, residual, eps, relu):
    return _bn_act_fwd(x, scale, bias, residual, eps, relu)[0]


def _bn_act_fwd(x, scale, bias, residual, eps, relu):
    shape = x.shape
    c = shape[-1]
    x2d = x.reshape(-1, c)
    res2d = residual.reshape(-1, c) if residual is not None else None
    if pallas_mode() == 'fallback':
        mean, var, rstd = _batch_stats(x2d, eps)
        out2d = _apply_ref(x2d, mean, rstd,
                           scale.astype(jnp.float32),
                           bias.astype(jnp.float32), res2d, relu)
    else:
        xp, m = _pad_rows(x2d, _BLOCK_M)
        s, q = _stats_pallas(xp, _BLOCK_M)
        # zero pad rows contribute nothing to the sums; divide by the
        # REAL row count (flax fast variance, clipped at zero)
        mean = s[0] / m
        var = jnp.maximum(q[0] / m - mean * mean, 0.0)
        rstd = jax.lax.rsqrt(var + eps)
        rp = _pad_rows(res2d, _BLOCK_M)[0] if res2d is not None \
            else None
        out2d = _apply_pallas(xp, rp, mean, rstd, scale, bias, relu,
                              _BLOCK_M)[:x2d.shape[0]]
    out = out2d.reshape(shape)
    # Saved set: the bf16 activation (materialized anyway as the
    # producing conv's output), the OUTPUT (materialized anyway as the
    # next layer's input; its sign is the relu mask, so neither a mask
    # tensor nor the pre-activation sum survives the boundary), and
    # two (C,) vectors.  No activation-sized f32 residuals.
    return (out, mean, var), (x, scale, mean, rstd, out,
                              residual is not None)


def _bn_act_bwd(eps, relu, saved, cts):
    g, g_mean, g_var = cts
    x, scale, mean, rstd, out, has_residual = saved
    shape = x.shape
    c = shape[-1]
    xf = x.reshape(-1, c).astype(jnp.float32)
    gf = g.reshape(-1, c).astype(jnp.float32)
    m = xf.shape[0]
    xhat = (xf - mean) * rstd          # recomputed, never materialized
    if relu:
        gm = gf * (out.reshape(-1, c) > 0)
    else:
        gm = gf
    scale_f = scale.astype(jnp.float32)
    dbeta = jnp.sum(gm, axis=0)
    dgamma = jnp.sum(gm * xhat, axis=0)
    dx = (scale_f * rstd) * (gm - dbeta / m - xhat * (dgamma / m))
    # the mean/var outputs feed the (undifferentiated) running-stats
    # update, so their cotangents are normally zero constants that XLA
    # folds away -- but the closed form is cheap, keep the op honest
    # under arbitrary transforms
    dx = dx + (g_mean.astype(jnp.float32)
               + 2.0 * (xf - mean) * g_var.astype(jnp.float32)) / m
    dres = gm.reshape(shape).astype(x.dtype) if has_residual else None
    return (dx.reshape(shape).astype(x.dtype),
            dgamma.astype(scale.dtype), dbeta.astype(scale.dtype),
            dres)


_bn_act.defvjp(_bn_act_fwd, _bn_act_bwd)


def batch_norm_act(x, scale, bias, eps=1e-5, residual=None, relu=True):
    """Training-mode fused BatchNorm + optional residual add +
    optional relu over the last axis of ``x``.

    Args:
      x: (..., C) activation (bf16 or f32).
      scale, bias: (C,) affine parameters (f32 masters).
      eps: variance epsilon.
      residual: optional (..., C) tensor added AFTER the affine,
        BEFORE the relu (the ResNet shortcut).
      relu: apply max(y, 0) as the final step.

    Returns:
      ``(out, batch_mean, batch_var)``; ``out`` has ``x.dtype``, the
      statistics are f32 ``(C,)`` (feed them to the running-average
      update exactly like ``flax.linen.BatchNorm``'s).
    """
    return _bn_act(x, scale, bias, residual, eps, relu)
