"""Fused softmax cross-entropy.

The framework-wide loss (classifier heads at
``models/classifier.py``, seq2seq at ``models/seq2seq.py``; the
reference delegates to Chainer's ``softmax_cross_entropy``).  The
Pallas forward computes per-row max / log-sum-exp / label logit in one
VMEM pass without writing the (B, V) probability matrix back to HBM;
the backward recomputes probabilities from the saved LSE
(``p = exp(logits - lse)``), which XLA fuses into the (unavoidable)
(B, V) gradient write.
"""

import functools

import jax
import jax.numpy as jnp

from chainermn_tpu.ops._common import interpret_flag, pallas_mode


def softmax_cross_entropy_reference(logits, labels):
    """Pure-jnp oracle: per-example loss, (B,) float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - picked


def _ce_kernel(logits_ref, labels_ref, loss_ref, lse_ref, *, block_b):
    logits = logits_ref[:].astype(jnp.float32)          # (block_b, V)
    labels = labels_ref[:]                              # (block_b, 1)
    v = logits.shape[-1]
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_b, v), 1)
    onehot = cols == labels
    picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    loss_ref[:] = (lse - picked)[:, None]
    lse_ref[:] = lse[:, None]


def _ce_pallas(logits, labels, block_b):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, v = logits.shape
    grid = (b // block_b,)
    loss, lse = pl.pallas_call(
        functools.partial(_ce_kernel, block_b=block_b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, v), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        interpret=interpret_flag(),
        name='cross_entropy_fwd',
    )(logits, labels[:, None].astype(jnp.int32))
    return loss[:, 0], lse[:, 0]


@jax.custom_vjp
def _ce(logits, labels):
    loss, _ = _ce_fwd(logits, labels)
    return loss


def _ce_fwd(logits, labels):
    if pallas_mode() == 'fallback':
        lf = logits.astype(jnp.float32)
        m = jnp.max(lf, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(lf - m[:, None]), axis=-1))
        picked = jnp.take_along_axis(lf, labels[:, None], axis=-1)[:, 0]
        loss = lse - picked
    else:
        b = logits.shape[0]
        block_b = 8
        pad = (-b) % block_b
        lp = jnp.pad(logits, ((0, pad), (0, 0))) if pad else logits
        yp = jnp.pad(labels, (0, pad)) if pad else labels
        loss, lse = _ce_pallas(lp, yp, block_b)
        loss, lse = loss[:b], lse[:b]
    return loss, (logits, labels, lse)


def _ce_bwd(res, g):
    logits, labels, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    dlogits = (p - onehot) * g[:, None]
    return dlogits.astype(logits.dtype), None


_ce.defvjp(_ce_fwd, _ce_bwd)


def softmax_cross_entropy(logits, labels):
    """Per-example softmax cross-entropy. logits (B, V), labels (B,)
    int -> (B,) float32 losses."""
    return _ce(logits, labels)
