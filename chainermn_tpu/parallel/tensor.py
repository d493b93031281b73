"""Tensor (operator) parallelism primitives.

Megatron-style sharded matmul pair for use inside ``shard_map``: a
column-parallel projection (weights split on the output dim, no
communication in) followed by a row-parallel projection (weights split
on the input dim, one ``psum`` out).  One collective per block instead
of per layer -- the layout "How to Scale Your Model" prescribes for
feed-forward/attention blocks on ICI meshes.  (SURVEY 2.2: TP is not a
reference parity requirement but the natural extension of its sharded
design.)
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------
# Megatron conjugate pair (Shoeybi et al.'s f/g operators).
#
# The updaters differentiate INSIDE shard_map with check_vma=False,
# where jax transposes ``psum`` to ``psum``: a cotangent that is
# already replicated over the model axis gets multiplied by the axis
# size at every reduction it crosses (measured, not theoretical --
# the naive block's grads come out exactly tp x too large).  The
# correct transposes for the "loss replicated over the model axis"
# convention are the conjugates below: the region EXIT reduces
# forward and passes cotangents through untouched (every rank already
# holds the full replicated cotangent), and the region ENTRY is the
# identity forward but psums cotangents backward (each rank's
# backward contributes only its own weight shard's term of dL/dx).
# Differentiating OUTSIDE shard_map hits the same custom rules, so
# both supported autodiff placements agree.

def _tp_mark(name, axis):
    """Trace-time collective-issue mark (fires per compilation): the
    model-axis twin of the strategies' allreduce_grad mark, so the
    telemetry report can split dp vs tp collective issues."""
    from chainermn_tpu import telemetry as _telemetry
    if _telemetry.live() is not None:
        _telemetry.event(name, kind='collective_trace',
                         axes=[axis] if isinstance(axis, str)
                         else list(axis))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_reduce(x, axis):
    """Megatron ``g``: exit a tensor-parallel region.  Forward is
    ``psum`` over ``axis`` (completes the sharded contraction);
    backward is the identity -- the downstream cotangent is already
    replicated over ``axis``, and a psum transpose would scale it by
    the axis size."""
    _tp_mark('tensor:tp_reduce', axis)
    return lax.psum(x, axis)


def _tp_reduce_fwd(x, axis):
    _tp_mark('tensor:tp_reduce', axis)
    return lax.psum(x, axis), None


def _tp_reduce_bwd(axis, _res, ct):
    return (ct,)


tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_copy(x, axis):
    """Megatron ``f``: enter a tensor-parallel region with a
    replicated activation.  Forward is the identity; backward psums
    the cotangents over ``axis`` -- each rank's backward computes only
    its own weight shard's contribution to dL/dx, and the residual
    stream (and every parameter upstream, layer norms included) needs
    their sum."""
    return x


def _tp_copy_fwd(x, axis):
    return x, None


def _tp_copy_bwd(axis, _res, ct):
    return (lax.psum(ct, axis),)


tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


def column_parallel_dense(x, w, b=None):
    """``y_local = x @ w_local`` -- w sharded on columns (output dim);
    output stays sharded on the feature dim, no collective."""
    y = jnp.einsum('...d,df->...f', x, w)
    if b is not None:
        y = y + b
    return y


def row_parallel_dense(x_local, w, axis, b=None,
                       grad_conjugate=False):
    """``y = psum_axis(x_local @ w_local)`` -- w sharded on rows (input
    dim), input arrives feature-sharded from a column-parallel layer;
    the psum completes the logical matmul.

    ``grad_conjugate=True`` exits through :func:`tp_reduce` (identity
    backward) instead of a raw ``psum`` -- REQUIRED when the caller
    differentiates this block inside ``shard_map`` with
    ``check_vma=False`` (the updaters' mode), where the raw psum's
    transpose scales cotangents by the axis size.  Pair it with
    :func:`tp_copy` at the region entry."""
    y = jnp.einsum('...d,df->...f', x_local, w)
    y = tp_reduce(y, axis) if grad_conjugate else lax.psum(y, axis)
    if b is not None:
        y = y + b  # bias applied once, after the reduction
    return y


def tp_mlp(x, w_in, b_in, w_out, b_out, axis, activation=jnp.tanh):
    """Column->activation->row feed-forward with one psum total.

    Pass ``activation=None`` for a purely linear block."""
    h = column_parallel_dense(x, w_in, b_in)
    if activation is not None:
        h = activation(h)
    return row_parallel_dense(h, w_out, axis, b_out)


def qkv_attention(x, wqkv, causal=False, attn_fn=None, bqkv=None):
    """Shared attention core: fused QKV projection
    (``wqkv``: (d_model, 3, heads, d_head), optional ``bqkv``:
    (3, heads, d_head)) -> attention -> heads re-flattened,
    ``(B, T, heads * d_head)``.  Used with the full head set by
    ``moe.moe_transformer_block`` (replicated weights) and with the
    LOCAL head group by :func:`tp_attention` and the tp transformer
    (head-sharded weights and bias)."""
    qkv = jnp.einsum('btd,dchf->btchf', x, wqkv)  # c=3
    if bqkv is not None:
        qkv = qkv + bqkv  # sharded with the heads, added pre-psum
    if attn_fn is None:
        from chainermn_tpu import ops
        attn_fn = ops.flash_attention
    attn = attn_fn(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                   causal=causal)
    return attn.reshape(attn.shape[:2] + (-1,))


def tp_attention(x, wqkv, wo, axis, n_heads, causal=False, bo=None,
                 attn_fn=None):
    """Megatron-sharded self-attention: one psum per block.

    The QKV projection is column-parallel with HEADS as the sharded
    unit -- ``wqkv``: (d_model, 3, local_heads, d_head), each device
    computing attention for its own head group with no communication
    (heads are embarrassingly parallel) -- and the output projection
    is row-parallel, ``wo``: (local_heads * d_head, d_model), whose
    ``psum`` sums the head groups' contributions, completing the
    logical concat-then-project.  Requires
    ``n_heads % axis_size == 0``.

    x: (B, T, d_model) replicated over ``axis``; returns the same.
    ``attn_fn(q, k, v, causal=...)`` defaults to the fused Pallas
    flash kernel.
    """
    p = lax.axis_size(axis)
    if n_heads % p:
        raise ValueError('tp_attention needs n_heads %% axis_size '
                         '== 0, got %d heads over %d devices'
                         % (n_heads, p))
    if wqkv.shape[2] * p != n_heads:
        raise ValueError('wqkv carries %d local heads on %d devices '
                         'but n_heads=%d'
                         % (wqkv.shape[2], p, n_heads))
    attn = qkv_attention(x, wqkv, causal=causal, attn_fn=attn_fn)
    return row_parallel_dense(attn, wo, axis, bo)


def tp_transformer_block(x, params, axis, n_heads, causal=True,
                         layer_norm=None):
    """A full Megatron block: LN -> TP attention -> residual -> LN ->
    TP MLP -> residual, two psums per block total.

    ``params``: ``ln1_scale/ln1_bias/wqkv/wo/bo`` (attention) and
    ``ln2_scale/ln2_bias/w_in/b_in/w_out/b_out`` (MLP; ``b_in`` is
    sharded with ``w_in``'s columns, ``bo``/``b_out`` replicated).
    ``layer_norm`` defaults to the fused kernel.
    """
    if layer_norm is None:
        from chainermn_tpu import ops
        layer_norm = ops.layer_norm
    h = layer_norm(x, params['ln1_scale'], params['ln1_bias'])
    x = x + tp_attention(h, params['wqkv'], params['wo'], axis,
                         n_heads, causal=causal, bo=params['bo'])
    h = layer_norm(x, params['ln2_scale'], params['ln2_bias'])
    return x + tp_mlp(h, params['w_in'], params['b_in'],
                      params['w_out'], params['b_out'], axis,
                      activation=jax.nn.gelu)
