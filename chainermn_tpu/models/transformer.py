"""Decoder-only transformer LM -- the long-context flagship.

Not a reference-parity model (the reference's zoo stops at 2017 CNNs);
this is the workload that exercises the long-context machinery the
reference lacks and SURVEY 5 marks as the design axis: the fused
attention kernel (``ops.flash_attention``) on one chip, ring attention
(``parallel.ring_attention``) when the sequence dim is sharded over a
mesh axis, fused LayerNorm, and fused softmax cross-entropy with a
vocab-sharded-friendly shape.

All matmuls are bfloat16-by-default (MXU-native); accumulation and
softmax bookkeeping stay float32.
"""

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

from chainermn_tpu import ops


class _TpDense(nn.Module):
    """Explicit-shape kernel/bias holder for the tensor-parallel path.

    The tp-local parameter TREE must mirror the unsharded oracle's
    module names (``block_0/qkv/kernel`` ...) so that the GLOBAL
    arrays -- local shapes times the ``model`` axis, reassembled by
    ``shard_map`` out_specs / :func:`tp_param_specs` -- are exactly
    the oracle's parameter tree: init the oracle once, place with the
    tp shardings, and the two models share ONE checkpoint format.
    ``nn.Dense``/``nn.DenseGeneral`` cannot declare the local shapes
    (they re-derive the kernel shape from the input and reject the
    shard), hence this holder."""

    kernel_shape: Tuple[int, ...]
    bias_shape: Optional[Tuple[int, ...]] = None

    @nn.compact
    def __call__(self):
        k = self.param('kernel', nn.initializers.lecun_normal(),
                       self.kernel_shape)
        b = (self.param('bias', nn.initializers.zeros,
                        self.bias_shape)
             if self.bias_shape is not None else None)
        return k, b


class TransformerBlock(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    sequence_axis: Optional[str] = None
    dropout: float = 0.0
    sp_scheme: str = 'ring'  # 'ring' | 'ulysses' (see parallel.sequence)
    tp_axis: Optional[str] = None  # Megatron tensor parallelism

    def _tp_call(self, x):
        """Megatron-sharded block body: heads and MLP columns split
        over ``tp_axis``, one psum per half-block (attention, MLP)
        via the row-parallel exits.  Entries/exits use the
        ``tp_copy``/``tp_reduce`` conjugate pair so gradients taken
        INSIDE ``shard_map`` (the updaters' mode, check_vma=False)
        match the unsharded oracle -- see parallel/tensor.py."""
        from chainermn_tpu.parallel import tensor

        tp = lax.axis_size(self.tp_axis)
        if self.n_heads % tp or self.d_ff % tp:
            raise ValueError(
                'tp_axis=%r of size %d must divide n_heads=%d and '
                'd_ff=%d' % (self.tp_axis, tp, self.n_heads,
                             self.d_ff))
        d_head = self.d_model // self.n_heads
        heads_l = self.n_heads // tp
        d_ff_l = self.d_ff // tp

        ln1_g = self.param('ln1_scale', nn.initializers.ones,
                           (self.d_model,))
        ln1_b = self.param('ln1_bias', nn.initializers.zeros,
                           (self.d_model,))
        h = ops.layer_norm(x, ln1_g, ln1_b).astype(self.dtype)
        h = tensor.tp_copy(h, self.tp_axis)
        wqkv, bqkv = _TpDense((self.d_model, 3, heads_l, d_head),
                              (3, heads_l, d_head), name='qkv')()
        attn = tensor.qkv_attention(
            h, wqkv.astype(self.dtype), causal=True,
            bqkv=bqkv.astype(self.dtype))
        wo, bo = _TpDense((heads_l * d_head, self.d_model),
                          (self.d_model,), name='proj')()
        x = x + tensor.row_parallel_dense(
            attn, wo.astype(self.dtype), self.tp_axis,
            bo.astype(self.dtype), grad_conjugate=True)

        ln2_g = self.param('ln2_scale', nn.initializers.ones,
                           (self.d_model,))
        ln2_b = self.param('ln2_bias', nn.initializers.zeros,
                           (self.d_model,))
        h = ops.layer_norm(x, ln2_g, ln2_b).astype(self.dtype)
        h = tensor.tp_copy(h, self.tp_axis)
        w_in, b_in = _TpDense((self.d_model, d_ff_l), (d_ff_l,),
                              name='ff_in')()
        g = nn.gelu(tensor.column_parallel_dense(
            h, w_in.astype(self.dtype), b_in.astype(self.dtype)))
        w_out, b_out = _TpDense((d_ff_l, self.d_model),
                                (self.d_model,), name='ff_out')()
        return x + tensor.row_parallel_dense(
            g, w_out.astype(self.dtype), self.tp_axis,
            b_out.astype(self.dtype), grad_conjugate=True)

    @nn.compact
    def __call__(self, x, train=False):
        if self.tp_axis is not None:
            if self.sequence_axis is not None:
                raise ValueError('tp_axis and sequence_axis cannot '
                                 'both be set on one block')
            if train and self.dropout > 0:
                raise ValueError('tp_axis blocks run without dropout '
                                 '(per-rank rng divergence would '
                                 'silently break the head groups); '
                                 'build with dropout=0.0')
            return self._tp_call(x)
        d_head = self.d_model // self.n_heads
        ln1_g = self.param('ln1_scale', nn.initializers.ones,
                           (self.d_model,))
        ln1_b = self.param('ln1_bias', nn.initializers.zeros,
                           (self.d_model,))
        h = ops.layer_norm(x, ln1_g, ln1_b).astype(self.dtype)
        qkv = nn.DenseGeneral((3, self.n_heads, d_head), axis=-1,
                              dtype=self.dtype, name='qkv')(h)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.sequence_axis is not None:
            # sequence dim sharded over the mesh axis
            from chainermn_tpu.parallel import (ring_attention,
                                                ulysses_attention)
            if self.sp_scheme not in ('ring', 'ulysses'):
                raise ValueError(
                    "sp_scheme must be 'ring' or 'ulysses', got %r"
                    % (self.sp_scheme,))
            sp = (ulysses_attention if self.sp_scheme == 'ulysses'
                  else ring_attention)
            attn = sp(q, k, v, self.sequence_axis, causal=True)
        else:
            attn = ops.flash_attention(q, k, v, causal=True)
        attn = attn.reshape(attn.shape[:2] + (self.d_model,))
        out = nn.Dense(self.d_model, dtype=self.dtype, name='proj')(attn)
        if train and self.dropout > 0:
            out = nn.Dropout(self.dropout, deterministic=False)(out)
        x = x + out

        ln2_g = self.param('ln2_scale', nn.initializers.ones,
                           (self.d_model,))
        ln2_b = self.param('ln2_bias', nn.initializers.zeros,
                           (self.d_model,))
        h = ops.layer_norm(x, ln2_g, ln2_b).astype(self.dtype)
        h = nn.Dense(self.d_ff, dtype=self.dtype, name='ff_in')(h)
        h = nn.gelu(h)
        h = nn.Dense(self.d_model, dtype=self.dtype, name='ff_out')(h)
        if train and self.dropout > 0:
            h = nn.Dropout(self.dropout, deterministic=False)(h)
        return x + h


class _TpEmbed(nn.Module):
    """Vocab-row-sharded embedding table holder (tp-local shape,
    oracle tree name ``embed/embedding``)."""

    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        return self.param('embedding', nn.initializers.normal(0.02),
                          self.shape)


class TransformerLM(nn.Module):
    """Causal LM.  With ``sequence_axis`` set, call inside
    ``shard_map`` with the token dim sharded over that axis; position
    embeddings are offset by the local shard's global start.

    With ``tp_axis`` set (mutually exclusive with ``sequence_axis``),
    call inside ``shard_map`` over a mesh binding that axis (the
    :class:`chainermn_tpu.parallel.MeshPlan` ``model`` axis):
    attention heads and MLP columns/rows split Megatron-style on the
    axis with one psum per half-block, the embedding table is
    vocab-row-sharded (masked local lookup + psum) and the vocab
    projection is row-parallel over ``d_model``.  The parameter tree
    is EXACTLY the unsharded oracle's -- init the ``tp_axis=None``
    twin and place its params with :func:`tp_param_specs`; activations
    stay replicated over the axis, so the batch shards on ``data``
    only.  Numerically pinned against the oracle in
    ``tests/test_transformer.py`` / ``tests/test_meshplan.py``.
    """

    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 32768
    dtype: Any = jnp.bfloat16
    sequence_axis: Optional[str] = None
    dropout: float = 0.0
    sp_scheme: str = 'ring'  # 'ring' | 'ulysses' (see parallel.sequence)
    tp_axis: Optional[str] = None  # Megatron tensor parallelism

    def _tp_embed(self, tokens):
        """Vocab-row-sharded lookup: each rank owns rows
        ``[r*V/tp, (r+1)*V/tp)``; off-shard tokens contribute zeros
        and ONE psum (``tp_reduce`` -- identity backward, so the local
        table rows receive exactly their own scatter-add gradients)
        completes the lookup."""
        from chainermn_tpu.parallel import tensor

        tp = lax.axis_size(self.tp_axis)
        if self.vocab_size % tp or self.d_model % tp:
            raise ValueError(
                'tp_axis=%r of size %d must divide vocab_size=%d and '
                'd_model=%d' % (self.tp_axis, tp, self.vocab_size,
                                self.d_model))
        v_local = self.vocab_size // tp
        emb = _TpEmbed((v_local, self.d_model), name='embed')()
        local = tokens - lax.axis_index(self.tp_axis) * v_local
        in_shard = (local >= 0) & (local < v_local)
        rows = jnp.take(emb, jnp.clip(local, 0, v_local - 1), axis=0)
        x = jnp.where(in_shard[..., None], rows,
                      jnp.zeros((), rows.dtype)).astype(self.dtype)
        # exact in any dtype: per token exactly one rank is nonzero
        return tensor.tp_reduce(x, self.tp_axis)

    def _tp_head(self, x):
        """Row-parallel vocab projection: ``d_model`` sliced per rank,
        f32 contraction completed by one psum, bias added once after
        (same arithmetic as the oracle's f32 ``lm_head`` Dense up to
        the split-contraction summation order)."""
        from chainermn_tpu.parallel import tensor

        tp = lax.axis_size(self.tp_axis)
        d_local = self.d_model // tp
        kernel, bias = _TpDense((d_local, self.vocab_size),
                                (self.vocab_size,), name='lm_head')()
        xh = tensor.tp_copy(x.astype(self.dtype), self.tp_axis)
        x_local = lax.dynamic_slice_in_dim(
            xh, lax.axis_index(self.tp_axis) * d_local, d_local,
            axis=-1)
        return tensor.row_parallel_dense(
            x_local.astype(jnp.float32), kernel.astype(jnp.float32),
            self.tp_axis, bias, grad_conjugate=True)

    @nn.compact
    def __call__(self, tokens, train=False):
        """tokens (B, T_local) int32 -> logits (B, T_local, V) f32."""
        tp_mode = self.tp_axis is not None
        if tp_mode and self.sequence_axis is not None:
            raise ValueError('tp_axis and sequence_axis cannot both '
                             'be set (compose tp with data/pipeline '
                             'axes via MeshPlan instead)')
        b, t = tokens.shape
        if tp_mode:
            x = self._tp_embed(tokens)
        else:
            x = nn.Embed(self.vocab_size, self.d_model,
                         dtype=self.dtype, name='embed')(tokens)
        pos0 = 0
        if self.sequence_axis is not None:
            pos0 = lax.axis_index(self.sequence_axis) * t
        pos_table = self.param(
            'pos_embed', nn.initializers.normal(0.02),
            (self.max_len, self.d_model))
        pos = lax.dynamic_slice_in_dim(pos_table, pos0, t, 0)
        x = x + pos.astype(self.dtype)
        for i in range(self.n_layers):
            x = TransformerBlock(
                self.d_model, self.n_heads, self.d_ff, self.dtype,
                self.sequence_axis, self.dropout, self.sp_scheme,
                tp_axis=self.tp_axis,
                name=f'block_{i}')(x, train=train)
        gf = self.param('lnf_scale', nn.initializers.ones,
                        (self.d_model,))
        bf = self.param('lnf_bias', nn.initializers.zeros,
                        (self.d_model,))
        x = ops.layer_norm(x, gf, bf)
        if tp_mode:
            return self._tp_head(x)
        logits = nn.Dense(self.vocab_size, dtype=jnp.float32,
                          name='lm_head')(x.astype(self.dtype))
        return logits


    # -- the serving protocol: what GenerationEngine calls on a model --
    # (docs/serving.md).  Thin: the bodies are this module's functions;
    # every step returns ``(logits, cache, counters)`` with ``counters``
    # the model's ``serve_counters``, none here.
    serve_counters = ()

    @nn.nowrap
    def check_serving(self, **asked):
        """Every engine option has a path in this family."""

    @nn.nowrap
    def window_ring(self, page_size):
        """Pages in a window layer's ring: no window layers, 0."""
        return 0

    @nn.nowrap
    def has_state_row(self):
        """Does a sequence hold a fixed-size state row beside its
        pages: no recurrent layers, no."""
        return False

    @nn.nowrap
    def init_kv_cache(self, n_slots, max_len=None, int8_kv=False):
        return init_kv_cache(self, n_slots, max_len, int8_kv=int8_kv)

    @nn.nowrap
    def init_paged_kv_cache(self, n_pages, page_size, int8_kv=False):
        return init_paged_kv_cache(self, n_pages, page_size,
                                   int8_kv=int8_kv)

    @nn.nowrap
    def kv_cache_specs(self, cache, axis='model'):
        return kv_cache_specs(cache, axis)

    @nn.nowrap
    def prefill(self, params, cache, tokens, length, slot):
        return prefill(self, params, cache, tokens, length, slot) + ((),)

    @nn.nowrap
    def decode_step(self, params, cache, tokens, positions, slots=None):
        return decode_step(self, params, cache, tokens, positions,
                           slots=slots) + ((),)

    @nn.nowrap
    def prefill_paged(self, params, cache, tokens, length, page_table,
                      pos0):
        return prefill_paged(self, params, cache, tokens, length,
                             page_table, pos0) + ((),)

    @nn.nowrap
    def decode_step_paged(self, params, cache, tokens, positions,
                          page_tables):
        return decode_step_paged(self, params, cache, tokens, positions,
                                 page_tables) + ((),)

    @nn.nowrap
    def decode_paged_grid(self, cache, lengths, n_full, n_ring=0, tp=1):
        """``(pages read, grid steps)`` of one ``decode_step_paged``
        over rows of these live ``lengths`` (host integers), summed
        over layers, from shapes alone: the engine's ``kv_pages_read``
        / ``kv_grid_steps`` counters.  ``cache`` may be its structs."""
        from chainermn_tpu import ops
        leaf = cache['k'][0]
        ps, heads, lanes = leaf.shape[1:]
        read, steps = ops.decode_paged_grid(
            lengths, (ps, heads // tp, lanes), leaf.dtype, n_full,
            quantized=_cache_int8(cache))
        return self.n_layers * read, self.n_layers * steps

    @nn.nowrap
    def spec_verify(self, params, cache, tokens, positions, slots=None):
        return spec_verify(self, params, cache, tokens, positions,
                           slots=slots)

    @nn.nowrap
    def spec_verify_paged(self, params, cache, tokens, positions,
                          page_tables):
        return spec_verify_paged(self, params, cache, tokens, positions,
                                 page_tables)


def tp_oracle(model):
    """The unsharded twin of a ``tp_axis`` model: same config, same
    parameter tree (init THIS one to get params for either)."""
    return model.clone(tp_axis=None, name=None)


def tp_param_specs(params, axis='model'):
    """``PartitionSpec`` tree for a ``TransformerLM(tp_axis=axis)``
    parameter tree (which IS the unsharded oracle's tree): attention
    heads and MLP columns/rows on ``axis``, embedding rows on the
    vocab dim, ``lm_head`` rows on ``d_model``, everything else
    (layer norms, positional table, post-reduction biases)
    replicated.  Feed to
    :meth:`chainermn_tpu.parallel.MeshPlan.param_shardings` or a
    ``StandardUpdater(param_specs=...)``."""
    from jax.sharding import PartitionSpec as P

    def one(path, leaf):
        names = {str(getattr(k, 'key', k)) for k in path}
        nd = getattr(leaf, 'ndim', 0)
        if 'embedding' in names:
            return P(axis, None)
        if 'qkv' in names:
            return (P(None, None, axis, None) if nd == 4
                    else P(None, axis, None))
        if 'ff_in' in names:
            return P(None, axis) if nd == 2 else P(axis)
        if 'ff_out' in names or 'proj' in names \
                or 'lm_head' in names:
            # row-parallel kernels; their biases ride post-psum,
            # replicated
            return P(axis, None) if nd == 2 else P()
        return P()

    import jax
    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------
# incremental decode: slot-addressed KV cache (ISSUE 11)
#
# Autoregressive serving never re-runs the prompt: the PREFILL pass
# computes the full causal forward once and banks every layer's K/V in
# a cache SLOT; each DECODE step then runs one token per live slot,
# appends its K/V at the slot's position, and attends the single query
# row against the cache (ops.flash_attention_decode -- one HBM pass,
# per-slot dynamic lengths).  The cache is a plain pytree holding ONE
# ARRAY PER LAYER (a tuple per leaf name), so it threads through
# jit/AOT executables, is donatable, and ``cache['k'][layer]`` is a
# pytree index and never an XLA slice: each layer's buffer is the
# executable's own donated parameter, written where it lies by one
# scatter and read whole by the kernel or the context gather (a stacked
# ``(n_layers, ...)`` array made XLA copy the whole pool on every call
# on the chip: PERF.md, PR 26).  Its last axis is d_head PADDED TO THE
# 128 LANES of a TPU tile (_LANES): the decode kernel's page tile
# occupies whole lanes whatever d_head is, and only an array whose
# minor axis fills them lies row-major on the chip by default -- a
# (pages, 16, 16, 64) leaf lies page-minor there, and every executable
# copied the whole pool into the kernel's layout and back.  The pad
# lanes hold zeros and the queries' pad lanes are zero, so no product
# changes.  It shards over a MeshPlan 'model' axis on its HEAD dim
# exactly like the attention weights (kv_cache_specs).
#
# These are module-level functions doing the SAME arithmetic as
# TransformerLM.__call__ over the SAME parameter tree (the
# pipeline_parts idiom): the flax module stays the single source of
# the parameters, and the parity pins in tests/test_transformer.py
# hold the two paths together (f32 rtol 1e-5, bf16/int8-KV 5e-2).
# The forward-only layer is written ONCE (_layer); the six entry
# points -- decode_step, decode_step_paged, prefill, prefill_paged,
# spec_verify, spec_verify_paged -- differ in their ``attend`` closure
# and in how they fetch their position rows, and in nothing else.

#: lanes of a TPU vector tile: the cache's head dim is padded to them
_LANES = 128


def _zero_cache(model, lead, dtype, tp, int8_kv):
    """The cache of both addressings: per leaf name ``n_layers``
    SEPARATE zeroed arrays ``(*lead, H_local, lanes)``, ``lanes`` being
    ``d_head`` rounded up to :data:`_LANES` (scales: ``(*lead,
    H_local)``)."""
    if model.n_heads % tp:
        raise ValueError('tp=%d must divide n_heads=%d'
                         % (tp, model.n_heads))
    d_head = model.d_model // model.n_heads
    shape = tuple(int(n) for n in lead) + (
        model.n_heads // tp, d_head + -d_head % _LANES)

    def leaves(shape, dtype):
        return tuple(jnp.zeros(shape, dtype)
                     for _ in range(model.n_layers))

    if int8_kv:
        return {'k': leaves(shape, jnp.int8),
                'v': leaves(shape, jnp.int8),
                'k_scale': leaves(shape[:-1], jnp.float32),
                'v_scale': leaves(shape[:-1], jnp.float32)}
    dtype = dtype or model.dtype
    return {'k': leaves(shape, dtype), 'v': leaves(shape, dtype)}


def init_kv_cache(model, n_slots, max_len=None, dtype=None, tp=1,
                  int8_kv=False):
    """Zeroed slot-addressed KV cache for ``model``.

    Layout: ``{'k'|'v': n_layers x (n_slots, S, H_local, lanes)}`` --
    a tuple with one array per layer -- with ``S = max_len or
    model.max_len``, ``H_local = n_heads / tp`` (pass the mesh's
    model-axis size as ``tp`` when the cache lives sharded inside
    ``shard_map``) and ``lanes`` = ``d_head`` rounded up to 128, the
    pad zero (why: the comment above).  ``int8_kv=True`` adds
    ``'k_scale'``/``'v_scale'``
    ``n_layers x (n_slots, S, H_local)`` f32 trees and stores k/v as
    int8 (:func:`chainermn_tpu.precision.quantize_kv` at write time)
    -- half the decode-bound HBM bytes of bf16.  Slots are REUSED
    without zeroing: reads mask by the live length, so a previous
    occupant's stale rows are never attended.
    """
    return _zero_cache(model, (n_slots, max_len or model.max_len),
                       dtype, tp, int8_kv)


def init_paged_kv_cache(model, n_pages, page_size, dtype=None, tp=1,
                        int8_kv=False):
    """Zeroed PAGED KV cache: a fixed pool of ``n_pages`` pages of
    ``page_size`` token positions each, shared by every sequence.

    Layout: ``{'k'|'v': n_layers x (n_pages, page_size, H_local,
    lanes)}`` (+ ``'k_scale'``/``'v_scale'`` ``n_layers x (n_pages,
    page_size, H_local)`` f32 under ``int8_kv``) -- the slot cache's
    layout with the ``(n_slots, S)`` slab axes re-cut into
    ``(n_pages, page_size)``, so :func:`kv_cache_specs` shards it
    unchanged (head axis over ``tp``).  Sequences address the pool
    through per-sequence page tables (:func:`decode_step_paged` /
    :func:`prefill_paged`); refcounting, prefix sharing and
    copy-on-write live host-side in
    :mod:`chainermn_tpu.serving.paged`.  By convention page 0 is the
    allocator's SCRATCH page: pad rows write there and no live table
    ever points at it, so garbage writes are structurally harmless.
    Pages are reused without zeroing -- reads mask by live length.
    """
    return _zero_cache(model, (n_pages, page_size), dtype, tp,
                       int8_kv)


def kv_cache_specs(cache, axis='model'):
    """``PartitionSpec`` tree for a cache under tensor parallelism:
    the head dim shards with the attention heads, everything else
    replicated (slots are NOT data-sharded -- continuous batching
    refills them independently of the mesh)."""
    import jax
    from jax.sharding import PartitionSpec as P

    def one(leaf):
        if leaf.ndim == 4:                      # k / v
            return P(None, None, axis, None)
        return P(None, None, axis)              # scales
    return jax.tree_util.tree_map(one, cache)


def _cache_int8(cache):
    return 'k_scale' in cache


def _dense(x, p, dtype):
    """``nn.Dense`` twin: promote input/kernel/bias to ``dtype``."""
    return (x.astype(dtype) @ p['kernel'].astype(dtype)
            + p['bias'].astype(dtype))


def _qkv_proj(h, bp, dtype):
    """``nn.DenseGeneral((3, H, d_head), axis=-1)`` twin over (..., d)
    activations: returns (..., 3, H, d_head)."""
    w = bp['qkv']['kernel'].astype(dtype)
    b = bp['qkv']['bias'].astype(dtype)
    return jnp.einsum('...d,dchf->...chf', h.astype(dtype), w) + b


def _pad_last(x, width):
    """``x`` with zeros appended on its last axis up to ``width``
    (k, v and q up to the cache's lanes; a scale is there already)."""
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(
        x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def _update_kv(cache, layer, k_new, v_new, put):
    """``cache`` with ``layer``'s leaves rewritten by ``put(leaf,
    value) -> leaf``: the one place a cache leaf is written.  Each
    leaf gets exactly one write per traced call, so XLA updates the
    donated buffer where it lies.  An int8 cache stores the quantized
    values and their scales (:func:`~chainermn_tpu.precision.
    quantize_kv`); a float cache the values in its own dtype."""
    from chainermn_tpu.precision import quantize_kv

    out = dict(cache)

    def write(name, val):
        leaves = cache[name]
        leaf = leaves[layer]
        leaf = put(leaf, _pad_last(val, leaf.shape[-1]).astype(
            leaf.dtype))
        out[name] = leaves[:layer] + (leaf,) + leaves[layer + 1:]

    for name, val in (('k', k_new), ('v', v_new)):
        if _cache_int8(cache):
            val, scale = quantize_kv(val)
            write(name + '_scale', scale)
        write(name, val)
    return out


def _scatter_kv(cache, layer, k_new, v_new, idx):
    """Write ``k_new``/``v_new`` at ``layer``'s ``[idx]``: ``idx`` is
    the pair of index arrays addressing the leaf's two leading axes
    (slot, position) or (page, offset)."""
    return _update_kv(cache, layer, k_new, v_new,
                      lambda leaf, val: leaf.at[idx].set(val))


def _layer_kv(cache, layer, rows=lambda leaf: leaf, d_head=None):
    """The attention operands of one layer, each leaf through
    ``rows`` (identity: the layer's buffer as it lies): positional
    ``(k, v)`` and the ``k_scale``/``v_scale`` keywords of an int8
    cache.  ``d_head`` cuts the pad lanes off k and v, for a reader
    that gathered its rows; the decode kernels take the padded
    buffers whole (:func:`_decode_attend`)."""
    kv = {name: rows(leaves[layer]) for name, leaves in cache.items()}
    k, v = kv.pop('k'), kv.pop('v')
    if d_head is not None:
        k, v = k[..., :d_head], v[..., :d_head]
    return (k, v), kv


def _decode_attend(kernel, cache, layer, q, *operands,
                   rows=lambda leaf: leaf):
    """``kernel(q, k, v, *operands)`` over ``layer``'s lane-padded
    buffers: the query is padded with zero lanes to match (every
    product with a pad lane is zero), the softmax scale stays that of
    the true ``d_head``, and the output is cut back to it."""
    d_head = q.shape[-1]
    (k, v), scales = _layer_kv(cache, layer, rows)
    out = kernel(_pad_last(q, k.shape[-1]), k, v, *operands,
                 scale=d_head ** -0.5, **scales)
    return out[..., :d_head]


def _attend_cache(cache, layer, q, slots, lengths):
    """One decode-attention read: row i's query against its slot's
    cache prefix.  With ``slots=None`` (full-slot decode bucket) the
    layer's buffer is the kernel's operand as it lies (the jaxpr pin
    in tests/test_transformer.py; what the chip's compiler makes of
    it is chip_smoke.py's check); a compacted bucket gathers its rows
    first (one extra pass -- the cost of running a smaller executable,
    documented in docs/serving.md)."""
    from chainermn_tpu import ops

    def rows(leaf):
        return leaf if slots is None else jnp.take(
            leaf, slots.astype(jnp.int32), axis=0)

    return _decode_attend(ops.flash_attention_decode, cache, layer, q,
                          lengths, rows=rows)


def _embed(model, params, tokens):
    """Token rows in the model's dtype, the forward-only twin of
    ``nn.Embed`` / ``TransformerLM._tp_embed``: under ``tp_axis`` the
    masked local lookup + one psum.  Each entry point adds its own
    position rows (a ``take``, a static slice, a ``dynamic_slice``)."""
    emb = params['embed']['embedding']
    if model.tp_axis is not None:
        v_local = model.vocab_size // lax.axis_size(model.tp_axis)
        local = tokens - lax.axis_index(model.tp_axis) * v_local
        in_shard = (local >= 0) & (local < v_local)
        rows = jnp.take(emb, jnp.clip(local, 0, v_local - 1), axis=0)
        x = jnp.where(in_shard[..., None], rows,
                      jnp.zeros((), rows.dtype)).astype(model.dtype)
        return lax.psum(x, model.tp_axis)
    return jnp.take(emb, tokens, axis=0).astype(model.dtype)


def _head_logits(model, params, x):
    """The lm head on (..., d_model) activations -- non-tp
    ``nn.Dense(vocab, dtype=f32)`` twin or the row-parallel tp form
    (one psum), matching ``TransformerLM._tp_head``."""
    from chainermn_tpu.parallel import tensor

    if model.tp_axis is None:
        return _dense(x.astype(model.dtype), params['lm_head'],
                      jnp.float32)
    tp = lax.axis_size(model.tp_axis)
    d_local = model.d_model // tp
    xh = x.astype(model.dtype)
    x_local = lax.dynamic_slice_in_dim(
        xh, lax.axis_index(model.tp_axis) * d_local, d_local, axis=-1)
    return tensor.row_parallel_dense(
        x_local.astype(jnp.float32),
        params['lm_head']['kernel'].astype(jnp.float32),
        model.tp_axis, params['lm_head']['bias'])


def _logits(model, params, x):
    """Final norm + lm head on (..., d_model) activations."""
    x = ops.layer_norm(x, params['lnf_scale'], params['lnf_bias'])
    return _head_logits(model, params, x)


def _last_logits(model, params, x, length):
    """A prefill's answer: the logits at row ``length - 1`` of the one
    prompt in ``x`` (1, T, d).  The head only needs the LAST VALID
    position's activation -- a (1, d) slice instead of a (T, vocab)
    logits block."""
    x_last = lax.dynamic_slice_in_dim(
        x[0], jnp.asarray(length, jnp.int32) - 1, 1, axis=0)
    return _logits(model, params, x_last)[0]


def _proj(model, bp, attn):
    """The attention output projection on flattened heads."""
    from chainermn_tpu.parallel import tensor

    dtype = model.dtype
    if model.tp_axis is not None:
        return tensor.row_parallel_dense(
            attn, bp['proj']['kernel'].astype(dtype), model.tp_axis,
            bp['proj']['bias'].astype(dtype))
    return _dense(attn, bp['proj'], dtype)


def _mlp(model, bp, h):
    """The feed-forward: ``ff_out(gelu(ff_in(h)))``."""
    from chainermn_tpu.parallel import tensor

    dtype = model.dtype
    if model.tp_axis is not None:
        g = nn.gelu(tensor.column_parallel_dense(
            h, bp['ff_in']['kernel'].astype(dtype),
            bp['ff_in']['bias'].astype(dtype)))
        return tensor.row_parallel_dense(
            g, bp['ff_out']['kernel'].astype(dtype), model.tp_axis,
            bp['ff_out']['bias'].astype(dtype))
    return _dense(nn.gelu(_dense(h, bp['ff_in'], dtype)), bp['ff_out'],
                  dtype)


def _layer(model, bp, x, cache, layer, attend):
    """One forward-only layer on ``x`` (..., d): norm -> qkv ->
    ``attend`` -> proj residual -> norm -> MLP residual.
    ``attend(cache, layer, q, k, v) -> (attn, cache)``, with q / k / v
    (..., H, d_head), is ALL that differs between the six entry points
    below: where this call's K/V are written and what the queries read
    -- a cache mode is a storage indirection, never a model change
    (``AfmoeLM._layer`` has the same contract)."""
    dtype = model.dtype
    h = ops.layer_norm(x, bp['ln1_scale'], bp['ln1_bias']).astype(dtype)
    qkv = _qkv_proj(h, bp, dtype)                  # (..., 3, H, d_head)
    q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
    attn, cache = attend(cache, layer, q, k, v)
    x = x + _proj(model, bp, attn.reshape(x.shape[:-1] + (-1,)))
    h = ops.layer_norm(x, bp['ln2_scale'], bp['ln2_bias']).astype(dtype)
    return x + _mlp(model, bp, h), cache


def _layers(model, params, x, cache, attend):
    """Every layer in turn over the one cache."""
    for i in range(model.n_layers):
        x, cache = _layer(model, params['block_%d' % i], x, cache, i,
                          attend)
    return x, cache


def _step(model, params, cache, tokens, positions, attend):
    """Decode and verify: ``tokens`` (...) int32 at absolute
    ``positions`` (...) -> ``(logits (..., vocab) f32 at every one of
    them, new_cache)``."""
    x = _embed(model, params, tokens) + jnp.take(
        params['pos_embed'], positions, axis=0).astype(model.dtype)
    x, cache = _layers(model, params, x, cache, attend)
    return _logits(model, params, x), cache


def decode_step(model, params, cache, tokens, positions, slots=None):
    """One incremental decode step: ``tokens`` (N,) int32 -- the last
    sampled token per row -- at ``positions`` (N,) int32 (0-based;
    this token's K/V lands there and attention covers
    ``positions + 1`` cache entries).  ``slots`` (N,) int32 maps rows
    to cache slots for a compacted active-slot bucket; ``None`` (the
    full bucket) requires ``N == n_slots`` and reads the cache in
    place.  Returns ``(logits (N, vocab) f32, new_cache)``.

    Works under ``tp_axis`` inside ``shard_map`` exactly like
    ``__call__`` (heads and cache sharded over the axis, one psum per
    half-block); parity vs the full-sequence causal forward is pinned
    in tests/test_transformer.py, including across slot refills.
    """
    n = tokens.shape[0]
    if slots is None and n != cache['k'][0].shape[0]:
        raise ValueError(
            'full-bucket decode needs one row per cache slot '
            '(%d rows vs %d slots); pass slots= for a compacted '
            'bucket' % (n, cache['k'][0].shape[0]))
    lengths = positions.astype(jnp.int32) + 1
    idx_slots = (jnp.arange(n) if slots is None
                 else slots.astype(jnp.int32))

    def attend(cache, layer, q, k, v):
        cache = _scatter_kv(cache, layer, k, v, (idx_slots, positions))
        return _attend_cache(cache, layer, q, slots, lengths), cache

    return _step(model, params, cache, tokens, positions, attend)


def decode_step_paged(model, params, cache, tokens, positions,
                      page_tables):
    """One incremental decode step against a PAGED cache
    (:func:`init_paged_kv_cache`): ``tokens``/``positions`` (N,) int32
    as in :func:`decode_step`, plus ``page_tables`` (N, n_max) int32
    mapping each row's token position ``p`` to pool page
    ``page_tables[i, p // page_size]``, offset ``p % page_size``.

    The table entry covering ``positions[i]`` must already be
    allocated (the serving scheduler appends a page BEFORE the tick
    that crosses a page boundary); entries beyond the live prefix are
    never read, so idle rows can point at the allocator's scratch
    page.  Arithmetic is identical to :func:`decode_step` -- parity
    (including under ``tp_axis`` and int8 KV) is pinned in
    tests/test_transformer.py.
    """
    ps = cache['k'][0].shape[1]
    positions = positions.astype(jnp.int32)
    lengths = positions + 1
    n = tokens.shape[0]
    pages = page_tables[jnp.arange(n), positions // ps]
    offsets = positions % ps

    def attend(cache, layer, q, k, v):
        cache = _scatter_kv(cache, layer, k, v, (pages, offsets))
        return _decode_attend(ops.flash_attention_decode_paged, cache,
                              layer, q, page_tables, lengths), cache

    return _step(model, params, cache, tokens, positions, attend)


def prefill(model, params, cache, tokens, length, slot):
    """Prefill one prompt into cache slot ``slot``: ``tokens``
    (1, T) int32 padded to a prompt bucket, ``length`` scalar int32
    (valid prefix; positions beyond it are written but never attended
    -- decode lengths start at ``length``).  Runs the full causal
    forward ONCE (the compute-bound regime: whole-prompt matmuls
    through the fused flash kernel), banks every layer's K/V at
    every layer's ``[slot, :T]``, and returns ``(logits (vocab,) f32 at
    position length-1, new_cache)`` -- the distribution the first
    generated token is sampled from."""
    b, t = tokens.shape
    if b != 1:
        raise ValueError('prefill takes one prompt per call, got '
                         'batch %d (prompt-length bucketing would be '
                         'meaningless across a batch)' % b)
    x = _embed(model, params, tokens) + params['pos_embed'][:t].astype(
        model.dtype)
    slot = jnp.asarray(slot, jnp.int32)

    def bank(leaf, val):
        return lax.dynamic_update_slice(
            leaf, val[None], (slot,) + (0,) * (leaf.ndim - 1))

    def attend(cache, layer, q, k, v):
        # the fresh K/V are attended as computed, then banked
        attn = ops.flash_attention(q, k, v, causal=True)
        return attn, _update_kv(cache, layer, k[0], v[0], bank)

    x, cache = _layers(model, params, x, cache, attend)
    return _last_logits(model, params, x, length), cache


def prefill_paged(model, params, cache, tokens, length, page_table,
                  pos0):
    """Prefill ONE CHUNK of a prompt into a paged cache
    (:func:`init_paged_kv_cache`): ``tokens`` (1, C) int32 -- the
    chunk, padded to a fixed width; ``length`` scalar int32 (valid
    chunk prefix); ``page_table`` (n_max,) int32 -- the sequence's
    pages; ``pos0`` scalar int32 -- the running absolute position
    (tokens already banked by earlier chunks).  Returns
    ``(logits (vocab,) f32 at chunk position length-1, new_cache)``.

    This is the chunked-prefill (SARATHI-style) building block: the
    scheduler interleaves these fixed-cost calls with decode ticks so
    a long prompt never freezes inter-token latency.  Each chunk's
    K/V is scattered into its pages (pad rows land on the scratch
    page 0); attention is :func:`~chainermn_tpu.ops.
    flash_attention_chunk` -- causal within the chunk plus the banked
    context masked at ``pos0`` -- so a whole-prompt call
    (``pos0 == 0``) computes bitwise the same causal forward as the
    slot :func:`prefill`.  int8 KV: the chunk half attends the fresh
    float K/V exactly like the slot prefill; only the banked context
    is dequantized.  Table entries covering ``[pos0, pos0+length)``
    must be allocated; nothing before ``pos0`` is written (shared
    prefix pages stay read-only -- the copy-on-write contract in
    ``docs/serving.md``).
    """
    b, c = tokens.shape
    if b != 1:
        raise ValueError('prefill_paged takes one prompt chunk per '
                         'call, got batch %d' % b)
    n_max = page_table.shape[0]
    ps = cache['k'][0].shape[1]
    pos0 = jnp.asarray(pos0, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    x = _embed(model, params, tokens) + lax.dynamic_slice_in_dim(
        params['pos_embed'], pos0, c, axis=0).astype(model.dtype)

    # chunk-row -> (page, offset): pad rows (t >= length) go to the
    # scratch page so the scatter never touches a live table entry
    t = jnp.arange(c, dtype=jnp.int32)
    p_abs = pos0 + t
    page_idx = jnp.clip(p_abs // ps, 0, n_max - 1)
    pages = jnp.where(t < length, page_table[page_idx].astype(
        jnp.int32), 0)
    offsets = p_abs % ps
    ctx_len = pos0[None]                               # (B=1,)

    def gather(leaf):
        g = jnp.take(leaf, page_table.astype(jnp.int32), axis=0)
        return g.reshape((1, n_max * ps) + g.shape[2:])

    def attend(cache, layer, q, k, v):
        cache = _scatter_kv(cache, layer, k[0], v[0], (pages, offsets))
        (k_ctx, v_ctx), scales = _layer_kv(cache, layer, gather,
                                           q.shape[-1])
        return ops.flash_attention_chunk(q, k, v, k_ctx, v_ctx,
                                         ctx_len, **scales), cache

    x, cache = _layers(model, params, x, cache, attend)
    return _last_logits(model, params, x, length), cache


def _roundtrip_kv(cache, k_new, v_new):
    """What the oracle's NEXT decode step would read back for the
    window's freshly written K/V: the cache-dtype cast (float caches)
    or the int8 quantize->dequantize roundtrip.  Feeding these -- not
    the raw float values -- as the chunk kernel's fresh half is what
    makes speculative verify argmax-equal to the sequential decode
    loop in every KV mode."""
    from chainermn_tpu.precision import dequantize_kv, quantize_kv
    if _cache_int8(cache):
        return (dequantize_kv(*quantize_kv(k_new)),
                dequantize_kv(*quantize_kv(v_new)))
    dt = cache['k'][0].dtype
    return k_new.astype(dt), v_new.astype(dt)


def _verify_attend(cache, layer, q, k_new, v_new, rows, positions):
    """One verify read: the window's queries (N, K, H, d_head) against
    its own roundtripped K/V (window-causal) and each row's banked
    context -- ``layer``'s leaves through ``rows`` -- masked at
    ``positions``."""
    k_att, v_att = _roundtrip_kv(cache, k_new, v_new)
    (k_ctx, v_ctx), scales = _layer_kv(cache, layer, rows,
                                       q.shape[-1])
    return ops.flash_attention_chunk(
        q, k_att, v_att, k_ctx, v_ctx, positions, **scales)


def spec_verify(model, params, cache, tokens, positions, slots=None):
    """Speculative-decoding verify pass: score K consecutive proposed
    tokens per row in ONE executable.  ``tokens`` (N, K) int32 -- row
    i's window ``[last committed token, draft_1, ..., draft_{K-1}]``
    written at absolute positions ``positions[i] + [0, K)``;
    ``positions`` (N,) int32; ``slots`` as in :func:`decode_step`
    (``None`` = full bucket, one row per slot).  Returns ``(logits
    (N, K, vocab) f32, new_cache)`` where ``logits[i, j]`` is the
    target's next-token distribution GIVEN the window prefix through
    ``tokens[i, j]`` -- row j's argmax verifies draft j+1 and row
    K-1's argmax is the bonus/correction token.

    Column 0 computes exactly what :func:`decode_step` would for
    ``tokens[:, 0]``, and inductively every accepted column matches
    the sequential decode loop -- attention is
    :func:`~chainermn_tpu.ops.flash_attention_chunk` (the chunked-
    prefill kernel: window-causal fresh half + banked context masked
    at ``positions``), with the fresh half fed the cache-roundtripped
    K/V so int8-KV verify attends the same dequantized values the
    oracle reads back.  Window entries at/beyond the cache depth are
    dropped by the scatter and never committed by the scheduler, so a
    window overhanging ``max_len`` is harmless.  Rollback after the
    accept-prefix decision is a position rewind: rejected columns'
    K/V (and int8 scales) stay as masked garbage, exactly like a
    reused slot."""

    n, kk = tokens.shape
    if slots is None and n != cache['k'][0].shape[0]:
        raise ValueError(
            'full-bucket verify needs one row per cache slot '
            '(%d rows vs %d slots); pass slots= for a compacted '
            'bucket' % (n, cache['k'][0].shape[0]))
    positions = positions.astype(jnp.int32)
    window = positions[:, None] + jnp.arange(kk, dtype=jnp.int32)
    idx_slots = (jnp.arange(n) if slots is None
                 else slots.astype(jnp.int32))

    def rows(leaf):
        return leaf if slots is None else jnp.take(leaf, idx_slots,
                                                   axis=0)

    def attend(cache, layer, q, k, v):
        cache = _scatter_kv(cache, layer, k, v,
                            (idx_slots[:, None], window))
        return _verify_attend(cache, layer, q, k, v, rows,
                              positions), cache

    return _step(model, params, cache, tokens, window, attend)


def spec_verify_paged(model, params, cache, tokens, positions,
                      page_tables):
    """:func:`spec_verify` against a PAGED cache: ``page_tables``
    (N, n_max) int32 as in :func:`decode_step_paged`; table entries
    covering ``[positions[i], positions[i] + K)`` must be allocated
    by the scheduler (the speculative page-growth step), and window
    rows past the pool's addressable range are routed to the scratch
    page like chunked-prefill pad rows.  Context is gathered through
    the page table (:func:`prefill_paged`'s read pattern) and masked
    at ``positions``; arithmetic is otherwise identical to the slab
    verify -- paging stays a storage indirection."""
    n, kk = tokens.shape
    n_max = page_tables.shape[1]
    ps = cache['k'][0].shape[1]
    positions = positions.astype(jnp.int32)
    window = positions[:, None] + jnp.arange(kk, dtype=jnp.int32)
    page_idx = jnp.clip(window // ps, 0, n_max - 1)
    pages = jnp.where(
        window < n_max * ps,
        jnp.take_along_axis(page_tables.astype(jnp.int32), page_idx,
                            axis=1), 0)                      # (N, K)
    offsets = window % ps

    def gather(leaf):
        g = jnp.take(leaf, page_tables.astype(jnp.int32), axis=0)
        return g.reshape((n, n_max * ps) + g.shape[3:])

    def attend(cache, layer, q, k, v):
        cache = _scatter_kv(cache, layer, k, v, (pages, offsets))
        return _verify_attend(cache, layer, q, k, v, gather,
                              positions), cache

    return _step(model, params, cache, tokens, window, attend)


def pipeline_parts(model, params, n_stages, pad_id=-1, tp_axis=None,
                   local_loss=False):
    """Split a ``TransformerLM`` parameter tree into
    :class:`~chainermn_tpu.training.PipelineUpdater` /
    :class:`~chainermn_tpu.training.MeshPipelineUpdater` pieces.

    Returns ``(stage_fn, prologue, loss_on_last, params_stacked,
    extra)``: the block stack becomes the stage-sharded body
    (``n_layers`` must divide into ``n_stages`` even groups) while
    embedding/positional table/final norm/head become the replicated
    ``extra`` tree.  The pipelined composition computes EXACTLY
    ``model.apply`` + :func:`lm_loss` with the same parameters and the
    same fused kernels -- a model trained unpipelined can be resumed
    pipelined and vice versa
    (``tests/test_pipeline_training.py::test_transformer_pipeline_parts``).

    ``model`` must have ``sequence_axis=None`` (pipeline shards the
    batch, not the sequence), ``tp_axis=None`` (the params tree IS
    the unsharded oracle's) and is used with ``train=False``
    semantics (no dropout).

    ``tp_axis`` (e.g. a 3-D plan's ``model`` axis) makes the STAGE
    BODY tensor-parallel: each stage's blocks run the Megatron
    ``_tp_call`` path (heads / MLP columns+rows split over the axis,
    conjugate custom-vjp psums -- exact under 1F1B's per-device
    backward), while the embedding/head ``extra`` ends stay
    replicated and collective-free.  Shard the stacked stage tree
    with :func:`pipeline_stage_specs`.

    ``local_loss=True`` returns a collective-free ``loss_on_last``
    (the 1F1B requirement: its vjp is taken per device): a LOCAL
    masked mean, exact vs :func:`lm_loss` whenever every data shard
    carries the same valid-token count -- always true at
    ``pad_id=-1`` (no padding); unevenly padded shards need the
    default GLOBAL form, whose data-axis psums require the gpipe
    schedule.
    """
    if model.sequence_axis is not None:
        raise ValueError('pipeline_parts shards the batch dimension; '
                         'build the model with sequence_axis=None')
    if model.tp_axis is not None:
        raise ValueError('pipeline_parts expects the unsharded block '
                         'body; build the model with tp_axis=None '
                         '(stage-internal tensor parallelism is the '
                         'tp_axis= argument HERE, over the oracle '
                         'parameter tree)')
    if model.dropout:
        raise ValueError('pipeline_parts runs the blocks without '
                         'dropout rngs; build the model with '
                         'dropout=0.0 (training would otherwise '
                         'silently drop the regularization the '
                         'unpipelined run applies)')
    if model.n_layers % n_stages:
        raise ValueError('%d layers do not split into %d stages'
                         % (model.n_layers, n_stages))
    import jax
    from chainermn_tpu.parallel.pipeline import stack_stage_params

    n_per = model.n_layers // n_stages
    block = TransformerBlock(model.d_model, model.n_heads, model.d_ff,
                             model.dtype, tp_axis=tp_axis)
    layer_trees = [params['block_%d' % i]
                   for i in range(model.n_layers)]
    per_stage = [stack_stage_params(layer_trees[s * n_per:
                                                (s + 1) * n_per])
                 for s in range(n_stages)]
    params_stacked = stack_stage_params(per_stage)
    extra = {'embedding': params['embed']['embedding'],
             'pos_embed': params['pos_embed'],
             'lnf_scale': params['lnf_scale'],
             'lnf_bias': params['lnf_bias'],
             'lm_head': params['lm_head']}

    def stage_fn(p_stage, x):
        for j in range(n_per):
            bp = jax.tree_util.tree_map(lambda a: a[j], p_stage)
            x = block.apply({'params': bp}, x)
        return x

    def prologue(e, tokens):
        # nn.Embed(dtype=model.dtype) lookup + position slice, as in
        # TransformerLM.__call__ with pos0 = 0
        x = jnp.take(e['embedding'], tokens, axis=0).astype(model.dtype)
        pos = e['pos_embed'][:tokens.shape[1]]
        return x + pos.astype(model.dtype)

    def masked_ce(e, outs, y_micro):
        h = ops.layer_norm(outs, e['lnf_scale'],
                           e['lnf_bias']).astype(model.dtype)
        logits = (h.astype(jnp.float32)
                  @ e['lm_head']['kernel'].astype(jnp.float32)
                  + e['lm_head']['bias'])
        v = logits.shape[-1]
        flat = logits.reshape(-1, v)
        yy = y_micro.reshape(-1).astype(jnp.int32)
        ce = ops.softmax_cross_entropy(flat, yy)
        mask = (yy != pad_id).astype(jnp.float32)
        return jnp.sum(ce * mask), jnp.sum(mask)

    def loss_on_last(e, outs, y_micro):
        from chainermn_tpu.training.pipeline_updater import AXIS_DATA
        total, n = masked_ce(e, outs, y_micro)
        # GLOBAL masked mean: sums psum'd over the data axis BEFORE
        # dividing, so unevenly padded shards weight each token
        # equally -- exactly lm_loss's reduction (a per-shard mean
        # pmean'd by the updater would weight a lightly-padded
        # shard's tokens less)
        total = lax.psum(total, AXIS_DATA)
        n = jnp.maximum(lax.psum(n, AXIS_DATA), 1.0)
        loss = total / n
        return loss, {'perp': jnp.exp(jnp.minimum(loss, 20.0))}

    def local_loss_on_last(e, outs, y_micro):
        # LOCAL masked mean (collective-free; see docstring): the
        # updater's last-stage data-mean completes the global mean
        # when shards hold equal valid-token counts
        total, n = masked_ce(e, outs, y_micro)
        loss = total / jnp.maximum(n, 1.0)
        return loss, {'perp': jnp.exp(jnp.minimum(loss, 20.0))}

    return (stage_fn, prologue,
            local_loss_on_last if local_loss else loss_on_last,
            params_stacked, extra)


def pipeline_stage_specs(params_stacked, pipe_axis='pipe',
                         tp_axis=None):
    """``PartitionSpec`` tree for a :func:`pipeline_parts` stacked
    stage tree: every leaf leads with ``pipe_axis`` (each stage's
    weights live on its pipe coordinate -- the
    :meth:`chainermn_tpu.parallel.MeshPlan.stage_specs` placement),
    and with ``tp_axis`` set the Megatron dims shard exactly as
    :func:`tp_param_specs` does for the unstacked tree -- attention
    heads and MLP columns on the axis, row-parallel kernels on their
    input dim, layer norms and post-psum biases replicated (per
    stage).  Leaves carry TWO leading stacking dims
    ``(n_stages, layers_per_stage)`` ahead of the block dims."""
    from jax.sharding import PartitionSpec as P

    def one(path, leaf):
        names = {str(getattr(k, 'key', k)) for k in path}
        nd = getattr(leaf, 'ndim', 0)
        if tp_axis is None:
            return P(pipe_axis)
        if 'qkv' in names:
            # kernel (S, L, d, 3, H, d_head) / bias (S, L, 3, H, d_head)
            return (P(pipe_axis, None, None, None, tp_axis, None)
                    if nd == 6
                    else P(pipe_axis, None, None, tp_axis, None))
        if 'ff_in' in names:
            # kernel (S, L, d, ff) / bias (S, L, ff): column-parallel
            return (P(pipe_axis, None, None, tp_axis) if nd == 4
                    else P(pipe_axis, None, tp_axis))
        if ('ff_out' in names or 'proj' in names) and nd == 4:
            # row-parallel kernels (S, L, in, d): input dim sharded
            return P(pipe_axis, None, tp_axis, None)
        # layer norms, post-psum biases: stage-stacked, tp-replicated
        return P(pipe_axis)

    import jax
    return jax.tree_util.tree_map_with_path(one, params_stacked)


def lm_loss_sum(apply_fn, pad_id=-1):
    """Next-token loss in sum/count form: returns
    ``((loss_sum, token_count), aux)``.

    For sequence-parallel training with a REAL ``pad_id``: feed this
    to ``mapped_global_loss(..., token_weighted=True)`` so the global
    loss is ``psum(sum)/psum(count)`` -- exact under uneven padding
    across shards, where pmean-of-local-means is Jensen-weighted and
    silently wrong (ADVICE r3).  :func:`lm_loss` is the mean form of
    this same computation."""

    def loss_fn(params, tokens, targets):
        logits = apply_fn(params, tokens)
        b, t, v = logits.shape
        ce = ops.softmax_cross_entropy(
            logits.reshape(b * t, v), targets.reshape(b * t).astype(
                jnp.int32))
        mask = (targets.reshape(b * t) != pad_id).astype(jnp.float32)
        return (jnp.sum(ce * mask), jnp.sum(mask)), {}

    return loss_fn


def lm_loss(apply_fn, pad_id=-1):
    """Next-token loss over (tokens, targets); fused cross-entropy.

    ``pad_id`` target positions are masked out (use -1 when every
    position is real)."""
    sum_fn = lm_loss_sum(apply_fn, pad_id)

    def loss_fn(params, tokens, targets):
        (total, n), _ = sum_fn(params, tokens, targets)
        loss = total / jnp.maximum(n, 1.0)
        return loss, {'perp': jnp.exp(jnp.minimum(loss, 20.0))}

    return loss_fn
