"""The ``xing4`` family of decoder LMs (``model_type`` ``xing4_0``):
latent attention under a four-stream residual path, beside sigmoid-
routed experts.

What a layer computes (``docs/serving.md`` has it beside the cache):

*Residual path* (manifold-constrained hyper-connections).  The state is
``hc_mult`` streams a token, ``X`` (n, d).  Around EACH of the layer's
two sub-layers ``F`` (attention, feed-forward) the per-token
coefficients of ``ops.mhc_coefficients`` read ``u = H_pre X``, and
write ``X' = H_res X + H_post^T F(RMSNorm(u))``; ``H_res`` is doubly
stochastic (Sinkhorn).  ``X_0`` is the embedding in every stream, the
logits come from ``RMSNorm(sum_i X_i)``.

*Latent attention* (DeepSeek-V2/V3's MLA).  Queries through a rank-
``q_lora_rank`` bottleneck with a norm; keys and values through ONE
latent ``c`` of ``kv_lora_rank`` values a position (normed) beside
``qk_rope_head_dim`` rotary values ``k_r`` that every head shares.
CACHED: ``[c | k_r]``.  Two forms of one function: EXPANDED (the full
forward, prefill) makes every head's 128 + 64 keys and 128 values from
``c`` and runs ``ops.flash_attention`` at 192 / 128; ABSORBED (decode)
folds ``W_kvb``'s key half into the query and its value half into the
output, so the decode kernel reads the latent rows as stored: one
"K/V head", group = all the query heads, scores over a whole row,
values its first ``kv_lora_rank`` lanes.  Rotary positions are YaRN's.

*Feed-forward*: a SwiGLU in the first ``first_k_dense_replace`` layers,
then ``models/_experts.py``: the body ``afmoe`` uses (``noaux_tc``:
sigmoid scores, top-k on score + a stored bias, ``n_group`` 1).

The layer is written ONCE (:meth:`Xing4LM._layer`); the full forward,
the paged prefill and the paged decode step are that body under three
``attend`` closures, which alone know where the latent lives.

Serving state: ONE leaf a layer, ``(pages, 1, page_size, 640)``: a
position's 576 values in one 640-lane row (five whole lane tiles: no
copy of a slice of an array whose minor dim is off the 128 lanes is
possible on the chip, and a 512 leaf beside a 64 -> 128 one would be
the same 640 lanes in two copies a page).  The ``1`` is the paged
kernels' K/V-head axis: the leaf is a head-major pool with one head.

Not in this family yet, each raising by name: the slot-addressed
cache, tensor parallelism, an int8 latent, speculative verify (and the
multi-token head that would draft for it), chunked prefill, prefix
sharing, training.
"""

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.models import _experts, _mla, _served

_LANES = 128


@dataclasses.dataclass(frozen=True)
class Xing4LM(_served.ServedLM):
    """Hyper-parameters under their published ``config.json`` keys."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    scoring_func: str = 'sigmoid'
    topk_method: str = 'noaux_tc'
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Any] = None
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16

    #: what the engine's executables hand back beside the tokens
    serve_counters = ('experts_touched', 'expert_load_max',
                      'latent_positions')
    #: what the engine calls a page of this family on its tick span
    page_counter = 'latent_pages_in_use'
    family = 'xing4'
    cache_name = 'paged latent cache'

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, 'rope_scaling',
                               tuple(sorted(self.rope_scaling.items())))
        if self.scoring_func != 'sigmoid' or self.topk_method != 'noaux_tc':
            raise NotImplementedError(
                'xing4 router %r / %r' % (self.scoring_func,
                                          self.topk_method))
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                'xing4 router with a group limit (n_group %d, topk_group '
                '%d)' % (self.n_group, self.topk_group))
        scaling = dict(self.rope_scaling or ())
        if scaling and scaling.get('type') != 'yarn':
            raise NotImplementedError('rope_scaling %r' % (scaling,))
        if self.kv_lora_rank % _LANES:
            raise ValueError('kv_lora_rank %d is not whole 128-lane '
                             'tiles' % self.kv_lora_rank)

    # -- shapes --------------------------------------------------------
    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self):
        """Values a cached position holds: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self):
        """Lanes a cached row takes: ``latent_dim`` up to whole tiles."""
        return -(-self.latent_dim // _LANES) * _LANES

    @property
    def softmax_scale(self):
        """``qk_head_dim ** -0.5`` times YaRN's ``mscale ** 2`` over
        all dims."""
        scaling = dict(self.rope_scaling or ())
        scale = self.qk_head_dim ** -0.5
        if scaling.get('mscale_all_dim'):
            m = (0.1 * scaling['mscale_all_dim']
                 * math.log(scaling['factor']) + 1.0)
            scale *= m * m
        return scale

    def param_shapes(self):
        """The parameter tree as shapes (names are the interface the
        plain reference's ``param_spec`` follows).  The residual path's
        leaves (``hc_*``) are float32 whatever the weights' dtype."""
        d, h, n = self.hidden_size, self.num_attention_heads, self.hc_mult
        f, e = self.moe_intermediate_size, self.n_routed_experts
        rows = n * (n + 2)

        def swiglu(width, lead=()):
            return {'w1': lead + (d, width), 'w3': lead + (d, width),
                    'w2': lead + (width, d)}

        def hyper():
            return {'phi': (rows, n * d), 'alpha': (3,), 'b': (rows,)}

        tree = {'embed': {'embedding': (self.vocab_size, d)},
                'final_norm': (d,), 'lm_head': (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            layer = {
                'attn_norm': (d,), 'mlp_norm': (d,),
                'hc_attn': hyper(), 'hc_mlp': hyper(),
                'wq_a': (d, self.q_lora_rank),
                'q_a_norm': (self.q_lora_rank,),
                'wq_b': (self.q_lora_rank, h * self.qk_head_dim),
                'wkv_a': (d, self.latent_dim),
                'kv_a_norm': (self.kv_lora_rank,),
                'wkv_b': (self.kv_lora_rank,
                          h * (self.qk_nope_head_dim + self.v_head_dim)),
                'wo': (h * self.v_head_dim, d)}
            if i < self.first_k_dense_replace:
                layer['mlp'] = swiglu(self.intermediate_size)
            else:
                layer.update(
                    router=(d, e), expert_bias=(e,),
                    experts=swiglu(f, (e,)),
                    shared=swiglu(f * self.n_shared_experts))
            tree['layer_%d' % i] = layer
        return tree

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters: N(0, 0.02) matrices, ``phi``, ``b`` and
        ``expert_bias``; norms and ``alpha`` 1 + N(0, 0.02); the
        residual path's leaves float32."""
        shapes = self.param_shapes()
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for n, (path, shape) in enumerate(paths):
            names = [str(getattr(k, 'key', k)) for k in path]
            draw = 0.02 * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)
            one = names[-1].endswith('_norm') or names[-1] == 'alpha'
            hyper = any(name.startswith('hc_') for name in names)
            out.append(((1.0 if one else 0.0) + draw).astype(
                jnp.float32 if hyper else dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- the layer, once -----------------------------------------------
    def _rms(self, x, weight):
        return _experts.rms(x, weight, self.rms_norm_eps, self.dtype)

    def _inv_freq(self):
        """YaRN's blend of the trained and the interpolated
        frequencies (DeepSeek-V3's form), ``qk_rope_head_dim / 2`` of
        them."""
        return _mla.inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             self.rope_scaling)

    def _rope(self, x, positions):
        """Rotary positions over ``x``'s last dim, rotate-half
        pairing."""
        return _mla.rope(x, positions, self._inv_freq())

    def _coefficients(self, x, hp):
        """``(H_pre, H_post, H_res)`` of streams ``x`` (T, n, d)."""
        from chainermn_tpu import ops
        return ops.mhc_coefficients(
            x.reshape(x.shape[0], -1), hp['phi'], hp['alpha'], hp['b'],
            n=self.hc_mult, iters=self.hc_sinkhorn_iters, eps=self.hc_eps,
            clamp=(self.mhc_h_res_clamp_min, self.mhc_h_res_clamp_max),
            norm_eps=self.rms_norm_eps)

    def _hyper(self, x, hp, f):
        """One sub-layer ``f`` under the residual path: ``x`` (T, n, d)
        -> ``(x', what f returned beside its output)``."""
        pre, post, res = self._coefficients(x, hp)
        xf = x.astype(jnp.float32)
        y, extra = f(jnp.einsum('ti,tid->td', pre, xf).astype(self.dtype))
        mixed = (jnp.einsum('tij,tjd->tid', res, xf)
                 + post[:, :, None] * y.astype(jnp.float32)[:, None, :])
        return mixed.astype(self.dtype), extra

    def _latent(self, lp, a, positions):
        """``(q_nope (T, H, 128), q_rope (T, H, 64), c (T, 512), k_r
        (T, 64))`` of normed rows ``a`` (T, d) at ``positions`` (T,)."""
        dtype, h = self.dtype, self.num_attention_heads
        c_q = self._rms(jnp.dot(a, lp['wq_a'].astype(dtype)),
                        lp['q_a_norm'])
        q = jnp.dot(c_q, lp['wq_b'].astype(dtype)).reshape(
            -1, h, self.qk_head_dim)
        ckv = jnp.dot(a, lp['wkv_a'].astype(dtype))
        c = self._rms(ckv[:, :self.kv_lora_rank], lp['kv_a_norm'])
        k_r = self._rope(ckv[:, self.kv_lora_rank:], positions)
        q_rope = self._rope(q[..., self.qk_nope_head_dim:],
                            positions[:, None])
        return q[..., :self.qk_nope_head_dim], q_rope, c, k_r

    def _kvb(self, lp):
        """``W_kvb`` as ``(W_k (C, H, 128), W_v (C, H, 128))``."""
        return _mla.split_kvb(
            lp['wkv_b'].astype(self.dtype), self.kv_lora_rank,
            self.num_attention_heads, self.qk_nope_head_dim)

    def _expanded(self, lp, q_nope, q_rope, c, k_r):
        """Causal attention over the rows themselves, every head's keys
        and values made from the latent: (T, H * v_head_dim)."""
        return _mla.expanded_attention(q_nope, q_rope, c, k_r,
                                       *self._kvb(lp), self.softmax_scale)

    def _latent_rows(self, c, k_r):
        """``[c | k_r | 0]``: what a cached position holds, lane-wide."""
        pad = self.latent_lanes - self.latent_dim
        return jnp.pad(jnp.concatenate([c, k_r], -1).astype(self.dtype),
                       ((0, 0), (0, pad)))

    def _layer(self, layer, x, lp, positions, cache, attend):
        """One layer on streams ``x`` (T, n, d) at ``positions`` (T,).
        ``attend(cache, layer, lp, q_nope, q_rope, c, k_r) -> ((T, H *
        v), cache)`` is all that differs between the full forward, prefill
        and decode: where the new latent rows go and what the queries
        read."""
        dtype = self.dtype

        def attention(u):
            a = self._rms(u, lp['attn_norm'])
            out, new = attend(cache, layer, lp,
                              *self._latent(lp, a, positions))
            return jnp.dot(out.astype(dtype), lp['wo'].astype(dtype)), new

        def feed_forward(u):
            m = self._rms(u, lp['mlp_norm'])
            if 'mlp' in lp:
                return _experts.swiglu(m, lp['mlp'], dtype), None
            out, counters = _experts.sigmoid_routed_experts(
                m, lp, self.num_experts_per_tok, self.norm_topk_prob,
                self.routed_scaling_factor, dtype)
            return out, counters[:2]

        x, cache = self._hyper(x, lp['hc_attn'], attention)
        x, counters = self._hyper(x, lp['hc_mlp'], feed_forward)
        return x, cache, counters

    def _embed(self, params, tokens):
        """``X_0``: the embedding in every stream, (T, n, d)."""
        x = jnp.take(params['embed']['embedding'], tokens, axis=0)
        return jnp.broadcast_to(
            x.astype(self.dtype)[:, None, :],
            (x.shape[0], self.hc_mult, x.shape[1]))

    def _layers(self, params, x, positions, cache, attend):
        """Every layer in turn; the expert counters as the mean over
        the expert layers (zeros in a model without one)."""
        seen = []
        for i in range(self.num_hidden_layers):
            x, cache, counters = self._layer(
                i, x, params['layer_%d' % i], positions, cache, attend)
            if counters is not None:
                seen.append(counters)
        if seen:
            counters = tuple(sum(c) / len(seen) for c in zip(*seen))
        else:
            counters = (jnp.zeros((), jnp.float32),) * 2
        return x, cache, counters

    def _logits(self, params, x):
        """Logits of streams ``x`` (T, n, d): the streams summed."""
        total = jnp.sum(x.astype(jnp.float32), axis=-2)
        return jnp.dot(self._rms(total, params['final_norm']),
                       params['lm_head'].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    # -- full-sequence forward -----------------------------------------
    def apply(self, params, tokens):
        """tokens (B, T) int32 -> float32 logits (B, T, V)."""
        def attend(cache, layer, lp, *latent):
            return self._expanded(lp, *latent), cache

        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

        def one(row):
            x, _, _ = self._layers(params, self._embed(params, row),
                                   positions, None, attend)
            return self._logits(params, x)

        return jnp.stack([one(row) for row in tokens])

    __call__ = apply

    # -- the serving protocol (``_served.ServedLM``) --------------------
    def init_paged_kv_cache(self, n_pages, page_size, int8_kv=False,
                            dtype=None):
        """``{'latent': one leaf a layer}``, a leaf ``(pages, 1,
        page_size, 640)`` (page 0 is the pool's scratch page)."""
        if int8_kv:
            raise NotImplementedError('Xing4LM: int8 latent cache')
        return {'latent': tuple(
            jnp.zeros((n_pages, 1, page_size, self.latent_lanes),
                      dtype or self.dtype)
            for _ in range(self.num_hidden_layers))}

    @staticmethod
    def paged_cache_bytes(cache):
        """``(bytes of one page over all layers, 0)``: no state row;
        ``cache`` may be its structs."""
        return _served.row_bytes(cache['latent']), 0

    def decode_step_paged(self, params, cache, tokens, positions,
                          page_tables):
        """One token a row: ``tokens`` / ``positions`` (N,) and
        ``page_tables`` (N, pages).  Returns ``(logits (N, V) float32,
        cache, counters)``."""
        from chainermn_tpu import ops

        ps = cache['latent'][0].shape[2]
        tables = page_tables.astype(jnp.int32)
        positions = positions.astype(jnp.int32)
        lengths = positions + 1
        pages = tables[jnp.arange(tokens.shape[0]), positions // ps]
        offsets = positions % ps
        pad = self.latent_lanes - self.latent_dim

        def attend(cache, layer, lp, q_nope, q_rope, c, k_r):
            leaf, _ = ops.paged_kv_append(
                cache['latent'][layer], None,
                self._latent_rows(c, k_r)[:, None, :], None, pages,
                offsets)
            cache = _served.with_leaves(cache, layer, latent=leaf)
            w_k, w_v = self._kvb(lp)
            # absorbed: the key half of W_kvb goes into the query, the
            # value half onto what comes back
            q = jnp.concatenate([
                jnp.einsum('thn,chn->thc', q_nope, w_k), q_rope], -1)
            ctx = ops.flash_attention_decode_paged(
                jnp.pad(q, ((0, 0), (0, 0), (0, pad))), leaf, None,
                tables, lengths, scale=self.softmax_scale,
                group=self.num_attention_heads, head_major=True,
                value_lanes=self.kv_lora_rank)
            out = jnp.einsum('thc,chv->thv', ctx, w_v)
            return out.reshape(out.shape[0], -1), cache

        x, cache, counters = self._layers(
            params, self._embed(params, tokens), positions, cache,
            attend)
        read = (jnp.sum(lengths).astype(jnp.float32)
                * self.num_hidden_layers)
        return self._logits(params, x), cache, counters + (read,)

    def decode_paged_grid(self, cache, lengths, n_full, n_ring, tp=1):
        """``(pages read, grid steps)`` of one ``decode_step_paged``
        over rows of these live ``lengths`` (host integers), summed
        over layers."""
        from chainermn_tpu import ops
        leaf = cache['latent'][0]
        grid = ops.decode_paged_grid(lengths, leaf.shape[1:], leaf.dtype,
                                     n_full, head_major=True, shared=True)
        return tuple(self.num_hidden_layers * g for g in grid)

    def prefill_paged(self, params, cache, tokens, length, page_table,
                      pos0):
        """A whole prompt in one call: ``tokens`` (1, C) padded to a
        bucket, ``length`` the valid prefix, ``page_table`` (pages,),
        ``pos0`` 0 (no chunks, no shared prefix: the engine refuses
        both for this family).  Every layer attends over the fresh
        rows, EXPANDED, and banks their latent a page at a time.
        Returns ``(logits (V,) float32 at ``length - 1``, cache,
        counters)``."""
        b, c_len = tokens.shape
        if b != 1:
            raise ValueError('prefill_paged takes one prompt per call, '
                             'got batch %d' % b)
        ps = cache['latent'][0].shape[2]
        table = page_table.astype(jnp.int32)
        length = jnp.asarray(length, jnp.int32)
        n_pages = -(-c_len // ps)
        page = jnp.arange(n_pages, dtype=jnp.int32)
        ids = jnp.where(page <= (length - 1) // ps,
                        table[jnp.minimum(page, table.shape[0] - 1)], 0)

        def attend(cache, layer, lp, q_nope, q_rope, c, k_r):
            rows = jnp.pad(self._latent_rows(c, k_r),
                           ((0, n_pages * ps - c_len), (0, 0)))
            leaf = cache['latent'][layer]
            leaf = leaf.at[ids].set(
                rows.reshape(n_pages, 1, ps, -1).astype(leaf.dtype))
            return (self._expanded(lp, q_nope, q_rope, c, k_r),
                    _served.with_leaves(cache, layer, latent=leaf))

        positions = (jnp.asarray(pos0, jnp.int32)
                     + jnp.arange(c_len, dtype=jnp.int32))
        x, cache, counters = self._layers(
            params, self._embed(params, tokens[0]), positions, cache,
            attend)
        x_last = lax.dynamic_slice_in_dim(x, length - 1, 1, axis=0)
        return (self._logits(params, x_last)[0], cache,
                counters + (jnp.zeros((), jnp.float32),))
