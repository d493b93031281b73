"""The ``afmoe`` family of decoder LMs (Arcee's Trinity models).

What a layer computes (``docs/serving.md`` has it beside the cache):
RMSNorm before and after both halves (four norms a layer); attention
with ``num_attention_heads`` query heads on ``num_key_value_heads`` K/V
heads, an RMSNorm over ``head_dim`` on q and on k, a sigmoid OUTPUT
GATE (``attn * sigmoid(a @ Wg)``) before the output projection; window
layers (``layer_types[i] == 'sliding_attention'``) carry rotary
positions and see the last ``sliding_window`` keys, full layers see
every key and carry NO positional encoding at all; the first
``num_dense_layers`` feed-forwards are SwiGLUs, the rest a dropless
sparse layer: sigmoid router, top-``k`` chosen on score + a stored
bias, gates the chosen scores normalised and scaled, beside a shared
expert every token takes.  No bias anywhere, untied head, embeddings
scaled by ``sqrt(hidden_size)``.

The layer is written ONCE (:meth:`AfmoeLM._layer`); the full-sequence
forward, the paged prefill and the paged decode step are that body
under three ``attend`` closures, which alone know where K/V live.

Serving state: ONE paged cache with two kinds of leaf.  A full layer's
leaf is addressed by the sequence's page table as ``TransformerLM``'s
is.  A window layer's is addressed through a RING of
:meth:`AfmoeLM.window_ring` pages: position ``p`` lives in ring column
``(p // page_size) % ring``, so a sequence never holds more window
pages than the ring however long it grows.  The engine hands both
tables as one int32 row, ``[full table | ring]``.  Leaves lie
``(pages, kv_heads, page_size, head_dim)``: the two minor dims are a
whole tile whatever the head count.

Not in this family yet, each raising by name: the slot-addressed cache,
tensor parallelism, int8 K/V, speculative verify, training.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.models import _experts, _mla, _served


@dataclasses.dataclass(frozen=True)
class AfmoeLM(_served.ServedLM):
    """Hyper-parameters under their published ``config.json`` keys."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    score_func: str = 'sigmoid'
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16

    #: what the engine's executables hand back beside the tokens
    serve_counters = ('experts_touched', 'expert_load_max')
    family = 'afmoe'

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            kinds = tuple('full_attention' if (i + 1) % n == 0
                          else 'sliding_attention'
                          for i in range(self.num_hidden_layers))
        else:
            kinds = tuple(self.layer_types)
        object.__setattr__(self, 'layer_types', kinds)
        if len(kinds) != self.num_hidden_layers or set(kinds) - {
                'sliding_attention', 'full_attention'}:
            raise ValueError('layer_types %r does not name %d window / '
                             'full layers' % (kinds,
                                              self.num_hidden_layers))
        if self.score_func != 'sigmoid':
            raise NotImplementedError('afmoe router score_func %r'
                                      % (self.score_func,))
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError('%d K/V heads do not divide %d query heads'
                             % (self.num_key_value_heads,
                                self.num_attention_heads))

    # -- shapes --------------------------------------------------------
    @property
    def group(self):
        return self.num_attention_heads // self.num_key_value_heads

    def sliding(self, layer):
        return self.layer_types[layer] == 'sliding_attention'

    def window_ring(self, page_size):
        """Pages in a window layer's ring: the window from any offset
        inside a page, ``ceil(window / page_size) + 1``; 0 for a model
        with no window layer."""
        if 'sliding_attention' not in self.layer_types:
            return 0
        return -(-self.sliding_window // int(page_size)) + 1

    def param_shapes(self):
        """The parameter tree as shapes (names are the interface the
        plain reference's ``param_spec`` follows)."""
        d, dh = self.hidden_size, self.head_dim
        hq = self.num_attention_heads * dh
        hkv = self.num_key_value_heads * dh
        f, e = self.moe_intermediate_size, self.num_experts

        def swiglu(width, lead=()):
            return {'w1': lead + (d, width), 'w3': lead + (d, width),
                    'w2': lead + (width, d)}

        tree = {'embed': {'embedding': (self.vocab_size, d)},
                'final_norm': (d,), 'lm_head': (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            layer = {'input_norm': (d,), 'post_attn_norm': (d,),
                     'pre_mlp_norm': (d,), 'post_mlp_norm': (d,),
                     'q_norm': (dh,), 'k_norm': (dh,),
                     'wq': (d, hq), 'wk': (d, hkv), 'wv': (d, hkv),
                     'wg': (d, hq), 'wo': (hq, d)}
            if i < self.num_dense_layers:
                layer['mlp'] = swiglu(self.intermediate_size)
            else:
                layer.update(
                    router=(d, e), expert_bias=(e,),
                    experts=swiglu(f, (e,)),
                    shared=swiglu(f * self.num_shared_experts))
            tree['layer_%d' % i] = layer
        return tree

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters: N(0, 0.02) matrices, norms 1 + N(0,
        0.02), ``expert_bias`` N(0, 0.02)."""
        shapes = self.param_shapes()
        leaves, treedef = jax.tree_util.tree_flatten(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        paths = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
        out = []
        for n, ((path, _), shape) in enumerate(zip(paths, leaves)):
            name = str(getattr(path[-1], 'key', path[-1]))
            draw = 0.02 * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)
            out.append(((1.0 if name.endswith('_norm') else 0.0)
                        + draw).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- the layer, once -----------------------------------------------
    def _rms(self, x, weight):
        return _experts.rms(x, weight, self.rms_norm_eps, self.dtype)

    def _rope(self, x, positions):
        """Rotary positions over all of ``head_dim``, rotate-half
        pairing; ``x`` (..., H, D), ``positions`` (...)."""
        half = self.head_dim // 2
        inv = self.rope_theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        return _mla.rope(x, positions[..., None], inv)

    def _swiglu(self, x, p):
        return _experts.swiglu(x, p, self.dtype)

    def _experts(self, m, lp):
        """The sparse feed-forward on rows ``m`` (T, d): returns it and
        the layer's two serve counters (``models/_experts.py``, the
        body this family shares with ``xing4`` and ``deepseek_v3``)."""
        out, counters = _experts.sigmoid_routed_experts(
            m, lp, self.num_experts_per_tok, self.route_norm,
            self.route_scale, self.dtype)
        return out, counters[:2]

    def _layer(self, layer, x, lp, positions, cache, attend):
        """One layer on ``x`` (..., d) at ``positions`` (...).
        ``attend(cache, layer, q, k, v) -> (attn, cache)`` is all that
        differs between the full forward, prefill and decode: where the
        new K/V go and what the queries read."""
        dtype = self.dtype
        lead = x.shape[:-1]
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        a = self._rms(x, lp['input_norm'])
        q = jnp.dot(a, lp['wq'].astype(dtype)).reshape(
            lead + (hq, self.head_dim))
        k = jnp.dot(a, lp['wk'].astype(dtype)).reshape(
            lead + (hkv, self.head_dim))
        v = jnp.dot(a, lp['wv'].astype(dtype)).reshape(
            lead + (hkv, self.head_dim))
        gate = jnp.dot(a, lp['wg'].astype(dtype))
        q = self._rms(q, lp['q_norm'])
        k = self._rms(k, lp['k_norm'])
        if self.sliding(layer):
            q, k = self._rope(q, positions), self._rope(k, positions)
        attn, cache = attend(cache, layer, q, k, v)
        out = jnp.dot(attn.reshape(lead + (-1,)).astype(dtype)
                      * jax.nn.sigmoid(gate), lp['wo'].astype(dtype))
        x = x + self._rms(out, lp['post_attn_norm'])
        m = self._rms(x, lp['pre_mlp_norm'])
        if 'mlp' in lp:
            ff, counters = self._swiglu(m, lp['mlp']), None
        else:
            ff, counters = self._experts(
                m.reshape(-1, m.shape[-1]), lp)
            ff = ff.reshape(m.shape)
        return x + self._rms(ff, lp['post_mlp_norm']), cache, counters

    def _embed(self, params, tokens):
        x = jnp.take(params['embed']['embedding'], tokens, axis=0)
        if self.mup_enabled:
            x = x.astype(jnp.float32) * math.sqrt(self.hidden_size)
        return x.astype(self.dtype)

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
        return jnp.dot(self._rms(x, params['final_norm']),
                       params['lm_head'].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _window(self, layer):
        return self.sliding_window if self.sliding(layer) else None

    # -- full-sequence forward -----------------------------------------
    def apply(self, params, tokens):
        """tokens (B, T) int32 -> float32 logits (B, T, V)."""
        from chainermn_tpu import ops

        def attend(cache, layer, q, k, v):
            return ops.flash_attention(
                q, k, v, causal=True, window=self._window(layer)), cache

        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        x, _, _ = self._layers(params, self._embed(params, tokens),
                               positions, None, attend)
        return self._logits(params, x)

    __call__ = apply

    # -- the serving protocol (``_served.ServedLM``) --------------------
    def init_paged_kv_cache(self, n_pages, page_size, n_window_pages=0,
                            int8_kv=False, dtype=None):
        """``{'k' | 'v': one leaf a layer}``, a leaf ``(pages,
        kv_heads, page_size, head_dim)`` with ``pages`` ``n_pages`` for
        a full layer and ``n_window_pages`` for a window layer (page 0
        of either kind is its pool's scratch page)."""
        if int8_kv:
            raise NotImplementedError('AfmoeLM: int8 K/V cache')
        if self.window_ring(page_size) and n_window_pages < 2:
            raise ValueError('window layers need their own pages '
                             '(n_window_pages)')

        def leaves():
            return tuple(jnp.zeros(
                (n_window_pages if self.sliding(i) else n_pages,
                 self.num_key_value_heads, page_size, self.head_dim),
                dtype or self.dtype)
                for i in range(self.num_hidden_layers))

        return {'k': leaves(), 'v': leaves()}

    def _tables(self, cache, page_tables):
        page_size = cache['k'][0].shape[2]
        n_full = page_tables.shape[-1] - self.window_ring(page_size)
        return (page_size, page_tables[..., :n_full].astype(jnp.int32),
                page_tables[..., n_full:].astype(jnp.int32))

    def decode_step_paged(self, params, cache, tokens, positions,
                          page_tables):
        """One token a row: ``tokens`` / ``positions`` (N,) and
        ``page_tables`` (N, full + ring).  Returns ``(logits (N, V)
        float32, cache, counters)``."""
        from chainermn_tpu import ops

        ps, full, ring = self._tables(cache, page_tables)
        positions = positions.astype(jnp.int32)
        lengths = positions + 1
        rows = jnp.arange(tokens.shape[0])
        offsets = positions % ps
        full_pages = full[rows, positions // ps]
        if ring.shape[1]:
            ring_pages = ring[rows, (positions // ps) % ring.shape[1]]

        def attend(cache, layer, q, k, v):
            window = self._window(layer)
            pages, table = ((full_pages, full) if window is None
                            else (ring_pages, ring))
            k_leaf, v_leaf = ops.paged_kv_append(
                cache['k'][layer], cache['v'][layer], k, v, pages,
                offsets)
            cache = _served.with_leaves(cache, layer, k=k_leaf, v=v_leaf)
            return ops.flash_attention_decode_paged(
                q, cache['k'][layer], cache['v'][layer], table, lengths,
                scale=self.head_dim ** -0.5, group=self.group,
                window=window, head_major=True), cache

        x, cache, counters = self._layers(
            params, self._embed(params, tokens), positions, cache,
            attend)
        return self._logits(params, x), cache, counters

    def decode_paged_grid(self, cache, lengths, n_full, n_ring, tp=1):
        """``(pages read, grid steps)`` of one ``decode_step_paged``
        over rows of these live ``lengths`` (host integers), summed
        over layers: a full layer's call over ``n_full`` table columns,
        a window layer's over its ring."""
        from chainermn_tpu import ops
        total = [0, 0]
        for window, n_max in ((None, n_full),
                              (self.sliding_window, n_ring)):
            layers = [i for i in range(self.num_hidden_layers)
                      if self._window(i) == window]
            if layers:
                leaf = cache['k'][layers[0]]
                grid = ops.decode_paged_grid(
                    lengths, leaf.shape[1:], leaf.dtype, n_max,
                    window=window, head_major=True)
                total = [t + len(layers) * g for t, g in zip(total, grid)]
        return tuple(total)

    def prefill_paged(self, params, cache, tokens, length, page_table,
                      pos0):
        """A whole prompt in one call: ``tokens`` (1, C) padded to a
        bucket, ``length`` the valid prefix, ``page_table`` (full +
        ring,), ``pos0`` 0 (no chunks, no shared prefix: the engine
        refuses both for this family).  Every layer attends over the
        fresh K/V; a full layer banks every page, a window layer only
        the pages its ring holds once the prompt is in, each column
        written at most once.  Returns ``(logits (V,) float32 at
        ``length - 1``, cache, counters)``."""
        from chainermn_tpu import ops

        b, c = tokens.shape
        if b != 1:
            raise ValueError('prefill_paged takes one prompt per call, '
                             'got batch %d' % b)
        ps, full, ring = self._tables(cache, page_table)
        length = jnp.asarray(length, jnp.int32)
        n_pages = -(-c // ps)
        page = jnp.arange(n_pages, dtype=jnp.int32)
        last = (length - 1) // ps
        full_ids = jnp.where(
            page <= last, full[jnp.minimum(page, full.shape[0] - 1)], 0)
        if ring.shape[0]:
            ring_ids = jnp.where(
                jnp.logical_and(page <= last,
                                page > last - ring.shape[0]),
                ring[page % ring.shape[0]], 0)

        def pages_of(x):
            # (C, kv_heads, D) -> (pages, kv_heads, page_size, D)
            x = jnp.pad(x, ((0, n_pages * ps - c), (0, 0), (0, 0)))
            return jnp.swapaxes(
                x.reshape((n_pages, ps) + x.shape[1:]), 1, 2)

        def attend(cache, layer, q, k, v):
            window = self._window(layer)
            ids = full_ids if window is None else ring_ids
            cache = _served.with_leaves(cache, layer, **{
                name: cache[name][layer].at[ids].set(
                    pages_of(new[0]).astype(cache[name][layer].dtype))
                for name, new in (('k', k), ('v', v))})
            return ops.flash_attention(q, k, v, causal=True,
                                       window=window), cache

        positions = (jnp.asarray(pos0, jnp.int32)
                     + jnp.arange(c, dtype=jnp.int32))[None]
        x, cache, counters = self._layers(
            params, self._embed(params, tokens), positions, cache,
            attend)
        x_last = lax.dynamic_slice_in_dim(x[0], length - 1, 1, axis=0)
        return self._logits(params, x_last)[0], cache, counters
