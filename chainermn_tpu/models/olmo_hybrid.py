"""The ``olmo_hybrid`` family of decoder LMs (AI2's Olmo-Hybrid): layers
of two kinds in one stack, chosen by ``layer_types[i]``.

A ``linear_attention`` layer is a Gated DeltaNet mixer: projections
``q``, ``k`` (``linear_num_key_heads`` x ``linear_key_head_dim``), ``v``
and an output gate ``z`` (``linear_num_value_heads`` x
``linear_value_head_dim``) and two per-head scalars ``a``, ``b``; a
causal depthwise convolution of ``linear_conv_kernel_dim`` taps over
``q | k | v`` then ``silu``; ``q`` and ``k`` L2-normalised per head
(``q`` scaled by ``dk ** -0.5``); ``beta = 2 * sigmoid(b)`` (the 2 is
``linear_allow_neg_eigval``), ``g = -exp(A_log) * softplus(a +
dt_bias)``; the gated delta rule (:mod:`chainermn_tpu.ops.gated_delta`)
on a per-head float32 state ``(dk, dv)``; an RMSNorm over ``dv`` times
``silu(z)``, then the output projection.  It keeps NO keys and values:
a sequence's whole past is its state and the last ``taps - 1``
pre-convolution positions, whatever its length.

A ``full_attention`` layer is softmax attention over every earlier
position with an RMSNorm over the whole projected ``q`` and ``k`` and
no positional encoding (``rope_theta`` is null).  Both kinds:
``h = x + norm(mix(x))``, ``h = h + norm(swiglu(h))`` (the OLMo-2
order), no bias, untied head.

The layer is written ONCE (:meth:`OlmoHybridLM._layer`); the
full-sequence forward, the paged prefill and the paged decode step are
that body under three pairs of closures, which alone know where K/V
and the recurrent state live.

Serving state: ONE cache with two kinds of leaf.  A full layer has a
K and a V page pool ``(pages, kv_heads, page_size, head_dim)`` addressed
by the sequence's page table, as ``AfmoeLM``'s full layers are; a
linear layer owns no page.  It has a STATE leaf (``ops.state_shape``:
``(rows, ...)`` float32) and a convolution-TAIL leaf (``ops.tail_shape``:
the last ``taps - 1`` positions before the convolution), one row a
sequence (:meth:`has_state_row`), row 0 the scratch
row as page 0 is the scratch page.  The engine hands both addresses as
one int32 row, ``[full table | state row]``.  A prefill writes its row
whole, so a reused row needs no zeroing.

Not in this family yet, each raising by name: the slot-addressed cache,
prefix sharing and chunked prefill (both need a state snapshot at the
boundary), int8 K/V, speculative verify, tensor parallelism, training.
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.models import _experts, _served

_KINDS = ('linear_attention', 'full_attention')
#: seeded leaves that are not N(0, 0.02): (mean, std) by name.  The
#: decay ``exp(g)`` then spreads over about (0.5, 1) and the
#: convolution's output is of order one, so that no path is dead.
_INIT = {'A_log': (-1.2, 0.3), 'dt_bias': (0.0, 0.5), 'conv': (0.0, 0.5)}


@dataclasses.dataclass(frozen=True)
class OlmoHybridLM(_served.ServedLM):
    """Hyper-parameters under their published ``config.json`` keys."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    dtype: Any = jnp.bfloat16

    #: what the engine's executables hand back beside the tokens: the
    #: state rows the call moved, the real prompt tokens it ran through
    #: the chunked rule
    serve_counters = ('state_rows', 'scan_tokens')
    family = 'olmo_hybrid'

    def __post_init__(self):
        if self.layer_types is None:
            kinds = tuple(_KINDS[(i + 1) % 4 == 0]
                          for i in range(self.num_hidden_layers))
        else:
            kinds = tuple(self.layer_types)
        object.__setattr__(self, 'layer_types', kinds)
        if len(kinds) != self.num_hidden_layers or set(kinds) - set(
                _KINDS):
            raise ValueError('layer_types %r does not name %d linear / '
                             'full layers' % (kinds,
                                              self.num_hidden_layers))
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                '%d K/V heads, %d query heads and hidden %d do not '
                'divide' % (self.num_key_value_heads,
                            self.num_attention_heads, self.hidden_size))
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                'olmo_hybrid with %d key heads on %d value heads'
                % (self.linear_num_key_heads,
                   self.linear_num_value_heads))

    # -- shapes --------------------------------------------------------
    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def group(self):
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def conv_channels(self):
        """``q | k | v`` as the convolution sees them."""
        return self.linear_num_key_heads * (
            2 * self.linear_key_head_dim + self.linear_value_head_dim)

    def linear(self, layer):
        return self.layer_types[layer] == 'linear_attention'

    def _nth(self, layer):
        """``layer``'s place among the layers of its kind: its index in
        the cache's tuples of leaves."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    def has_state_row(self):
        """Does a sequence hold a fixed-size state row beside its
        pages: ONE for all linear layers (each layer's state and tail
        lie at that row of their leaves), none without a linear
        layer."""
        return 'linear_attention' in self.layer_types

    def param_shapes(self):
        """The parameter tree as shapes (names are the interface the
        plain reference's ``param_spec`` follows)."""
        d, dh = self.hidden_size, self.head_dim
        hq = self.num_attention_heads * dh
        hkv = self.num_key_value_heads * dh
        heads = self.linear_num_value_heads
        wk = heads * self.linear_key_head_dim
        wv = heads * self.linear_value_head_dim
        f = self.intermediate_size
        tree = {'embed': {'embedding': (self.vocab_size, d)},
                'final_norm': (d,), 'lm_head': (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            layer = {'post_attn_norm': (d,), 'post_mlp_norm': (d,),
                     'mlp': {'w1': (d, f), 'w3': (d, f), 'w2': (f, d)}}
            if self.linear(i):
                layer.update(
                    wq=(d, wk), wk=(d, wk), wv=(d, wv), wz=(d, wv),
                    wa=(d, heads), wb=(d, heads),
                    conv=(self.linear_conv_kernel_dim,
                          self.conv_channels),
                    A_log=(heads,), dt_bias=(heads,),
                    o_norm=(self.linear_value_head_dim,), wo=(wv, d))
            else:
                layer.update(wq=(d, hq), wk=(d, hkv), wv=(d, hkv),
                             q_norm=(hq,), k_norm=(hkv,), wo=(hq, d))
            tree['layer_%d' % i] = layer
        return tree

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters: N(0, 0.02) matrices, norms 1 + N(0,
        0.02), ``A_log`` / ``dt_bias`` / ``conv`` as :data:`_INIT`."""
        shapes = self.param_shapes()
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for n, (path, shape) in enumerate(paths):
            name = str(getattr(path[-1], 'key', path[-1]))
            mean, std = _INIT.get(
                name, (float(name.endswith('_norm')), 0.02))
            out.append((mean + std * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)
            ).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- the layer, once -----------------------------------------------
    def _rms(self, x, weight):
        return _experts.rms(x, weight, self.rms_norm_eps, self.dtype)

    def _qkv(self, y):
        """The convolution's float32 output ``y`` (..., channels) to the
        rule's operands: ``silu``, the split into heads, ``q`` and
        ``k`` L2-normalised per head (eps 1e-6) and ``q`` scaled."""
        heads, dk = self.linear_num_key_heads, self.linear_key_head_dim
        y = jax.nn.silu(y)
        q, k, v = jnp.split(y, [heads * dk, 2 * heads * dk], axis=-1)

        def unit(x):
            x = x.reshape(x.shape[:-1] + (heads, dk))
            return x * lax.rsqrt(jnp.sum(jnp.square(x), -1,
                                         keepdims=True) + 1e-6)

        v = v.reshape(v.shape[:-1] + (heads, self.linear_value_head_dim))
        return tuple(x.astype(self.dtype)
                     for x in (unit(q) * dk ** -0.5, unit(k), v))

    def _layer(self, layer, x, lp, cache, attend, recur):
        """One layer on ``x`` (..., d).  ``attend(cache, layer, q, k, v)
        -> (attn, cache)`` (a full layer) and ``recur(cache, layer,
        taps, qkv, g, beta) -> (o, cache)`` (a linear layer: ``qkv``
        before the convolution, ``o`` float32 per head) are all that
        differs between the full forward, prefill and decode: where the
        K/V or the state and tail live."""
        dtype = self.dtype
        lead = x.shape[:-1]
        if self.linear(layer):
            heads = self.linear_num_value_heads
            qkv = jnp.concatenate(
                [jnp.dot(x, lp[w].astype(dtype))
                 for w in ('wq', 'wk', 'wv')], axis=-1)
            gate = jnp.dot(x, lp['wz'].astype(dtype))
            a, b = (jnp.dot(x, lp[w].astype(dtype),
                            preferred_element_type=jnp.float32)
                    for w in ('wa', 'wb'))
            g = -jnp.exp(lp['A_log'].astype(jnp.float32)) \
                * jax.nn.softplus(a + lp['dt_bias'].astype(jnp.float32))
            beta = jax.nn.sigmoid(b) * (
                2.0 if self.linear_allow_neg_eigval else 1.0)
            o, cache = recur(cache, layer, lp['conv'], qkv, g, beta)
            mixed = self._rms(o, lp['o_norm']) * jax.nn.silu(
                gate.reshape(lead + (heads, -1)))
        else:
            hq, hkv = self.num_attention_heads, self.num_key_value_heads
            q, k = (self._rms(jnp.dot(x, lp[w].astype(dtype)), lp[norm])
                    .reshape(lead + (heads, self.head_dim))
                    for w, norm, heads in (('wq', 'q_norm', hq),
                                           ('wk', 'k_norm', hkv)))
            v = jnp.dot(x, lp['wv'].astype(dtype)).reshape(
                lead + (hkv, self.head_dim))
            mixed, cache = attend(cache, layer, q, k, v)
        out = jnp.dot(mixed.reshape(lead + (-1,)).astype(dtype),
                      lp['wo'].astype(dtype))
        x = x + self._rms(out, lp['post_attn_norm'])
        return x + self._rms(_experts.swiglu(x, lp['mlp'], dtype),
                             lp['post_mlp_norm']), cache

    def _layers(self, params, tokens, cache, attend, recur):
        x = jnp.take(params['embed']['embedding'], tokens,
                     axis=0).astype(self.dtype)
        for i in range(self.num_hidden_layers):
            x, cache = self._layer(i, x, params['layer_%d' % i], cache,
                                   attend, recur)
        return x, cache

    def _logits(self, params, x):
        return jnp.dot(self._rms(x, params['final_norm']),
                       params['lm_head'].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _scan(self, taps, qkv, g, beta, length=None):
        """A whole sequence through one linear layer's recurrence from
        an empty state: ``qkv`` (T, channels), ``g`` / ``beta`` (T, H).
        Returns ``(o (T, H, dv) float32, the final state)``."""
        from chainermn_tpu import ops
        return ops.gated_delta_rule(
            *self._qkv(ops.causal_conv(qkv, taps)), g, beta,
            length=length)

    # -- full-sequence forward -----------------------------------------
    def apply(self, params, tokens):
        """tokens (B, T) int32 -> float32 logits (B, T, V)."""
        from chainermn_tpu import ops

        def attend(cache, layer, q, k, v):
            return ops.flash_attention(q, k, v, causal=True), cache

        def recur(cache, layer, taps, qkv, g, beta):
            return jax.vmap(lambda *row: self._scan(taps, *row)[0])(
                qkv, g, beta), cache

        x, _ = self._layers(params, tokens, None, attend, recur)
        return self._logits(params, x)

    __call__ = apply

    # -- the serving protocol (``_served.ServedLM``) --------------------
    def init_paged_kv_cache(self, n_pages, page_size, n_state_rows=0,
                            int8_kv=False, dtype=None):
        """``{'k' | 'v': a page pool a FULL layer, 'state' | 'tail': a
        leaf of ``n_state_rows`` rows a LINEAR layer}``: pools
        ``(pages, kv_heads, page_size, head_dim)`` (page 0 the scratch
        page), states ``ops.state_shape`` float32 and tails
        ``ops.tail_shape``; row 0 of both the scratch row."""
        from chainermn_tpu import ops
        if int8_kv:
            raise NotImplementedError('OlmoHybridLM: int8 K/V cache')
        if self.has_state_row() and n_state_rows < 2:
            raise ValueError('linear layers need their own state rows '
                             '(n_state_rows)')
        dtype = dtype or self.dtype
        n_linear = self.layer_types.count('linear_attention')

        def leaves(n, shape, dtype):
            return tuple(jnp.zeros(shape, dtype) for _ in range(n))

        pool = (n_pages, self.num_key_value_heads, page_size,
                self.head_dim)
        state = ops.state_shape(
            n_state_rows, self.linear_num_value_heads,
            self.linear_key_head_dim, self.linear_value_head_dim)
        tail = ops.tail_shape(n_state_rows, self.linear_conv_kernel_dim,
                              self.conv_channels, dtype)
        n_full = self.num_hidden_layers - n_linear
        return {'k': leaves(n_full, pool, dtype),
                'v': leaves(n_full, pool, dtype),
                'state': leaves(n_linear, state, jnp.float32),
                'tail': leaves(n_linear, tail, dtype)}

    @staticmethod
    def paged_cache_bytes(cache):
        """``(bytes of one K/V page, bytes of one state row)``, each
        over all the layers that hold one; ``cache`` may be its
        structs."""
        return (_served.row_bytes(cache['k'] + cache['v']),
                _served.row_bytes(cache['state'] + cache['tail']))

    def _tables(self, page_tables):
        """``[full table | state row]`` apart (the row of a model with
        no linear layer: the scratch row, never read)."""
        tables = page_tables.astype(jnp.int32)
        if not self.has_state_row():
            return tables, jnp.zeros(tables.shape[:-1], jnp.int32)
        return tables[..., :-1], tables[..., -1]

    def _counters(self, state_rows, scan_tokens):
        return (jnp.asarray(state_rows, jnp.float32),
                jnp.asarray(scan_tokens, jnp.float32))

    def decode_step_paged(self, params, cache, tokens, positions,
                          page_tables):
        """One token a row: ``tokens`` / ``positions`` (N,) and
        ``page_tables`` (N, full table + 1), the last column each
        sequence's state row.  Returns ``(logits (N, V) float32, cache,
        counters)``."""
        from chainermn_tpu import ops

        full, state_rows = self._tables(page_tables)
        positions = positions.astype(jnp.int32)
        ps = cache['k'][0].shape[2] if cache['k'] else 1
        pages = full[jnp.arange(tokens.shape[0]), positions // ps]

        def attend(cache, layer, q, k, v):
            at = self._nth(layer)
            k_leaf, v_leaf = ops.paged_kv_append(
                cache['k'][at], cache['v'][at], k, v, pages,
                positions % ps)
            return ops.flash_attention_decode_paged(
                q, k_leaf, v_leaf, full, positions + 1,
                scale=self.head_dim ** -0.5, group=self.group,
                head_major=True), _served.with_leaves(
                    cache, at, k=k_leaf, v=v_leaf)

        def recur(cache, layer, taps, qkv, g, beta):
            at = self._nth(layer)
            y, tail = ops.causal_conv_step(
                cache['tail'][at], state_rows, qkv, taps)
            o, state = ops.gated_delta_step(
                cache['state'][at], state_rows, *self._qkv(y), g, beta)
            return o, _served.with_leaves(cache, at, state=state,
                                          tail=tail)

        x, cache = self._layers(params, tokens, cache, attend, recur)
        return (self._logits(params, x), cache,
                self._counters(tokens.shape[0] * self.has_state_row(), 0))

    def decode_paged_grid(self, cache, lengths, n_full, n_ring=0, tp=1):
        """``(pages read, grid steps)`` of one ``decode_step_paged``
        over rows of these live ``lengths`` (host integers), summed
        over the FULL layers: a linear layer reads no page."""
        from chainermn_tpu import ops
        if not cache['k']:
            return 0, 0
        leaf = cache['k'][0]
        read, steps = ops.decode_paged_grid(
            lengths, leaf.shape[1:], leaf.dtype, n_full, head_major=True)
        return len(cache['k']) * read, len(cache['k']) * steps

    def prefill_paged(self, params, cache, tokens, length, page_table,
                      pos0):
        """A whole prompt in one call: ``tokens`` (1, C) padded to a
        bucket, ``length`` the valid prefix, ``page_table`` (full table
        + 1,), ``pos0`` 0 (no chunks, no shared prefix: the engine
        refuses both for this family).  A full layer attends over the
        fresh K/V and banks every page the prompt reaches; a linear
        layer runs the chunked rule, in which a position at or past
        ``length`` changes nothing, and writes the sequence's state row
        and tail WHOLE.  Returns ``(logits (V,) float32 at ``length -
        1``, cache, counters)``."""
        from chainermn_tpu import ops

        b, c = tokens.shape
        if b != 1:
            raise ValueError('prefill_paged takes one prompt per call, '
                             'got batch %d' % b)
        del pos0
        full, state_row = self._tables(page_table)
        length = jnp.asarray(length, jnp.int32)

        def attend(cache, layer, q, k, v):
            at = self._nth(layer)
            ps = cache['k'][at].shape[2]
            n_pages = -(-c // ps)
            page = jnp.arange(n_pages, dtype=jnp.int32)
            ids = jnp.where(page <= (length - 1) // ps,
                            full[jnp.minimum(page, full.shape[0] - 1)], 0)

            def banked(leaf, new):
                # (C, kv_heads, D) -> (pages, kv_heads, page_size, D)
                new = jnp.pad(new, ((0, n_pages * ps - c), (0, 0), (0, 0)))
                new = jnp.swapaxes(
                    new.reshape((n_pages, ps) + new.shape[1:]), 1, 2)
                return leaf.at[ids].set(new.astype(leaf.dtype))

            return (ops.flash_attention(q, k, v, causal=True),
                    _served.with_leaves(
                        cache, at, k=banked(cache['k'][at], k[0]),
                        v=banked(cache['v'][at], v[0])))

        def recur(cache, layer, taps, qkv, g, beta):
            at = self._nth(layer)
            o, state = self._scan(taps, qkv[0], g[0], beta[0], length)
            tail = ops.conv_tail(qkv[0], length, taps.shape[0])
            return o[None], _served.with_leaves(
                cache, at,
                state=cache['state'][at].at[state_row].set(
                    ops.pack_state(state)),
                tail=cache['tail'][at].at[state_row].set(
                    ops.pack_tail(tail, cache['tail'][at].dtype)))

        x, cache = self._layers(params, tokens, cache, attend, recur)
        x_last = lax.dynamic_slice_in_dim(x[0], length - 1, 1, axis=0)
        return (self._logits(params, x_last)[0], cache,
                self._counters(self.has_state_row(),
                               length * self.has_state_row()))
