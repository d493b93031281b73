"""The ``phi4flash`` family of decoder LMs (Microsoft's
Phi-4-mini-flash-reasoning; the SambaY decoder-hybrid-decoder of
arXiv:2507.06607): FIVE kinds of mixer in one stack, chosen by the
layer's index (:attr:`Phi4FlashLM.kinds`).

With ``N`` layers, the first ``N / 2`` are the SELF-decoder: Mamba
layers (even) and window-attention layers (odd).  Layer ``N / 2`` is
one more Mamba layer whose scan output ``m`` (before its gate) is KEPT:
the ``memory``.  Layer ``N / 2 + 1`` is full attention, and its K and V
are THE cache of the CROSS-decoder: of the layers after it the even are
gated memory units, ``(silu(u W_in) * m) W_out`` on the memory of the
same position, and the odd are cross attention with a query projection
of their own over layer ``N / 2 + 1``'s K/V.  Neither owns a cache.

Every layer: ``h = x + mix(LN(x))``, ``y = h + MLP(LN'(h))``, LayerNorm
with weight and bias, ``MLP(u) = (silu(g) * v) W_2`` with ``[g | v] = u
W_1``; a final LayerNorm and the embedding, transposed, as the head
(tied).  No positional encoding anywhere.

*Mamba*: ``[x~ | z] = u W_in``; ``x = silu(conv4(x~) + b)``; ``[dt | B
| C] = x W_x``; ``delta = softplus(dt W_dt + b_dt)``; the selective
scan of :mod:`chainermn_tpu.ops.selective_scan` over ``A = -exp(A_log)``
with a float32 state ``(N, Di)`` a layer; ``(m * silu(z)) W_out``.

*Differential attention* (all attention layers; Ye et al.,
arXiv:2410.05258): query heads in pairs ``(2j, 2j + 1)``, K heads in
pairs ``(2p, 2p + 1)``, values ``[v_2p | v_2p+1]`` (twice the head
width); query pair ``j`` reads K/V pair ``j // (H / Hkv)``; ``o_j = (1
- lambda_init) RMSNorm(softmax(q1 k1^T) v - lambda softmax(q2 k2^T)
v)``.  On the kernels the repo has: K and V lie PACKED BY PAIR,
``[k1 | k2]`` and ``[v1 | v2]`` in one row of ``2 * head_dim`` lanes
(128: lane-dense, which a 64-wide head is not), and query head ``h``
enters as a ``2 * head_dim`` row with its values in the half its
parity names and zeros in the other, so that its scores are those of
``q_h`` on ITS key of the pair; scale ``head_dim ** -0.5``.

The layer is written ONCE (:meth:`Phi4FlashLM._layer`); the full
forward, the paged prefill and the paged decode step are that body
under three pairs of closures, which alone know where K/V, states and
tails live.  The prefill runs the cross-decoder for ONE position: its
output at position ``t`` depends on other positions only through layer
``N / 2 + 1``'s K/V, so a prompt takes the self-decoder, the memory
layer and that K/V projection over all its positions and everything
after over its last.

Serving state: ONE cache with three kinds of leaf.  ``'k'`` / ``'v'``:
a RING leaf ``(window pages, Hkv / 2, page_size, 2 * head_dim)`` a
window layer, as ``AfmoeLM``'s window layers have, and after them ONE
full leaf (layer ``N / 2 + 1``'s) which that layer writes and every
cross layer reads.  ``'state'`` / ``'tail'``: a rows-leaf a Mamba layer
(``ops.state_shape(rows, 1, N, Di)`` float32; ``ops.tail_shape``), one
row a sequence, as ``OlmoHybridLM``'s linear layers have.  The engine
hands the three addresses as one int32 row, ``[full table | ring |
state row]``.

Not in this family yet, each raising by name: the slot-addressed cache,
prefix sharing and chunked prefill (both need a state snapshot at the
boundary), int8 K/V, speculative verify, tensor parallelism, training
(the scan has no backward).
"""

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.models import _served

#: leaves that stay float32 whatever dtype the weights take, by name
#: (the reference's ``F32_LEAVES`` has the same)
F32_LEAVES = ('A_log', 'D', 'dt_bias', 'lambda_q1', 'lambda_k1',
              'lambda_q2', 'lambda_k2')


@dataclasses.dataclass(frozen=True)
class Phi4FlashLM(_served.ServedLM):
    """Hyper-parameters under their published ``config.json`` keys;
    the Mamba sizes (which ``config.json`` does not give) under the
    family's names and defaults."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None
    dtype: Any = jnp.bfloat16

    #: what the engine's executables hand back beside the tokens: the
    #: state rows the call moved, the real prompt tokens it ran through
    #: the scan, and the positions of the SHARED K/V leaf it attended,
    #: times the layers that read them
    serve_counters = ('state_rows', 'scan_tokens', 'shared_kv_positions')
    family = 'phi4flash'

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            object.__setattr__(self, 'mamba_dt_rank',
                               -(-self.hidden_size // 16))
        n = self.num_hidden_layers
        if n < 4 or n % 2 or self.mb_per_layer != 2:
            raise ValueError(
                'phi4flash: %d layers with mb_per_layer %d do not split '
                'into a self-decoder of Mamba / window pairs, a memory '
                'layer, a K/V layer and a cross-decoder'
                % (n, self.mb_per_layer))
        if not self.tie_word_embeddings or self.mlp_bias \
                or self.lm_head_bias:
            raise NotImplementedError(
                'phi4flash with an untied head or a bias in its MLP or '
                'head')
        h, hkv = self.num_attention_heads, self.num_key_value_heads
        if self.hidden_size % h or h % 2 or hkv % 2 or h % hkv:
            raise ValueError(
                '%d query heads on %d K/V heads of hidden %d do not '
                'pair' % (h, hkv, self.hidden_size))

    # -- shapes --------------------------------------------------------
    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @functools.cached_property
    def kinds(self):
        """``'mamba' | 'window' | 'memory' | 'full' | 'gmu' | 'cross'``
        a layer."""
        half = self.num_hidden_layers // 2

        def kind(i):
            if i < half:
                return ('mamba', 'window')[i % 2]
            if i <= half + 1:
                return ('memory', 'full')[i - half]
            return ('gmu', 'cross')[(i - half) % 2]

        return tuple(kind(i) for i in range(self.num_hidden_layers))

    def _count(self, *kinds):
        return sum(k in kinds for k in self.kinds)

    def _nth(self, layer):
        """``layer``'s leaves in the cache's tuples: a window or full
        layer's place among the layers that OWN K/V (the full layer is
        the last of them), a Mamba layer's among the Mamba layers; a
        cross layer reads the full layer's."""
        kinds = self.kinds
        if kinds[layer] == 'cross':
            return self._count('window')
        own = (('window', 'full') if kinds[layer] in ('window', 'full')
               else ('mamba', 'memory'))
        return sum(k in own for k in kinds[:layer])

    def lambda_init(self, layer):
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    def window_ring(self, page_size):
        """Pages in a window layer's ring: the window from any offset."""
        return -(-self.sliding_window // page_size) + 1

    def has_state_row(self):
        """A sequence holds ONE state row for all its Mamba layers."""
        return True

    def param_shapes(self):
        """The parameter tree as shapes (names are the interface the
        plain reference's ``param_spec`` follows).  There is no
        ``lm_head``: the head is the embedding."""
        d, f, dh = self.hidden_size, self.intermediate_size, self.head_dim
        hq = self.num_attention_heads * dh
        hkv = self.num_key_value_heads * dh
        di, n, r = self.d_inner, self.mamba_d_state, self.mamba_dt_rank
        norm = {'scale': (d,), 'bias': (d,)}
        tree = {'embed': {'embedding': (self.vocab_size, d)},
                'final_norm': dict(norm)}
        for i, kind in enumerate(self.kinds):
            layer = {'norm1': dict(norm), 'norm2': dict(norm),
                     'mlp': {'w1': (d, 2 * f), 'w2': (f, d)}}
            if kind in ('mamba', 'memory'):
                layer.update(
                    in_proj=(d, 2 * di), conv=(self.mamba_d_conv, di),
                    conv_bias=(di,), x_proj=(di, r + 2 * n),
                    dt_proj=(r, di), dt_bias=(di,), A_log=(di, n),
                    D=(di,), out_proj=(di, d))
            elif kind == 'gmu':
                layer.update(in_proj=(d, di), out_proj=(di, d))
            else:
                layer.update(
                    wo=(hq, d), bo=(d,), sub_norm=(2 * dh,),
                    **{'lambda_' + name: (dh,)
                       for name in ('q1', 'k1', 'q2', 'k2')})
                if kind == 'cross':
                    layer.update(wq=(d, hq), bq=(hq,))
                else:
                    layer.update(wqkv=(d, hq + 2 * hkv),
                                 bqkv=(hq + 2 * hkv,))
            tree['layer_%d' % i] = layer
        return tree

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters: N(0, 0.02) matrices and biases, norm
        weights 1 + N(0, 0.02); ``A_log = log(1..N)`` a channel, ``D =
        1``, ``dt_bias`` the inverse softplus of a step log-uniform in
        [0.001, 0.1], lambda vectors N(0, 0.1), taps N(0, 0.5); the
        leaves of ``F32_LEAVES`` stay float32 whatever ``dtype``."""
        shapes = self.param_shapes()
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        f32 = jnp.float32
        out = []
        for n, (path, shape) in enumerate(paths):
            name = str(getattr(path[-1], 'key', path[-1]))
            draw = jax.random.normal(jax.random.fold_in(key, n), shape,
                                     f32)
            if name == 'A_log':
                leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=f32)), shape)
            elif name == 'D':
                leaf = jnp.ones(shape, f32)
            elif name == 'dt_bias':
                step = jnp.exp(jax.random.uniform(
                    jax.random.fold_in(key, n), shape, f32,
                    math.log(0.001), math.log(0.1)))
                leaf = step + jnp.log(-jnp.expm1(-step))
            elif name.startswith('lambda_'):
                leaf = 0.1 * draw
            elif name == 'conv':
                leaf = 0.5 * draw
            else:
                leaf = float(name in ('scale', 'sub_norm')) + 0.02 * draw
            out.append(leaf.astype(f32 if name in F32_LEAVES else dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- the layer, once -----------------------------------------------
    def _ln(self, x, p):
        from chainermn_tpu import ops
        return ops.layer_norm(x, p['scale'], p['bias'],
                              eps=self.layer_norm_eps)

    def _mlp(self, u, p):
        dtype = self.dtype
        gate, up = jnp.split(jnp.dot(u, p['w1'].astype(dtype)), 2,
                             axis=-1)
        return jnp.dot(jax.nn.silu(gate) * up, p['w2'].astype(dtype))

    def _ssm(self, lp, y):
        """The convolution's float32 output ``y`` (..., Di), bias in,
        to the scan's operands ``(x, delta, A, B, C, D)``."""
        f32, dtype = jnp.float32, self.dtype
        n, r = self.mamba_d_state, self.mamba_dt_rank
        x = jax.nn.silu(y).astype(dtype)
        dt, b, c = jnp.split(
            jnp.dot(x, lp['x_proj'].astype(dtype),
                    preferred_element_type=f32), [r, r + n], axis=-1)
        delta = jax.nn.softplus(
            jnp.dot(dt.astype(dtype), lp['dt_proj'].astype(dtype),
                    preferred_element_type=f32)
            + lp['dt_bias'].astype(f32))
        return (x, delta, -jnp.exp(lp['A_log'].astype(f32)), b, c,
                lp['D'].astype(f32))

    def _sequence(self, lp, xt, length=None):
        """One sequence ``xt`` (T, Di) through a Mamba layer's
        convolution and scan from an empty state: ``(m (T, Di)
        float32, the final state)``."""
        from chainermn_tpu import ops
        y = ops.causal_conv(xt, lp['conv']) \
            + lp['conv_bias'].astype(jnp.float32)
        return ops.selective_scan(*self._ssm(lp, y), length=length)

    def _qkv(self, lp, u):
        """``u`` (..., d) through a window or full layer's one
        projection: ``q`` (..., H, dh), ``k`` / ``v`` (..., Hkv, dh)."""
        h, hkv = self.num_attention_heads, self.num_key_value_heads
        dh = self.head_dim
        q, k, v = jnp.split(
            jnp.dot(u, lp['wqkv'].astype(self.dtype))
            + lp['bqkv'].astype(self.dtype),
            [h * dh, (h + hkv) * dh], axis=-1)
        return tuple(t.reshape(u.shape[:-1] + (heads, dh))
                     for t, heads in ((q, h), (k, hkv), (v, hkv)))

    def _pairs(self, q, k, v):
        """Heads ``(..., H, dh)`` / ``(..., Hkv, dh)`` (``k``, ``v``
        None in a cross layer) to the packed form: ``q`` (..., H, 2 dh)
        with head ``h`` in the half ``h % 2`` and zeros in the other,
        ``k`` / ``v`` (..., Hkv / 2, 2 dh)."""
        first = (jnp.arange(q.shape[-2]) % 2 == 0)[:, None]
        zero = jnp.zeros_like(q)
        q = jnp.concatenate([jnp.where(first, q, zero),
                             jnp.where(first, zero, q)], axis=-1)
        if k is None:
            return q, None, None
        lead = k.shape[:-2]
        return q, k.reshape(lead + (-1, 2 * self.head_dim)), \
            v.reshape(lead + (-1, 2 * self.head_dim))

    def _differ(self, layer, a, lp):
        """The two maps' results ``a`` (..., H, 2 dh), heads ``2j`` and
        ``2j + 1`` those of query pair ``j``, to ``o`` (..., H dh)."""
        f32 = jnp.float32
        init = self.lambda_init(layer)

        def dot(a, b):
            return jnp.sum(lp[a].astype(f32) * lp[b].astype(f32))

        lam = jnp.exp(dot('lambda_q1', 'lambda_k1')) \
            - jnp.exp(dot('lambda_q2', 'lambda_k2')) + init
        a = a.astype(f32)
        o = a[..., 0::2, :] - lam * a[..., 1::2, :]
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + self.layer_norm_eps)
        o = o * lp['sub_norm'].astype(f32) * (1.0 - init)
        return o.reshape(o.shape[:-2] + (-1,)).astype(self.dtype)

    def _layer(self, layer, x, lp, cache, memory, attend, recur):
        """One layer on ``x`` (..., d).  ``attend(cache, layer, q, k, v)
        -> (a, cache)`` (an attention layer: packed heads, ``k`` / ``v``
        None in a cross layer) and ``recur(cache, layer, lp, xt) -> (m,
        cache)`` (a Mamba layer: ``xt`` before the convolution, ``m``
        float32) are all that differs between the full forward, prefill
        and decode: where K/V, the states and the tails live.
        ``memory`` is the memory layer's ``m`` once that layer has run.
        Returns ``(x, cache, memory)``."""
        dtype, f32 = self.dtype, jnp.float32
        kind = self.kinds[layer]
        lead = x.shape[:-1]
        u = self._ln(x, lp['norm1'])
        if kind in ('mamba', 'memory'):
            xt, z = jnp.split(jnp.dot(u, lp['in_proj'].astype(dtype)), 2,
                              axis=-1)
            m, cache = recur(cache, layer, lp, xt)
            if kind == 'memory':
                memory = m
            mixed = jnp.dot((m * jax.nn.silu(z.astype(f32))).astype(dtype),
                            lp['out_proj'].astype(dtype))
        elif kind == 'gmu':
            gate = jax.nn.silu(
                jnp.dot(u, lp['in_proj'].astype(dtype)).astype(f32))
            mixed = jnp.dot((gate * memory).astype(dtype),
                            lp['out_proj'].astype(dtype))
        else:
            if kind == 'cross':
                q = (jnp.dot(u, lp['wq'].astype(dtype))
                     + lp['bq'].astype(dtype)).reshape(
                         lead + (-1, self.head_dim))
                k = v = None
            else:
                q, k, v = self._qkv(lp, u)
            a, cache = attend(cache, layer, *self._pairs(q, k, v))
            mixed = jnp.dot(self._differ(layer, a, lp),
                            lp['wo'].astype(dtype)) \
                + lp['bo'].astype(dtype)
        x = x + mixed
        return x + self._mlp(self._ln(x, lp['norm2']), lp['mlp']), \
            cache, memory

    def _layers(self, params, x, cache, memory, layers, attend, recur):
        for i in layers:
            x, cache, memory = self._layer(
                i, x, params['layer_%d' % i], cache, memory, attend,
                recur)
        return x, cache, memory

    def _embed(self, params, tokens):
        return jnp.take(params['embed']['embedding'], tokens,
                        axis=0).astype(self.dtype)

    def _logits(self, params, x):
        """The final norm, then the embedding as the head."""
        return jnp.einsum(
            '...d,vd->...v', self._ln(x, params['final_norm']),
            params['embed']['embedding'].astype(self.dtype),
            preferred_element_type=jnp.float32)

    @property
    def _scale(self):
        return self.head_dim ** -0.5

    @property
    def _group(self):
        """Packed query heads a packed K/V head."""
        return 2 * self.num_attention_heads // self.num_key_value_heads

    def _window(self, layer):
        return (self.sliding_window if self.kinds[layer] == 'window'
                else None)

    # -- full-sequence forward -----------------------------------------
    def apply(self, params, tokens):
        """tokens (B, T) int32 -> float32 logits (B, T, V): every layer
        at every position."""
        from chainermn_tpu import ops

        def attend(shared, layer, q, k, v):
            if k is None:
                k, v = shared
            elif self.kinds[layer] == 'full':
                shared = (k, v)
            return ops.flash_attention(
                q, k, v, causal=True, scale=self._scale,
                window=self._window(layer)), shared

        def recur(shared, layer, lp, xt):
            return jax.vmap(lambda row: self._sequence(lp, row)[0])(
                xt), shared

        x, _, _ = self._layers(
            params, self._embed(params, tokens), None, None,
            range(self.num_hidden_layers), attend, recur)
        return self._logits(params, x)

    __call__ = apply

    # -- the serving protocol (``_served.ServedLM``) --------------------
    def init_paged_kv_cache(self, n_pages, page_size, n_window_pages=0,
                            n_state_rows=0, int8_kv=False, dtype=None):
        """``{'k' | 'v': a ring leaf of ``n_window_pages`` a WINDOW
        layer, then the ONE full leaf of ``n_pages``; 'state' | 'tail':
        a leaf of ``n_state_rows`` rows a MAMBA layer}``.  Page 0 and
        row 0 are their pools' scratch."""
        from chainermn_tpu import ops
        if int8_kv:
            raise NotImplementedError('Phi4FlashLM: int8 K/V cache')
        if n_window_pages < 2 or n_state_rows < 2:
            raise ValueError(
                'window layers need their own pages (n_window_pages) '
                'and Mamba layers their own state rows (n_state_rows)')
        dtype = dtype or self.dtype
        n_window = self._count('window')
        n_mamba = self._count('mamba', 'memory')
        page = (self.num_key_value_heads // 2, page_size,
                2 * self.head_dim)

        def leaves(shapes, dtype):
            return tuple(jnp.zeros(shape, dtype) for shape in shapes)

        pools = [(n_window_pages,) + page] * n_window \
            + [(n_pages,) + page]
        state = ops.state_shape(n_state_rows, 1, self.mamba_d_state,
                                self.d_inner)
        tail = ops.tail_shape(n_state_rows, self.mamba_d_conv,
                              self.d_inner, dtype)
        return {'k': leaves(pools, dtype), 'v': leaves(pools, dtype),
                'state': leaves([state] * n_mamba, jnp.float32),
                'tail': leaves([tail] * n_mamba, dtype)}

    @staticmethod
    def paged_cache_bytes(cache):
        """``(bytes of one full page, of one state row, of one ring
        page)``, each over all the layers that HOLD one: the full page
        once, whatever the number of layers that read it.  ``cache``
        may be its structs."""
        return (_served.row_bytes((cache['k'][-1], cache['v'][-1])),
                _served.row_bytes(cache['state'] + cache['tail']),
                _served.row_bytes(cache['k'][:-1] + cache['v'][:-1]))

    def _tables(self, cache, page_tables):
        """``[full table | ring | state row]`` apart, and the page
        size."""
        ps = cache['k'][0].shape[2]
        tables = page_tables.astype(jnp.int32)
        n_full = tables.shape[-1] - self.window_ring(ps) - 1
        return (ps, tables[..., :n_full], tables[..., n_full:-1],
                tables[..., -1])

    def _counters(self, state_rows, scan_tokens, shared_kv_positions):
        return tuple(jnp.asarray(c, jnp.float32) for c in (
            state_rows, scan_tokens, shared_kv_positions))

    @property
    def _readers(self):
        """Layers that attend the shared leaf in one call."""
        return 1 + self._count('cross')

    def decode_step_paged(self, params, cache, tokens, positions,
                          page_tables):
        """One token a row: ``tokens`` / ``positions`` (N,) and
        ``page_tables`` (N, full + ring + 1).  Returns ``(logits (N, V)
        float32, cache, counters)``."""
        from chainermn_tpu import ops

        ps, full, ring, state_rows = self._tables(cache, page_tables)
        positions = positions.astype(jnp.int32)
        lengths = positions + 1
        rows = jnp.arange(tokens.shape[0])
        offsets = positions % ps
        full_pages = full[rows, positions // ps]
        ring_pages = ring[rows, (positions // ps) % ring.shape[1]]

        def attend(cache, layer, q, k, v):
            at = self._nth(layer)
            window = self._window(layer)
            if k is not None:
                k_leaf, v_leaf = ops.paged_kv_append(
                    cache['k'][at], cache['v'][at], k, v,
                    full_pages if window is None else ring_pages,
                    offsets)
                cache = _served.with_leaves(cache, at, k=k_leaf,
                                            v=v_leaf)
            return ops.flash_attention_decode_paged(
                q, cache['k'][at], cache['v'][at],
                full if window is None else ring, lengths,
                scale=self._scale, group=self._group, window=window,
                head_major=True), cache

        def recur(cache, layer, lp, xt):
            at = self._nth(layer)
            y, tail = ops.causal_conv_step(
                cache['tail'][at], state_rows, xt, lp['conv'],
                lp['conv_bias'])
            m, state = ops.selective_scan_step(
                cache['state'][at], state_rows, *self._ssm(lp, y))
            return m, _served.with_leaves(cache, at, state=state,
                                          tail=tail)

        x, cache, _ = self._layers(
            params, self._embed(params, tokens), cache, None,
            range(self.num_hidden_layers), attend, recur)
        return (self._logits(params, x), cache, self._counters(
            tokens.shape[0], 0, jnp.sum(lengths) * self._readers))

    def decode_paged_grid(self, cache, lengths, n_full, n_ring, tp=1):
        """``(pages read, grid steps)`` of one ``decode_step_paged``
        over rows of these live ``lengths`` (host integers): every
        reader of the ONE full leaf over ``n_full`` table columns, a
        window layer over its ring."""
        from chainermn_tpu import ops
        leaf = cache['k'][-1]
        total = [0, 0]
        for layers, n_max, window in (
                (self._readers, n_full, None),
                (self._count('window'), n_ring, self.sliding_window)):
            grid = ops.decode_paged_grid(
                lengths, leaf.shape[1:], leaf.dtype, n_max,
                window=window, head_major=True)
            total = [t + layers * g for t, g in zip(total, grid)]
        return tuple(total)

    def prefill_paged(self, params, cache, tokens, length, page_table,
                      pos0):
        """A whole prompt in one call: ``tokens`` (1, C) padded to a
        bucket, ``length`` the valid prefix, ``page_table`` (full +
        ring + 1,), ``pos0`` 0 (no chunks, no shared prefix: the engine
        refuses both for this family).  The self-decoder and the memory
        layer run over every position: a window layer banks the pages
        its ring holds once the prompt is in, a Mamba layer writes the
        sequence's state row and tail WHOLE.  The K/V layer projects
        every position and banks every page the prompt reaches; then
        it, the cross-decoder, the final norm and the head run for
        position ``length - 1`` ALONE, over those K and V.  Returns
        ``(logits (V,) float32 there, cache, counters)``."""
        from chainermn_tpu import ops

        b, c = tokens.shape
        if b != 1:
            raise ValueError('prefill_paged takes one prompt per call, '
                             'got batch %d' % b)
        del pos0
        ps, full, ring, state_row = self._tables(cache, page_table)
        length = jnp.asarray(length, jnp.int32)
        n_pages = -(-c // ps)
        page = jnp.arange(n_pages, dtype=jnp.int32)
        last = (length - 1) // ps
        full_ids = jnp.where(
            page <= last, full[jnp.minimum(page, full.shape[0] - 1)], 0)
        ring_ids = jnp.where(
            jnp.logical_and(page <= last, page > last - ring.shape[0]),
            ring[page % ring.shape[0]], 0)

        def banked(cache, layer, ids, k, v):
            # (C, heads, D) -> (pages, heads, page_size, D), written at
            # the sequence's pages (the scratch page where it has none)
            def pages_of(x):
                x = jnp.pad(x, ((0, n_pages * ps - c), (0, 0), (0, 0)))
                return jnp.swapaxes(
                    x.reshape((n_pages, ps) + x.shape[1:]), 1, 2)

            at = self._nth(layer)
            return _served.with_leaves(cache, at, **{
                name: cache[name][at].at[ids].set(
                    pages_of(new[0]).astype(cache[name][at].dtype))
                for name, new in (('k', k), ('v', v))})

        def attend(cache, layer, q, k, v):
            return (ops.flash_attention(
                q, k, v, causal=True, scale=self._scale,
                window=self.sliding_window),
                    banked(cache, layer, ring_ids, k, v))

        def recur(cache, layer, lp, xt):
            at = self._nth(layer)
            m, state = self._sequence(lp, xt[0], length)
            tail = ops.conv_tail(xt[0], length, self.mamba_d_conv)
            return m[None], _served.with_leaves(
                cache, at,
                state=cache['state'][at].at[state_row].set(
                    ops.pack_state(state[None])),
                tail=cache['tail'][at].at[state_row].set(
                    ops.pack_tail(tail, cache['tail'][at].dtype)))

        shared_at = self.kinds.index('full')
        x, cache, memory = self._layers(
            params, self._embed(params, tokens), cache, None,
            range(shared_at), attend, recur)
        # THE K/V: every position's, from the K/V layer's own norm and
        # projection (its queries are wanted at one position only)
        lp = params['layer_%d' % shared_at]
        _, k, v = self._pairs(*self._qkv(lp, self._ln(x, lp['norm1'])))
        cache = banked(cache, shared_at, full_ids, k, v)
        live = jnp.arange(c) < length

        def attend_last(cache, layer, q, _k, _v):
            # one query row (1, H, 2 dh) over the K/V of every position
            f32 = jnp.float32
            hkv = k.shape[2]
            s = jnp.einsum('hgd,thd->hgt', q.reshape(hkv, -1, q.shape[-1]),
                           k[0], preferred_element_type=f32) * self._scale
            p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
            a = jnp.einsum('hgt,thd->hgd', p.astype(self.dtype), v[0],
                           preferred_element_type=f32)
            return a.reshape(q.shape), cache

        def at_last(a):
            return lax.dynamic_slice_in_dim(a[0], length - 1, 1, axis=0)

        x_last, cache, _ = self._layers(
            params, at_last(x), cache, at_last(memory),
            range(shared_at, self.num_hidden_layers), attend_last, None)
        return (self._logits(params, x_last)[0], cache, self._counters(
            1, length, length * self._readers))
