"""The ``solar_open2`` family of decoder LMs (Upstage's Solar Open 2):
linear-attention layers with a decay PER KEY CHANNEL beside gated
grouped-query attention without positions, every layer's feed-forward
sparse.

Layer ``i`` is ``gqa`` if ``i`` is in ``gqa_layers`` (one in four),
else ``kda``.  Both kinds, pre-norm: ``h = x + mixer(rms(x))``, ``h = h
+ moe(rms(h))``; no bias but the two named below, untied head,
embeddings unscaled.

A ``kda`` mixer is Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692): ``q``, ``k``, ``v`` (``linear_attn_config``:
``num_heads`` heads of ``head_dim``, keys and values alike), each
through a causal depthwise convolution of ``short_conv_kernel_size``
taps (the three side by side are ONE convolution over ``q | k | v``)
then ``silu``; ``q`` and ``k`` L2-normalised per head (``q`` scaled by
``dk ** -0.5``); the decay ``g = -exp(A_log[h]) * softplus((x Wf1) Wf2
+ dt_bias)``, a VECTOR over the key dimension a head (``Wf1``, ``Wf2``
the low-rank pair ``kda_use_full_proj`` false names; rank
``head_dim``); ``beta = 2 * sigmoid(x Wb)`` (the 2 is
``kda_allow_neg_eigval``); the gated delta rule with that decay
(:mod:`chainermn_tpu.ops.gated_delta`: ``S~ = diag(exp(g_t)) S_{t-1}``)
on a per-head float32 state ``(dk, dv)``; an RMSNorm over ``dv`` times
``sigmoid((x Wg1) Wg2 + b_g)``, then the output projection.  It keeps
no keys and values: a sequence's whole past is its state and the last
``taps - 1`` pre-convolution positions.

A ``gqa`` mixer is causal softmax attention of ``num_attention_heads``
query heads on ``num_key_value_heads`` K/V heads with NO positional
encoding (``use_rope`` false) and no q / k norm, its output gated
elementwise, ``a * sigmoid(x Wg)`` (``use_gqa_gate``: ``afmoe``'s
gate), before the output projection.

The feed-forward of EVERY layer (``first_k_dense_replace`` 0) is
``models/_experts.py``'s: a sigmoid router over ``router_experts``
outputs, top-``num_experts_per_tok`` on score + a stored bias, gates
normalised, beside ``n_shared_experts`` shared experts as one SwiGLU.

*A share of the experts*, named as ``deepseek_v3`` names it:
``n_routed_experts`` counts the experts this model HOLDS,
``router_experts`` (default: the same) how many the router chooses
among, ``first_expert`` which id the first held one has.  The layer
computes its own experts' part; the exchange is not built
(``docs/mesh_parallelism.md``).  A sliced vocabulary is a smaller
``vocab_size``.

The layer is written ONCE (:meth:`SolarOpen2LM._layer`); the
full-sequence forward, the paged prefill and the paged decode step are
that body under three pairs of closures (``olmo_hybrid``'s pattern).

Serving state: ONE cache with two kinds of leaf.  A ``gqa`` layer has a
K and a V page pool ``(pages, kv_heads, page_size, head_dim)``; a
``kda`` layer a STATE leaf (``ops.state_shape``: 64 x 128 x 128
float32, 4,194,304 B a row) and a convolution-TAIL leaf
(``ops.tail_shape`` over the 24,576 channels of ``q | k | v``), one
row a sequence.  The engine hands both addresses as one int32 row,
``[full table | state row]``.

Not in this family yet, each raising by name: the slot-addressed cache,
prefix sharing and chunked prefill (both need a state snapshot at the
boundary), int8 K/V, speculative verify, tensor parallelism, training
(the chunked rule has no backward); rotary positions (``use_rope``),
dense leading layers, the full-rank gate projections
(``kda_use_full_proj``), an ungated ``gqa`` layer.
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.models import _experts, _served

#: the published ``linear_attn_config``
_LINEAR = {'short_conv_kernel_size': 4, 'head_dim': 128, 'num_heads': 64,
           'num_kv_heads': None}
#: seeded leaves that are not N(0, 0.02): (mean, std) by name.  The
#: decay ``exp(g)`` then spreads over about (0.3, 1) from channel to
#: channel and the convolution's output is of order one, so that no
#: path is dead.
_INIT = {'A_log': (-0.7, 0.4), 'dt_bias': (0.3, 0.8), 'conv': (0.0, 0.5)}
#: positions of a prompt a ``kda`` layer solves before its state moves
#: on (``ops/gated_delta.py``: 524,288 B of pair arrays a position)
SEGMENT = 1024


@dataclasses.dataclass(frozen=True)
class SolarOpen2LM(_served.ServedLM):
    """Hyper-parameters under their published ``config.json`` keys,
    then the share, which no ``config.json`` has."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240      # a dense layer's: there is none
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 0
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    use_rope: bool = False
    gqa_interval: int = 3
    gqa_layers: Optional[Tuple[int, ...]] = None
    use_gqa_gate: bool = True
    linear_attn_config: Any = None
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    router_experts: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16

    #: what the engine's executables hand back beside the tokens, each
    #: expert counter the mean over the layers: held experts with a
    #: row, the fullest held expert's rows over the held mean, the
    #: assignments on held experts; then the state rows the call moved
    #: and the real prompt tokens it ran through the chunked rule
    serve_counters = ('experts_touched', 'expert_load_max',
                      'held_assignments', 'state_rows', 'scan_tokens')
    family = 'solar_open2'

    def __post_init__(self):
        if self.gqa_layers is None:
            every = self.gqa_interval + 1
            layers = tuple(range(0, self.num_hidden_layers, every))
        else:
            layers = tuple(int(i) for i in self.gqa_layers)
        object.__setattr__(self, 'gqa_layers', layers)
        object.__setattr__(self, 'linear_attn_config', dict(
            _LINEAR, **(self.linear_attn_config or {})))
        if any(not 0 <= i < self.num_hidden_layers for i in layers):
            raise ValueError('gqa_layers %r are not among %d layers'
                             % (layers, self.num_hidden_layers))
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError('%d K/V heads do not divide %d query heads'
                             % (self.num_key_value_heads,
                                self.num_attention_heads))
        for key, wrong, what in (
                ('use_rope', True, 'rotary positions in a gqa layer'),
                ('use_gqa_gate', False, 'an ungated gqa layer'),
                ('kda_use_full_proj', True,
                 'full-rank gate projections in a kda layer')):
            if getattr(self, key) == wrong:
                raise NotImplementedError('solar_open2 with %s (%s %r)'
                                          % (what, key, wrong))
        if self.first_k_dense_replace:
            raise NotImplementedError(
                'solar_open2 with %d dense leading layers '
                '(first_k_dense_replace)' % self.first_k_dense_replace)
        if self.linear_attn_config['num_kv_heads'] not in (
                None, self.linear_heads):
            raise NotImplementedError(
                'solar_open2 kda with %r key / value heads on %d'
                % (self.linear_attn_config['num_kv_heads'],
                   self.linear_heads))
        width = self.router_width
        if not 0 <= self.first_expert <= width - self.n_routed_experts:
            raise ValueError(
                'experts %d .. %d are not among the router\'s %d'
                % (self.first_expert,
                   self.first_expert + self.n_routed_experts - 1, width))

    # -- shapes --------------------------------------------------------
    @property
    def router_width(self):
        return self.router_experts or self.n_routed_experts

    @property
    def group(self):
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def linear_heads(self):
        return self.linear_attn_config['num_heads']

    @property
    def linear_head_dim(self):
        """``dk`` and ``dv`` of a ``kda`` head, and the rank of the two
        low-rank gate projections."""
        return self.linear_attn_config['head_dim']

    @property
    def conv_taps(self):
        return self.linear_attn_config['short_conv_kernel_size']

    @property
    def conv_channels(self):
        """``q | k | v`` as the (three) convolutions see them."""
        return 3 * self.linear_heads * self.linear_head_dim

    def kda(self, layer):
        return layer not in self.gqa_layers

    def _nth(self, layer):
        """``layer``'s place among the layers of its kind: its index in
        the cache's tuples of leaves."""
        return sum(1 for i in range(layer)
                   if self.kda(i) == self.kda(layer))

    def has_state_row(self):
        """ONE state row a sequence for all ``kda`` layers; none in a
        model without one."""
        return len(self.gqa_layers) < self.num_hidden_layers

    def param_shapes(self):
        """The parameter tree as shapes (names are the interface the
        plain reference's ``param_spec`` follows)."""
        d, dh = self.hidden_size, self.head_dim
        hq = self.num_attention_heads * dh
        hkv = self.num_key_value_heads * dh
        heads, dl = self.linear_heads, self.linear_head_dim
        wide = heads * dl
        f, e = self.moe_intermediate_size, self.n_routed_experts

        def swiglu(width, lead=()):
            return {'w1': lead + (d, width), 'w3': lead + (d, width),
                    'w2': lead + (width, d)}

        tree = {'embed': {'embedding': (self.vocab_size, d)},
                'final_norm': (d,), 'lm_head': (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            layer = {'input_norm': (d,), 'pre_mlp_norm': (d,),
                     'router': (d, self.router_width),
                     'expert_bias': (self.router_width,),
                     'experts': swiglu(f, (e,)),
                     'shared': swiglu(f * self.n_shared_experts)}
            if self.kda(i):
                layer.update(
                    wq=(d, wide), wk=(d, wide), wv=(d, wide),
                    conv=(self.conv_taps, self.conv_channels),
                    wf1=(d, dl), wf2=(dl, wide), dt_bias=(wide,),
                    A_log=(heads,), wb=(d, heads),
                    wg1=(d, dl), wg2=(dl, wide), b_g=(wide,),
                    o_norm=(dl,), wo=(wide, d))
            else:
                layer.update(wq=(d, hq), wk=(d, hkv), wv=(d, hkv),
                             wg=(d, hq), wo=(hq, d))
            tree['layer_%d' % i] = layer
        return tree

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters: N(0, 0.02) matrices and biases, norms 1 +
        N(0, 0.02), ``A_log`` / ``dt_bias`` / ``conv`` as
        :data:`_INIT`."""
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
        """The convolutions' float32 output ``y`` (..., channels) to the
        rule's operands: ``silu``, the split into heads, ``q`` and
        ``k`` L2-normalised per head (eps 1e-6) and ``q`` scaled."""
        heads, dl = self.linear_heads, self.linear_head_dim
        q, k, v = (x.reshape(x.shape[:-1] + (heads, dl))
                   for x in jnp.split(jax.nn.silu(y), 3, axis=-1))

        def unit(x):
            return x * lax.rsqrt(jnp.sum(jnp.square(x), -1,
                                         keepdims=True) + 1e-6)

        return tuple(x.astype(self.dtype)
                     for x in (unit(q) * dl ** -0.5, unit(k), v))

    def _low_rank(self, a, lp, down, up, bias):
        """``(a W_down) W_up + bias`` in float32 (..., heads, dl)."""
        dtype = self.dtype
        y = jnp.dot(jnp.dot(a, lp[down].astype(dtype)),
                    lp[up].astype(dtype),
                    preferred_element_type=jnp.float32)
        y = y + lp[bias].astype(jnp.float32)
        return y.reshape(y.shape[:-1] + (self.linear_heads, -1))

    def _layer(self, layer, x, lp, cache, attend, recur):
        """One layer on ``x`` (..., d).  ``attend(cache, layer, q, k, v)
        -> (attn, cache)`` (a ``gqa`` layer) and ``recur(cache, layer,
        taps, qkv, g, beta) -> (o, cache)`` (a ``kda`` layer: ``qkv``
        before the convolutions, ``g`` (..., H, dk), ``o`` float32 per
        head) are all that differs between the full forward, prefill
        and decode.  Returns the layer's three expert counters too."""
        dtype = self.dtype
        lead = x.shape[:-1]
        a = self._rms(x, lp['input_norm'])
        if self.kda(layer):
            qkv = jnp.concatenate(
                [jnp.dot(a, lp[w].astype(dtype))
                 for w in ('wq', 'wk', 'wv')], axis=-1)
            g = -jnp.exp(lp['A_log'].astype(jnp.float32))[:, None] \
                * jax.nn.softplus(
                    self._low_rank(a, lp, 'wf1', 'wf2', 'dt_bias'))
            beta = jax.nn.sigmoid(jnp.dot(
                a, lp['wb'].astype(dtype),
                preferred_element_type=jnp.float32)) * (
                    2.0 if self.kda_allow_neg_eigval else 1.0)
            o, cache = recur(cache, layer, lp['conv'], qkv, g, beta)
            mixed = self._rms(o, lp['o_norm']) * jax.nn.sigmoid(
                self._low_rank(a, lp, 'wg1', 'wg2', 'b_g')).astype(dtype)
        else:
            q, k, v = (
                jnp.dot(a, lp[w].astype(dtype)).reshape(
                    lead + (heads, self.head_dim))
                for w, heads in (('wq', self.num_attention_heads),
                                 ('wk', self.num_key_value_heads),
                                 ('wv', self.num_key_value_heads)))
            attn, cache = attend(cache, layer, q, k, v)
            mixed = attn.reshape(lead + (-1,)).astype(dtype) \
                * jax.nn.sigmoid(jnp.dot(a, lp['wg'].astype(dtype)))
        x = x + jnp.dot(mixed.reshape(lead + (-1,)).astype(dtype),
                        lp['wo'].astype(dtype))
        m = self._rms(x, lp['pre_mlp_norm'])
        ff, counters = _experts.sigmoid_routed_experts(
            m.reshape(-1, m.shape[-1]), lp, self.num_experts_per_tok,
            self.norm_topk_prob, self.routed_scaling_factor, dtype,
            first=self.first_expert)
        return x + ff.reshape(m.shape), cache, counters

    def _layers(self, params, tokens, cache, attend, recur):
        """Every layer in turn; the expert counters as the mean over
        the layers."""
        x = jnp.take(params['embed']['embedding'], tokens,
                     axis=0).astype(self.dtype)
        seen = []
        for i in range(self.num_hidden_layers):
            x, cache, counters = self._layer(
                i, x, params['layer_%d' % i], cache, attend, recur)
            seen.append(counters)
        return x, cache, tuple(sum(c) / len(seen) for c in zip(*seen))

    def _logits(self, params, x):
        return jnp.dot(self._rms(x, params['final_norm']),
                       params['lm_head'].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _scan(self, taps, qkv, g, beta, length=None):
        """A whole sequence through one ``kda`` layer's recurrence from
        an empty state: ``qkv`` (T, channels), ``g`` (T, H, dk),
        ``beta`` (T, H).  Returns ``(o (T, H, dv) float32, the final
        state)``.  Over :data:`SEGMENT` positions the prompt goes
        through a segment at a time, the state and the convolutions'
        last ``taps - 1`` inputs carried: what is float32 a position
        (the convolutions' output, the rule's operands and its pair
        matrices) is then a segment's, whatever the prompt's length.
        A bucket's pad lies past ``length``: identity steps."""
        from chainermn_tpu import ops
        t = qkv.shape[0]
        k = taps.shape[0]
        length = t if length is None else length

        def segment(carry, x):
            state, before, start = carry
            qkv, g, beta = x
            seen = jnp.concatenate([before, qkv])
            o, state = ops.gated_delta_rule(
                *self._qkv(ops.causal_conv(seen, taps)[k - 1:]), g, beta,
                state0=state, length=length - start)
            return (state, seen[seen.shape[0] - (k - 1):],
                    start + qkv.shape[0]), o

        heads, dl = self.linear_heads, self.linear_head_dim
        carry = (jnp.zeros((heads, dl, dl), jnp.float32),
                 jnp.zeros((k - 1, qkv.shape[1]), qkv.dtype),
                 jnp.zeros((), jnp.int32))
        seg = min(SEGMENT, t)
        n = -(-t // seg)
        (state, _, _), o = lax.scan(segment, carry, tuple(
            jnp.pad(x, ((0, n * seg - t),) + ((0, 0),) * (x.ndim - 1)
                    ).reshape((n, seg) + x.shape[1:])
            for x in (qkv, g, beta)))
        return o.reshape((n * seg,) + o.shape[2:])[:t], state

    # -- full-sequence forward -----------------------------------------
    def apply(self, params, tokens):
        """tokens (B, T) int32 -> float32 logits (B, T, V)."""
        from chainermn_tpu import ops

        def attend(cache, layer, q, k, v):
            return ops.flash_attention(q, k, v, causal=True), cache

        def recur(cache, layer, taps, qkv, g, beta):
            return jax.vmap(lambda *row: self._scan(taps, *row)[0])(
                qkv, g, beta), cache

        x, _, _ = self._layers(params, tokens, None, attend, recur)
        return self._logits(params, x)

    __call__ = apply

    # -- the serving protocol (``_served.ServedLM``) --------------------
    def init_paged_kv_cache(self, n_pages, page_size, n_state_rows=0,
                            int8_kv=False, dtype=None):
        """``{'k' | 'v': a page pool a GQA layer, 'state' | 'tail': a
        leaf of ``n_state_rows`` rows a KDA layer}``: pools ``(pages,
        kv_heads, page_size, head_dim)`` (page 0 the scratch page),
        states ``ops.state_shape`` float32 and tails
        ``ops.tail_shape``; row 0 of both the scratch row."""
        from chainermn_tpu import ops
        if int8_kv:
            raise NotImplementedError('SolarOpen2LM: int8 K/V cache')
        if self.has_state_row() and n_state_rows < 2:
            raise ValueError('kda layers need their own state rows '
                             '(n_state_rows)')
        dtype = dtype or self.dtype
        n_gqa = len(self.gqa_layers)
        n_kda = self.num_hidden_layers - n_gqa

        def leaves(n, shape, dtype):
            return tuple(jnp.zeros(shape, dtype) for _ in range(n))

        pool = (n_pages, self.num_key_value_heads, page_size,
                self.head_dim)
        state = ops.state_shape(n_state_rows, self.linear_heads,
                                self.linear_head_dim,
                                self.linear_head_dim)
        tail = ops.tail_shape(n_state_rows, self.conv_taps,
                              self.conv_channels, dtype)
        return {'k': leaves(n_gqa, pool, dtype),
                'v': leaves(n_gqa, pool, dtype),
                'state': leaves(n_kda, state, jnp.float32),
                'tail': leaves(n_kda, tail, dtype)}

    @staticmethod
    def paged_cache_bytes(cache):
        """``(bytes of one K/V page, bytes of one state row)``, each
        over all the layers that hold one; ``cache`` may be its
        structs."""
        return (_served.row_bytes(cache['k'] + cache['v']),
                _served.row_bytes(cache['state'] + cache['tail']))

    def _tables(self, page_tables):
        """``[full table | state row]`` apart (the row of a model with
        no ``kda`` layer: the scratch row, never read)."""
        tables = page_tables.astype(jnp.int32)
        if not self.has_state_row():
            return tables, jnp.zeros(tables.shape[:-1], jnp.int32)
        return tables[..., :-1], tables[..., -1]

    def _counters(self, experts, state_rows, scan_tokens):
        return experts + (jnp.asarray(state_rows, jnp.float32),
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

        x, cache, experts = self._layers(params, tokens, cache, attend,
                                         recur)
        return (self._logits(params, x), cache, self._counters(
            experts, tokens.shape[0] * self.has_state_row(), 0))

    def decode_paged_grid(self, cache, lengths, n_full, n_ring=0, tp=1):
        """``(pages read, grid steps)`` of one ``decode_step_paged``
        over rows of these live ``lengths`` (host integers), summed
        over the GQA layers: a ``kda`` layer reads no page."""
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
        refuses both for this family).  A GQA layer attends over the
        fresh K/V and banks every page the prompt reaches; a ``kda``
        layer runs the chunked rule, in which a position at or past
        ``length`` changes nothing, and writes the sequence's state row
        and tail WHOLE.  The experts see every position of the bucket,
        pad ones too.  Returns ``(logits (V,) float32 at ``length -
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

        x, cache, experts = self._layers(params, tokens, cache, attend,
                                         recur)
        x_last = lax.dynamic_slice_in_dim(x[0], length - 1, 1, axis=0)
        return (self._logits(params, x_last)[0], cache, self._counters(
            experts, self.has_state_row(),
            length * self.has_state_row()))
