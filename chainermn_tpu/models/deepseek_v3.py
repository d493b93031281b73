"""The ``deepseek_v3`` family of decoder LMs (``model_type``
``deepseek_v3``: DeepSeek-V3's layer; Kanana-2-30B-A3B is one) as a
TRAINED family: a differentiable full forward, nothing served.

What a layer computes: ``x <- x + Attn(RMSNorm_w(x))``, ``x <- x +
FFN(RMSNorm_w(x))``; the logits are ``RMSNorm_w(x) W_head``; no bias
anywhere, the head untied.

*Attention*: latent attention (``models/_mla.py``, shared with
``xing4``), EXPANDED: ``q = u W_q`` per head ``[q_nope | q_rope]`` (no
query latent when ``q_lora_rank`` is null), ``[c | k_r] = u W_kva``,
``c`` normed, rotary on ``q_rope`` and ``k_r`` (``rope_interleave``:
adjacent pairs), ``[k_nope | v] = c W_kvb`` per head, then
``ops.flash_attention`` at 192 / 128, whose backward carries the two
widths.

*Feed-forward*: a SwiGLU in the first ``first_k_dense_replace`` layers,
then ``models/_experts.py`` (``noaux_tc``: sigmoid scores, top-k on
score + the stored ``expert_bias``, normalised gates x
``routed_scaling_factor``, ``n_shared_experts`` shared experts as one
SwiGLU).  ``expert_bias`` takes no gradient (it only chooses) and no
balance rule updates it here.

*A share of the experts.*  ``n_routed_experts`` counts the experts
this model HOLDS; ``router_experts`` (default: the same) how many the
router chooses among, and ``first_expert`` which id the first held one
has: expert parallelism's share of a layer, without the exchange
(``docs/mesh_parallelism.md``).

``train_recompute='layer'`` puts each layer's body under
``jax.checkpoint`` with a policy that keeps what
``ops/flash_attention.py`` names (``RESIDUAL_NAMES``): the attention
kernel's output (T x H x v) and its rows' statistics (T x H float32),
which only the kernel can make, and its merged ``q`` / ``k`` / ``v``
(T x H x 192, 192 and 128).  The backward holds one layer's internals
at a time and recomputes a layer's forward from its input (T x d, kept
a layer) BUT FOR the attention kernel, which runs once, and the query
projection, rotary and latent expansion that feed it.  The step's
counters say what that costs: ``checkpoint_kept_bytes``, the named
values' bytes summed over the layers (0 without the rule).

The layer is written once (:meth:`DeepseekV3LM._layer`).  Serving entry
points raise by name (``unserved``, through ``_served.ServedLM``):
served, this family is ``xing4``'s path less the residual streams, the
query latent and YaRN, and is not built.
"""

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from chainermn_tpu.models import _experts, _mla, _served
from chainermn_tpu.ops.flash_attention import (RESIDUAL_NAMES,
                                               residual_bytes)


@dataclasses.dataclass(frozen=True)
class DeepseekV3LM(_served.ServedLM):
    """Hyper-parameters under their published ``config.json`` keys
    (defaults: Kanana-2-30B-A3B's), then the share and the recompute
    rule, which no ``config.json`` has."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    scoring_func: str = 'sigmoid'
    topk_method: str = 'noaux_tc'
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Optional[Any] = None
    rope_interleave: bool = True
    max_position_embeddings: int = 32768
    router_experts: Optional[int] = None
    first_expert: int = 0
    #: None, or 'layer': the backward recomputes a layer's forward but
    #: for the attention kernel: its inputs, output and statistics are
    #: kept
    train_recompute: Optional[str] = None
    dtype: Any = jnp.bfloat16

    #: keys of the loss's aux that ``StandardUpdater.update`` hangs on
    #: its ``train_update`` span
    span_counters = ('held_assignments', 'assignments',
                     'experts_with_row', 'expert_load_max_over_mean',
                     'checkpoint_kept_bytes')
    #: every serving member refuses with this
    unserved = ('this family is trained, not served (served, it is '
                'xing4\'s path less the residual streams, the query '
                'latent and YaRN)')

    def __post_init__(self):
        if self.scoring_func != 'sigmoid' or self.topk_method != 'noaux_tc':
            raise NotImplementedError(
                'deepseek_v3 router %r / %r' % (self.scoring_func,
                                                self.topk_method))
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                'deepseek_v3 router with a group limit (n_group %d, '
                'topk_group %d)' % (self.n_group, self.topk_group))
        if self.q_lora_rank is not None:
            raise NotImplementedError(
                'deepseek_v3 with a query latent (q_lora_rank %r): '
                'xing4 has that path' % (self.q_lora_rank,))
        if self.rope_scaling:
            raise NotImplementedError('rope_scaling %r'
                                      % (self.rope_scaling,))
        if self.train_recompute not in (None, 'layer'):
            raise ValueError('train_recompute %r (None or "layer")'
                             % (self.train_recompute,))
        width = self.router_width
        if not 0 <= self.first_expert <= width - self.n_routed_experts:
            raise ValueError(
                'experts %d .. %d are not among the router\'s %d'
                % (self.first_expert,
                   self.first_expert + self.n_routed_experts - 1, width))

    @classmethod
    def from_config(cls, cfg, **overrides):
        """As the base's.  A configuration cut to a chip's share says
        so beside the published keys (``router_experts``,
        ``first_expert``); ``train.recompute`` is the recompute rule
        where no ``train_recompute`` key is."""
        train = cfg.get('train', {})
        if 'recompute' in train:
            cfg = {'train_recompute': train['recompute'], **cfg}
        return super().from_config(cfg, **overrides)

    # -- shapes --------------------------------------------------------
    @property
    def router_width(self):
        return self.router_experts or self.n_routed_experts

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        return self.qk_head_dim ** -0.5

    def param_shapes(self):
        """The parameter tree as shapes (names are the interface the
        plain reference's ``param_spec`` follows)."""
        d, h = self.hidden_size, self.num_attention_heads
        f, e = self.moe_intermediate_size, self.n_routed_experts

        def swiglu(width, lead=()):
            return {'w1': lead + (d, width), 'w3': lead + (d, width),
                    'w2': lead + (width, d)}

        tree = {'embed': {'embedding': (self.vocab_size, d)},
                'final_norm': (d,), 'lm_head': (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            layer = {
                'attn_norm': (d,), 'mlp_norm': (d,),
                'wq': (d, h * self.qk_head_dim),
                'wkv_a': (d, self.kv_lora_rank + self.qk_rope_head_dim),
                'kv_a_norm': (self.kv_lora_rank,),
                'wkv_b': (self.kv_lora_rank,
                          h * (self.qk_nope_head_dim + self.v_head_dim)),
                'wo': (h * self.v_head_dim, d)}
            if i < self.first_k_dense_replace:
                layer['mlp'] = swiglu(self.intermediate_size)
            else:
                layer.update(
                    router=(d, self.router_width),
                    expert_bias=(self.router_width,),
                    experts=swiglu(f, (e,)),
                    shared=swiglu(f * self.n_shared_experts))
            tree['layer_%d' % i] = layer
        return tree

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters: N(0, 0.02) matrices and ``expert_bias``;
        norms 1 + N(0, 0.02)."""
        shapes = self.param_shapes()
        paths, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for n, (path, shape) in enumerate(paths):
            name = str(getattr(path[-1], 'key', path[-1]))
            draw = 0.02 * jax.random.normal(
                jax.random.fold_in(key, n), shape, jnp.float32)
            out.append(((1.0 if name.endswith('_norm') else 0.0)
                        + draw).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- the layer, once -----------------------------------------------
    def _rms(self, x, weight):
        return _experts.rms(x, weight, self.rms_norm_eps, self.dtype)

    def _attention(self, lp, a, positions):
        """Expanded latent attention on normed rows ``a`` (B, T, d) at
        ``positions`` (T,): (B, T, H * v_head_dim)."""
        dtype, h = self.dtype, self.num_attention_heads
        rank, nope = self.kv_lora_rank, self.qk_nope_head_dim
        freq = _mla.inv_freq(self.qk_rope_head_dim, self.rope_theta)
        q = jnp.dot(a, lp['wq'].astype(dtype)).reshape(
            a.shape[:2] + (h, self.qk_head_dim))
        ckv = jnp.dot(a, lp['wkv_a'].astype(dtype))
        c = self._rms(ckv[..., :rank], lp['kv_a_norm'])
        k_r = _mla.rope(ckv[..., rank:], positions, freq,
                        self.rope_interleave)
        q_rope = _mla.rope(q[..., nope:], positions[:, None], freq,
                           self.rope_interleave)
        w_k, w_v = _mla.split_kvb(lp['wkv_b'].astype(dtype), rank, h,
                                  nope)
        return _mla.expanded_attention(q[..., :nope], q_rope, c, k_r,
                                       w_k, w_v, self.softmax_scale)

    def _layer(self, x, lp, positions):
        """One layer on ``x`` (B, T, d): ``(x', the expert layer's
        counters or None)``."""
        dtype = self.dtype
        out = self._attention(lp, self._rms(x, lp['attn_norm']),
                              positions)
        x = x + jnp.dot(out.astype(dtype), lp['wo'].astype(dtype))
        m = self._rms(x, lp['mlp_norm'])
        if 'mlp' in lp:
            return x + _experts.swiglu(m, lp['mlp'], dtype), None
        y, counters = _experts.sigmoid_routed_experts(
            m.reshape(-1, m.shape[-1]), lp, self.num_experts_per_tok,
            self.norm_topk_prob, self.routed_scaling_factor, dtype,
            first=self.first_expert)
        return x + y.reshape(x.shape), counters

    def hidden(self, params, tokens):
        """tokens (B, T) int32 -> ``(final-normed rows (B, T, d), the
        step's counters)``.  The counters: assignments on held experts
        and all assignments, summed over the expert layers; held
        experts with a row and the fullest held expert's rows over the
        held mean, the mean over them; the bytes the layers'
        checkpoints keep beside their inputs."""
        x = jnp.take(params['embed']['embedding'], tokens,
                     axis=0).astype(self.dtype)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        layer, kept = self._layer, 0
        if self.train_recompute == 'layer':
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies
                .save_only_these_names(*RESIDUAL_NAMES))
            kept = self.num_hidden_layers * sum(residual_bytes(
                *tokens.shape, self.num_attention_heads,
                self.qk_head_dim, self.v_head_dim, self.dtype).values())
        seen = []
        for i in range(self.num_hidden_layers):
            x, counters = layer(x, params['layer_%d' % i], positions)
            if counters is not None:
                seen.append(counters)
        zero = jnp.zeros((), jnp.float32)
        touched, load, held = (tuple(sum(c) for c in zip(*seen))
                               if seen else (zero,) * 3)
        n = max(len(seen), 1)
        counters = {
            'held_assignments': held,
            'assignments': zero + (len(seen) * tokens.size
                                   * self.num_experts_per_tok),
            'experts_with_row': touched / n,
            'expert_load_max_over_mean': load / n,
            'checkpoint_kept_bytes': zero + kept}
        return self._rms(x, params['final_norm']), counters

    def _logits(self, params, x):
        return jnp.dot(x, params['lm_head'].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def apply(self, params, tokens):
        """tokens (B, T) int32 -> float32 logits (B, T, V)."""
        return self._logits(params, self.hidden(params, tokens)[0])

    __call__ = apply

    def loss_fn(self):
        """``loss(params, tokens, targets) -> (mean next-token
        cross-entropy, aux)`` for ``StandardUpdater(has_aux=True)``;
        the aux holds the step's expert counters, and the function
        names them (``span_counters``) so that the updater hangs them
        on its span."""
        from chainermn_tpu import ops

        def loss(params, tokens, targets):
            x, counters = self.hidden(params, tokens)
            logits = self._logits(params, x)
            ce = ops.softmax_cross_entropy(
                logits.reshape(-1, logits.shape[-1]),
                targets.reshape(-1).astype(jnp.int32))
            return jnp.mean(ce), counters

        loss.span_counters = self.span_counters
        return loss
