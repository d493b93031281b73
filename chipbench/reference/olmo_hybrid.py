"""Plain reference of the ``olmo_hybrid`` decoder LM (AI2 Olmo-Hybrid):
Gated DeltaNet linear-attention layers (Yang et al., arXiv:2412.06464)
beside full-attention layers, written from the family's public
description with no network at hand.  float32 throughout at
``highest``, ``jax.numpy`` only, no kernels, no cache, no chunks: the
recurrence is a ``lax.scan`` over POSITIONS with one ``(dk, dv)`` state
a head, the convolution is four shifted adds, attention is a masked
softmax a head.  Nothing here imports the program.

A linear layer on ``x`` (T, d), per head ``h``::

    q, k = x Wq, x Wk (H x dk)    v, z = x Wv, x Wz (H x dv)
    a, b = x Wa, x Wb (H)
    q|k|v <- silu(conv4(q|k|v))            taps t-3 .. t, zeros before
    q^ = q / |q| * dk^-1/2,  k^ = k / |k|  (x * rsqrt(sum x^2 + 1e-6))
    beta = 2 sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
    S~ = exp(g_t) S_{t-1};  u_t = v_t - S~^T k^_t
    S_t = S~ + beta_t k^_t u_t^T;  o_t = S_t^T q^_t
    y = RMSNorm_dv(o_t) * w_o_norm * silu(z_t);  out = y Wo

``config.json`` names the widths, ``layer_types``, the ``linear_*``
sizes, ``linear_allow_neg_eigval`` and that ``rope_theta`` is null.
What it does NOT name follows the family's public modelling code as
remembered, and each is a DEPARTURE IF WRONG (the configuration file
lists them under ``assumed``):

1. the OLMo-2 order of norms: ``h = x + norm(mix(x))``, ``h = h +
   norm(mlp(h))``; no norm before a mixer or before the feed-forward;
2. a full layer has an RMSNorm over the WHOLE projected ``q`` and
   ``k`` (all heads together), softmax scale ``head_dim ** -0.5``,
   ``head_dim = hidden_size / num_attention_heads``, and NO positional
   encoding (``rope_theta`` null);
3. the convolution is depthwise (one filter of 4 taps a channel of
   ``q | k | v``), causal, without bias, followed by ``silu``;
4. the L2 norms use eps 1e-6 inside the root; the output norm is an
   RMSNorm over ``dv`` with one weight vector shared by the heads;
5. ``A_log`` and ``dt_bias`` are stored per head; the seeded draws
   (N(-1.2, 0.3) and N(0, 0.5)) put the decay ``exp(g)`` over about
   (0.5, 1); the taps are N(0, 0.5) so that the ``silu`` is not in its
   linear range;
6. SwiGLU feed-forward, untied head, no bias anywhere, embeddings
   unscaled.

In the fp8 control (``control='fp8'``) both operands of every matrix
product are rounded to float8_e4m3fn, as ``common.Precision`` has it,
and so are the recurrence's ``q^``, ``k^``, ``v``; the state, the
decays and the norms stay float32, as the configuration states them.
The head is multiplied a block of the vocabulary at a time, so the
control's scale is per block of it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

#: blocks the vocabulary is multiplied in (the largest count up to this
#: that divides it): 100,352 x 3,840 float32 at once would be 1.5 GB
HEAD_BLOCKS = 8


def _widths(cfg):
    heads = cfg['linear_num_value_heads']
    return (heads, cfg['linear_key_head_dim'],
            cfg['linear_value_head_dim'])


def param_spec(cfg):
    """``(shape, mean, std)`` per leaf, in the parameter tree the
    program's ``OlmoHybridLM.param_shapes`` declares (names are the
    interface).  N(0, 0.02) matrices, norms 1 + N(0, 0.02); ``A_log``,
    ``dt_bias`` and the taps as departure 5 has them."""
    d, f = cfg['hidden_size'], cfg['intermediate_size']
    dh = d // cfg['num_attention_heads']
    hq, hkv = (cfg[k] * dh for k in ('num_attention_heads',
                                     'num_key_value_heads'))
    heads, dk, dv = _widths(cfg)
    std = 0.02
    norm = lambda n: ((n,), 1.0, std)                  # noqa: E731
    mat = lambda *shape: (shape, 0.0, std)             # noqa: E731
    spec = {'embed': {'embedding': mat(cfg['vocab_size'], d)},
            'final_norm': norm(d), 'lm_head': mat(d, cfg['vocab_size'])}
    for i, kind in enumerate(cfg['layer_types']):
        layer = {'post_attn_norm': norm(d), 'post_mlp_norm': norm(d),
                 'mlp': {'w1': mat(d, f), 'w3': mat(d, f),
                         'w2': mat(f, d)}}
        if kind == 'linear_attention':
            layer.update(
                wq=mat(d, heads * dk), wk=mat(d, heads * dk),
                wv=mat(d, heads * dv), wz=mat(d, heads * dv),
                wa=mat(d, heads), wb=mat(d, heads),
                conv=((cfg['linear_conv_kernel_dim'],
                       heads * (2 * dk + dv)), 0.0, 0.5),
                A_log=((heads,), -1.2, 0.3),
                dt_bias=((heads,), 0.0, 0.5),
                o_norm=norm(dv), wo=mat(heads * dv, d))
        else:
            layer.update(wq=mat(d, hq), wk=mat(d, hkv), wv=mat(d, hkv),
                         q_norm=norm(hq), k_norm=norm(hkv),
                         wo=mat(hq, d))
        spec['layer_%d' % i] = layer
    return spec


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _leaf(key, shape, mean, std, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def init_params(cfg, seed, dtype=jnp.float32):
    """The seeded weights, made on the device a leaf at a time."""
    def make(spec, key):
        if isinstance(spec, dict):
            return {name: make(sub, jax.random.fold_in(key, n))
                    for n, (name, sub) in enumerate(sorted(spec.items()))}
        return _leaf(key, *spec, dtype)

    return make(param_spec(cfg), common.seed_key(seed))


def _rms(x, weight, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * weight.astype(jnp.float32))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)


def conv4(x, taps):
    """``x`` (T, C), ``taps`` (K, C): ``y[t] = sum_j taps[j] * x[t - (K
    - 1) + j]`` with zeros before the sequence, as K shifted adds."""
    k, t = taps.shape[0], x.shape[0]
    y = jnp.zeros_like(x)
    for j in range(k):
        shift = k - 1 - j
        y = y + taps[j] * jnp.pad(x, ((shift, 0), (0, 0)))[:t]
    return y


def delta_rule(q, k, v, g, beta):
    """The recurrence as defined, one position at a time: ``q`` / ``k``
    (T, H, dk), ``v`` (T, H, dv), ``g`` / ``beta`` (T, H) -> ``o`` (T,
    H, dv), from a zero state a head."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        u = v_t - jnp.einsum('hkv,hk->hv', s, k_t,
                             precision=common.HIGHEST)
        s = s + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum('hkv,hk->hv', s, q_t,
                             precision=common.HIGHEST)

    zero = jnp.zeros(q.shape[1:] + v.shape[2:], jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]


def _linear_mix(x, lp, cfg, prec):
    heads, dk, dv = _widths(cfg)
    t = x.shape[0]
    f32 = jnp.float32
    qkv = jnp.concatenate([prec.einsum('td,df->tf', x, lp[w])
                           for w in ('wq', 'wk', 'wv')], -1)
    qkv = jax.nn.silu(conv4(qkv, lp['conv'].astype(f32)))
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q = _unit(q.reshape(t, heads, dk)) * dk ** -0.5
    k = _unit(k.reshape(t, heads, dk))
    v = v.reshape(t, heads, dv)
    a = prec.einsum('td,dh->th', x, lp['wa'])
    b = prec.einsum('td,dh->th', x, lp['wb'])
    g = -jnp.exp(lp['A_log'].astype(f32)) * jax.nn.softplus(
        a + lp['dt_bias'].astype(f32))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg['linear_allow_neg_eigval']
                                else 1.0)
    o = delta_rule(prec.operand(q), prec.operand(k), prec.operand(v),
                   g, beta)
    z = prec.einsum('td,df->tf', x, lp['wz']).reshape(t, heads, dv)
    y = _rms(o, lp['o_norm'], cfg['rms_norm_eps']) * jax.nn.silu(z)
    return prec.einsum('tf,fd->td', y.reshape(t, heads * dv), lp['wo'])


def _attention_mix(x, lp, cfg, prec):
    """Causal softmax attention, query head ``i`` on K/V head ``i //
    (H / Hkv)``, one K/V head at a time, no positions."""
    t = x.shape[0]
    eps = cfg['rms_norm_eps']
    h, h_kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    dim = cfg['hidden_size'] // h
    q = _rms(prec.einsum('td,df->tf', x, lp['wq']), lp['q_norm'], eps)
    k = _rms(prec.einsum('td,df->tf', x, lp['wk']), lp['k_norm'], eps)
    v = prec.einsum('td,df->tf', x, lp['wv'])
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one_head(args):
        qg, kg, vg = args                   # (T, G, D), (T, D), (T, D)
        s = prec.einsum('qgd,kd->gqk', qg, kg) / math.sqrt(dim)
        s = jnp.where(mask, s, -jnp.inf)
        return prec.einsum('gqk,kd->qgd', jax.nn.softmax(s, -1), vg)

    out = jax.lax.map(one_head, (
        jnp.moveaxis(q.reshape(t, h_kv, h // h_kv, dim), 1, 0),
        jnp.moveaxis(k.reshape(t, h_kv, dim), 1, 0),
        jnp.moveaxis(v.reshape(t, h_kv, dim), 1, 0)))
    return prec.einsum('tf,fd->td',
                       jnp.moveaxis(out, 0, 1).reshape(t, h * dim),
                       lp['wo'])


def _layer(x, lp, kind, cfg, prec):
    """One layer on ``x`` (T, d) float32."""
    eps = cfg['rms_norm_eps']
    mix = (_linear_mix if kind == 'linear_attention'
           else _attention_mix)(x, lp, cfg, prec)
    x = x + _rms(mix, lp['post_attn_norm'], eps)
    p = lp['mlp']
    gate = prec.einsum('td,df->tf', x, p['w1'])
    up = prec.einsum('td,df->tf', x, p['w3'])
    ff = prec.einsum('tf,fd->td', jax.nn.silu(gate) * up, p['w2'])
    return x + _rms(ff, lp['post_mlp_norm'], eps)


def _frozen(cfg):
    """What the layer functions read of ``cfg``, hashable: one compiled
    layer of each kind serves every layer of that kind."""
    keys = ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
            'linear_num_value_heads', 'linear_key_head_dim',
            'linear_value_head_dim', 'linear_allow_neg_eigval',
            'rms_norm_eps')
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(x, lp, kind, frozen, precision):
    return _layer(x, lp, kind, dict(frozen), common.Precision(precision))


def hidden(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> what the head multiplies, ``(T, d)`` after the
    final norm.  A layer at a time, each compiled on its own, so that
    one layer's weights are upcast at a time."""
    x = jnp.take(params['embed']['embedding'], tokens, axis=0).astype(
        jnp.float32)
    for i, kind in enumerate(cfg['layer_types']):
        x = _layer_jit(x, params['layer_%d' % i], kind, _frozen(cfg),
                       prec.name)
    return _rms(x, params['final_norm'], cfg['rms_norm_eps'])


def head(params, x, prec):
    """float32 logits ``(rows, V)`` of final-normed rows ``x``."""
    return prec.einsum('td,dv->tv', x, params['lm_head'])


def forward(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> float32 logits ``(T, V)``."""
    return head(params, hidden(params, tokens, cfg, prec), prec)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_readings(lm_head, x, chosen, precision):
    """Per row of ``x``: the best logit, its token, and the logit of
    ``chosen``, the vocabulary a block at a time."""
    prec = common.Precision(precision)
    vocab = lm_head.shape[1]
    n = next(n for n in range(HEAD_BLOCKS, 0, -1) if vocab % n == 0)
    width = vocab // n

    def block(carry, at):
        best, token, picked = carry
        logits = prec.einsum('td,dv->tv', x, jax.lax.dynamic_slice_in_dim(
            lm_head, at, width, axis=1))
        top = jnp.max(logits, -1)
        inside = jnp.logical_and(chosen >= at, chosen < at + width)
        mine = jnp.take_along_axis(
            logits, jnp.clip(chosen - at, 0, width - 1)[:, None],
            axis=1)[:, 0]
        return (jnp.maximum(best, top),
                jnp.where(top > best, at + jnp.argmax(logits, -1), token),
                jnp.where(inside, mine, picked)), None

    rows = x.shape[0]
    start = (jnp.full((rows,), -jnp.inf, jnp.float32),
             jnp.zeros((rows,), jnp.int32),
             jnp.zeros((rows,), jnp.float32))
    return jax.lax.scan(block, start,
                        jnp.arange(0, vocab, width, dtype=jnp.int32))[0]


def served_token_gaps(params, cfg, sequences, n_prompts, pad_to,
                      precision='float32', control=None):
    """For each served request, the reference's forward ONCE over the
    prompt with its served tokens, and at every served position the gap
    by which the served token's logit lies below the reference's best
    (the contract of ``reference/transformer_lm.served_token_gaps``;
    with ``control`` set no token is taken from anybody: the gap is
    that of the token the lower precision puts first).  Rows are padded
    to ``pad_to`` (causal, and the recurrence runs forward: what follows
    a position cannot reach it).  Logits are made for the served
    positions only."""
    prec = common.Precision(precision)
    out = []
    for seq, n_prompt in zip(sequences, n_prompts):
        row = np.zeros((pad_to,), np.int32)
        row[:len(seq)] = seq
        row = jnp.asarray(row)
        at = np.arange(n_prompt - 1, len(seq) - 1)   # predicts seq[at+1]
        # a fixed count of rows, so that one program serves all
        rows = np.zeros((-(-len(at) // 256) * 256,), np.int32)
        rows[:len(at)] = at
        x = hidden(params, row, cfg, prec)[rows]
        if control is not None:
            low = hidden(params, row, cfg,
                         common.Precision(control))[rows]
            chosen = _head_readings(
                params['lm_head'], low,
                jnp.zeros(rows.shape, jnp.int32), control)[1]
        else:
            chosen = np.zeros(rows.shape, np.int32)
            chosen[:len(at)] = np.asarray(seq)[at + 1]
        best, _, picked = _head_readings(
            params['lm_head'], x, jnp.asarray(chosen), precision)
        out.append(np.asarray(best - picked)[:len(at)])
    return out
