"""Plain reference of the ``xing4`` decoder LM (``model_type``
``xing4_0``: Xing4.0-29B-A4B), written from the public descriptions of
its mechanisms with no network at hand.  float32 throughout at
``highest``, ``jax.numpy`` only, no kernels, no cache, no sorting, no
absorbed products: attention is the EXPANDED form (every head's keys
and values made from the latent), every expert is computed on every
token and weighted by its mostly-zero gate, the Sinkhorn rounds are a
loop.  Nothing here imports the program.  The served comparison runs A
LAYER AT A TIME (one jitted layer, its weights upcast inside it, an
expert block at a time), so the 4.79 B parameters never sit on the chip
in float32 at once.

``config.json`` names the widths, the ranks, the head dims, YaRN's
numbers, the router (``scoring_func``, ``topk_method``, ``n_group``,
``norm_topk_prob``, ``routed_scaling_factor``), ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps`` and the clamp.  What it does NOT name
follows DeepSeek-V2/V3's modelling code (MLA, YaRN, ``noaux_tc``) and
the mHC paper (arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606) as remembered, and each is a DEPARTURE IF WRONG (the
configuration file lists them under ``assumed``):

1. residual path, around each of a layer's two sub-layers ``F``: the
   state is ``X`` (4, d); ``xbar = RMSNorm(vec(X))`` over all 4 d values
   with NO weight; ``h = xbar @ phi^T`` (24 values: 4 read gates, 4
   write gates, a 4 x 4 matrix row-major); ``H_pre = sigmoid(a_pre h +
   b_pre)``, ``H_post = 2 sigmoid(a_post h + b_post)``, ``H_res =
   SK(clip(a_res h + b_res, -30, 30))``, ``SK`` = ``exp`` then 20 rounds
   of ``M / (rowsum + 1e-6)``, ``M / (colsum + 1e-6)``; ``u = H_pre X``,
   ``X' = H_res X + H_post^T F(RMSNorm_w(u))``;
2. ``X_0`` is the embedding in all four streams; the logits come from
   ``RMSNorm_w(sum_i X_i)``; the head is untied; no bias anywhere;
3. one weighted RMSNorm before each sub-layer (on ``u``), none after;
4. MLA as DeepSeek-V3: ``c_q = RMSNorm_w(u W_qa)``; ``[q_nope | q_rope]
   = c_q W_qb`` per head (128 | 64); ``[c | k_r] = u W_kva`` (512 | 64),
   ``c <- RMSNorm_w(c)``; rotary on ``q_rope`` and on ``k_r``, which all
   heads share; ``[k_nope | v] = c W_kvb`` per head (128 | 128);
5. rotary: rotate-half pairing (dim ``i`` with ``i + 32``); YaRN's
   frequencies blend ``theta^(-2i/64)`` and the same over ``factor`` by a
   linear ramp between the dims that make ``beta_fast`` and
   ``beta_slow`` rotations over the original 4,096 positions; the
   rotary's own magnitude factor is ``mscale / mscale_all_dim`` = 1;
6. the softmax scale is ``192^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim
   * ln(factor) + 1``;
7. router: ``s = sigmoid(u W_r)``; the stored bias
   (``e_score_correction_bias``, here ``expert_bias``) is added to
   CHOOSE the top-4 and never enters a gate; ``n_group`` 1, so no group
   limit; gates are the chosen scores over their sum (+ 1e-20) times
   ``routed_scaling_factor``; one shared expert every token takes;
8. the multi-token-prediction module (``num_nextn_predict_layers``) adds
   nothing to the next-token logits and is not built.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
# what the two sparse-expert references share: a seeded leaf, a SwiGLU,
# logits a block of rows at a time (128 x 131,072 float32 is 67 MB) and
# the precision that stores activations in bfloat16
from chipbench.reference.afmoe import (
    _StoredBf16, _by_row_blocks, _leaf, _swiglu)

#: experts upcast to float32 and computed at a time
EXPERT_BLOCK = 4


def _rows(cfg):
    n = cfg['hc_mult']
    return n * (n + 2)


def param_spec(cfg):
    """``(shape, mean, std)`` per leaf, in the parameter tree the
    program's ``Xing4LM.param_shapes`` declares (names are the
    interface).  N(0, 0.02) matrices, ``phi``, ``b`` and
    ``expert_bias``; norms and ``alpha`` 1 + N(0, 0.02): no path is
    dead, and the residual path's coefficients differ token by token."""
    d, h = cfg['hidden_size'], cfg['num_attention_heads']
    n, rows = cfg['hc_mult'], _rows(cfg)
    f, e = cfg['moe_intermediate_size'], cfg['n_routed_experts']
    rank, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    nope, dv = cfg['qk_nope_head_dim'], cfg['v_head_dim']
    std = 0.02
    norm = lambda width: ((width,), 1.0, std)          # noqa: E731
    mat = lambda *shape: (shape, 0.0, std)             # noqa: E731

    def swiglu(width, lead=()):
        return {'w1': mat(*lead, d, width), 'w3': mat(*lead, d, width),
                'w2': mat(*lead, width, d)}

    def hyper():
        return {'phi': mat(rows, n * d), 'alpha': ((3,), 1.0, std),
                'b': mat(rows)}

    spec = {'embed': {'embedding': mat(cfg['vocab_size'], d)},
            'final_norm': norm(d), 'lm_head': mat(d, cfg['vocab_size'])}
    for i in range(cfg['num_hidden_layers']):
        layer = {'attn_norm': norm(d), 'mlp_norm': norm(d),
                 'hc_attn': hyper(), 'hc_mlp': hyper(),
                 'wq_a': mat(d, cfg['q_lora_rank']),
                 'q_a_norm': norm(cfg['q_lora_rank']),
                 'wq_b': mat(cfg['q_lora_rank'], h * (nope + rope)),
                 'wkv_a': mat(d, rank + rope), 'kv_a_norm': norm(rank),
                 'wkv_b': mat(rank, h * (nope + dv)),
                 'wo': mat(h * dv, d)}
        if i < cfg['first_k_dense_replace']:
            layer['mlp'] = swiglu(cfg['intermediate_size'])
        else:
            layer.update(router=mat(d, e), expert_bias=mat(e),
                         experts=swiglu(f, (e,)),
                         shared=swiglu(f * cfg['n_shared_experts']))
        spec['layer_%d' % i] = layer
    return spec


def init_params(cfg, seed, dtype=jnp.float32):
    """The seeded weights, made on the device a leaf at a time; the
    residual path's leaves (under ``hc_*``) stay float32 whatever
    ``dtype`` the weights take."""
    def make(spec, key, keep):
        if isinstance(spec, dict):
            return {name: make(sub, jax.random.fold_in(key, n),
                               keep or name.startswith('hc_'))
                    for n, (name, sub) in enumerate(sorted(spec.items()))}
        return _leaf(key, *spec, jnp.float32 if keep else dtype)

    return make(param_spec(cfg), common.seed_key(seed), False)


def _rms(x, weight, eps):
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + eps)
    return x if weight is None else x * weight.astype(jnp.float32)


def yarn_inv_freq(cfg):
    """The ``qk_rope_head_dim / 2`` rotary frequencies."""
    dim, base = cfg['qk_rope_head_dim'], float(cfg['rope_theta'])
    extra = base ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    s = cfg.get('rope_scaling')
    if not s:
        return jnp.asarray(extra, jnp.float32)
    orig = s['original_max_position_embeddings']

    def dim_of(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(s['beta_fast'])), 0)
    high = min(math.ceil(dim_of(s['beta_slow'])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    return jnp.asarray(extra / s['factor'] * ramp + extra * (1 - ramp),
                       jnp.float32)


def softmax_scale(cfg):
    scale = (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']) ** -0.5
    s = cfg.get('rope_scaling')
    if s and s.get('mscale_all_dim'):
        m = 0.1 * s['mscale_all_dim'] * math.log(s['factor']) + 1.0
        scale *= m * m
    return scale


def _rope(x, cfg):
    """``x`` (T, ..., D) at positions 0..T-1: rotate-half pairing."""
    t, half = x.shape[0], x.shape[-1] // 2
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * yarn_inv_freq(cfg)).reshape(
                 (t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1)


def coefficients(x, hp, cfg, prec):
    """``(H_pre (T, 4), H_post (T, 4), H_res (T, 4, 4))`` of streams
    ``x`` (T, 4, d)."""
    n = cfg['hc_mult']
    xbar = _rms(x.reshape(x.shape[0], -1), None, cfg['rms_norm_eps'])
    h = prec.einsum('tk,rk->tr', xbar, hp['phi'])
    alpha = hp['alpha'].astype(jnp.float32)
    b = hp['b'].astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * h[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * h[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * h[:, 2 * n:] + b[2 * n:],
                         cfg['mhc_h_res_clamp_min'],
                         cfg['mhc_h_res_clamp_max'])).reshape(-1, n, n)
    for _ in range(cfg['hc_sinkhorn_iters']):
        m = m / (jnp.sum(m, -1, keepdims=True) + cfg['hc_eps'])
        m = m / (jnp.sum(m, -2, keepdims=True) + cfg['hc_eps'])
    return pre, post, m


def _hyper(x, hp, cfg, prec, f):
    """One sub-layer under the residual path."""
    pre, post, res = coefficients(x, hp, cfg, prec)
    u = jnp.einsum('ti,tid->td', pre, x, precision=common.HIGHEST)
    y, extra = f(prec.store(u))
    x = (jnp.einsum('tij,tjd->tid', res, x, precision=common.HIGHEST)
         + post[:, :, None] * y[:, None, :])
    return prec.store(x), extra


def route(m, lp, cfg):
    """``(gates (T, E) float32, chosen (T, k))``."""
    score = jax.nn.sigmoid(jnp.einsum(
        'td,de->te', m.astype(jnp.float32),
        lp['router'].astype(jnp.float32), precision=common.HIGHEST))
    _, chosen = jax.lax.top_k(
        score + lp['expert_bias'].astype(jnp.float32),
        cfg['num_experts_per_tok'])
    picked = jnp.take_along_axis(score, chosen, axis=1)
    if cfg['norm_topk_prob']:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg['routed_scaling_factor']
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(score).at[rows, chosen].set(picked), chosen


def _experts(m, lp, cfg, prec):
    """Every expert on every token, a block of experts at a time,
    weighted by the gate."""
    gates, chosen = route(m, lp, cfg)
    n = cfg['n_routed_experts']
    block = math.gcd(EXPERT_BLOCK, n)

    def one_block(total, at):
        w = {k: jax.lax.dynamic_slice_in_dim(v, at, block, 0)
             for k, v in lp['experts'].items()}
        gate = prec.einsum('td,edf->etf', m, w['w1'])
        up = prec.einsum('td,edf->etf', m, w['w3'])
        y = prec.einsum('etf,efd->etd', jax.nn.silu(gate) * up, w['w2'])
        g = jax.lax.dynamic_slice_in_dim(gates, at, block, 1)
        return total + jnp.einsum('etd,te->td', y, g,
                                  precision=common.HIGHEST), None

    routed, _ = jax.lax.scan(one_block, jnp.zeros_like(m),
                             jnp.arange(0, n, block))
    return routed + _swiglu(m, lp['shared'], prec), chosen


def _attention(a, lp, cfg, prec):
    """Expanded latent attention on normed rows ``a`` (T, d): (T, H *
    v_head_dim), one head at a time."""
    eps, h = cfg['rms_norm_eps'], cfg['num_attention_heads']
    rank, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    t = a.shape[0]
    c_q = prec.store(_rms(prec.einsum('td,dr->tr', a, lp['wq_a']),
                          lp['q_a_norm'], eps))
    q = prec.einsum('tr,rf->tf', c_q, lp['wq_b']).reshape(t, h, -1)
    ckv = prec.einsum('td,dr->tr', a, lp['wkv_a'])
    c = prec.store(_rms(ckv[:, :rank], lp['kv_a_norm'], eps))
    k_r = prec.store(_rope(ckv[:, rank:], cfg))             # (T, 64)
    kv = prec.einsum('tc,cf->tf', c, lp['wkv_b']).reshape(t, h, -1)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg)
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scale = softmax_scale(cfg)

    def one_head(args):
        qn, qr, kn, v = args                 # (T, 128) (T, 64) ...
        s = (prec.einsum('qd,kd->qk', qn, kn)
             + prec.einsum('qd,kd->qk', qr, k_r)) * scale
        s = jnp.where(mask, s, -jnp.inf)
        return prec.einsum('qk,kd->qd', jax.nn.softmax(s, -1), v)

    heads = lambda x: jnp.moveaxis(x, 1, 0)              # noqa: E731
    out = jax.lax.map(one_head, (
        heads(q_nope), heads(q_rope), heads(kv[..., :nope]),
        heads(kv[..., nope:])))
    return jnp.moveaxis(out, 0, 1).reshape(t, -1)


def _layer(x, lp, cfg, prec):
    """One layer on streams ``x`` (T, 4, d); also the chosen experts
    (or None)."""
    eps = cfg['rms_norm_eps']

    def attention(u):
        a = prec.store(_rms(u, lp['attn_norm'], eps))
        return prec.einsum('tf,fd->td', _attention(a, lp, cfg, prec),
                           lp['wo']), None

    def feed_forward(u):
        m = prec.store(_rms(u, lp['mlp_norm'], eps))
        if 'mlp' in lp:
            return _swiglu(m, lp['mlp'], prec), None
        return _experts(m, lp, cfg, prec)

    x, _ = _hyper(x, lp['hc_attn'], cfg, prec, attention)
    return _hyper(x, lp['hc_mlp'], cfg, prec, feed_forward)


def _embed(params, tokens, cfg):
    x = jnp.take(params['embed']['embedding'], tokens, axis=0).astype(
        jnp.float32)
    return jnp.broadcast_to(x[:, None, :],
                            (x.shape[0], cfg['hc_mult'], x.shape[1]))


def _final(params, x, cfg, prec):
    return prec.store(_rms(jnp.sum(x, axis=1), params['final_norm'],
                           cfg['rms_norm_eps']))


def hidden(params, tokens, cfg, prec, with_routing=False, layer_fn=None):
    """tokens ``(T,)`` -> what the head multiplies, ``(T, d)`` after
    the final norm (and, asked, the chosen experts of every expert
    layer, ``(layers, T, k)``).  ``layer_fn``: a jitted ``_layer``, so
    that the caller runs a layer at a time."""
    layer_fn = layer_fn or (lambda x, lp: _layer(x, lp, cfg, prec))
    x = _embed(params, tokens, cfg)
    routing = []
    for i in range(cfg['num_hidden_layers']):
        x, chosen = layer_fn(x, params['layer_%d' % i])
        if chosen is not None:
            routing.append(chosen)
    x = _final(params, x, cfg, prec)
    if not with_routing:
        return x
    k = cfg['num_experts_per_tok']
    return x, (jnp.stack(routing) if routing
               else jnp.zeros((0, x.shape[0], k), jnp.int32))


def head(params, x, prec):
    """float32 logits ``(rows, V)`` of final-normed rows ``x``."""
    return prec.einsum('td,dv->tv', x, params['lm_head'])


def forward(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> float32 logits ``(T, V)``."""
    return head(params, hidden(params, tokens, cfg, prec), prec)


def _runner(cfg, prec):
    """``hidden`` with routing, a jitted layer at a time."""
    layer_fn = jax.jit(lambda x, lp: _layer(x, lp, cfg, prec))
    return lambda p, t: hidden(p, t, cfg, prec, with_routing=True,
                               layer_fn=layer_fn)


def served_token_gaps(params, cfg, sequences, n_prompts, pad_to,
                      precision='float32', control=None):
    """For each served request, the reference's forward ONCE over the
    prompt with its served tokens, and at every served position the gap
    by which the served token's logit lies below the reference's best
    (the contract of ``reference/afmoe.served_token_gaps``; with
    ``control`` set no token is taken from anybody: the gap is that of
    the token the lower precision puts first).  Rows are padded to
    ``pad_to`` (causal: what follows a position cannot reach it).

    Beside the sound comparison it prints ``routing_agreement``: the
    share of (served position, expert layer) pairs whose top-k SET in
    this float32 forward equals the set chosen when the same forward's
    activations are stored in bfloat16 where the program stores them."""
    prec = common.Precision(precision)
    hid = _runner(cfg, prec)

    @jax.jit
    def gaps_of(p, x, chosen):
        def block(xb, cb):
            logits = head(p, xb, prec)
            return jnp.max(logits, -1) - jnp.take_along_axis(
                logits, cb[:, None], axis=1)[:, 0]
        return _by_row_blocks(block, x, chosen)

    if control is not None:
        cprec = common.Precision(control)
        low_hid = _runner(cfg, cprec)
        low_best = jax.jit(lambda p, x: _by_row_blocks(
            lambda xb: jnp.argmax(head(p, xb, cprec), -1), x))
    else:
        bf16 = _runner(cfg, _StoredBf16())
    out, same, pairs = [], 0, 0
    for seq, n_prompt in zip(sequences, n_prompts):
        row = np.zeros((pad_to,), np.int32)
        row[:len(seq)] = seq
        row = jnp.asarray(row)
        x, routing = hid(params, row)
        at = np.arange(n_prompt - 1, len(seq) - 1)   # predicts seq[at+1]
        # a fixed count of rows, so that one program serves all
        rows = np.zeros((-(-len(at) // 256) * 256,), np.int32)
        rows[:len(at)] = at
        if control is not None:
            chosen = low_best(params, low_hid(params, row)[0][rows])
        else:
            chosen = np.zeros(rows.shape, np.int32)
            chosen[:len(at)] = np.asarray(seq)[at + 1]
            if routing.shape[0]:
                a = np.sort(np.asarray(routing)[:, at], -1)
                b = np.sort(np.asarray(bf16(params, row)[1])[:, at], -1)
                same += int(np.all(a == b, -1).sum())
                pairs += a.shape[0] * a.shape[1]
        out.append(np.asarray(gaps_of(params, x[rows],
                                      jnp.asarray(chosen)))[:len(at)])
    if pairs:
        print('[chipbench reference] routing_agreement %.6f over %d '
              '(position, expert layer) pairs: float32 top-k set '
              'against the set under bfloat16-stored activations'
              % (same / pairs, pairs), flush=True)
    return out
