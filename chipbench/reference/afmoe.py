"""Plain reference of the ``afmoe`` decoder LM (Arcee Trinity), written
from the family's public description with no network at hand.  float32
throughout at ``highest``, ``jax.numpy`` only, no kernels, no cache, no
sorting: every expert is computed on every token and weighted by its
mostly-zero gate.  Nothing here imports the program.

``config.json`` names the widths, the router (``score_func``,
``route_norm``, ``route_scale``, ``num_experts_per_tok``,
``num_shared_experts``), ``layer_types``, ``sliding_window``,
``num_dense_layers`` and ``mup_enabled``.  What it does NOT name follows
the family's public modelling code as remembered, and each is a
DEPARTURE IF WRONG (the configuration file lists them under
``assumed``):

1. four RMSNorms a layer: before attention, on the attention output
   before it joins the residual, before the feed-forward, on its output
   before it joins the residual (``h + norm(out)``, both halves);
2. an RMSNorm over ``head_dim`` on q and on k (before rotary);
3. an output gate: ``attn * sigmoid(a @ Wg)`` before ``Wo``, ``Wg`` as
   wide as ``Wq``;
4. rotary positions on WINDOW layers only (all of ``head_dim``,
   rotate-half pairing, theta ``rope_theta``, no scaling); a full layer
   has no positional encoding at all;
5. a window layer's query at position ``p`` sees keys ``p - window + 1
   .. p`` (``window`` keys, itself among them);
6. the stored ``expert_bias`` is added to the sigmoid scores to CHOOSE
   the top-k and never enters a gate; gates are the chosen scores over
   their sum (+ 1e-20) times ``route_scale``;
7. embeddings are multiplied by ``sqrt(hidden_size)`` under
   ``mup_enabled``; the head is untied; no bias anywhere.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

#: experts upcast to float32 and computed at a time
EXPERT_BLOCK = 16


def param_spec(cfg):
    """``(shape, mean, std)`` per leaf, in the parameter tree the
    program's ``AfmoeLM.param_shapes`` declares (names are the
    interface).  N(0, 0.02) matrices; the norms 1 + N(0, 0.02) and
    ``expert_bias`` N(0, 0.02), so that no path is dead."""
    d, dh = cfg['hidden_size'], cfg['head_dim']
    hq = cfg['num_attention_heads'] * dh
    hkv = cfg['num_key_value_heads'] * dh
    f, e = cfg['moe_intermediate_size'], cfg['num_experts']
    std = 0.02
    norm = lambda n: ((n,), 1.0, std)                  # noqa: E731

    def swiglu(width, lead=()):
        return {'w1': (lead + (d, width), 0.0, std),
                'w3': (lead + (d, width), 0.0, std),
                'w2': (lead + (width, d), 0.0, std)}

    spec = {'embed': {'embedding': ((cfg['vocab_size'], d), 0.0, std)},
            'final_norm': norm(d),
            'lm_head': ((d, cfg['vocab_size']), 0.0, std)}
    for i in range(cfg['num_hidden_layers']):
        layer = {'input_norm': norm(d), 'post_attn_norm': norm(d),
                 'pre_mlp_norm': norm(d), 'post_mlp_norm': norm(d),
                 'q_norm': norm(dh), 'k_norm': norm(dh),
                 'wq': ((d, hq), 0.0, std), 'wk': ((d, hkv), 0.0, std),
                 'wv': ((d, hkv), 0.0, std), 'wg': ((d, hq), 0.0, std),
                 'wo': ((hq, d), 0.0, std)}
        if i < cfg['num_dense_layers']:
            layer['mlp'] = swiglu(cfg['intermediate_size'])
        else:
            layer.update(
                router=((d, e), 0.0, std),
                expert_bias=((e,), 0.0, std),
                experts=swiglu(f, (e,)),
                shared=swiglu(f * cfg['num_shared_experts']))
        spec['layer_%d' % i] = layer
    return spec


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _leaf(key, shape, mean, std, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def init_params(cfg, seed, dtype=jnp.float32):
    """The seeded weights, made on the device a leaf at a time: one
    float32 draw of the whole tree would be twice the chip's memory."""
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


def _rope(x, theta):
    """``x`` (T, H, D) at positions 0..T-1: rotate-half pairing."""
    t, _, dim = x.shape
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1)


def _swiglu(x, p, prec):
    gate = prec.einsum('td,df->tf', x, p['w1'])
    up = prec.einsum('td,df->tf', x, p['w3'])
    return prec.einsum('tf,fd->td', jax.nn.silu(gate) * up, p['w2'])


def route(m, lp, cfg):
    """``(gates (T, E) float32, chosen (T, k))``: a token's gate is its
    normalised, scaled sigmoid score on the k experts chosen by score +
    bias, 0 elsewhere."""
    score = jax.nn.sigmoid(jnp.einsum(
        'td,de->te', m.astype(jnp.float32),
        lp['router'].astype(jnp.float32), precision=common.HIGHEST))
    _, chosen = jax.lax.top_k(
        score + lp['expert_bias'].astype(jnp.float32),
        cfg['num_experts_per_tok'])
    picked = jnp.take_along_axis(score, chosen, axis=1)
    if cfg['route_norm']:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg['route_scale']
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(score).at[rows, chosen].set(picked), chosen


def _experts(m, lp, cfg, prec):
    """Every expert on every token, a block of experts at a time,
    weighted by the gate."""
    gates, chosen = route(m, lp, cfg)
    n = cfg['num_experts']
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


def _attention(q, k, v, window, prec):
    """q (T, H, D), k / v (T, Hkv, D): causal softmax attention, query
    head ``i`` on K/V head ``i // (H / Hkv)``, one K/V head at a time;
    ``window`` keys ending at the query's own position, or all."""
    t, h, dim = q.shape
    h_kv = k.shape[1]
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = j <= i
    if window is not None:
        mask = jnp.logical_and(mask, j > i - window)

    def one_head(args):
        qg, kg, vg = args                   # (T, G, D), (T, D), (T, D)
        s = prec.einsum('qgd,kd->gqk', qg, kg) / math.sqrt(dim)
        s = jnp.where(mask, s, -jnp.inf)
        return prec.einsum('gqk,kd->qgd', jax.nn.softmax(s, -1), vg)

    out = jax.lax.map(one_head, (
        jnp.moveaxis(q.reshape(t, h_kv, h // h_kv, dim), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(t, h * dim)


def _layer(x, lp, layer, cfg, prec):
    """One layer on ``x`` (T, d); also the chosen experts (or None)."""
    eps, dim = cfg['rms_norm_eps'], cfg['head_dim']
    t = x.shape[0]
    sliding = cfg['layer_types'][layer] == 'sliding_attention'
    a = prec.store(_rms(x, lp['input_norm'], eps))
    q = prec.einsum('td,df->tf', a, lp['wq']).reshape(t, -1, dim)
    k = prec.einsum('td,df->tf', a, lp['wk']).reshape(t, -1, dim)
    v = prec.einsum('td,df->tf', a, lp['wv']).reshape(t, -1, dim)
    gate = prec.einsum('td,df->tf', a, lp['wg'])
    q, k = _rms(q, lp['q_norm'], eps), _rms(k, lp['k_norm'], eps)
    if sliding:
        q, k = _rope(q, cfg['rope_theta']), _rope(k, cfg['rope_theta'])
    attn = _attention(q, k, v, cfg['sliding_window'] if sliding
                      else None, prec)
    out = prec.einsum('tf,fd->td', attn * jax.nn.sigmoid(gate),
                      lp['wo'])
    x = x + _rms(out, lp['post_attn_norm'], eps)
    m = prec.store(_rms(x, lp['pre_mlp_norm'], eps))
    if 'mlp' in lp:
        ff, chosen = _swiglu(m, lp['mlp'], prec), None
    else:
        ff, chosen = _experts(m, lp, cfg, prec)
    return x + _rms(ff, lp['post_mlp_norm'], eps), chosen


def hidden(params, tokens, cfg, prec, with_routing=False):
    """tokens ``(T,)`` -> what the head multiplies, ``(T, d)`` after
    the final norm (and, asked, the chosen experts of every expert
    layer, ``(layers, T, k)``)."""
    x = jnp.take(params['embed']['embedding'], tokens, axis=0).astype(
        jnp.float32)
    if cfg['mup_enabled']:
        x = x * math.sqrt(cfg['hidden_size'])
    routing = []
    for i in range(cfg['num_hidden_layers']):
        x, chosen = _layer(x, params['layer_%d' % i], i, cfg, prec)
        if chosen is not None:
            routing.append(chosen)
    x = prec.store(_rms(x, params['final_norm'], cfg['rms_norm_eps']))
    return (x, jnp.stack(routing)) if with_routing else x


def head(params, x, prec):
    """float32 logits ``(rows, V)`` of final-normed rows ``x``."""
    return prec.einsum('td,dv->tv', x, params['lm_head'])


def forward(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> float32 logits ``(T, V)``."""
    return head(params, hidden(params, tokens, cfg, prec), prec)


#: rows of logits made at a time: 128 x 200,192 float32 is 100 MB
HEAD_ROWS = 128


def _by_row_blocks(fn, *arrays):
    """``fn`` over blocks of :data:`HEAD_ROWS` rows of ``arrays``
    (padded to whole blocks), the per-row results stitched back."""
    rows = arrays[0].shape[0]
    pad = -rows % HEAD_ROWS
    blocks = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, HEAD_ROWS) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda block: fn(*block), blocks)
    return out.reshape((-1,) + out.shape[2:])[:rows]


def served_token_gaps(params, cfg, sequences, n_prompts, pad_to,
                      precision='float32', control=None):
    """For each served request, the reference's forward ONCE over the
    prompt with its served tokens, and at every served position the gap
    by which the served token's logit lies below the reference's best
    (the contract of ``reference/transformer_lm.served_token_gaps``;
    with ``control`` set no token is taken from anybody: the gap is
    that of the token the lower precision puts first).  Rows are padded
    to ``pad_to`` (causal: what follows a position cannot reach it).
    Logits are made for the served positions only, a block of rows at a
    time: all ``pad_to`` rows of a 200,192-row vocabulary would be
    3.3 GB beside the weights.

    Beside the sound comparison it prints ``routing_agreement``: the
    share of (served position, expert layer) pairs whose top-k SET in
    this float32 forward equals the set chosen when the same forward's
    activations are stored in bfloat16 between layers, as the program
    stores them -- a reading of how often a near-tie between the k-th
    and the next expert flips under the program's precision."""
    prec = common.Precision(precision)
    hid = jax.jit(lambda p, t: hidden(p, t, cfg, prec,
                                      with_routing=True))

    @jax.jit
    def gaps_of(p, x, chosen):
        def block(xb, cb):
            logits = head(p, xb, prec)
            return jnp.max(logits, -1) - jnp.take_along_axis(
                logits, cb[:, None], axis=1)[:, 0]
        return _by_row_blocks(block, x, chosen)

    if control is not None:
        cprec = common.Precision(control)
        low_hid = jax.jit(lambda p, t: hidden(p, t, cfg, cprec))
        low_best = jax.jit(lambda p, x: _by_row_blocks(
            lambda xb: jnp.argmax(head(p, xb, cprec), -1), x))
    else:
        bf16 = jax.jit(lambda p, t: hidden(
            p, t, cfg, _StoredBf16(), with_routing=True)[1])
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
            chosen = low_best(params, low_hid(params, row)[rows])
        else:
            chosen = np.zeros(rows.shape, np.int32)
            chosen[:len(at)] = np.asarray(seq)[at + 1]
            if routing.shape[0]:
                a = np.sort(np.asarray(routing)[:, at], -1)
                b = np.sort(np.asarray(bf16(params, row))[:, at], -1)
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


class _StoredBf16(common.Precision):
    """float32 products, activations stored in bfloat16 where the
    program stores them: what a near-tie in the router sees."""

    def store(self, x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
