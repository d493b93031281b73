"""Plain reference of the ``deepseek_v3`` decoder LM (``model_type``
``deepseek_v3``; the configuration here is Kanana-2-30B-A3B) AS IT IS
TRAINED ON ONE CHIP'S SHARE of an expert-parallel deployment: float32
throughout at ``highest``, ``jax.numpy`` only, no kernels, no sorting;
attention is the EXPANDED latent form, a head and a block of queries at
a time; every HELD expert is computed on every token and weighted by
its mostly-zero gate.  Nothing here imports the program.

The share (the configuration file's ``deployment``): the router is
``router_experts`` wide and a token takes its ``num_experts_per_tok``
best of ALL of them, the gates normalised over all the chosen; this
chip holds the experts ``first_expert .. + n_routed_experts - 1`` and
adds only their part.  What the absent
experts would add is left out and the partial result goes on to the
next layer.  The vocabulary is the slice the file's ``vocab_size``
counts: logits, loss and token ids are over the slice.

What ``config.json`` does not name follows DeepSeek-V3's modelling code
as remembered (no network at hand); each is a DEPARTURE IF WRONG and
the configuration file lists it under ``assumed``:

1. block: ``x <- x + Attn(RMSNorm_w(x))``, ``x <- x + FFN(RMSNorm_w(x))``,
   logits ``RMSNorm_w(x) W_head``; one weighted RMSNorm before each
   sub-layer and none after; the head untied; no bias anywhere;
2. MLA with ``q_lora_rank`` null: ``q = u W_q`` per head ``[q_nope 128 |
   q_rope 64]``; ``[c 512 | k_r 64] = u W_kva``, ``c <- RMSNorm_w(c)``;
   rotary on ``q_rope`` and on ``k_r``, which all heads share;
   ``[k_nope 128 | v 128] = c W_kvb`` per head; scores ``softmax(q .
   [k_nope | k_r] 192^-1/2)``, causal; out ``(sum p v) W_o``;
3. rotary, ``rope_interleave`` true: the pair turned by ``pos *
   theta^(-2i/64)`` is the ADJACENT dims ``(2i, 2i + 1)`` (HF puts the
   dims even-then-odd and turns halves: the same dot products); no
   scaling;
4. router (``noaux_tc``, ``n_group`` 1): ``s = sigmoid(u W_r)`` in
   float32; top-6 of ``s + e_score_correction_bias`` (here
   ``expert_bias``), which never enters a gate; gates = chosen ``s``
   over their sum (+ 1e-20) x ``routed_scaling_factor``; the
   ``n_shared_experts`` shared experts are ONE SwiGLU of their summed
   width;
5. objective: the mean next-token cross-entropy.  ``expert_bias`` gets
   no gradient and no update rule (DeepSeek-V3's bias update speed and
   its sequence-wise balance coefficient are no keys of
   ``config.json``), so it stays as seeded.

WHERE THE NUMBERS LIVE.  ``common.follow_training`` keeps the start,
the parameters, Adam's two moments, a gradient and each one's successor
at once: eight float32 trees of 575.9 M, 18 GB.  ``init_params``
without a ``dtype`` (the reference's own call) therefore leaves the
tree on the HOST (JAX's CPU device), where Adam's arithmetic then runs;
``make_grad_fn`` brings a layer's weights to the chip, computes there
(a layer at a time, forward then backward through ``jax.vjp``), and
sends the layer's gradient back.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
from chipbench.reference.afmoe import _leaf, _rms, _swiglu

#: held experts upcast and computed at a time
EXPERT_BLOCK = 4
#: queries a block of one head's score matrix
QUERY_BLOCK = 2048


def router_width(cfg):
    return cfg.get('router_experts') or cfg['n_routed_experts']


def first_expert(cfg):
    return cfg.get('first_expert', 0)


def param_spec(cfg):
    """``(shape, mean, std)`` per leaf, in the parameter tree the
    program's ``DeepseekV3LM.param_shapes`` declares (names are the
    interface).  N(0, 0.02) matrices and ``expert_bias``; norms 1 +
    N(0, 0.02): no path is dead."""
    d, h = cfg['hidden_size'], cfg['num_attention_heads']
    f, e = cfg['moe_intermediate_size'], cfg['n_routed_experts']
    rank, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    nope, dv = cfg['qk_nope_head_dim'], cfg['v_head_dim']
    std = 0.02
    norm = lambda width: ((width,), 1.0, std)          # noqa: E731
    mat = lambda *shape: (shape, 0.0, std)             # noqa: E731

    def swiglu(width, lead=()):
        return {'w1': mat(*lead, d, width), 'w3': mat(*lead, d, width),
                'w2': mat(*lead, width, d)}

    spec = {'embed': {'embedding': mat(cfg['vocab_size'], d)},
            'final_norm': norm(d), 'lm_head': mat(d, cfg['vocab_size'])}
    for i in range(cfg['num_hidden_layers']):
        layer = {'attn_norm': norm(d), 'mlp_norm': norm(d),
                 'wq': mat(d, h * (nope + rope)),
                 'wkv_a': mat(d, rank + rope), 'kv_a_norm': norm(rank),
                 'wkv_b': mat(rank, h * (nope + dv)),
                 'wo': mat(h * dv, d)}
        if i < cfg['first_k_dense_replace']:
            layer['mlp'] = swiglu(cfg['intermediate_size'])
        else:
            layer.update(router=mat(d, router_width(cfg)),
                         expert_bias=mat(router_width(cfg)),
                         experts=swiglu(f, (e,)),
                         shared=swiglu(f * cfg['n_shared_experts']))
        spec['layer_%d' % i] = layer
    return spec


def _host():
    return jax.devices('cpu')[0]


def init_params(cfg, seed, dtype=None):
    """The seeded weights, made on the default device a leaf at a time.
    With a ``dtype`` (the program's weights) they stay there; without
    (the reference's own float32 tree) each leaf goes to the host as it
    is made: see the module's note."""
    host = _host() if dtype is None else None

    def make(spec, key):
        if isinstance(spec, dict):
            return {name: make(sub, jax.random.fold_in(key, n))
                    for n, (name, sub) in enumerate(sorted(spec.items()))}
        leaf = _leaf(key, *spec, dtype or jnp.float32)
        return leaf if host is None else jax.device_put(leaf, host)

    return make(param_spec(cfg), common.seed_key(seed))


def rope_adjacent(x, freq):
    """``x`` (T, ..., D) at positions 0..T-1: the adjacent dims ``(2i,
    2i + 1)`` turned by ``pos * freq[i]``, the dims left where they
    are."""
    t = x.shape[0]
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * freq).reshape(
        (t,) + (1,) * (x.ndim - 2) + (freq.shape[0],))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def inv_freq(cfg):
    dim = cfg['qk_rope_head_dim']
    return jnp.asarray(float(cfg['rope_theta']) ** -(
        np.arange(0, dim, 2, dtype=np.float64) / dim), jnp.float32)


def route(m, lp, cfg):
    """``(gates (T, E_router) float32, chosen (T, k))`` over ALL the
    router's experts."""
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


def routed_part(m, experts, gates, prec):
    """``sum_e gates[:, e] SwiGLU_e(m)`` over the experts given, every
    one on every token, a block of experts at a time (rematerialised:
    a backward holds one block's products)."""
    n = gates.shape[1]
    block = math.gcd(EXPERT_BLOCK, n)

    @jax.checkpoint
    def one_block(total, at, m, experts, gates):
        w = {k: jax.lax.dynamic_slice_in_dim(v, at, block, 0)
             for k, v in experts.items()}
        gate = prec.einsum('td,edf->etf', m, w['w1'])
        up = prec.einsum('td,edf->etf', m, w['w3'])
        y = prec.einsum('etf,efd->etd', jax.nn.silu(gate) * up, w['w2'])
        g = jax.lax.dynamic_slice_in_dim(gates, at, block, 1)
        return total + jnp.einsum('etd,te->td', y, g,
                                  precision=common.HIGHEST)

    routed, _ = jax.lax.scan(
        lambda total, at: (one_block(total, at, m, experts, gates), None),
        jnp.zeros_like(m), jnp.arange(0, n, block))
    return routed


def _experts(m, lp, cfg, prec):
    """The held experts' part beside the shared expert; also the
    chosen experts."""
    gates, chosen = route(m, lp, cfg)
    held = jax.lax.dynamic_slice_in_dim(
        gates, first_expert(cfg), cfg['n_routed_experts'], 1)
    return (routed_part(m, lp['experts'], held, prec)
            + _swiglu(m, lp['shared'], prec)), chosen


def _attention(a, lp, cfg, prec):
    """Expanded latent attention on normed rows ``a`` (T, d): (T, H *
    v_head_dim), one head and one block of queries at a time."""
    eps, h = cfg['rms_norm_eps'], cfg['num_attention_heads']
    rank, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    t = a.shape[0]
    freq = inv_freq(cfg)
    q = prec.einsum('td,df->tf', a, lp['wq']).reshape(t, h, -1)
    ckv = prec.einsum('td,dr->tr', a, lp['wkv_a'])
    c = prec.store(_rms(ckv[:, :rank], lp['kv_a_norm'], eps))
    k_r = prec.store(rope_adjacent(ckv[:, rank:], freq))     # (T, 64)
    kv = prec.einsum('tc,cf->tf', c, lp['wkv_b']).reshape(t, h, -1)
    q_nope, q_rope = q[..., :nope], rope_adjacent(q[..., nope:], freq)
    scale = (nope + cfg['qk_rope_head_dim']) ** -0.5
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError('%d rows are not whole blocks of %d queries'
                         % (t, block))
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one_block(qn, qr, kn, k_r, v, at):
        s = (prec.einsum('qd,kd->qk', qn, kn)
             + prec.einsum('qd,kd->qk', qr, k_r)) * scale
        s = jnp.where(key_pos <= at + jnp.arange(block)[:, None], s,
                      -jnp.inf)
        return prec.einsum('qk,kd->qd', jax.nn.softmax(s, -1), v)

    def one_head(args):
        qn, qr, kn, v = args                 # (T, 128) (T, 64) ...
        blocks = lambda x: x.reshape(-1, block, x.shape[-1])  # noqa: E731
        out = jax.lax.map(
            lambda b: one_block(b[0], b[1], kn, k_r, v, b[2]),
            (blocks(qn), blocks(qr), jnp.arange(0, t, block)))
        return out.reshape(t, -1)

    heads = lambda x: jnp.moveaxis(x, 1, 0)              # noqa: E731
    out = jax.lax.map(one_head, (
        heads(q_nope), heads(q_rope), heads(kv[..., :nope]),
        heads(kv[..., nope:])))
    return jnp.moveaxis(out, 0, 1).reshape(t, -1)


def _layer(x, lp, cfg, prec):
    """One layer on ``x`` (T, d); also the chosen experts (or None)."""
    eps = cfg['rms_norm_eps']
    a = prec.store(_rms(x, lp['attn_norm'], eps))
    x = prec.store(x + prec.einsum(
        'tf,fd->td', prec.store(_attention(a, lp, cfg, prec)), lp['wo']))
    m = prec.store(_rms(x, lp['mlp_norm'], eps))
    if 'mlp' in lp:
        ff, chosen = _swiglu(m, lp['mlp'], prec), None
    else:
        ff, chosen = _experts(m, lp, cfg, prec)
    return prec.store(x + ff), chosen


def _loss_sum(x, final_norm, lm_head, targets, cfg, prec):
    x = prec.store(_rms(x, final_norm, cfg['rms_norm_eps']))
    logits = prec.einsum('td,dv->tv', x, lm_head)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))


def hidden(params, tokens, cfg, prec, with_routing=False):
    """tokens ``(T,)`` -> the last layer's output ``(T, d)``, before
    the final norm (and, asked, the chosen experts of every expert
    layer, ``(layers, T, k)``)."""
    x = jnp.take(params['embed']['embedding'], tokens, axis=0).astype(
        jnp.float32)
    routing = []
    for i in range(cfg['num_hidden_layers']):
        x, chosen = _layer(x, params['layer_%d' % i], cfg, prec)
        if chosen is not None:
            routing.append(chosen)
    return (x, jnp.stack(routing)) if with_routing else x


def forward(params, tokens, cfg, prec=None):
    """tokens ``(T,)`` -> float32 logits ``(T, V)`` (small sizes: the
    whole model in one piece)."""
    prec = prec or common.Precision('float32')
    x = _rms(hidden(params, tokens, cfg, prec), params['final_norm'],
             cfg['rms_norm_eps'])
    return prec.einsum('td,dv->tv', prec.store(x), params['lm_head'])


class _StoredBf16(common.Precision):
    """float32 products of operands rounded to bfloat16, activations
    stored in bfloat16: what a near-tie in the router sees in the
    program, where ``Policy.bf16`` casts every leaf (the router's
    among them) and the model keeps its rows in bfloat16."""

    def operand(self, x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    store = operand


def make_grad_fn(cfg, precision='float32'):
    """``grad_fn(params, (tokens, targets)) -> (mean loss, grads)`` over
    the whole batch, a row and a LAYER at a time: the forward keeps
    each layer's input, the backward takes a layer's ``jax.vjp`` from
    the last to the first.  ``params`` may live on the host: a layer's
    weights come to the default device for its two passes and its
    gradient goes back where the weights were."""
    prec = common.Precision(precision)
    chip = jax.devices()[0]

    def body(x, lp):
        return _layer(x, lp, cfg, prec)[0]

    layer_fwd = jax.jit(body)
    layer_bwd = jax.jit(lambda x, lp, g: jax.vjp(body, x, lp)[1](g))
    head_grad = jax.jit(jax.value_and_grad(
        lambda x, norm, head, y: _loss_sum(x, norm, head, y, cfg, prec),
        argnums=(0, 1, 2)))
    embed_grad = jax.jit(lambda tokens, g: jnp.zeros(
        (cfg['vocab_size'], cfg['hidden_size']), jnp.float32).at[
            tokens].add(g))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    n_layers = cfg['num_hidden_layers']
    low = _StoredBf16()
    sound_choice = jax.jit(lambda x, lp: _layer(x, lp, cfg, prec)[1])
    low_layer = jax.jit(lambda x, lp: _layer(
        x, jax.tree_util.tree_map(low.operand, lp), cfg, low))
    said = []

    def here(tree):
        return jax.device_put(tree, chip)

    def back(tree, like):
        return jax.tree_util.tree_map(
            lambda g, p: jax.device_put(g, p.sharding)
            if hasattr(p, 'sharding') else g, tree, like)

    def agreement(params, xs):
        """Printed once, no limit on it: the share of (position, expert
        layer) pairs whose top-k SET in this float32 forward equals the
        set under bfloat16 weights and stored activations."""
        x, same, pairs = low.operand(xs[0]), 0, 0
        for i in range(n_layers):
            lp = here(params['layer_%d' % i])
            x, chosen = low_layer(x, lp)
            if chosen is not None:
                sound = np.sort(np.asarray(sound_choice(xs[i], lp)))
                same += int(np.all(
                    sound == np.sort(np.asarray(chosen)), -1).sum())
                pairs += sound.shape[0]
        if pairs:
            print('[chipbench reference] routing_agreement %.6f over %d '
                  '(position, expert layer) pairs: float32 top-k set '
                  'against the set under bfloat16 weights and stored '
                  'activations' % (same / pairs, pairs), flush=True)

    def row_grad(params, tokens, targets):
        xs = [jnp.take(here(params['embed']['embedding']), tokens,
                       axis=0).astype(jnp.float32)]
        for i in range(n_layers):
            xs.append(layer_fwd(xs[-1], here(params['layer_%d' % i])))
        if precision == 'float32' and not said:
            said.append(agreement(params, xs))
        loss, (g, d_norm, d_head) = head_grad(
            xs.pop(), here(params['final_norm']),
            here(params['lm_head']), targets)
        grads = {'final_norm': back(d_norm, params['final_norm']),
                 'lm_head': back(d_head, params['lm_head'])}
        for i in reversed(range(n_layers)):
            name = 'layer_%d' % i
            g, d_layer = layer_bwd(xs.pop(), here(params[name]), g)
            grads[name] = back(d_layer, params[name])
        embedding = params['embed']['embedding']
        grads['embed'] = {'embedding': back(embed_grad(tokens, g),
                                            embedding)}
        return loss, grads

    def grad_fn(params, batch):
        tokens, targets = (jnp.asarray(a) for a in batch)
        total, grads = 0.0, None
        for row in range(tokens.shape[0]):
            loss, g = row_grad(params, tokens[row], targets[row])
            total = total + loss
            grads = g if grads is None else add(grads, g)
        n = tokens.size
        return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)

    return grad_fn


def batch_of(examples):
    """Examples ``[(tokens, targets), ...]`` -> the two batched arrays."""
    return tuple(np.stack([e[i] for e in examples]) for i in (0, 1))


def matmul_weights_per_token(cfg):
    """Weights of the matrices one token meets in one forward HERE:
    attention's four in every layer, the dense SwiGLU, then per expert
    layer the shared SwiGLU, the router and the held experts a token's
    assignments land on IN EXPECTATION (``k`` x held / router width of
    them, a uniform router's share), and the head over the held rows of
    the vocabulary.  The embedding is a lookup."""
    d, h = cfg['hidden_size'], cfg['num_attention_heads']
    rank, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    nope, dv = cfg['qk_nope_head_dim'], cfg['v_head_dim']
    f = cfg['moe_intermediate_size']
    dense = cfg['first_k_dense_replace']
    sparse = cfg['num_hidden_layers'] - dense
    attention = (d * h * (nope + rope) + d * (rank + rope)
                 + rank * h * (nope + dv) + h * dv * d)
    held = (cfg['num_experts_per_tok'] * cfg['n_routed_experts']
            / router_width(cfg))
    expert_layer = (3 * d * f * cfg['n_shared_experts']
                    + d * router_width(cfg) + held * 3 * d * f)
    return (cfg['num_hidden_layers'] * attention
            + dense * 3 * d * cfg['intermediate_size']
            + sparse * expert_layer + d * cfg['vocab_size'])


def attention_flops_per_sample(cfg, seq_len):
    """Forward FLOPs of the score and value products of one sequence:
    the causal half (``n (n + 1) / 2`` live pairs), 2 x (192 + 128) a
    pair and head, every layer."""
    width = (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
             + cfg['v_head_dim'])
    pairs = seq_len * (seq_len + 1) / 2.0
    return (pairs * cfg['num_attention_heads'] * 2 * width
            * cfg['num_hidden_layers'])


def train_flops_per_sample(cfg, mix):
    """What one sequence's forward and backward REQUIRE: 3 x (2 x the
    matrices' weights a token + the causal half of attention); a
    recomputed layer counts once."""
    t = mix['seq_len']
    return 3.0 * (2.0 * matmul_weights_per_token(cfg) * t
                  + attention_flops_per_sample(cfg, t))
