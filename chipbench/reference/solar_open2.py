"""Plain reference of the ``solar_open2`` decoder LM (Upstage Solar Open
2; the configuration here is Solar-Open2-250B) AS ONE CHIP'S SHARE of
an expert-parallel deployment holds it, written from ``config.json``
and the papers its keys name, with no network at hand.  float32
throughout at ``highest``, ``jax.numpy`` only, no kernels, no cache, no
chunks: the recurrence is a ``lax.scan`` over POSITIONS with one ``(dk,
dv)`` state a head, the convolutions are four shifted adds, attention
is a masked softmax, every HELD expert is computed on every token and
weighted by its mostly-zero gate.  Nothing here imports the program.

THE LAYERS.  Each line marked (+) is NOT named by ``config.json``; it
is listed under ``assumed`` in the configuration file and is a
DEPARTURE IF WRONG.

Layer ``i`` is ``gqa`` if ``i`` is in ``gqa_layers`` (0, 4, 8, ...: one
in four), else ``kda``.  Both kinds, pre-norm (+)::

    h = x + mixer(rms(x));  h = h + moe(rms(h))

RMSNorm eps ``rms_norm_eps`` 1e-5, no bias but the two named below,
untied head, embeddings unscaled (+).

``kda`` mixer on ``x`` (T, 4096), H = 64 heads, ``dk = dv = 128``
(``linear_attn_config``; ``num_kv_heads`` null = 64).  The keys
``kda_use_full_proj``, ``kda_allow_neg_eigval`` and
``short_conv_kernel_size`` name Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692): the gated delta rule with the decay a VECTOR over
the key dimension::

    q, k, v = x Wq, x Wk, x Wv                  each 4096 -> 8192
    q, k, v <- silu(conv4(.))   each its own depthwise causal
                                convolution of 4 taps, no bias (+)
    q^ = q / |q| * 128^-1/2,  k^ = k / |k|      per head, eps 1e-6
                                                inside the root (+)
    g = -exp(A_log[h]) * softplus((x Wf1) Wf2 + dt_bias)   in R^(H x dk)
        Wf1 4096 -> 128, Wf2 128 -> 8192: kda_use_full_proj false read
        as Kimi Linear's low-rank pair (+); A_log per head, dt_bias per
        channel (+)
    beta = 2 * sigmoid(x Wb)    per head; the 2 is kda_allow_neg_eigval
    S~ = diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - S~^T k^_t)
    S_t = S~ + k^_t u_t^T;  o_t = S_t^T q^_t    S (dk, dv) float32 a
                                                head, zero before the
                                                sequence
    y = RMSNorm_dv(o_t) * w * sigmoid((x Wg1) Wg2 + b_g)   low-rank as
        Wf, one norm weight shared by the heads, the gate's activation
        sigmoid (+)
    out = y Wo                                  8192 -> 4096

``gqa`` mixer: ``q = x Wq`` (64 x 128), ``k, v = x Wk, x Wv`` (8 x
128), NO positional encoding (``use_rope`` false), no q / k norm (+),
causal softmax at scale ``128^-1/2``, ``a <- a * sigmoid(x Wg)``
elementwise over all 8,192 (``use_gqa_gate`` true read as ``afmoe``'s
gate (+); with it the model counts 250.29 B), ``out = a Wo``.

``moe``, every layer (``first_k_dense_replace`` 0): router 4096 -> 320
in float32, sigmoid scores with a stored selection bias that steers
WHICH 8 and never their weight (+: the ``solar_open`` / ``glm4_moe``
convention; ``config.json`` names no scoring function), gates
normalised (``norm_topk_prob``), scale ``routed_scaling_factor`` 1,
experts SwiGLU 4096 -> 1280 -> 4096, one shared expert of 1280
(``n_shared_experts`` x ``moe_intermediate_size``) every token takes.
``intermediate_size`` 10240 belongs to dense layers, of which there are
none.

THE SHARE (the configuration file's ``deployment``): the router is
``router_experts`` wide and a token takes its 8 best of ALL of them,
the gates normalised over all the chosen; this chip holds the experts
``first_expert .. + n_routed_experts - 1`` and adds only their part.
What the absent experts would add is left out and the partial result
goes on to the next layer.  The vocabulary is the slice the file's
``vocab_size`` counts: logits and token ids are over the slice.

The seeded draws: N(0, 0.02) matrices and biases, norms 1 + N(0,
0.02); ``A_log`` N(-0.7, 0.4) and ``dt_bias`` N(0.3, 0.8), so that the
per-channel decay ``exp(g)`` spreads over about (0.3, 1); the taps N(0,
0.5), so that the ``silu`` is out of its linear range (+).

In the fp8 control (``control='fp8'``) both operands of every matrix
product are rounded to float8_e4m3fn, as ``common.Precision`` has it,
and so are the recurrence's ``q^``, ``k^``, ``v`` and what the program
stores between sub-layers; the state, the decays, ``beta``, the norms
and the router stay float32, as the configuration states them.

MEMORY.  13,824 positions beside the served engine's 11 GB: a ``kda``
mixer runs :data:`HEAD_BLOCK` heads at a time (the heads do not meet
before the output projection), attention a K/V head and
:data:`QUERY_BLOCK` queries at a time, the experts four at a time
(``reference/deepseek_v3.py``'s ``route`` and ``routed_part``: the same
router and the same share), each layer compiled on its own so that one
layer's weights are upcast at a time.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
from chipbench.reference.afmoe import _swiglu
from chipbench.reference.deepseek_v3 import route, routed_part
from chipbench.reference.olmo_hybrid import (_head_readings, _leaf, _rms,
                                             _unit, conv4)

#: heads of a ``kda`` mixer computed at a time
HEAD_BLOCK = 16
#: queries a block of one K/V head's score matrix
QUERY_BLOCK = 512


def router_width(cfg):
    return cfg.get('router_experts') or cfg['n_routed_experts']


def _linear(cfg):
    """``(heads, head_dim, taps)`` of a ``kda`` mixer."""
    lin = cfg['linear_attn_config']
    if lin.get('num_kv_heads') not in (None, lin['num_heads']):
        raise NotImplementedError('kda with %r key / value heads'
                                  % (lin['num_kv_heads'],))
    return lin['num_heads'], lin['head_dim'], lin['short_conv_kernel_size']


def param_spec(cfg):
    """``(shape, mean, std)`` per leaf, in the parameter tree the
    program's ``SolarOpen2LM.param_shapes`` declares (names are the
    interface).  ``conv`` is the three convolutions' taps side by side,
    ``q | k | v``."""
    d, dh = cfg['hidden_size'], cfg['head_dim']
    hq = cfg['num_attention_heads'] * dh
    hkv = cfg['num_key_value_heads'] * dh
    heads, dl, taps = _linear(cfg)
    wide = heads * dl
    f, e = cfg['moe_intermediate_size'], cfg['n_routed_experts']
    std = 0.02
    norm = lambda n: ((n,), 1.0, std)                  # noqa: E731
    mat = lambda *shape: (shape, 0.0, std)             # noqa: E731

    def swiglu(width, lead=()):
        return {'w1': mat(*lead, d, width), 'w3': mat(*lead, d, width),
                'w2': mat(*lead, width, d)}

    spec = {'embed': {'embedding': mat(cfg['vocab_size'], d)},
            'final_norm': norm(d), 'lm_head': mat(d, cfg['vocab_size'])}
    for i in range(cfg['num_hidden_layers']):
        layer = {'input_norm': norm(d), 'pre_mlp_norm': norm(d),
                 'router': mat(d, router_width(cfg)),
                 'expert_bias': mat(router_width(cfg)),
                 'experts': swiglu(f, (e,)),
                 'shared': swiglu(f * cfg['n_shared_experts'])}
        if i in cfg['gqa_layers']:
            layer.update(wq=mat(d, hq), wk=mat(d, hkv), wv=mat(d, hkv),
                         wg=mat(d, hq), wo=mat(hq, d))
        else:
            layer.update(
                wq=mat(d, wide), wk=mat(d, wide), wv=mat(d, wide),
                conv=((taps, 3 * wide), 0.0, 0.5),
                wf1=mat(d, dl), wf2=mat(dl, wide),
                dt_bias=((wide,), 0.3, 0.8),
                A_log=((heads,), -0.7, 0.4), wb=mat(d, heads),
                wg1=mat(d, dl), wg2=mat(dl, wide), b_g=mat(wide),
                o_norm=norm(dl), wo=mat(wide, d))
        spec['layer_%d' % i] = layer
    return spec


def init_params(cfg, seed, dtype=jnp.float32):
    """The seeded weights, made on the device a leaf at a time."""
    def make(spec, key):
        if isinstance(spec, dict):
            return {name: make(sub, jax.random.fold_in(key, n))
                    for n, (name, sub) in enumerate(sorted(spec.items()))}
        return _leaf(key, *spec, dtype)

    return make(param_spec(cfg), common.seed_key(seed))


def delta_rule(q, k, v, g, beta):
    """The recurrence as defined, one position at a time: ``q`` / ``k``
    / ``g`` (T, H, dk), ``v`` (T, H, dv), ``beta`` (T, H) -> ``o`` (T,
    H, dv), from a zero state a head."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum(
            'hkv,hk->hv', s, k_t, precision=common.HIGHEST))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum('hkv,hk->hv', s, q_t,
                             precision=common.HIGHEST)

    zero = jnp.zeros(q.shape[1:] + v.shape[2:], jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]


def _kda_mix(a, lp, cfg, prec):
    """A ``kda`` mixer on normed rows ``a`` (T, d), :data:`HEAD_BLOCK`
    heads at a time."""
    heads, dl, _ = _linear(cfg)
    t = a.shape[0]
    f32 = jnp.float32
    block = math.gcd(HEAD_BLOCK, heads)
    width = block * dl
    # the low-rank pairs' narrow halves are shared by the heads
    f_low = prec.einsum('td,dr->tr', a, lp['wf1'])
    g_low = prec.einsum('td,dr->tr', a, lp['wg1'])
    beta = jax.nn.sigmoid(prec.einsum('td,dh->th', a, lp['wb'])) * (
        2.0 if cfg['kda_allow_neg_eigval'] else 1.0)

    def cols(w, at, offset=0):
        """``width`` columns of ``w`` from ``offset + at * dl``."""
        return jax.lax.dynamic_slice_in_dim(
            w, offset + at * dl, width, axis=w.ndim - 1)

    def one_block(out, at):
        q, k, v = (
            jax.nn.silu(conv4(
                prec.einsum('td,df->tf', a, cols(lp[w], at)),
                cols(lp['conv'], at, n * heads * dl).astype(f32))
            ).reshape(t, block, dl)
            for n, w in enumerate(('wq', 'wk', 'wv')))
        q, k = _unit(q) * dl ** -0.5, _unit(k)
        a_log = jax.lax.dynamic_slice_in_dim(lp['A_log'], at, block, 0)
        g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            (prec.einsum('tr,rf->tf', f_low, cols(lp['wf2'], at))
             + cols(lp['dt_bias'], at).astype(f32)).reshape(t, block, dl))
        o = delta_rule(prec.operand(q), prec.operand(k), prec.operand(v),
                       g, jax.lax.dynamic_slice_in_dim(beta, at, block, 1))
        gate = (prec.einsum('tr,rf->tf', g_low, cols(lp['wg2'], at))
                + cols(lp['b_g'], at).astype(f32)).reshape(t, block, dl)
        y = _rms(o, lp['o_norm'], cfg['rms_norm_eps']) \
            * jax.nn.sigmoid(gate)
        return out + prec.einsum(
            'tf,fd->td', y.reshape(t, width),
            jax.lax.dynamic_slice_in_dim(lp['wo'], at * dl, width, 0)
        ), None

    out, _ = jax.lax.scan(one_block, jnp.zeros_like(a),
                          jnp.arange(0, heads, block))
    return out


def _gqa_mix(a, lp, cfg, prec):
    """Causal softmax attention on normed rows ``a`` (T, d), query head
    ``i`` on K/V head ``i // (H / Hkv)``, one K/V head and one block of
    queries at a time, no positions; the output gate; the output
    projection."""
    t = a.shape[0]
    h, h_kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    dim = cfg['head_dim']
    q = prec.einsum('td,df->tf', a, lp['wq']).reshape(t, h_kv, h // h_kv,
                                                      dim)
    k = prec.einsum('td,df->tf', a, lp['wk']).reshape(t, h_kv, dim)
    v = prec.einsum('td,df->tf', a, lp['wv']).reshape(t, h_kv, dim)
    block = math.gcd(QUERY_BLOCK, t)
    key_pos = jnp.arange(t)[None, :]

    def one_head(args):
        qg, kg, vg = args                   # (T, G, D), (T, D), (T, D)

        def one_block(b):
            qb, at = b
            s = prec.einsum('qgd,kd->gqk', qb, kg) / math.sqrt(dim)
            s = jnp.where(key_pos <= at + jnp.arange(block)[:, None], s,
                          -jnp.inf)
            return prec.einsum('gqk,kd->qgd', jax.nn.softmax(s, -1), vg)

        out = jax.lax.map(one_block, (
            qg.reshape((-1, block) + qg.shape[1:]),
            jnp.arange(0, t, block)))
        return out.reshape((t,) + out.shape[2:])

    attn = jax.lax.map(one_head, (jnp.moveaxis(q, 1, 0),
                                  jnp.moveaxis(k, 1, 0),
                                  jnp.moveaxis(v, 1, 0)))
    attn = jnp.moveaxis(attn, 0, 1).reshape(t, h * dim)
    gate = prec.einsum('td,df->tf', a, lp['wg'])
    return prec.einsum('tf,fd->td', attn * jax.nn.sigmoid(gate), lp['wo'])


def moe(m, lp, cfg, prec, first=None, held=None, shared=True):
    """The sparse feed-forward on normed rows ``m``: the part of the
    experts ``first .. first + held - 1`` (default: the
    configuration's share, whose weights ``lp['experts']`` are), beside
    the shared expert unless ``shared`` is false."""
    first = cfg.get('first_expert', 0) if first is None else first
    held = cfg['n_routed_experts'] if held is None else held
    gates = jax.lax.dynamic_slice_in_dim(route(m, lp, cfg)[0], first, held,
                                         1)
    out = routed_part(m, lp['experts'], gates, prec)
    return out + _swiglu(m, lp['shared'], prec) if shared else out


def _layer(x, lp, kda, cfg, prec):
    """One layer on ``x`` (T, d) float32."""
    eps = cfg['rms_norm_eps']
    a = prec.store(_rms(x, lp['input_norm'], eps))
    x = prec.store(x + (_kda_mix if kda else _gqa_mix)(a, lp, cfg, prec))
    m = prec.store(_rms(x, lp['pre_mlp_norm'], eps))
    return prec.store(x + moe(m, lp, cfg, prec))


def _frozen(cfg):
    """What the layer functions read of ``cfg``, hashable: one compiled
    layer of each kind serves every layer of that kind."""
    keys = ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
            'head_dim', 'kda_allow_neg_eigval', 'rms_norm_eps',
            'n_routed_experts', 'num_experts_per_tok', 'norm_topk_prob',
            'routed_scaling_factor')
    return tuple((k, cfg[k]) for k in keys) + (
        ('first_expert', cfg.get('first_expert', 0)),
        ('linear_attn_config',
         tuple(sorted(cfg['linear_attn_config'].items()))))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(x, lp, kda, frozen, precision):
    cfg = dict(frozen)
    cfg['linear_attn_config'] = dict(cfg['linear_attn_config'])
    return _layer(x, lp, kda, cfg, common.Precision(precision))


def hidden(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> what the head multiplies, ``(T, d)`` after the
    final norm.  A layer at a time, each compiled on its own."""
    x = jnp.take(params['embed']['embedding'], tokens, axis=0).astype(
        jnp.float32)
    for i in range(cfg['num_hidden_layers']):
        x = _layer_jit(x, params['layer_%d' % i],
                       i not in cfg['gqa_layers'], _frozen(cfg), prec.name)
    return prec.store(_rms(x, params['final_norm'], cfg['rms_norm_eps']))


def head(params, x, prec):
    """float32 logits ``(rows, V)`` of final-normed rows ``x``."""
    return prec.einsum('td,dv->tv', x, params['lm_head'])


def forward(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> float32 logits ``(T, V)``."""
    return head(params, hidden(params, tokens, cfg, prec), prec)


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
