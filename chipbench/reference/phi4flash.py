"""Plain reference of the ``phi4flash`` decoder LM (Microsoft
Phi-4-mini-flash-reasoning; SambaY, arXiv:2507.06607), written from the
model's published description with no network at hand.  float32
throughout at ``highest``, ``jax.numpy`` only, no kernels, no cache, no
chunks, EVERY layer at EVERY position: the selective scan is a
``lax.scan`` over positions with one ``(Di, N)`` state, the convolution
is four shifted adds, differential attention is two masked softmaxes a
head pair, subtracted.  Nothing here imports the program.

T positions, d = ``hidden_size``, H / Hkv heads of ``dh = d / H``, Di =
2 d, N = 16, K = 4 taps, R = ceil(d / 16), F = ``intermediate_size``, W
= ``sliding_window``; layer ``i`` of ``L`` (0-based)::

    h = x + mix_i(LN(x));  y = h + MLP_i(LN'(h))      LN: weight, bias
    MLP(u) = (silu(g) * v) W_2,  [g | v] = u W_1      gate FIRST
    logits = LN_f(x) E^T                              E the embedding

    mix_i:  i < L/2 even: Mamba     i < L/2 odd: window attention
            i = L/2: Mamba, its scan output m KEPT
            i = L/2 + 1: full attention, its K, V THE cache
            after: (i - L/2) even: gated memory unit, odd: cross attention

    Mamba(u):  [x~ | z] = u W_in;  x = silu(conv4(x~) + b_conv)
               [dt | B | C] = x W_x;  delta = softplus(dt W_dt + b_dt)
               h_t = exp(delta_t A) h_{t-1} + (delta_t x_t) B_t^T
               m_t = h_t C_t + D x_t;  out = (m * silu(z)) W_out
               A = -exp(A_log) (Di x N), h_{-1} = 0
    GMU_i(u; m) = (silu(u W_in) * m) W_out            m of layer L/2
    DiffAttn_i(u):  [q | k | v] = u W_qkv + b
               query pair j: q1 = q[2j], q2 = q[2j + 1]
               K/V pair p = j // (H / Hkv): k1 = k[2p], k2 = k[2p + 1],
               v = [v[2p] | v[2p + 1]]
               a1 = softmax(q1 k1^T / sqrt(dh) + mask) v, a2 likewise
               lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)
               lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)
               o_j = (1 - lambda_init(i)) RMSNorm_2dh(a1 - lambda a2)
               out = concat_j(o_j) W_o + b_o
               mask causal; in a window layer also t - s < W
    Cross_i: the same with its own W_q, b_q, lambdas, norm weight, W_o,
               b_o, and k, v those of layer L/2 + 1

``config.json`` gives the widths, the window, ``mb_per_layer``, the
tied head and that the MLP and head carry no bias.  What it does NOT
give follows the model's published description as remembered, and each
is a DEPARTURE IF WRONG (the configuration file lists them under
``assumed``): the Mamba sizes, the index rule above, differential
attention with its lambda schedule and interleaved pairing, the
attention biases, LayerNorm over RMSNorm, no rotary.

In the fp8 control (``control='fp8'``) both operands of every matrix
product are rounded to float8_e4m3fn, as ``common.Precision`` has it,
and so are the scan's ``x``, ``B`` and ``C``; the state, ``delta``, the
decays, the lambdas and the norms stay float32, as the configuration
states them.  The head is multiplied a block of the vocabulary at a
time, so the control's scale is per block of it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

#: blocks the vocabulary is multiplied in (the largest count up to this
#: that divides it): 200,064 x 2,560 float32 at once would be 2 GB
HEAD_BLOCKS = 16
#: rows the feed-forward takes at a time (5,120 x 20,480 float32 at once
#: would be 420 MB, several times over)
MLP_ROWS = 1024
#: leaves that stay float32 whatever dtype the weights take
F32_LEAVES = ('A_log', 'D', 'dt_bias', 'lambda_q1', 'lambda_k1',
              'lambda_q2', 'lambda_k2')
#: the Mamba sizes ``config.json`` does not give: the family's defaults
MAMBA = {'mamba_d_state': 16, 'mamba_d_conv': 4, 'mamba_expand': 2}


def widths(cfg):
    """``(Di, N, K, R)`` of a configuration."""
    size = {k: cfg.get(k, v) for k, v in MAMBA.items()}
    rank = cfg.get('mamba_dt_rank') or -(-cfg['hidden_size'] // 16)
    return (size['mamba_expand'] * cfg['hidden_size'],
            size['mamba_d_state'], size['mamba_d_conv'], rank)


def layer_kinds(cfg):
    """``'mamba' | 'window' | 'memory' | 'full' | 'gmu' | 'cross'`` a
    layer, by the index rule."""
    n = cfg['num_hidden_layers']
    half = n // 2

    def kind(i):
        if i < half:
            return ('mamba', 'window')[i % 2]
        if i <= half + 1:
            return ('memory', 'full')[i - half]
        return ('gmu', 'cross')[(i - half) % 2]

    return [kind(i) for i in range(n)]


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def param_spec(cfg):
    """``(shape, draw)`` per leaf, in the parameter tree the program's
    ``Phi4FlashLM.param_shapes`` declares (names are the interface; no
    ``lm_head``: the head is the embedding).  ``draw`` is ``(mean,
    std)`` of a normal, or the name of one of the three leaves that are
    no normal draw: ``'A_log'`` (log(1..N) a channel), ``'D'`` (ones),
    ``'dt_bias'`` (the inverse softplus of a step log-uniform in
    [0.001, 0.1]), so that the decays spread and no path is dead."""
    d, f = cfg['hidden_size'], cfg['intermediate_size']
    dh = d // cfg['num_attention_heads']
    hq, hkv = (cfg[k] * dh for k in ('num_attention_heads',
                                     'num_key_value_heads'))
    di, n, taps, r = widths(cfg)
    std = 0.02
    vec = lambda n: ((n,), (0.0, std))                 # noqa: E731
    mat = lambda *shape: (shape, (0.0, std))           # noqa: E731
    norm = lambda: {'scale': ((d,), (1.0, std)),       # noqa: E731
                    'bias': vec(d)}
    spec = {'embed': {'embedding': mat(cfg['vocab_size'], d)},
            'final_norm': norm()}
    for i, kind in enumerate(layer_kinds(cfg)):
        layer = {'norm1': norm(), 'norm2': norm(),
                 'mlp': {'w1': mat(d, 2 * f), 'w2': mat(f, d)}}
        if kind in ('mamba', 'memory'):
            layer.update(
                in_proj=mat(d, 2 * di), conv=((taps, di), (0.0, 0.5)),
                conv_bias=vec(di), x_proj=mat(di, r + 2 * n),
                dt_proj=mat(r, di), dt_bias=((di,), 'dt_bias'),
                A_log=((di, n), 'A_log'), D=((di,), 'D'),
                out_proj=mat(di, d))
        elif kind == 'gmu':
            layer.update(in_proj=mat(d, di), out_proj=mat(di, d))
        else:
            layer.update(
                wo=mat(hq, d), bo=vec(d),
                sub_norm=((2 * dh,), (1.0, std)),
                **{'lambda_' + name: ((dh,), (0.0, 0.1))
                   for name in ('q1', 'k1', 'q2', 'k2')})
            if kind == 'cross':
                layer.update(wq=mat(d, hq), bq=vec(hq))
            else:
                layer.update(wqkv=mat(d, hq + 2 * hkv),
                             bqkv=vec(hq + 2 * hkv))
        spec['layer_%d' % i] = layer
    return spec


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, shape, draw, dtype):
    f32 = jnp.float32
    if draw == 'A_log':
        out = jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32)), shape)
    elif draw == 'D':
        out = jnp.ones(shape, f32)
    elif draw == 'dt_bias':
        step = jnp.exp(jax.random.uniform(
            key, shape, f32, math.log(0.001), math.log(0.1)))
        out = step + jnp.log(-jnp.expm1(-step))
    else:
        out = draw[0] + draw[1] * jax.random.normal(key, shape, f32)
    return out.astype(dtype)


def init_params(cfg, seed, dtype=jnp.float32):
    """The seeded weights, made on the device a leaf at a time; the
    leaves of ``F32_LEAVES`` stay float32 whatever ``dtype`` the
    weights take."""
    def make(spec, key, name):
        if isinstance(spec, dict):
            return {name: make(sub, jax.random.fold_in(key, n), name)
                    for n, (name, sub) in enumerate(sorted(spec.items()))}
        return _leaf(key, *spec,
                     jnp.float32 if name in F32_LEAVES else dtype)

    return make(param_spec(cfg), common.seed_key(seed), None)


def _ln(x, p, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + eps)
    return x * p['scale'].astype(jnp.float32) \
        + p['bias'].astype(jnp.float32)


def conv4(x, taps):
    """``x`` (T, C), ``taps`` (K, C): ``y[t] = sum_j taps[j] * x[t - (K
    - 1) + j]`` with zeros before the sequence, as K shifted adds."""
    k, t = taps.shape[0], x.shape[0]
    y = jnp.zeros_like(x)
    for j in range(k):
        shift = k - 1 - j
        y = y + taps[j] * jnp.pad(x, ((shift, 0), (0, 0)))[:t]
    return y


def scan(x, delta, a, b, c, d):
    """The recurrence as defined, one position at a time: ``x`` /
    ``delta`` (T, Di), ``a`` (Di, N), ``b`` / ``c`` (T, N), ``d`` (Di,)
    -> ``m`` (T, Di), from a zero state."""
    def step(h, now):
        x_t, dt, b_t, c_t = now
        h = jnp.exp(dt[:, None] * a) * h \
            + (dt * x_t)[:, None] * b_t[None, :]
        return h, jnp.einsum('dn,n->d', h, c_t,
                             precision=common.HIGHEST) + d * x_t

    return jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                        (x, delta, b, c))[1]


def _mamba(u, lp, cfg, prec):
    """``(out, m)`` of a Mamba layer on ``u`` (T, d)."""
    f32 = jnp.float32
    di, n, _, r = widths(cfg)
    xz = prec.einsum('td,df->tf', u, lp['in_proj'])
    x = jax.nn.silu(conv4(xz[:, :di], lp['conv'].astype(f32))
                    + lp['conv_bias'].astype(f32))
    dbc = prec.einsum('tf,fr->tr', x, lp['x_proj'])
    delta = jax.nn.softplus(
        prec.einsum('tr,rf->tf', dbc[:, :r], lp['dt_proj'])
        + lp['dt_bias'].astype(f32))
    m = scan(prec.operand(x), delta, -jnp.exp(lp['A_log'].astype(f32)),
             prec.operand(dbc[:, r:r + n]), prec.operand(dbc[:, r + n:]),
             lp['D'].astype(f32))
    return prec.einsum('tf,fd->td', m * jax.nn.silu(xz[:, di:]),
                       lp['out_proj']), m


def _diff_attention(q, k, v, lp, lam_init, cfg, prec, window):
    """``q`` (T, H, dh), ``k`` / ``v`` (T, Hkv, dh): the two softmax
    maps a query pair, one pair at a time."""
    f32 = jnp.float32
    t, h, dh = q.shape
    hkv = k.shape[1]
    per = (h // 2) // (hkv // 2)            # query pairs a K/V pair
    at = jnp.arange(t)
    mask = at[None, :] <= at[:, None]
    if window is not None:
        mask = jnp.logical_and(mask, at[:, None] - at[None, :] < window)

    def dot(a, b):
        return jnp.sum(lp[a].astype(f32) * lp[b].astype(f32))

    lam = jnp.exp(dot('lambda_q1', 'lambda_k1')) \
        - jnp.exp(dot('lambda_q2', 'lambda_k2')) + lam_init
    k = k.reshape(t, hkv // 2, 2, dh)
    v = v.reshape(t, hkv // 2, 2 * dh)

    def one_pair(j):
        p = j // per
        qs = jax.lax.dynamic_slice_in_dim(q, 2 * j, 2, axis=1)
        ks = jax.lax.dynamic_index_in_dim(k, p, axis=1, keepdims=False)
        vs = jax.lax.dynamic_index_in_dim(v, p, axis=1, keepdims=False)
        s = prec.einsum('qmd,kmd->mqk', qs, ks) / math.sqrt(dh)
        s = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        a = prec.einsum('mqk,kd->mqd', s, vs)
        return a[0] - lam * a[1]                       # (T, 2 dh)

    o = jax.lax.map(one_pair, jnp.arange(h // 2))      # (H/2, T, 2 dh)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg['layer_norm_eps'])
    o = o * lp['sub_norm'].astype(f32) * (1.0 - lam_init)
    return prec.einsum('tf,fd->td',
                       jnp.moveaxis(o, 0, 1).reshape(t, h * dh),
                       lp['wo']) + lp['bo'].astype(f32)


def _mlp(u, p, prec):
    def rows(block):
        gv = prec.einsum('td,df->tf', block, p['w1'])
        half = gv.shape[1] // 2
        return prec.einsum('tf,fd->td',
                           jax.nn.silu(gv[:, :half]) * gv[:, half:],
                           p['w2'])

    t = u.shape[0]
    if t <= MLP_ROWS or t % MLP_ROWS:
        return rows(u)
    return jax.lax.map(rows, u.reshape(-1, MLP_ROWS, u.shape[1])
                       ).reshape(t, -1)


def _layer(x, lp, carried, kind, lam_init, cfg, prec):
    """One layer on ``x`` (T, d) float32.  ``carried`` is ``(m, k, v)``:
    the memory layer's scan output and the K/V layer's keys and values
    (zeros until those layers have run)."""
    eps = cfg['layer_norm_eps']
    m, k, v = carried
    h, hkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    dh = cfg['hidden_size'] // h
    t = x.shape[0]
    u = _ln(x, lp['norm1'], eps)
    if kind in ('mamba', 'memory'):
        mix, scanned = _mamba(u, lp, cfg, prec)
        if kind == 'memory':
            m = scanned
    elif kind == 'gmu':
        mix = prec.einsum(
            'tf,fd->td',
            jax.nn.silu(prec.einsum('td,df->tf', u, lp['in_proj'])) * m,
            lp['out_proj'])
    else:
        if kind == 'cross':
            q = prec.einsum('td,df->tf', u, lp['wq']) \
                + lp['bq'].astype(jnp.float32)
            k_i, v_i = k, v
        else:
            qkv = prec.einsum('td,df->tf', u, lp['wqkv']) \
                + lp['bqkv'].astype(jnp.float32)
            q = qkv[:, :h * dh]
            k_i = qkv[:, h * dh:(h + hkv) * dh].reshape(t, hkv, dh)
            v_i = qkv[:, (h + hkv) * dh:].reshape(t, hkv, dh)
            if kind == 'full':
                k, v = k_i, v_i
        mix = _diff_attention(
            q.reshape(t, h, dh), k_i, v_i, lp, lam_init, cfg, prec,
            cfg['sliding_window'] if kind == 'window' else None)
    x = x + mix
    return x + _mlp(_ln(x, lp['norm2'], eps), lp['mlp'], prec), (m, k, v)


def _frozen(cfg):
    """What the layer functions read of ``cfg``, hashable: one compiled
    layer of each kind serves every layer of that kind."""
    keys = ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
            'sliding_window', 'layer_norm_eps', 'mamba_d_state',
            'mamba_d_conv', 'mamba_expand', 'mamba_dt_rank')
    return tuple((k, cfg[k]) for k in keys if k in cfg)


@functools.partial(jax.jit, static_argnums=(3, 5, 6))
def _layer_jit(x, lp, carried, kind, lam_init, frozen, precision):
    return _layer(x, lp, carried, kind, lam_init, dict(frozen),
                  common.Precision(precision))


def hidden(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> what the head multiplies, ``(T, d)`` after the
    final norm.  A layer at a time, each compiled on its own, so that
    one layer's weights are upcast at a time; every layer at every
    position."""
    f32 = jnp.float32
    t = tokens.shape[0]
    x = jnp.take(params['embed']['embedding'], tokens, axis=0).astype(f32)
    hkv = cfg['num_key_value_heads']
    dh = cfg['hidden_size'] // cfg['num_attention_heads']
    carried = (jnp.zeros((t, widths(cfg)[0]), f32),
               jnp.zeros((t, hkv, dh), f32), jnp.zeros((t, hkv, dh), f32))
    for i, kind in enumerate(layer_kinds(cfg)):
        x, carried = _layer_jit(
            x, params['layer_%d' % i], carried, kind,
            jnp.asarray(lambda_init(i), f32), _frozen(cfg), prec.name)
    return _ln(x, params['final_norm'], cfg['layer_norm_eps'])


def head(params, x, prec):
    """float32 logits ``(rows, V)`` of final-normed rows ``x``: the
    embedding, transposed."""
    return prec.einsum('td,vd->tv', x, params['embed']['embedding'])


def forward(params, tokens, cfg, prec):
    """tokens ``(T,)`` -> float32 logits ``(T, V)``."""
    return head(params, hidden(params, tokens, cfg, prec), prec)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_readings(embedding, x, chosen, precision):
    """Per row of ``x``: the best logit, its token, and the logit of
    ``chosen``, the vocabulary a block at a time."""
    prec = common.Precision(precision)
    vocab = embedding.shape[0]
    n = next(n for n in range(HEAD_BLOCKS, 0, -1) if vocab % n == 0)
    width = vocab // n

    def block(carry, at):
        best, token, picked = carry
        logits = prec.einsum('td,vd->tv', x, jax.lax.dynamic_slice_in_dim(
            embedding, at, width, axis=0))
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
    prompt with its served tokens, every layer at every position, and
    at every served position the gap by which the served token's logit
    lies below the reference's best (the contract of
    ``reference/olmo_hybrid.served_token_gaps``; with ``control`` set no
    token is taken from anybody: the gap is that of the token the lower
    precision puts first).  Rows are padded to ``pad_to`` (causal, and
    the recurrences run forward: what follows a position cannot reach
    it).  Logits are made for the served positions only."""
    prec = common.Precision(precision)
    embedding = params['embed']['embedding']
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
                embedding, low, jnp.zeros(rows.shape, jnp.int32),
                control)[1]
        else:
            chosen = np.zeros(rows.shape, np.int32)
            chosen[:len(at)] = np.asarray(seq)[at + 1]
        best, _, picked = _head_readings(
            embedding, x, jnp.asarray(chosen), precision)
        out.append(np.asarray(best - picked)[:len(at)])
    return out
