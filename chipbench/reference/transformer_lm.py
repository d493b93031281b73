"""Plain reference of the decoder-only LM the program calls
``models.TransformerLM``: learned token and position embeddings,
pre-norm blocks (LayerNorm eps 1e-6 -> fused qkv -> causal softmax
attention -> projection; LayerNorm -> d_ff -> tanh-GELU -> d_model),
final LayerNorm, an UNTIED vocabulary head with a bias.  Where that
departs from GPT-2 is listed in the configuration file under
``assumed``.  float32 throughout, no kernels, no cache, no batching
tricks.  Nothing here imports the program."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops
from chipbench.reference import common

def param_spec(cfg):
    """``(shape, mean, std)`` per leaf, in the parameter tree the
    program's module declares (names are the interface).  GPT-2's
    N(0, 0.02), residual exits scaled by 1/sqrt(2 L); norms and biases
    get a small spread so that no gradient path is dead."""
    d, h = cfg['n_embd'], cfg['n_head']
    d_ff, layers = cfg['n_inner'], cfg['n_layer']
    std, out_std = 0.02, 0.02 / math.sqrt(2 * layers)
    block = {
        'ln1_scale': ((d,), 1.0, std), 'ln1_bias': ((d,), 0.0, std),
        'qkv': {'kernel': ((d, 3, h, d // h), 0.0, std),
                'bias': ((3, h, d // h), 0.0, std)},
        'proj': {'kernel': ((d, d), 0.0, out_std),
                 'bias': ((d,), 0.0, std)},
        'ln2_scale': ((d,), 1.0, std), 'ln2_bias': ((d,), 0.0, std),
        'ff_in': {'kernel': ((d, d_ff), 0.0, std),
                  'bias': ((d_ff,), 0.0, std)},
        'ff_out': {'kernel': ((d_ff, d), 0.0, out_std),
                   'bias': ((d,), 0.0, std)},
    }
    spec = {'block_%d' % i: block for i in range(layers)}
    spec.update({
        'embed': {'embedding': ((cfg['vocab_size'], d), 0.0, std)},
        'pos_embed': ((cfg['n_positions'], d), 0.0, std),
        'lnf_scale': ((d,), 1.0, std), 'lnf_bias': ((d,), 0.0, std),
        'lm_head': {'kernel': ((d, cfg['vocab_size']), 0.0, std),
                    'bias': ((cfg['vocab_size'],), 0.0, std)},
    })
    return spec


def init_params(cfg, seed, dtype=jnp.float32):
    """The seeded weights, made on the device in one jitted call."""
    spec = param_spec(cfg)
    return jax.jit(lambda key: common.init_from_spec(spec, key, dtype))(
        common.seed_key(seed))


def _layer_norm(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, bp, prec):
    b, t, d = x.shape
    heads = bp['qkv']['kernel'].shape[2]
    h = _layer_norm(x, bp['ln1_scale'], bp['ln1_bias'])
    qkv = prec.einsum('btd,dchf->btchf', h, bp['qkv']['kernel']) \
        + bp['qkv']['bias']
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = prec.einsum('bqhf,bkhf->bhqk', q, k) / math.sqrt(d // heads)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = prec.einsum('bhqk,bkhf->bqhf', jax.nn.softmax(scores, -1), v)
    x = x + prec.einsum('btd,de->bte', attn.reshape(b, t, d),
                        bp['proj']['kernel']) + bp['proj']['bias']
    h = _layer_norm(x, bp['ln2_scale'], bp['ln2_bias'])
    h = _gelu_tanh(prec.einsum('btd,df->btf', h, bp['ff_in']['kernel'])
                   + bp['ff_in']['bias'])
    return x + prec.einsum('btf,fd->btd', h, bp['ff_out']['kernel']) \
        + bp['ff_out']['bias']


def forward(params, tokens, cfg, prec):
    """tokens ``(B, T)`` -> float32 logits ``(B, T, V)``.  Blocks are
    rematerialised one by one so that a backward pass holds one block's
    internals at a time."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32),
                                    params)
    t = tokens.shape[1]
    x = jnp.take(params['embed']['embedding'], tokens, axis=0) \
        + params['pos_embed'][:t]
    block = jax.checkpoint(lambda x, bp: _block(x, bp, prec))
    for i in range(cfg['n_layer']):
        x = block(x, params['block_%d' % i])
    x = _layer_norm(x, params['lnf_scale'], params['lnf_bias'])
    return prec.einsum('btd,dv->btv', x, params['lm_head']['kernel']) \
        + params['lm_head']['bias']


def _loss_sum(params, tokens, targets, cfg, prec):
    logits = forward(params, tokens, cfg, prec)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))


def make_grad_fn(cfg, precision='float32', rows_per_block=2):
    """``grad_fn(params, (tokens, targets)) -> (mean loss, grads)`` over
    the whole batch, computed in blocks of rows so that it fits beside
    nothing else on one chip."""
    prec = common.Precision(precision)
    block_grad = jax.jit(jax.value_and_grad(
        lambda p, x, y: _loss_sum(p, x, y, cfg, prec)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

    def grad_fn(params, batch):
        tokens, targets = (jnp.asarray(a) for a in batch)
        total, grads = 0.0, None
        for at in range(0, tokens.shape[0], rows_per_block):
            rows = slice(at, at + rows_per_block)
            loss, g = block_grad(params, tokens[rows], targets[rows])
            total = total + loss
            grads = g if grads is None else add(grads, g)
        n = tokens.size
        return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)

    return grad_fn


def batch_of(examples):
    """Examples ``[(tokens, targets), ...]`` -> the two batched arrays."""
    return tuple(np.stack([e[i] for e in examples]) for i in (0, 1))


def served_token_gaps(params, cfg, sequences, n_prompts, pad_to,
                      precision='float32', control=None):
    """For each served request, the reference's forward ONCE over the
    prompt with its served tokens, and at every served position the gap
    by which the served token's logit lies below the reference's best.

    ``sequences`` are the token rows (prompt + served tokens),
    ``n_prompts`` the prompt lengths.  With ``control`` set (a lower
    precision), no token is taken from anybody: at each position the
    gap is that of the token the lower precision puts first.  Rows are
    padded to ``pad_to`` (causal: what follows a position cannot reach
    it), so one program serves every length.  Returns one array of
    gaps per request."""
    prec = common.Precision(precision)
    fwd = jax.jit(lambda p, t: forward(p, t, cfg, prec))
    low = None
    if control is not None:
        cprec = common.Precision(control)
        low = jax.jit(lambda p, t: jnp.argmax(forward(p, t, cfg, cprec),
                                              -1))
    out = []
    for seq, n_prompt in zip(sequences, n_prompts):
        row = np.zeros((1, pad_to), np.int32)
        row[0, :len(seq)] = seq
        logits = fwd(params, jnp.asarray(row))[0]
        at = np.arange(n_prompt - 1, len(seq) - 1)   # predicts seq[at+1]
        chosen = (np.asarray(low(params, jnp.asarray(row)))[0, at]
                  if low is not None else np.asarray(seq)[at + 1])
        rows = np.asarray(logits[at])
        out.append(rows.max(-1) - rows[np.arange(len(at)), chosen])
    return out


def train_flops_per_sample(cfg, mix):
    return 3 * flops.transformer_lm_forward_flops(cfg, mix['seq_len'])
