"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464):
a linear-attention layer's recurrence, and the short causal convolution
that sits before it.

Per head, with a state ``S`` (dk, dv) that is zero before the sequence::

    S~  = exp(g_t) * S_{t-1}
    u_t = beta_t * (v_t - S~^T k_t)
    S_t = S~ + k_t u_t^T
    o_t = S_t^T q_t

(``q`` and ``k`` arrive normalised and scaled; ``g <= 0`` is the log of
the decay, ``beta`` the write strength; both float32.)

Three forms of it:

- :func:`gated_delta_reference`: the recurrence as written, a
  ``lax.scan`` over positions.  The oracle of the tests.
- :func:`gated_delta_rule`: a whole prompt in CHUNKS.  Inside a chunk
  of ``C`` positions the ``C`` rank-one updates are solved at once (the
  WY form: ``(I + strict_lower(diag(beta) K K^T * decay))^-1``, a unit
  lower-triangular system), every chunk in parallel; only the
  ``(dk, dv)`` state is carried from chunk to chunk, ``T / C`` steps of
  a few small products each instead of ``T`` steps.  Positions at or
  past ``length`` are the identity (``g = 0``, ``beta = 0``): a prompt
  padded to its bucket leaves the state as its last real token did.
- :func:`gated_delta_step`: one token a row for decode.  Each row's
  state is read and written where it lies in the ``(rows, ...)`` leaf:
  on the chip a Pallas kernel whose grid step takes one row's state
  through VMEM, the leaf its aliased output; elsewhere a gather, the
  update and a scatter.

A decay PER KEY CHANNEL (Kimi Delta Attention, Kimi Linear,
arXiv:2510.26692): ``g`` one rank higher, ``(T, H, dk)``, and ``S~ =
diag(exp(g_t)) S_{t-1}``.  The three forms read the rank off ``g`` and
each keeps a body of its own for it (the scalar bodies are untouched: a
``(c, c)`` pair matrix a head is cheaper than what follows).  In chunks
the decay now sits INSIDE the contraction, ``A_ij = sum_d k_i[d] k_j[d]
exp(G_i[d] - G_j[d])`` with ``G`` the running sum of ``g``; the obvious
factoring ``(k_i exp(G_i)) . (k_j exp(-G_j))`` overflows float32 within
a chunk (``exp(g)`` 1e-3 a step on one channel is ``exp(-G)`` 1e48
after 16 steps).  No positive difference is ever exponentiated here
(:func:`_channel_pairs`): a chunk is cut into sub-blocks of 16; a pair
in two sub-blocks is factored through the LATER sub-block's first
position ``r``, ``exp(G_i - G_r) * exp(G_r - G_j)``, both at most 1 (a
factor that underflows belongs to a product that is smaller still); a
pair inside one sub-block takes the three-index form ``(16, 16, dk)``,
masked before the exponential.  All of a call's chunks are solved at
once, and the three-index arrays are 524,288 B a position at 64 heads
of 128: a caller takes a long prompt through in segments, the state
carried from one to the next (``state0``), as ``models/solar_open2.py``
does 1,024 positions at a time.

The state leaf's shape is :func:`state_shape`: ``(rows, H / P, dk,
P * dv)`` float32, ``P`` heads side by side in the lanes so that the
minor dim is a whole number of 128-lane tiles (``dv`` 192: two heads,
384 lanes; ``(.., 96, 192)`` would pad to 256 lanes, a third more bytes
than it holds, in HBM and in every copy).  ``dk`` lies on the sublanes.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops._common import interpret_flag, pallas_mode

_LANES = 128
#: scoped VMEM the step kernel asks for: one row's state in and out,
#: each twice (the pipeline's two buffers), is 8.8 MB at 30 x 96 x 192
_VMEM_LIMIT = 32 * 1024 * 1024
#: positions solved at once by the chunked rule: on the chip a layer's
#: rule at 30 heads of 96 x 192 took 0.68 / 0.91 / 0.85 ms (1,024
#: positions) and 3.27 / 3.43 / 5.11 ms (3,072) at 32 / 64 / 128
CHUNK = 32
#: the same for a decay per key channel: four sub-blocks of 16, and half
#: the dependent steps of the scan over chunks
CHANNEL_CHUNK = 64


# ----------------------------------------------------------------------
# the short causal convolution
# ----------------------------------------------------------------------

def causal_conv(x, w):
    """Depthwise causal convolution over time: ``x`` (T, C), ``w``
    (K, C), ``y[t] = sum_j w[j] * x[t - (K - 1) + j]`` in float32, with
    zeros before the sequence: K shifted adds."""
    k, t = w.shape[0], x.shape[0]
    xp = jnp.pad(x.astype(jnp.float32), ((k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(w[j] * xp[j:j + t] for j in range(k))


def conv_tail(x, length, k):
    """The ``k - 1`` positions of ``x`` (T, C) before position
    ``length`` (zeros where the sequence has not begun): what the next
    token's convolution reads."""
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return lax.dynamic_slice_in_dim(xp, length, k - 1, axis=0)


def _tail_rows(channels, dtype):
    """Rows of 128 lanes one position's channels take in the tail leaf:
    a whole number of the dtype's sublane tiles (16 of bfloat16), so
    that a position starts on a tile (11,520 channels: 90 -> 96)."""
    tile = 8 * max(4 // jnp.dtype(dtype).itemsize, 1)
    return -(-channels // (_LANES * tile)) * tile


def tail_shape(rows, taps, channels, dtype):
    """The tail leaf: ``(rows, (taps - 1) * P, 128)``, a position's
    channels as ``P`` rows of lanes (:func:`_tail_rows`).  Its two
    minor dims are whole tiles, so the leaf lies unpadded and a row of
    it is one block of the step kernel."""
    return (rows, (taps - 1) * _tail_rows(channels, dtype), _LANES)


def pack_tail(tail, dtype):
    """``(K - 1, C)`` positions -> one row of the tail leaf."""
    k, c = tail.shape
    rows = _tail_rows(c, dtype)
    tail = jnp.pad(tail.astype(dtype), ((0, 0), (0, rows * _LANES - c)))
    return tail.reshape(k * rows, _LANES)


def _conv_step_kernel(rows_ref, x_ref, w_ref, tail_ref, y_ref,
                      tail_out_ref, *, taps, rows):
    del rows_ref
    x = x_ref[0]
    y = w_ref[taps - 1] * x.astype(jnp.float32)
    for j in range(taps - 1):
        y += w_ref[j] * tail_ref[0, j * rows:(j + 1) * rows].astype(
            jnp.float32)
    y_ref[0] = y
    if taps > 2:
        tail_out_ref[0, :(taps - 2) * rows] = tail_ref[0, rows:]
    tail_out_ref[0, (taps - 2) * rows:] = x


def causal_conv_step(tail, rows, x, w, bias=None):
    """One position a row, the tails where they lie: ``tail`` the leaf
    of :func:`tail_shape`, ``rows`` (N,) each sequence's row of it,
    ``x`` (N, C) the new position before the convolution, ``w`` (K, C),
    ``bias`` (C,) added to the result where the convolution has one.
    Returns ``(y (N, C) float32, tail)``: the convolution at the new
    position and the leaf with each row shifted by one position, in
    place (a Pallas kernel on the chip: one row's tail through VMEM a
    grid step, the leaf its aliased output, kept in HBM: donate it)."""
    n, c = x.shape
    taps = w.shape[0]
    p = tail.shape[1] // (taps - 1)
    f32 = jnp.float32

    def lanes(a, dtype):            # (.., C) -> (.., P, 128)
        a = jnp.pad(a.astype(dtype),
                    ((0, 0),) * (a.ndim - 1) + ((0, p * _LANES - c),))
        return a.reshape(a.shape[:-1] + (p, _LANES))

    x, w = lanes(x, tail.dtype), lanes(w, f32)
    if pallas_mode() == 'fallback':
        seen = jnp.concatenate([tail[rows], x], axis=1)    # (N, K P, 128)
        y = jnp.einsum('nkpl,kpl->npl',
                       seen.reshape(n, taps, p, _LANES).astype(f32), w)
        tail = tail.at[rows].set(seen[:, p:])
    else:
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        new = pl.BlockSpec((1, p, _LANES), lambda i, rows: (i, 0, 0))
        leaf = pl.BlockSpec((1,) + tail.shape[1:],
                            lambda i, rows: (rows[i], 0, 0))
        y, tail = pl.pallas_call(
            functools.partial(_conv_step_kernel, taps=taps, rows=p),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n,),
                in_specs=[new, pl.BlockSpec((taps, p, _LANES),
                                            lambda i, rows: (0, 0, 0)),
                          leaf],
                out_specs=[new, leaf]),
            # the leaf is said to lie in HBM (and with it the operand
            # it aliases): left to itself the compiler stages a leaf of
            # a few MB in VMEM whole around the call, a copy each way.
            # The enclosing jit must DONATE the leaf, as the engine's
            # executables do: this compiler (jax 0.9.0) aborts on a
            # coloured operand that is its own copy of a parameter
            out_shape=[jax.ShapeDtypeStruct((n, p, _LANES), f32),
                       pltpu.HBM(tail.shape, tail.dtype)],
            # operands count the prefetched rows: the tail is 3
            input_output_aliases={3: 1},
            interpret=interpret_flag(),
            name='causal_conv_step',
        )(rows.astype(jnp.int32), x, w, tail)
    y = y.reshape(n, p * _LANES)[:, :c]
    return y if bias is None else y + bias.astype(f32), tail


# ----------------------------------------------------------------------
# the state leaf
# ----------------------------------------------------------------------

def state_pack(heads, dv):
    """Heads side by side in the state leaf's lanes: the fewest whose
    ``dv`` together fill whole 128-lane tiles, 1 where the head count
    does not divide by that."""
    pack = _LANES // math.gcd(dv, _LANES)
    return pack if heads % pack == 0 else 1


def state_shape(rows, heads, dk, dv):
    pack = state_pack(heads, dv)
    return (rows, heads // pack, dk, pack * dv)


def pack_state(s):
    """``(..., H, dk, dv)`` -> the leaf's ``(..., H / P, dk, P * dv)``."""
    *lead, h, dk, dv = s.shape
    pack = state_pack(h, dv)
    s = s.reshape(*lead, h // pack, pack, dk, dv)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // pack, dk,
                                           pack * dv)


def unpack_state(s, heads):
    """The inverse of :func:`pack_state`."""
    *lead, groups, dk, lanes = s.shape
    pack = heads // groups
    s = s.reshape(*lead, groups, dk, pack, lanes // pack)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, heads, dk,
                                           lanes // pack)


# ----------------------------------------------------------------------
# the recurrence as written, and in chunks
# ----------------------------------------------------------------------

def _one_step(s, q, k, v, g, beta):
    """The update on ``s`` (..., dk, dv) with ``q`` / ``k`` (..., dk),
    ``v`` (..., dv), ``beta`` (...) and ``g`` (...) or, a decay per key
    channel, (..., dk): ``(o, s)``."""
    s = s * (jnp.exp(g)[..., None] if g.ndim == k.ndim
             else jnp.exp(g)[..., None, None])
    u = (v - jnp.einsum('...kv,...k->...v', s, k)) * beta[..., None]
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum('...kv,...k->...v', s, q), s


def gated_delta_reference(q, k, v, g, beta, state0=None):
    """``q`` / ``k`` (T, H, dk), ``v`` (T, H, dv), ``beta`` (T, H),
    ``g`` (T, H) or (T, H, dk), ``state0`` (H, dk, dv) or zeros: ``(o
    (T, H, dv), state)`` in float32, one position at a time."""
    f32 = jnp.float32
    t, h, dk = q.shape
    if state0 is None:
        state0 = jnp.zeros((h, dk, v.shape[-1]), f32)

    def body(s, x):
        o, s = _one_step(s, *x)
        return s, o

    state, o = lax.scan(body, state0.astype(f32), tuple(
        x.astype(f32) for x in (q, k, v, g, beta)))
    return o, state


#: rows of a diagonal block solved one after the other
_SOLVE_BLOCK = 16


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., c, c),
    ``c`` a power of two.  Forward substitution, row by row, inside the
    diagonal blocks of 16 (all of them at once); then the blocks are
    merged pairwise, ``[[L, 0], [B, H]]^-1 = [[L', 0], [-H' B L', H']]``,
    two small products a level.  As stable as substitution (a power
    series in ``a`` is not: with ``beta`` near 2 on repeated keys its
    terms reach 1e30 before they cancel), and on the chip two levels of
    products where XLA's triangular solve is ``c`` dependent steps."""
    f32 = jnp.float32
    c = a.shape[-1]
    lead = a.shape[:-2]
    size = min(c, _SOLVE_BLOCK)
    n = c // size
    view = a.reshape(lead + (n, size, n, size))
    diag = jnp.stack([view[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(size, dtype=f32)
    rows = []
    for i in range(size):
        row = jnp.broadcast_to(eye[i], diag.shape[:-2] + (size,))
        if i:
            row = row - jnp.einsum(
                '...j,...jk->...k', diag[..., i, :i],
                jnp.stack(rows, axis=-2), precision=lax.Precision.HIGHEST)
        rows.append(row)
    blocks = jnp.stack(rows, axis=-2)                  # (..., n, s, s)
    dot = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    while size < c:
        n //= 2
        view = a.reshape(lead + (n, 2 * size, n, 2 * size))
        off = jnp.stack([view[..., p, size:, p, :size] for p in range(n)],
                        axis=-3)
        pair = blocks.reshape(lead + (n, 2, size, size))
        low, high = pair[..., 0, :, :], pair[..., 1, :, :]
        corner = -dot('...ij,...jk,...kl->...il', high, off, low)
        blocks = jnp.concatenate([
            jnp.concatenate([low, jnp.zeros_like(low)], axis=-1),
            jnp.concatenate([corner, high], axis=-1)], axis=-2)
        size *= 2
    return blocks[..., 0, :, :]


def _channel_pairs(qc, kc, kb, decay):
    """A chunk's two pair matrices with the decay INSIDE the
    contraction: ``a_ij = sum_d kb_i[d] k_j[d] exp(G_i[d] - G_j[d])``
    for ``j < i`` (the triangular system's) and ``attn_ij``, the same
    of ``q_i`` for ``j <= i``.  Operands (H, n, c, dk) float32,
    ``decay`` the running sums ``G`` (falling, <= 0).  No exponent is
    positive (the module's note): a pair inside a sub-block of 16 takes
    the three-index form, a pair across two is factored through the
    later one's first position."""
    f32 = jnp.float32
    h, n, c, dk = kc.shape
    sub = min(c, _SOLVE_BLOCK)
    m = c // sub
    qs, ks, bs, gs = (x.reshape(h, n, m, sub, dk)
                      for x in (qc, kc, kb, decay))
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    # (.., i, j, d): k_j exp(G_i - G_j), masked BEFORE the exponential
    ke = ks[..., None, :, :] * jnp.exp(jnp.where(
        lower[:, :, None], gs[..., :, None, :] - gs[..., None, :, :],
        -jnp.inf))
    a_diag = jnp.where(jnp.tril(lower, -1),
                       jnp.sum(bs[..., :, None, :] * ke, -1), 0.0)
    attn_diag = jnp.sum(qs[..., :, None, :] * ke, -1)
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    a_rows, attn_rows = [], []
    for i in range(m):
        a_row, attn_row = [a_diag[..., i, :, :]], [attn_diag[..., i, :, :]]
        if i:
            first = gs[..., i, :1, :]                  # G at r
            into = jnp.exp(gs[..., i, :, :] - first)   # r -> i, <= 1
            out_of = kc[..., :i * sub, :] * jnp.exp(
                first - decay[..., :i * sub, :])       # j -> r, <= 1
            a_row.insert(0, dot(
                'hnid,hnjd->hnij', bs[..., i, :, :] * into, out_of,
                precision=lax.Precision.HIGHEST))
            attn_row.insert(0, dot('hnid,hnjd->hnij',
                                   qs[..., i, :, :] * into, out_of))
        if i < m - 1:
            zeros = jnp.zeros((h, n, sub, (m - 1 - i) * sub), f32)
            a_row.append(zeros)
            attn_row.append(zeros)
        a_rows.append(jnp.concatenate(a_row, -1))
        attn_rows.append(jnp.concatenate(attn_row, -1))
    return jnp.concatenate(a_rows, -2), jnp.concatenate(attn_rows, -2)


def _channel_rule(q, k, v, g, beta, state):
    """The chunked rule with a decay per key channel, ``g`` (T, H, dk)
    (float32, masked past the prompt's length by the caller): every
    chunk of ``CHANNEL_CHUNK`` positions' system at once, then the
    state through the chunks in turn."""
    f32 = jnp.float32
    t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(CHANNEL_CHUNK, 1 << (t - 1).bit_length())
    pad = -t % c
    n = (t + pad) // c

    def chunks(x):
        # (T, H, ...) -> (H, n, c, ...); a padded position is an
        # identity step (g = 0, beta = 0)
        x = jnp.pad(x.astype(f32),
                    ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape((n, c) + x.shape[1:]), 2, 0)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta))
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    decay = jnp.cumsum(gc, axis=2)                 # (H, n, c, dk), <= 0
    kb = kc * bc[..., None]
    a, attn = _channel_pairs(qc, kc, kb, decay)
    # u = T (beta v), w = T (beta exp(G) k), T = (I + a)^-1
    rhs = jnp.concatenate([vc * bc[..., None], kb * jnp.exp(decay)], -1)
    solved = dot('hnij,hnjd->hnid', _unit_lower_inverse(a), rhs,
                 precision=lax.Precision.HIGHEST)
    u, w = solved[..., :dv], solved[..., dv:]
    qd = qc * jnp.exp(decay)
    kd = kc * jnp.exp(decay[..., -1:, :] - decay)
    last = jnp.exp(decay[..., -1, :])              # (H, n, dk)

    def body(s, x):
        w_i, u_i, qd_i, kd_i, attn_i, last_i = x
        v_new = u_i - dot('hck,hkv->hcv', w_i, s)
        o = dot('hck,hkv->hcv', qd_i, s) + dot('hij,hjv->hiv', attn_i,
                                               v_new)
        s = s * last_i[:, :, None] + dot('hck,hcv->hkv', kd_i, v_new)
        return s, o

    state, o = lax.scan(body, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (w, u, qd, kd, attn, last)))
    # (n, H, c, dv) -> (T, H, dv)
    return jnp.moveaxis(o, 1, 2).reshape(n * c, h, dv)[:t], state


def gated_delta_rule(q, k, v, g, beta, state0=None, length=None):
    """The same function as :func:`gated_delta_reference`, in chunks of
    ``CHUNK`` positions (``g`` (T, H, dk): of ``CHANNEL_CHUNK``, by
    :func:`_channel_rule`); positions at or past ``length`` change
    nothing (their ``o`` is what the final state gives their ``q``).
    The big products take their operands as stored (bfloat16 in
    serving) and accumulate in float32; the decays, the triangular
    system and the state are float32."""
    f32 = jnp.float32
    t, h, dk = q.shape
    dv = v.shape[-1]
    g, beta = g.astype(f32), beta.astype(f32)
    if length is not None:
        live = jnp.arange(t) < length
        g = jnp.where(live.reshape((t,) + (1,) * (g.ndim - 1)), g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    if state0 is None:
        state0 = jnp.zeros((h, dk, dv), f32)
    if g.ndim == 3:
        return _channel_rule(q, k, v, g, beta, state0.astype(f32))
    c = min(CHUNK, 1 << (t - 1).bit_length())
    pad = -t % c
    n = (t + pad) // c

    def chunks(x):
        # (T, H, ...) -> (H, n, c, ...); a padded position is an
        # identity step (g = 0, beta = 0)
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape((n, c) + x.shape[1:]), 2, 0)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta))
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    decay = jnp.cumsum(gc, axis=-1)                    # (H, n, c), <= 0
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp(decay_i - decay_j) for j <= i; masked BEFORE the exponential
    # (above the diagonal the difference is positive)
    pair = jnp.exp(jnp.where(
        lower, decay[..., :, None] - decay[..., None, :], -jnp.inf))
    kb = kc.astype(f32) * bc[..., None]
    a = dot('hnid,hnjd->hnij', kb, kc.astype(f32),
            precision=lax.Precision.HIGHEST) * pair
    a = jnp.where(jnp.tril(lower, -1), a, 0.0)
    # u = T (beta v), w = T (beta exp(decay) k), T = (I + a)^-1: two
    # right-hand sides side by side
    rhs = jnp.concatenate(
        [vc.astype(f32) * bc[..., None],
         kb * jnp.exp(decay)[..., None]], axis=-1)
    solved = dot('hnij,hnjd->hnid', _unit_lower_inverse(a), rhs,
                 precision=lax.Precision.HIGHEST)
    u, w = solved[..., :dv], solved[..., dv:]
    attn = dot('hnid,hnjd->hnij', qc, kc) * pair       # j <= i
    qd = qc.astype(f32) * jnp.exp(decay)[..., None]
    kd = kc.astype(f32) * jnp.exp(decay[..., -1:] - decay)[..., None]
    last = jnp.exp(decay[..., -1])                     # (H, n)

    def body(s, x):
        w_i, u_i, qd_i, kd_i, attn_i, last_i = x
        v_new = u_i - dot('hck,hkv->hcv', w_i, s)
        o = dot('hck,hkv->hcv', qd_i, s) + dot('hij,hjv->hiv', attn_i,
                                               v_new)
        s = s * last_i[:, None, None] + dot('hck,hcv->hkv', kd_i, v_new)
        return s, o

    state, o = lax.scan(body, state0.astype(f32), tuple(
        jnp.moveaxis(x, 1, 0) for x in (w, u, qd, kd, attn, last)))
    # (n, H, c, dv) -> (T, H, dv)
    o = jnp.moveaxis(o, 1, 2).reshape(n * c, h, dv)[:t]
    return o, state


# ----------------------------------------------------------------------
# one token a row, the state where it lies
# ----------------------------------------------------------------------

def _step_kernel(rows_ref, qt_ref, kt_ref, v_ref, decay_ref, beta_ref,
                 s_ref, o_ref, s_out_ref, *, pack, dv, channel):
    """One row: every head group's ``(dk, P * dv)`` tile through the
    update.  ``qt`` / ``kt`` are (dk, H): a head's column is broadcast
    over its ``dv`` lanes; ``v``, the decay and ``beta`` come spread
    over the lanes already, (H / P, P * dv).  ``channel``: the decay is
    per key channel and comes as ``kt`` does, a column over ``dk`` a
    head."""
    del rows_ref
    groups = s_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (1, pack * dv), 1)

    def columns(ref, group):
        out = ref[0, :, group * pack:group * pack + 1]     # (dk, 1)
        for p in range(1, pack):
            at = group * pack + p
            out = jnp.where(lane < p * dv, out, ref[0, :, at:at + 1])
        return out                                         # (dk, P*dv)

    for group in range(groups):
        kx, qx = columns(kt_ref, group), columns(qt_ref, group)
        s = s_ref[0, group] * (columns(decay_ref, group) if channel
                               else decay_ref[0, group:group + 1])
        u = (v_ref[0, group:group + 1]
             - jnp.sum(s * kx, axis=0, keepdims=True)) \
            * beta_ref[0, group:group + 1]
        s = s + kx * u
        o_ref[0, group:group + 1] = jnp.sum(s * qx, axis=0,
                                            keepdims=True)
        s_out_ref[0, group] = s


def _step_pallas(state, rows, q, k, v, g, beta):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, dk = q.shape
    dv = v.shape[-1]
    groups, lanes = state.shape[1], state.shape[3]
    pack = h // groups
    f32 = jnp.float32

    def spread(x):                  # (N, H) -> (N, H / P, P * dv)
        return jnp.repeat(x.astype(f32), dv, axis=1).reshape(
            n, groups, lanes)

    def row(shape):
        return pl.BlockSpec((1,) + shape, lambda i, rows: (i, 0, 0))

    cols, wide = row((dk, h)), row((groups, lanes))
    leaf = pl.BlockSpec((1, groups, dk, lanes),
                        lambda i, rows: (rows[i], 0, 0, 0))
    channel = g.ndim == 3
    decay = jnp.exp(g.astype(f32))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, pack=pack, dv=dv,
                          channel=channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n,),
            in_specs=[cols, cols, wide, cols if channel else wide, wide,
                      leaf],
            out_specs=[wide, leaf]),
        out_shape=[jax.ShapeDtypeStruct((n, groups, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the prefetched rows: the state is 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_flag(),
        name='gated_delta_step',
    )(rows.astype(jnp.int32),
      jnp.swapaxes(q.astype(f32), 1, 2), jnp.swapaxes(k.astype(f32), 1, 2),
      v.astype(f32).reshape(n, groups, lanes),
      jnp.swapaxes(decay, 1, 2) if channel else spread(decay),
      spread(beta), state)
    return o.reshape(n, h, dv), state


def gated_delta_step(state, rows, q, k, v, g, beta):
    """One position a row.  ``state``: the leaf of :func:`state_shape`;
    ``rows`` (N,) the row of each sequence in it; ``q`` / ``k`` (N, H,
    dk), ``v`` (N, H, dv), ``beta`` (N, H), ``g`` (N, H) or, a decay
    per key channel, (N, H, dk).  Returns ``(o (N,
    H, dv) float32, state)``: the rows updated in place, nothing else of
    the leaf moved.  Rows that share a state row (idle rows on row 0)
    overwrite each other, as a scatter's would."""
    if pallas_mode() != 'fallback':
        return _step_pallas(state, rows, q, k, v, g, beta)
    f32 = jnp.float32
    h = q.shape[1]
    o, new = _one_step(unpack_state(state[rows], h), *(
        x.astype(f32) for x in (q, k, v, g, beta)))
    return o, state.at[rows].set(pack_state(new))
