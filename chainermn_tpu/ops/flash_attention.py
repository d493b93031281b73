"""Fused blockwise (flash) attention for TPU.

The reference has no attention anywhere (era-appropriate CNN/MLP
workloads only; SURVEY 5 "long-context: absent") -- this op is part of
the long-context surface that is first-class here.  Design follows the
standard flash-attention recurrence (running max ``m``, rescaled
numerator/denominator), tiled so each (query-block, key-block) score
tile lives only in VMEM and the (T, T) matrix is never materialized in
HBM.  The MXU sees two large matmuls per tile; masking and the softmax
bookkeeping ride the VPU.

Layout: inputs are (B, T, H, D) like the rest of the framework; the
kernel grid is (B*H, T/block_q, T/block_k) -- the opposite-operand
stream is a *grid dimension*, so VMEM holds one (block_q, block_k)
tile plus the running (m, l, acc) scratch regardless of sequence
length.  The tiles are a function of the shapes (``_flash_blocks``):
a grid step costs ~0.4 us whatever it computes, so a tile is as large
as the sweep on the chip found worth it (512 to 1,024 positions a side
at the trainer's 1,024 x 64 heads) and the VMEM the kernels ask for
holds.  bfloat16 operands go to the MXU as stored and the computed
ones (``p``, ``ds``) are rounded to bfloat16 for their products; the
softmax statistics and the accumulators are float32.  The softmax
recurrence carries across the innermost grid axis in VMEM scratch;
outputs are written on its final step.  Per-row scalars (``lse``,
``delta``) cross HBM as (B*H, 1, T) rows, the sequence along the lanes.

The backward pass is the standard flash backward split into two Mosaic
kernels on TPU (dq over query blocks; dk/dv over key blocks, each
streaming the opposite operand the same way) with
``delta = rowsum(g * out)`` precomputed; non-TPU backends use an
equivalent blockwise ``lax.scan`` formulation that doubles as the
numerics oracle.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from chainermn_tpu.ops._common import NEG_INF, interpret_flag, pallas_mode


def mha_reference(q, k, v, causal=False, scale=None):
    """Pure-jnp oracle: full softmax attention. (B, T, H, D) in/out."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = scores.shape[-2:]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p.astype(v.dtype), v)


# ----------------------------------------------------------------------
# tiles: a function of the shapes
# ----------------------------------------------------------------------

_LANES = 128

# What one of the three kernels may ask of a core's VMEM (a v5e core
# has 128 MiB; the compiler's default scoped limit is 16 MiB).  Stated
# in each pallas_call; _flash_blocks keeps a step's working set under
# it.  The largest step the rule emits (float32 inputs, d 128, a
# 1,024 x 1,024 tile) takes 14.6 MB compiled for a v5e.
_VMEM_LIMIT = 32 * 1024 * 1024

# Largest (block_q, block_k) per kernel, from the sweep on the chip at
# the training cell's shapes (bf16[128,1024,64], causal; PERF.md
# section 6, PR 28): the forward and dQ are fastest with a head's whole
# 1,024 x 1,024 square in one step, dead half included; dK/dV, which
# streams queries past a resident key tile, at 512 x 512.
_TILE_CAP = {'fwd': (1024, 1024), 'dq': (1024, 1024), 'dkv': (512, 512)}

# float32 (block_q, block_k) temporaries a step holds at once (the
# score tile and what is made of it); Mosaic keeps 1.25 (forward) to
# 1.65 (backward) of them, read off its allocation for a described v5e
_TILE_TEMPS = 2


def _flash_vmem_bytes(kernel, block_q, block_k, d, itemsize):
    """A grid step's working set: the float32 score-tile temporaries,
    the double-buffered operand and result blocks, the scratch."""
    tile = _TILE_TEMPS * block_q * block_k * 4
    lanes = -(-max(d, _LANES) // _LANES) * _LANES  # whole 128-lane rows
    n_q, n_k = {'fwd': (2, 2), 'dq': (3, 2), 'dkv': (2, 4)}[kernel]
    blocks = 2 * (n_q * block_q + n_k * block_k) * lanes * itemsize
    acc_rows = block_k if kernel == 'dkv' else block_q
    scratch = (2 * acc_rows * lanes + 2 * block_q * _LANES) * 4
    return tile + blocks + scratch


def _padded_len(t):
    """What a sequence is padded to.  Up to 128 positions: itself (one
    block, the array's full extent).  Above: a multiple of the largest
    power-of-two multiple of 128 that is at most a quarter of it (512
    at most), so the padding stays under a quarter of the sequence and
    a tile of at least that size divides the result."""
    if t <= _LANES:
        return t
    unit = _LANES
    while unit < 512 and unit * 8 <= t:
        unit *= 2
    return -(-t // unit) * unit


def _flash_blocks(t_q, t_kv, d, dtype, window=None, kernel='fwd'):
    """``(block_q, block_k)`` for one of the three kernels from what
    the call can see.  Each is the largest power-of-two multiple of
    128 that divides the padded length, stays under the kernel's cap
    and keeps the step's working set inside ``_VMEM_LIMIT``; a
    sequence of at most 128 positions is one block.  A key tile wider
    than the window of a windowed layer would fetch keys no row of the
    query tile sees, so the window caps it."""
    cap_q, cap_k = _TILE_CAP[kernel]
    if window is not None:
        cap_k = min(cap_k, max(_LANES, -(-window // _LANES) * _LANES))
    itemsize = jnp.dtype(dtype).itemsize

    def largest(t, cap):
        t = _padded_len(t)
        if t <= _LANES:
            return t
        b = _LANES
        while b * 2 <= cap and t % (b * 2) == 0:
            b *= 2
        return b

    block_q, block_k = largest(t_q, cap_q), largest(t_kv, cap_k)
    # halve the longer side until the step fits (the operand blocks
    # grow with d past 128 and double for float32 inputs)
    while (_flash_vmem_bytes(kernel, block_q, block_k, d, itemsize)
           > _VMEM_LIMIT):
        if block_k >= block_q and block_k > _LANES:
            block_k //= 2
        elif block_q > _LANES:
            block_q //= 2
        else:
            break
    return block_q, block_k


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


# ----------------------------------------------------------------------
# what the three kernels share
# ----------------------------------------------------------------------

_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _dot(a, b, dims):
    """One MXU product with a float32 result.  bfloat16 operands go in
    as stored, one pass (a product of two bfloat16 numbers is exact in
    float32); Mosaic takes no other precision for them, and the tests'
    process-wide default is ``highest``.  float32 operands keep the
    caller's precision."""
    precision = (lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
                 else None)
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _col_to_row(col):
    """(rows, 128) lane-replicated per-row scalars -> (1, rows): the
    layout they cross HBM in, a sequence along the lanes."""
    return col.T[:1, :]


def _row_to_col(row):
    """(1, rows) -> (rows, 128) lane-replicated."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _tile_mask(shape, q0, k0, q_axis, causal, kv_len, window):
    """Which entries of a score tile count.  ``q0`` / ``k0`` are the
    tile's first query and key positions, ``q_axis`` the axis queries
    run along (0; 1 in the dK/dV kernel's transposed tile).  ``kv_len``
    is None where no key of the call is padding."""
    # q_pos - k_pos, one subtraction for both bounds
    rel = (lax.broadcasted_iota(jnp.int32, shape, q_axis)
           - lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
           + (q0 - k0))
    ok = None
    if causal:
        ok = rel >= 0
        if window is not None:
            ok = jnp.logical_and(ok, rel < window)
    if kv_len is not None:
        k_pos = k0 + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
        pad = k_pos < kv_len
        ok = pad if ok is None else jnp.logical_and(ok, pad)
    return ok


def _when_live(q0, q1, k0, k1, causal, window, accum):
    """Run ``accum`` if the tile of queries ``[q0, q1)`` and keys
    ``[k0, k1)`` has an entry that counts: its first key is not past
    its last query, and with a window its last key is inside the first
    query's window.  (Masking only the tiles an edge crosses bought
    nothing on the chip: the kernels wait for the MXU, not the VPU.)"""
    import jax.experimental.pallas as pl

    if not causal:
        accum()
        return
    live = k0 < q1
    if window is not None:
        live = jnp.logical_and(live, k1 - 1 > q0 - window)
    pl.when(live)(accum)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                acc_ref, *, scale, causal, kv_len, block_q, block_k,
                window=None):
    """One (batch*head, query-block, key-block) grid cell.

    The key-block axis is the innermost (sequential) grid dimension;
    the running (m, l, acc) state lives in VMEM scratch across its
    steps, so only one K/V tile is resident at a time.  ``m``/``l``
    are kept lane-replicated at (block_q, 128) -- the Mosaic-friendly
    layout for per-row scalars; ``lse`` leaves as a (1, block_q) row.
    ``kv_len`` (static, or None) masks out padded key positions
    >= kv_len.  ``window`` (static, causal only) keeps the ``window``
    keys ending at the query's own position: a key block wholly before
    every row's window is skipped like one past the causal frontier.
    (A row whose first visited block lies wholly before ITS window
    accumulates garbage at ``m = NEG_INF``; the first live block's
    ``alpha = exp(NEG_INF - m_new) = 0`` wipes it.)
    """
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0, k0 = qi * block_q, kj * block_k
    masked = causal or kv_len is not None

    def _accum():
        v = v_ref[0]                                  # (block_k, D)
        # the scale on the score tile, not on q: q goes to the MXU as
        # it is stored
        s = _dot(q_ref[0], k_ref[0], _NT) * scale     # (block_q, block_k)
        if masked:
            s = jnp.where(
                _tile_mask(s.shape, q0, k0, 0, causal, kv_len, window),
                s, NEG_INF)
        m_prev = m_ref[...]                           # (block_q, 128)
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        m_ref[...] = m_new
        l_ref[...] = (l_ref[...] * alpha
                      + jnp.sum(p, axis=-1, keepdims=True))
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + _dot(p.astype(v.dtype), v, _NN))

    _when_live(q0, q0 + block_q, k0, k0 + block_k, causal, window,
               _accum)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = _col_to_row(m_ref[...] + jnp.log(l_safe))


def _fwd_pallas(q, k, v, causal, scale, kv_len, block_q, block_k,
                group=1, window=None):
    """``(out, lse)``, ``lse`` as ``(B*H, 1, T)``: the sequence along
    the lanes, so a block of it is ``block_q`` floats beside the
    query block.  ``group`` query heads read one K/V head: row ``b``
    of the merged ``(B*H, T, D)`` queries takes its keys from row
    ``b // group`` of the ``(B*H/group, T, D)`` keys, in the index map,
    so the repeat is never materialised.  ``v`` may have a width of
    its own (``dv``): the output and the accumulator take it."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_kv, dv = k.shape[1], v.shape[2]
    grid = (bh, t_q // block_q, t_kv // block_k)

    def kv_row(b):
        # no divide on the scalar core in every grid step of the
        # ungrouped call (the trainer's)
        return b if group == 1 else b // group

    if causal:
        # clamp the fetched K/V block at the causal frontier: steps
        # beyond it are compute-skipped (pl.when), and the repeated
        # block index makes Pallas elide the now-useless DMA instead
        # of streaming ~2x the needed K/V traffic; a window clamps the
        # other end the same way
        def kv_ix(b, i, j):
            frontier = ((i + 1) * block_q + block_k - 1) // block_k - 1
            j = jnp.minimum(j, frontier)
            if window is not None:
                j = jnp.maximum(j, jnp.maximum(
                    i * block_q - window + 1, 0) // block_k)
            return (kv_row(b), j, 0)
    else:
        def kv_ix(b, i, j):
            return (kv_row(b), j, 0)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          kv_len=kv_len if kv_len < t_kv else None,
                          block_q=block_q, block_k=block_k,
                          window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_ix,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), kv_ix,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m (replicated)
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l (replicated)
            pltpu.VMEM((block_q, dv), jnp.float32),      # acc
        ],
        compiler_params=_compiler_params(),
        interpret=interpret_flag(),
        name='flash_attention_fwd',
    )(q, k, v)


def _fwd_blockwise_jnp(q, k, v, causal, scale, kv_len, block_k,
                       group=1, window=None):
    """Fallback forward: same recurrence as the kernel, via lax.scan
    (grouped K/V heads are repeated here, a key block at a time)."""
    bh, t_q, d = q.shape
    t_kv, dv = k.shape[1], v.shape[2]
    qf = q.astype(jnp.float32) * scale
    n_blocks = t_kv // block_k
    kb = k.reshape(-1, n_blocks, block_k, d).astype(jnp.float32)
    vb = v.reshape(-1, n_blocks, block_k, dv).astype(jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        if group != 1:
            kj = jnp.repeat(kj, group, axis=0)
            vj = jnp.repeat(vj, group, axis=0)
        s = jnp.einsum('bqd,bkd->bqk', qf, kj)
        q_pos = jnp.arange(t_q)[:, None]
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, q_pos >= k_pos)
        if window is not None:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum('bqk,bkd->bqd', p, vj)
        return (m_new, l, acc), None

    m0 = jnp.full((bh, t_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, t_q), jnp.float32)
    acc0 = jnp.zeros((bh, t_q, dv), jnp.float32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0),
        (jnp.arange(n_blocks), jnp.swapaxes(kb, 0, 1),
         jnp.swapaxes(vb, 0, 1)))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    return out, m + jnp.log(l_safe)


# ----------------------------------------------------------------------
# backward -- Pallas kernels (dq; dk/dv) on TPU, jnp scan fallback.
# Standard flash backward: delta = rowsum(g * out) precomputed, then
#   p  = exp(s - lse);  dp = g @ v^T;  ds = p * (dp - delta)
#   dq = scale * sum ds @ k;  dk = scale * sum ds^T @ q;  dv = sum p^T @ g
# lse and delta come in as (B*H, 1, T) rows.  The dq kernel turns its
# query block's rows into columns once, at its first key step; the
# dK/dV kernel works on the TRANSPOSED tile (keys down the sublanes,
# queries along the lanes), where a row is what broadcasts and every
# product is a plain or a b-transposed one.
# ----------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, lse_col, delta_col, *, scale,
                   causal, kv_len, block_q, block_k):
    """dq: grid (bh, query-block, key-block); K/V tiles stream over
    the innermost axis, dq accumulates in VMEM scratch."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lse_col[...] = _row_to_col(lse_ref[0])
        delta_col[...] = _row_to_col(delta_ref[0])

    q0, k0 = qi * block_q, kj * block_k
    masked = causal or kv_len is not None

    def _accum():
        k = k_ref[0]                                  # (block_k, D)
        s = _dot(q_ref[0], k, _NT) * scale            # (block_q, block_k)
        if masked:
            s = jnp.where(
                _tile_mask(s.shape, q0, k0, 0, causal, kv_len, None),
                s, NEG_INF)
        p = jnp.exp(s - lse_col[:, :1])
        dp = _dot(g_ref[0], v_ref[0], _NT)
        ds = p * (dp - delta_col[:, :1])
        acc_ref[...] = acc_ref[...] + _dot(ds.astype(k.dtype), k, _NN)

    _when_live(q0, q0 + block_q, k0, k0 + block_k, causal, None, _accum)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    kv_len, block_q, block_k):
    """dk/dv: grid (bh, key-block, query-block); Q/G/lse/delta tiles
    stream over the innermost axis, dk/dv accumulate in VMEM scratch.
    The tile is (block_k, block_q): s^T, p^T, dp^T, ds^T."""
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    qj = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q0, k0 = qj * block_q, ki * block_k
    masked = causal or kv_len is not None

    def _accum():
        q = q_ref[0]                                  # (block_q, D)
        g = g_ref[0]
        s = _dot(k_ref[0], q, _NT) * scale            # (block_k, block_q)
        if masked:
            s = jnp.where(
                _tile_mask(s.shape, q0, k0, 1, causal, kv_len, None),
                s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                   # row (1, block_q)
        dv_acc[...] = dv_acc[...] + _dot(p.astype(g.dtype), g, _NN)
        dp = _dot(v_ref[0], g, _NT)
        ds = p * (dp - delta_ref[0])
        dk_acc[...] = dk_acc[...] + _dot(ds.astype(q.dtype), q, _NN)

    _when_live(q0, q0 + block_q, k0, k0 + block_k, causal, None, _accum)

    @pl.when(qj == n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_specs(block_q, block_k):
    """Block specs of the backward kernels; ``width`` is the block's
    last dim: the key width for q / k / dq / dk, the value width for
    v / g / dv."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def q_blk(ix, width):
        return pl.BlockSpec((1, block_q, width), ix,
                            memory_space=pltpu.VMEM)

    def kv_blk(ix, width):
        return pl.BlockSpec((1, block_k, width), ix,
                            memory_space=pltpu.VMEM)

    def row_blk(ix):
        # a query block's lse / delta: block_q floats along the lanes
        def row_ix(*grid_ix):
            b, i, _ = ix(*grid_ix)
            return (b, 0, i)
        return pl.BlockSpec((1, 1, block_q), row_ix,
                            memory_space=pltpu.VMEM)

    return q_blk, kv_blk, row_blk


def _bwd_dq_pallas(q, k, v, g, lse, delta, causal, scale, kv_len,
                   block_q, block_k):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_kv, dv = k.shape[1], v.shape[2]
    q_blk, kv_blk, row_blk = _bwd_specs(block_q, block_k)
    # (b, i=query block, j=key block)
    by_i = lambda b, i, j: (b, i, 0)   # noqa: E731
    if causal:
        # same causal DMA elision as the forward (see _fwd_pallas)
        def by_j(b, i, j):
            frontier = ((i + 1) * block_q + block_k - 1) // block_k - 1
            return (b, jnp.minimum(j, frontier), 0)
    else:
        by_j = lambda b, i, j: (b, j, 0)   # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          kv_len=kv_len if kv_len < t_kv else None,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t_q // block_q, t_kv // block_k),
        in_specs=[q_blk(by_i, d), kv_blk(by_j, d), kv_blk(by_j, dv),
                  q_blk(by_i, dv), row_blk(by_i), row_blk(by_i)],
        out_specs=q_blk(by_i, d),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret_flag(),
        name='flash_attention_bwd_dq',
    )(q, k, v, g, lse, delta)


def _bwd_dkv_pallas(q, k, v, g, lse, delta, causal, scale, kv_len,
                    block_q, block_k):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_kv, dv = k.shape[1], v.shape[2]
    q_blk, kv_blk, row_blk = _bwd_specs(block_q, block_k)
    # (b, i=key block, j=query block); for causal, query blocks before
    # the key block are skipped -- clamp the fetch from below so the
    # leading dead steps re-fetch (elide) the first contributing block
    by_i = lambda b, i, j: (b, i, 0)   # noqa: E731
    if causal:
        def by_jq(b, i, j):
            return (b, jnp.maximum(j, (i * block_k) // block_q), 0)
    else:
        by_jq = lambda b, i, j: (b, j, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          kv_len=kv_len if kv_len < t_kv else None,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t_kv // block_k, t_q // block_q),
        in_specs=[q_blk(by_jq, d), kv_blk(by_i, d), kv_blk(by_i, dv),
                  q_blk(by_jq, dv), row_blk(by_jq), row_blk(by_jq)],
        out_specs=[kv_blk(by_i, d), kv_blk(by_i, dv)],
        out_shape=[jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t_kv, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret_flag(),
        name='flash_attention_bwd_dkv',
    )(q, k, v, g, lse, delta)


def _bwd_pallas(q, k, v, out, lse, g, causal, scale, kv_len, blocks):
    """``lse`` as the forward kernel left it, ``(B*H, 1, T)``.
    ``blocks``: the caller's explicit ``(block_q, block_k)`` for both
    kernels, or None for each kernel's own from the shapes.  ``v``,
    ``out`` and ``g`` may have a width of their own: dQ / dK come at
    the key width, dV and ``delta`` at the value width."""
    t_q = q.shape[1]
    t_kv, d = k.shape[1], max(q.shape[2], v.shape[2])
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]               # (bh, 1, t_q)

    def tiles(kernel):
        return blocks or _flash_blocks(t_q, t_kv, d, q.dtype,
                                       kernel=kernel)

    dq = _bwd_dq_pallas(q, k, v, g, lse, delta, causal, scale, kv_len,
                        *tiles('dq'))
    dk, dv = _bwd_dkv_pallas(q, k, v, g, lse, delta, causal, scale,
                             kv_len, *tiles('dkv'))
    return dq, dk, dv


# ----------------------------------------------------------------------
# backward (blockwise, lax.scan over key blocks) -- fallback/oracle
# ----------------------------------------------------------------------

def _bwd_blockwise(q, k, v, out, lse, g, causal, scale, kv_len, block_k):
    bh, t_q, d = q.shape
    t_kv, d_v = k.shape[1], v.shape[2]
    n_blocks = t_kv // block_k
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)   # (bh, t_q)
    kb = jnp.swapaxes(k.reshape(bh, n_blocks, block_k, d), 0, 1)
    vb = jnp.swapaxes(v.reshape(bh, n_blocks, block_k, d_v), 0, 1)

    def body(dq, inp):
        j, kj, vj = inp
        kjf = kj.astype(jnp.float32)
        s = jnp.einsum('bqd,bkd->bqk', qf, kjf) * scale
        q_pos = jnp.arange(t_q)[:, None]
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, q_pos >= k_pos)
        s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                      # (bh, tq, bk)
        dp = jnp.einsum('bqd,bkd->bqk', gf, vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum('bqk,bkd->bqd', ds, kjf)
        dkj = jnp.einsum('bqk,bqd->bkd', ds, qf)
        dvj = jnp.einsum('bqk,bqd->bkd', p, gf)
        return dq, (dkj, dvj)

    dq0 = jnp.zeros((bh, t_q, d), jnp.float32)
    dq, (dk, dv) = lax.scan(
        body, dq0, (jnp.arange(n_blocks), kb, vb))
    dk = jnp.swapaxes(dk, 0, 1).reshape(bh, t_kv, d)
    dv = jnp.swapaxes(dv, 0, 1).reshape(bh, t_kv, d_v)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ----------------------------------------------------------------------
# public op
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, kv_len, blocks):
    """``blocks``: the caller's explicit ``(block_q, block_k)``, used
    by all three kernels, or None: each kernel's own tiles from the
    (padded) shapes."""
    out, _ = _flash_fwd(q, k, v, causal, scale, kv_len, blocks)
    return out


def _scan_block(blocks, t_kv):
    """The jnp fallback's key block: the caller's, else 128 keys a scan
    step as ever (the derived tiles are the Mosaic kernels')."""
    return blocks[1] if blocks else min(_LANES, t_kv)


#: the names :func:`_flash_fwd` gives its five residuals, in their
#: order: the merged ``q``, ``k``, ``v`` (the caller's arithmetic,
#: relaid for the kernels), the kernel's output and its rows'
#: log-sum-exp (which only the kernel can make).  A caller's
#: ``jax.checkpoint(f, policy=jax.checkpoint_policies.
#: save_only_these_names(...))`` keeps the ones it lists, and its
#: backward then makes ``f`` again but for them; under any other
#: checkpoint, or none, a name is the identity and lowers to nothing.
RESIDUAL_NAMES = ('flash_q', 'flash_k', 'flash_v', 'flash_out',
                  'flash_lse')


def residual_bytes(b, t, h, d, dv, dtype):
    """``{name: bytes}`` of the residuals of ONE self-attention call
    :func:`flash_attention` makes with its own tiles: ``b * h`` rows of
    ``t`` positions (padded as the call pads them), ``q`` / ``k`` at
    the key width ``d``, ``v`` / the output at the value width ``dv``,
    in ``dtype``, and one float32 statistic a row."""
    rows, size = b * h * _padded_len(t), jnp.dtype(dtype).itemsize
    return dict(zip(RESIDUAL_NAMES, (rows * d * size, rows * d * size,
                                     rows * dv * size, rows * dv * size,
                                     rows * 4)))


def _flash_fwd(q, k, v, causal, scale, kv_len, blocks):
    if pallas_mode() == 'fallback':
        out, lse = _fwd_blockwise_jnp(q, k, v, causal, scale, kv_len,
                                      _scan_block(blocks, k.shape[1]))
    else:
        block_q, block_k = blocks or _flash_blocks(
            q.shape[1], k.shape[1], max(q.shape[2], v.shape[2]),
            q.dtype)
        out, lse = _fwd_pallas(q, k, v, causal, scale, kv_len,
                               block_q, block_k)
    q, k, v, out, lse = res = tuple(map(
        checkpoint_name, (q, k, v, out, lse), RESIDUAL_NAMES))
    return out, res


def _flash_bwd(causal, scale, kv_len, blocks, res, g):
    q, k, v, out, lse = res
    if pallas_mode() == 'fallback':
        return _bwd_blockwise(q, k, v, out, lse, g, causal, scale,
                              kv_len, _scan_block(blocks, k.shape[1]))
    return _bwd_pallas(q, k, v, out, lse, g, causal, scale, kv_len,
                       blocks)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ----------------------------------------------------------------------
# decode attention -- one query row per sequence against a KV cache.
#
# The serving regime flips the bound: prefill streams the whole prompt
# through the MXU, but every subsequent token attends ONE query row
# against the sequence's cached K/V -- pure HBM bandwidth, no reuse.
# The decode kernel therefore reuses the forward kernel's
# online-softmax recurrence (running m/l/acc in VMEM scratch) but
# carries one query row per head per grid cell, streams the cache in
# ONE HBM pass, and masks by a PER-SEQUENCE dynamic length (each cache
# slot is filled to a different depth under continuous batching).
# Forward-only by design: decode is inference, there is no backward.
# On the TPU the slab cache is read by the PAGED kernel below through
# an identity page table (_decode_pallas).
#
# int8 KV cache: pass int8 k/v plus per-(position, head) symmetric
# scales (precision.quantize_kv) and the dequant multiply runs in
# VMEM right before each tile's products -- the HBM bytes the step is
# bound by are the int8 ones.
# ----------------------------------------------------------------------

def decode_attention_reference(q, k, v, lengths, scale=None,
                               k_scale=None, v_scale=None):
    """Pure-jnp oracle for :func:`flash_attention_decode`.

    q: (B, H, D) -- the current token's query per sequence;
    k/v: (B, S, H, D) cache (float, or int8 with ``k_scale``/
    ``v_scale`` (B, S, H) per-(position, head) scales);
    lengths: (B,) int32 -- positions ``>= lengths[b]`` are masked out.
    Returns (B, H, D) in q's dtype.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)[..., None]
    if v_scale is not None:
        vf = vf * v_scale.astype(jnp.float32)[..., None]
    s = jnp.einsum('bhd,bkhd->bhk', q.astype(jnp.float32), kf) * scale
    k_pos = jnp.arange(k.shape[1])
    ok = k_pos[None, None, :] < lengths[:, None, None]
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhk,bkhd->bhd', p, vf).astype(q.dtype)


def _decode_blockwise_jnp(q, k, v, lengths, scale, block_k,
                          k_scale=None, v_scale=None):
    """Fallback decode: the kernel's online-softmax recurrence via
    ``lax.scan`` over key blocks -- ONE consumption of the cache
    operands, never a materialized (S,)-wide probability row in f32
    beyond the per-block tile."""
    bh, t_kv, d = k.shape
    n_blocks = t_kv // block_k
    qf = q.astype(jnp.float32) * scale                 # (bh, d)
    kb = jnp.swapaxes(k.reshape(bh, n_blocks, block_k, d), 0, 1)
    vb = jnp.swapaxes(v.reshape(bh, n_blocks, block_k, d), 0, 1)
    scan_over = [jnp.arange(n_blocks), kb, vb]
    if k_scale is not None:
        scan_over.append(jnp.swapaxes(
            k_scale.reshape(bh, n_blocks, block_k), 0, 1))
        scan_over.append(jnp.swapaxes(
            v_scale.reshape(bh, n_blocks, block_k), 0, 1))

    def body(carry, inp):
        m, l, acc = carry
        if k_scale is not None:
            j, kj, vj, ksj, vsj = inp
            kjf = kj.astype(jnp.float32) * ksj[..., None]
            vjf = vj.astype(jnp.float32) * vsj[..., None]
        else:
            j, kj, vj = inp
            kjf = kj.astype(jnp.float32)
            vjf = vj.astype(jnp.float32)
        s = jnp.einsum('bd,bkd->bk', qf, kjf)          # (bh, block_k)
        k_pos = j * block_k + jnp.arange(block_k)
        s = jnp.where(k_pos[None, :] < lengths[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.einsum('bk,bkd->bd', p, vjf)
        return (m_new, l, acc), None

    m0 = jnp.full((bh,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh,), jnp.float32)
    acc0 = jnp.zeros((bh, d), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, acc0), tuple(scan_over))
    l_safe = jnp.maximum(l, 1e-30)
    return (acc / l_safe[:, None]).astype(q.dtype)


def _decode_pallas(q, k, v, lengths, scale, block_k,
                   k_scale=None, v_scale=None):
    """Slab decode through the paged kernel: a (B, S, H, D) cache IS a
    page pool of ``S / block_k`` pages per sequence (a leading-dim
    reshape, no copy) read through the identity page table -- one
    Mosaic kernel serves both cache layouts, which is what makes
    ``page_size == block_k`` arithmetic identical by construction."""
    b, t_kv = k.shape[:2]
    pad_k = (-t_kv) % block_k
    n_blocks = (t_kv + pad_k) // block_k

    def pages(x):
        if x is None:
            return None
        if pad_k:
            x = jnp.pad(x, ((0, 0), (0, pad_k))
                        + ((0, 0),) * (x.ndim - 2))
        return x.reshape((b * n_blocks, block_k) + x.shape[2:])

    tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * n_blocks
              + jnp.arange(n_blocks, dtype=jnp.int32)[None, :])
    return _decode_paged_call(
        q, pages(k), pages(v), tables, lengths.astype(jnp.int32),
        scale, pages(k_scale), pages(v_scale),
        interpret=interpret_flag())


def flash_attention_decode(q, k, v, lengths, scale=None,
                           k_scale=None, v_scale=None, block_k=None):
    """Single-token decode attention against a per-sequence KV cache.

    q: (B, H, D) -- one query row per sequence (the token being
    generated); k/v: (B, S, H, D) -- the cache, filled to
    ``lengths[b]`` positions per sequence (the current token's K/V
    already written at ``lengths[b] - 1``).  Positions at or beyond
    ``lengths[b]`` -- padding, stale rows from a previous occupant of
    the cache slot -- receive no probability mass, which is what makes
    slot REUSE safe without zeroing (``docs/serving.md``).

    Causality is implicit: future positions are simply not in the
    cache yet.  The cache is streamed in ONE HBM pass (the grid's
    sequential key-block axis) with the online-softmax running state
    in VMEM scratch; nothing (S,)-sized is materialized beyond the
    per-block tile.  Forward-only -- decode is inference.

    int8 KV mode: pass int8 ``k``/``v`` with per-(position, head)
    symmetric scales ``k_scale``/``v_scale`` (B, S, H) from
    :func:`chainermn_tpu.precision.quantize_kv`; dequantization runs
    in VMEM per tile, so the HBM traffic the decode step is bound by
    is halved vs bf16 (quartered vs f32).

    ``block_k`` defaults to 128.
    """
    if block_k is None:
        block_k = _LANES
    b, h, d = q.shape
    t_kv = k.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError('int8 KV decode needs BOTH k_scale and '
                         'v_scale (or neither)')
    if scale is None:
        scale = d ** -0.5
    block_k = min(block_k, max(t_kv, 1))
    if pallas_mode() != 'fallback':
        return _decode_pallas(q, k, v, lengths, scale, block_k,
                              k_scale, v_scale)

    def merge(x):
        # (B, S, H, D) -> (B*H, S, D)
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    def merge_scale(s):
        # (B, S, H) -> (B*H, S)
        return jnp.swapaxes(s, 1, 2).reshape(b * h, s.shape[1])

    qm = q.reshape(b * h, d)
    km, vm = merge(k), merge(v)
    ksm = merge_scale(k_scale) if k_scale is not None else None
    vsm = merge_scale(v_scale) if v_scale is not None else None
    lengths_bh = jnp.repeat(lengths.astype(jnp.int32), h)
    pad_k = (-t_kv) % block_k
    if pad_k:
        km = jnp.pad(km, ((0, 0), (0, pad_k), (0, 0)))
        vm = jnp.pad(vm, ((0, 0), (0, pad_k), (0, 0)))
        if ksm is not None:
            ksm = jnp.pad(ksm, ((0, 0), (0, pad_k)))
            vsm = jnp.pad(vsm, ((0, 0), (0, pad_k)))
    out = _decode_blockwise_jnp(qm, km, vm, lengths_bh, scale, block_k,
                                ksm, vsm)
    return out.reshape(b, h, d)


# ----------------------------------------------------------------------
# paged decode attention -- the same single-query online-softmax
# recurrence as flash_attention_decode, but the KV cache is a POOL of
# fixed-size pages shared across sequences (vLLM-style PagedAttention)
# and each sequence reads its own pages through a PER-SEQUENCE page
# table: key-block j of sequence b lives at page ``page_tables[b, j]``.
# The page table rides in SMEM (scalar prefetch) and the pools stay in
# HBM: the kernel copies the pages a sequence owns into VMEM itself,
# several a grid step (_paged_pages_per_step), the next step's while
# this one computes -- one HBM pass over only the live pages, never
# the whole pool.  The grid is the sequences' LIVE steps one after the
# other: none is stepped for a page past a sequence's fill level.
#
# int8 KV pages compose exactly like the slot cache: per-(position,
# head) symmetric scales (precision.quantize_kv) stored page-shaped,
# dequantized per tile in VMEM.
# ----------------------------------------------------------------------

def decode_attention_paged_reference(q, k, v, page_tables, lengths,
                                     scale=None, k_scale=None,
                                     v_scale=None):
    """Pure-jnp oracle for :func:`flash_attention_decode_paged`.

    q: (B, H, D) -- the current token's query per sequence;
    k/v: (P, page_size, H, D) -- the shared page pool (float, or int8
    with ``k_scale``/``v_scale`` (P, page_size, H) scales);
    page_tables: (B, n_max_pages) int32 -- page ids per sequence in
    position order (entries past the live prefix are ignored);
    lengths: (B,) int32 -- positions ``>= lengths[b]`` are masked out.

    Gathers each sequence's pages into the contiguous (B, S, H, D)
    layout and defers to :func:`decode_attention_reference` -- which
    is exactly the correctness claim: paging is a storage indirection,
    never an arithmetic change.
    """
    b = q.shape[0]
    _, ps, h, d = k.shape
    tables = page_tables.astype(jnp.int32)

    def gather(x):
        g = jnp.take(x, tables.reshape(-1), axis=0)
        return g.reshape((b, tables.shape[1] * ps) + x.shape[2:])

    return decode_attention_reference(
        q, gather(k), gather(v), lengths, scale=scale,
        k_scale=None if k_scale is None else gather(k_scale),
        v_scale=None if v_scale is None else gather(v_scale))


def _decode_paged_blockwise_jnp(q, k, v, page_tables, lengths, scale,
                                k_scale=None, v_scale=None, group=1,
                                window=None, head_major=False):
    """Fallback paged decode: ``lax.scan`` over the page-table axis --
    each step gathers ONE page per sequence and applies the kernel's
    online-softmax update.  The pool operands enter the scan once
    (one consumption in the jaxpr) and nothing (S,)-wide is ever
    materialized beyond the per-page tile.  ``group`` / ``window`` /
    ``head_major`` as in :func:`flash_attention_decode_paged`."""
    b, h, d = q.shape
    ps = k.shape[2] if head_major else k.shape[1]
    n_max = page_tables.shape[1]
    qf = q.astype(jnp.float32) * scale                 # (B, H, D)
    quantized = k_scale is not None
    if window is not None:
        start = jnp.maximum(lengths - window, 0)       # (B,)

    def page_tile(x, pages):
        tile = jnp.take(x, pages, axis=0)
        if head_major:                        # (B, Hkv, ps, D)
            tile = jnp.swapaxes(tile, 1, 2)
        if group != 1:                        # (B, ps, Hkv, D)
            tile = jnp.repeat(tile, group, axis=2)
        return tile

    def body(carry, j):
        m, l, acc = carry
        if window is None:
            pages = page_tables[:, j]                  # (B,)
            k_pos = (j * ps + jnp.arange(ps))[None, None, :]
        else:
            # the ring: logical page ``first + j`` lies in column
            # ``(first + j) mod n_max``
            page_no = start // ps + j
            pages = jnp.take_along_axis(
                page_tables, (page_no % n_max)[:, None], axis=1)[:, 0]
            k_pos = (page_no[:, None] * ps
                     + jnp.arange(ps)[None, :])[:, None, :]
        kj = page_tile(k, pages)                       # (B, ps, H, D)
        vj = page_tile(v, pages)
        kjf = kj.astype(jnp.float32)
        vjf = vj.astype(jnp.float32)
        if quantized:
            kjf = kjf * jnp.take(k_scale, pages,
                                 axis=0).astype(jnp.float32)[..., None]
            vjf = vjf * jnp.take(v_scale, pages,
                                 axis=0).astype(jnp.float32)[..., None]
        s = jnp.einsum('bhd,bkhd->bhk', qf, kjf)       # (B, H, ps)
        ok = k_pos < lengths[:, None, None]
        if window is not None:
            ok = jnp.logical_and(ok, k_pos >= start[:, None, None])
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum('bhk,bkhd->bhd',
                                                  p, vjf)
        return (m_new, l, acc), None

    m0 = jnp.full((b, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h), jnp.float32)
    acc0 = jnp.zeros((b, h, v.shape[-1]), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, acc0),
                              jnp.arange(n_max))
    l_safe = jnp.maximum(l, 1e-30)
    return (acc / l_safe[..., None]).astype(q.dtype)


# ----------------------------------------------------------------------
# pages a grid step: a function of the shapes
# ----------------------------------------------------------------------

# K + V bytes (as they lie in VMEM) one grid step of the paged decode
# kernel fetches.  From the sweep on the chip at the two serving cells'
# shapes (PERF.md section 6, PR 30): a step costs ~1.4 us before it
# reads a byte, the HBM's time for ~1.1 MB.  At 1 MiB (8 pages of
# 64 KB) the page-major kernel is fastest (4 pages tie, 16 lose 12%:
# a partly live step computes its whole tile) and the head-major one
# within 5% of its best (32 pages).
_PAGED_STEP_BYTES = 1024 * 1024

# float32 copies of the step's K/V tile the page-major (VPU) path holds
# at once: k, v and the product made of each
_PAGED_TEMPS = 4


def _sublanes(dtype):
    """Rows of the dtype's VMEM tile: 8 of 32 bits, 16 of bfloat16,
    32 of int8."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def _vmem_bytes(shape, dtype):
    """Bytes of an array as VMEM holds it: the minor dim padded to the
    128 lanes, the second-minor to the dtype's sublane tile."""
    n = jnp.dtype(dtype).itemsize
    for extent in shape[:-2]:
        n *= extent
    rows = _sublanes(dtype)
    return (n * (-(-shape[-2] // rows) * rows)
            * (-(-shape[-1] // _LANES) * _LANES))


def _paged_step_vmem(pages, page_shape, dtype, quantized, head_major,
                     shared=False):
    """``(fetched, held)`` bytes of a grid step that carries ``pages``
    pages: what its copies bring into VMEM (K and V, with their float32
    scale tiles; ONE leaf where keys and values share it), and the
    two-slot scratch plus the float32 working set of the compute."""
    tile = (1 if shared else 2) * _vmem_bytes(page_shape, dtype)
    if quantized:
        tile += 2 * _vmem_bytes(page_shape[:-1] + (1,), jnp.float32)
    # the head-major path feeds the MXU the pages as stored: its float32
    # values are score rows, under one page's worth
    temps = ((1 if head_major else _PAGED_TEMPS)
             * _vmem_bytes(page_shape, jnp.float32))
    return pages * tile, pages * (2 * tile + temps)


def _paged_pages_per_step(page_shape, dtype, n_max, quantized=False,
                          head_major=False, shared=False):
    """How many pages one grid step of the paged decode kernel carries,
    from what the call can see: the largest power of two that is at
    most the table's width, whose step fetches at most
    ``_PAGED_STEP_BYTES`` of K + V and holds its two-slot scratch and
    working set inside ``_VMEM_LIMIT``.  1 where a page is that large
    already or the table is one page wide.  The head-major layout
    places a page at a sublane offset of the step's tile, so a page
    size off the dtype's sublane tile is one page a step too.
    ``shared``: keys and values are one leaf, fetched once."""
    if head_major and page_shape[-2] % _sublanes(dtype):
        return 1
    pages = 1
    while pages * 2 <= n_max:
        fetched, held = _paged_step_vmem(pages * 2, page_shape, dtype,
                                         quantized, head_major, shared)
        if fetched > _PAGED_STEP_BYTES or held > _VMEM_LIMIT:
            break
        pages *= 2
    return pages


def _paged_live(lengths, page_size, n_max, window, xp=jnp):
    """A row's first live logical page and how many are live, from its
    length: every page a live position lies in, at most the table's
    width, at least one (a row of length 0 fetches a page and attends
    nothing).  On traced arrays and SMEM scalars; on host integers with
    ``xp=numpy``."""
    first = (0 if window is None
             else xp.maximum(lengths - window, 0) // page_size)
    last = xp.maximum(lengths - 1, 0) // page_size
    return first, xp.minimum(last - first + 1, n_max)


def decode_paged_grid(lengths, page_shape, dtype, n_max, window=None,
                      quantized=False, head_major=False, shared=False):
    """``(pages read, grid steps)`` of one paged decode call over rows
    of these ``lengths`` (host integers; the engine sends a padded row
    as length 1): what the kernel's copies fetch and how many steps
    its grid takes.  For the engine's counters: integer arithmetic on
    the host, no device read."""
    import numpy as np
    pages = _paged_pages_per_step(page_shape, dtype, n_max, quantized,
                                  head_major, shared)
    ps = page_shape[-2] if head_major else page_shape[0]
    lengths = np.asarray(lengths, np.int64)    # noqa: shardlint (host)
    live = _paged_live(lengths, ps, n_max, window, xp=np)[1]
    return int(live.sum()), int((-(-live // pages)).sum())


def _decode_paged_kernel(table_ref, len_ref, row_ref, step_ref, q_ref,
                         *refs, scale, page_size, pages, n_max,
                         quantized, window=None, head_major=False,
                         value_lanes=None):
    """One LIVE (sequence, step) pair of the flattened grid: the
    online-softmax update of ALL heads' single query rows against
    ``pages`` PAGES of the pool, which the kernel fetches itself.  The
    pools stay in HBM; the page table, the lengths and the grid's
    ``(row, step)`` lists are scalar-prefetched (SMEM).  Grid step ``t``
    starts one copy ``pool[table[row, column]] -> VMEM`` for each live
    page of step ``t + 1`` (the row's next, or the next row's first)
    into the other of two scratch slots, then waits for its own copies
    and computes: the copies hide behind the compute.  The grid has no
    dead step: it is as long as the rows' live steps together.

    A step's pages land side by side in its slot, a (pages * page_size,
    H, D) tile, so the one-row-per-head products run on the VPU --
    multiply by the broadcast query, reduce over the lane (D) axis --
    instead of H separate M=1 matmuls; the softmax state is per head,
    (H, 1) / (H, D) in VMEM scratch.  Positions of a slot's dead pages
    (stale K/V of an earlier step) are masked like any position past
    the length.

    ``head_major``: the pages are (Hkv, page_size, D), the tile
    (Hkv, pages * page_size, D) and the queries (Hkv, G, D), the G
    query heads of a group riding ONE read of their K/V head's pages:
    two batched MXU products a step, state (Hkv, G, 1) / (Hkv, G, D).
    ``window``: only the ``window`` positions before ``length`` are
    live, step ``s`` starts at LOGICAL page ``first + s * pages`` and
    each page is addressed through the ring, ``(first + j) % n_max``.
    ``value_lanes`` (head-major): there is ONE pool, a LATENT row a
    position that all the group's heads share; scores run over the
    whole row and the values are its first ``value_lanes`` lanes, so a
    page is fetched once and read twice where it lies in VMEM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_pools = 1 if value_lanes else 2
    pools, refs = refs[:n_pools], refs[n_pools:]
    n = 2 * pages if quantized else 0      # a scale tile a page
    scales, refs = refs[:n], refs[n:]
    o_ref, bufs = refs[0], refs[1:1 + n_pools]
    sem, m_ref, l_ref, acc_ref = refs[1 + n_pools:]
    k_buf, v_buf = bufs[0], bufs[-1]       # the same slots when shared
    t = pl.program_id(0)

    def copies(at, act):
        """Start (or wait for) the copies of grid step ``at``."""
        row, slot = row_ref[at], at % 2
        first, live = _paged_live(len_ref[row], page_size, n_max, window)
        page0 = step_ref[at] * pages

        def one(p, carry):
            column = first + page0 + p
            if window is not None:
                column = column % n_max
            page = table_ref[row, column]
            where = pl.ds(pl.multiple_of(p * page_size, page_size),
                          page_size)
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                dst = (buf.at[slot, :, where] if head_major
                       else buf.at[slot, where])
                getattr(pltpu.make_async_copy(
                    pool.at[page], dst, sem.at[slot, i]), act)()
            return carry

        lax.fori_loop(0, jnp.minimum(live - page0, pages), one, 0)

    @pl.when(t == 0)
    def _prime():
        # a slot's dead pages are masked by position: p is 0 there, and
        # 0 x what an untouched VMEM may hold must still be 0
        v_buf[...] = jnp.zeros_like(v_buf)
        copies(0, 'start')

    @pl.when(t + 1 < pl.num_programs(0))
    def _fetch_next():
        copies(t + 1, 'start')

    b, step, slot = row_ref[t], step_ref[t], t % 2
    length = len_ref[b]
    first, live = _paged_live(length, page_size, n_max, window)
    k0 = (first + step * pages) * page_size            # first position

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    copies(t, 'wait')

    def mask(k_pos):
        ok = k_pos < length
        if window is not None:
            ok = jnp.logical_and(ok, k_pos >= length - window)
        return ok

    @pl.when(k0 < length)
    def _accum():
        if head_major:
            q = q_ref[0]                               # (Hkv, G, D)
            k = k_buf[slot]                            # (Hkv, keys, D)
            v = v_buf[slot]
            if value_lanes:
                v = v[..., :value_lanes]
            # one MXU pass in the pool's own dtype, whatever the
            # process-wide default precision (Mosaic refuses 'highest'
            # on bfloat16 operands)
            s = lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                precision=lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32) * scale
            k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(mask(k_pos), s, NEG_INF)     # (Hkv, G, keys)
            m_prev = m_ref[...]                        # (Hkv, G, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                precision=lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            return
        q = q_ref[0].astype(jnp.float32) * scale       # (H, D)
        k = k_buf[slot].astype(jnp.float32)            # (keys, H, D)
        v = v_buf[slot].astype(jnp.float32)
        if quantized:
            k_scale, v_scale = (
                jnp.concatenate([ref[0] for ref in tiles], axis=0)
                for tiles in (scales[:pages], scales[pages:]))
            k = k * k_scale                            # (keys, H, 1)
            v = v * v_scale
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)  # (keys, H, 1)
        k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(mask(k_pos), s, NEG_INF)
        m_prev = m_ref[...]                            # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0)
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v, axis=0)

    @pl.when((step + 1) * pages >= live)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _decode_paged_pallas(q, k, v, page_tables, lengths, scale,
                         k_scale=None, v_scale=None, group=1,
                         window=None, head_major=False, pages=None,
                         interpret=False, value_lanes=None):
    """``pages``: pages a grid step, for the sweep alone
    (``benchmarks/flash_attention_bench.py``); every caller leaves it
    to the rule.  ``interpret``: the caller's ``interpret_flag()``,
    an argument so that the jitted form below is keyed by it."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    ps = k.shape[2] if head_major else k.shape[1]
    n_max = page_tables.shape[1]
    quantized = k_scale is not None
    pools = (k,) if value_lanes else (k, v)
    if pages is None:
        pages = _paged_pages_per_step(k.shape[1:], k.dtype, n_max,
                                      quantized, head_major,
                                      shared=bool(value_lanes))
    pad = -d % _LANES
    if pad:
        # Mosaic copies no slice of an array whose minor dim is off the
        # 128 lanes: a narrower pool is padded, a COPY of it every
        # call (the models keep theirs lane-wide; a zero lane adds
        # nothing to a product)
        q, *pools = (jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
                     for x in (q,) + pools)
        d += pad
    # the grid: the rows' live steps one after the other, step t being
    # step ``step_of[t]`` of row ``row_of[t]``
    steps = -(-_paged_live(lengths, ps, n_max, window)[1] // pages)
    ends = jnp.cumsum(steps)
    t = jnp.arange(b * -(-n_max // pages) + 1, dtype=jnp.int32)
    row_of = jnp.minimum(
        jnp.sum(t[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        b - 1)
    step_of = t - (ends - steps)[row_of]
    scales, scale_specs = [], []
    if quantized:
        # (P, ps, H) -> (P, ps, H, 1): the scale tile lines up with
        # the page tile's (H, D) minor dims and broadcasts over lanes.
        # One lane wide, so no copy of the kernel's own can carry it:
        # the tiles of a step's pages come as that many blocks each,
        # addressed like the pages, a dead one clamped to the row's
        # last live page (a block fetched before is not fetched again)
        def scale_ix(p, t, table_ref, len_ref, row_ref, step_ref):
            i = row_ref[t]
            first, live = _paged_live(len_ref[i], ps, n_max, window)
            column = first + jnp.minimum(step_ref[t] * pages + p,
                                         live - 1)
            if window is not None:
                column = column % n_max
            return (table_ref[i, column], 0, 0, 0)

        scales = [x for x in (k_scale.astype(jnp.float32)[..., None],
                              v_scale.astype(jnp.float32)[..., None])
                  for _ in range(pages)]
        scale_specs = [
            pl.BlockSpec((1, ps, h, 1), functools.partial(scale_ix, p))
            for _ in range(2) for p in range(pages)]
    if head_major:
        # (B, H, D) -> (B, Hkv, G, D): a group's query heads are
        # adjacent, so this is a view
        h_kv = h // group
        q = q.reshape(b, h_kv, group, d)
        row, state = (1, h_kv, group, d), (h_kv, group)
        tile = (2, h_kv, pages * ps, d)
    else:
        row, state = (1, h, d), (h,)
        tile = (2, pages * ps, h, d)
    d_out = value_lanes or d

    def row_spec(width):
        block = row[:-1] + (width,)
        return pl.BlockSpec(
            block, lambda t, tables, lens, rows, steps: (rows[t],) + (
                0,) * (len(block) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # page_tables, lengths, row_of, step_of
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=[row_spec(d)] + [pl.BlockSpec(memory_space=pl.ANY)
                                  for _ in pools] + scale_specs,
        out_specs=row_spec(d_out),
        scratch_shapes=[
            # two slots of K and of V (of the one leaf they share)
            pltpu.VMEM(tile, pool.dtype) for pool in pools] + [
            pltpu.SemaphoreType.DMA((2, 2)),           # (slot, k / v)
            pltpu.VMEM(state + (1,), jnp.float32),     # m
            pltpu.VMEM(state + (1,), jnp.float32),     # l
            pltpu.VMEM(state + (d_out,), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_paged_kernel, scale=scale,
                          page_size=ps, pages=pages, n_max=n_max,
                          quantized=quantized, window=window,
                          head_major=head_major, value_lanes=value_lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (d_out,), q.dtype),
        # the slots and the prefetch run from one step into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='flash_attention_decode_paged',
    )(page_tables, lengths, row_of, step_of, q, *pools, *scales)
    if value_lanes:
        return out.reshape(b, h, d_out)
    return out.reshape(b, h, d)[..., :d - pad]


# One traced and lowered kernel for every layer of an executable: a
# model's layers call with the same shapes, so under ``jit`` the kernel
# is traced once and lowered to one function that each layer calls
# (tracing and lowering a Pallas kernel is not cached across set-ups,
# and it is most of a warm set-up: PERF.md section 6, PR 30).
_decode_paged_call = jax.jit(
    _decode_paged_pallas,
    static_argnames=('scale', 'group', 'window', 'head_major', 'pages',
                     'interpret', 'value_lanes'))


def flash_attention_decode_paged(q, k, v, page_tables, lengths,
                                 scale=None, k_scale=None,
                                 v_scale=None, group=1, window=None,
                                 head_major=False, value_lanes=None):
    """Single-token decode attention against a PAGED KV cache.

    q: (B, H, D) -- one query row per sequence; k/v:
    (P, page_size, H, D) -- the shared page pool;
    page_tables: (B, n_max_pages) int32 -- each sequence's pages in
    position order (token position ``p`` lives at page
    ``page_tables[b, p // page_size]``, offset ``p % page_size``);
    lengths: (B,) int32 -- live prefix per sequence.  Table entries at
    or beyond ``ceil(lengths[b] / page_size)`` are never read, so a
    host-side allocator can leave them pointing at its scratch page.

    Arithmetic is that of :func:`flash_attention_decode` (same
    online-softmax recurrence, a key block == the pages of one grid
    step): paging only changes where the blocks live.  The page table
    is scalar-prefetched into SMEM and the kernel copies exactly the
    sequence's own pages out of HBM, several a step, in one pass --
    memory traffic scales with LIVE tokens, not with pool capacity,
    which is what lets N sequences sharing a prompt prefix read one
    banked copy (``docs/serving.md``).  A pool whose minor dim is not
    a multiple of 128 lanes is padded first, a copy of it: keep pools
    lane-wide, as the models do.

    int8 KV pages: pass int8 ``k``/``v`` with per-(position, head)
    scales ``k_scale``/``v_scale`` (P, page_size, H) from
    :func:`chainermn_tpu.precision.quantize_kv`, dequantized per tile
    in VMEM exactly like the slot-cache kernel.

    Four static arguments, all off by default (the call then lowers
    to what it lowered to before they existed):

    ``group``: query heads per K/V head.  The pool holds
    ``Hkv = H / group`` heads; query head ``i`` reads K/V head
    ``i // group``, and the ``group`` heads of one K/V head ride ONE
    read of its page.
    ``head_major``: the pool is (P, Hkv, page_size, D), so that a
    leaf's two minor dims are a whole ``(page_size, D)`` tile however
    few K/V heads there are (4 heads as the second-minor dim would pad
    to 16 sublanes in bf16, four times the bytes).  ``group > 1``
    needs it; no int8 scales in this layout yet.
    ``window``: only positions ``lengths[b] - window .. lengths[b] - 1``
    are attended, and ``page_tables`` is a RING: position ``p`` lives
    in column ``(p // page_size) % n_max_pages``.  The ring must hold
    the window from any offset: ``n_max_pages >= ceil(window /
    page_size) + 1``.
    ``value_lanes``: LATENT attention (absorbed MLA).  ``v`` is None
    and ``k`` the one head-major leaf (P, Hkv, page_size, D) of latent
    rows; scores run over all ``D`` lanes of a row, the values are its
    first ``value_lanes`` lanes, and the result is (B, H, value_lanes).
    A page is fetched ONCE a step: as two leaves the same bytes would
    cross HBM twice.
    """
    b, h, d = q.shape
    if k.ndim != 4:
        raise ValueError('paged cache must be (P, page_size, H, D), '
                         'got shape %r' % (k.shape,))
    if (k_scale is None) != (v_scale is None):
        raise ValueError('int8 KV decode needs BOTH k_scale and '
                         'v_scale (or neither)')
    if (value_lanes is None) == (v is None):
        raise ValueError('pass v, or value_lanes for a latent leaf '
                         'whose rows hold keys and values (v=None)')
    if value_lanes and (not head_major or value_lanes % _LANES
                        or value_lanes > k.shape[-1]):
        raise ValueError('a latent leaf is head-major and its values '
                         'are whole 128-lane tiles of a row: '
                         'value_lanes %r of %d' % (value_lanes,
                                                   k.shape[-1]))
    if group != 1 and not head_major:
        raise ValueError('grouped K/V heads need the head-major pool '
                         '(head_major=True)')
    if head_major and k_scale is not None:
        raise NotImplementedError(
            'int8 K/V in the head-major paged layout')
    if h != group * k.shape[1 if head_major else 2]:
        raise ValueError('%d query heads are not %d groups of %d'
                         % (h, k.shape[1 if head_major else 2], group))
    if window is not None:
        ps = k.shape[2] if head_major else k.shape[1]
        if page_tables.shape[1] < -(-window // ps) + 1:
            raise ValueError(
                'a ring of %d pages of %d cannot hold a window of %d '
                'from every offset' % (page_tables.shape[1], ps, window))
    if scale is None:
        scale = d ** -0.5
    tables = page_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    if pallas_mode() == 'fallback':
        return _decode_paged_blockwise_jnp(
            q, k, k[..., :value_lanes] if value_lanes else v, tables,
            lens, scale, k_scale, v_scale, group, window, head_major)
    return _decode_paged_call(q, k, v, tables, lens, scale, k_scale,
                              v_scale, group, window, head_major,
                              interpret=interpret_flag(),
                              value_lanes=value_lanes)


def _kv_append_kernel(pages_ref, offsets_ref, *refs):
    """``refs``: the new rows, the pools' pages and the pages out, one
    of each a pool (K and V, or the one latent leaf)."""
    import jax.experimental.pallas as pl

    at = offsets_ref[pl.program_id(0)]
    n = len(refs) // 3
    for new_ref, page_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                          refs[2 * n:]):
        page = page_ref[0]                             # (Hkv, ps, D)
        row = lax.broadcasted_iota(jnp.int32, page.shape, 1)
        out_ref[0] = jnp.where(row == at, new_ref[0], page)


def _kv_append_pallas(k, v, k_new, v_new, pages, offsets,
                      interpret=False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pools = [(k, k_new)] + ([] if v is None else [(v, v_new)])
    b, h_kv, d = k_new.shape
    ps, n = k.shape[2], len(pools)
    new = pl.BlockSpec((1, h_kv, 1, d), lambda i, p, o: (i, 0, 0, 0))
    page = pl.BlockSpec((1, h_kv, ps, d),
                        lambda i, p, o: (p[i], 0, 0, 0))
    out = pl.pallas_call(
        _kv_append_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[new] * n + [page] * n, out_specs=[page] * n),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype)
                   for pool, _ in pools],
        # operands count the two prefetched scalars and the new rows:
        # the first pool is operand 2 + n
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
        name='paged_kv_append',
    )(pages.astype(jnp.int32), offsets.astype(jnp.int32),
      *[new.astype(pool.dtype)[:, :, None] for pool, new in pools],
      *[pool for pool, _ in pools])
    return tuple(list(out) + [None])[:2]


# As ``_decode_paged_call``: one traced and lowered kernel for every
# layer of an executable that appends into leaves of one shape.
_kv_append_call = jax.jit(_kv_append_pallas,
                          static_argnames=('interpret',))


def paged_kv_append(k, v, k_new, v_new, pages, offsets):
    """One token a sequence into a HEAD-MAJOR page pool, in place:
    ``k`` / ``v`` (P, Hkv, page_size, D), ``k_new`` / ``v_new``
    (B, Hkv, D), written at ``[pages[b], :, offsets[b]]``.  Returns
    the two pools.  ``v`` and ``v_new`` None: ONE pool (a latent leaf,
    keys and values in one row), returned with None beside it.

    Why a kernel: an XLA scatter whose update is a (Hkv, D) slab wants
    the slab contiguous, so on the chip it relays the whole pool out to
    ``(P, page_size, Hkv, D)`` and back, every call (what
    ``chip_smoke.serving_pool_check`` catches).  Here a grid step
    takes one row's page through VMEM and puts the token's row into it
    with a select; the pools are the call's aliased outputs, so nothing
    else of them moves.  Rows that share a page (idle rows on the
    scratch page) overwrite each other, as a scatter's would."""
    if pallas_mode() == 'fallback':
        out = [pool.at[pages, :, offsets].set(new.astype(pool.dtype))
               for pool, new in ((k, k_new), (v, v_new))
               if pool is not None]
        return tuple(out + [None])[:2]
    return _kv_append_call(k, v, k_new, v_new, pages, offsets,
                           interpret=interpret_flag())


# ----------------------------------------------------------------------
# chunked-prefill attention -- a CHUNK of C query rows against the
# sequence's banked context plus itself.
#
# Chunked prefill (SARATHI-style) splits a long prompt into fixed-size
# chunks interleaved with decode steps.  Chunk queries at absolute
# positions ``ctx_len + [0, C)`` attend (a) every banked context
# position ``< ctx_len`` and (b) causally within the chunk.  The two
# parts are computed with the SAME blockwise online-softmax machinery
# as the forward kernel and merged exactly via their logsumexps -- for
# ``ctx_len == 0`` the merge is the identity, so a whole-prompt
# "chunk" is bitwise the plain causal forward.
# ----------------------------------------------------------------------

def chunk_attention_reference(q, k_new, v_new, k_ctx, v_ctx, ctx_len,
                              scale=None, k_scale=None, v_scale=None):
    """Pure-jnp oracle for :func:`flash_attention_chunk`.

    q/k_new/v_new: (B, C, H, D) -- the chunk's fresh Q/K/V at absolute
    positions ``ctx_len + [0, C)``; k_ctx/v_ctx: (B, S, H, D) -- the
    banked context (float, or int8 with (B, S, H) scales); ctx_len:
    (B,) int32 dynamic context length (ctx positions ``>= ctx_len[b]``
    are masked out).  Returns (B, C, H, D) in q's dtype.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    c = q.shape[1]
    kcf = k_ctx.astype(jnp.float32)
    vcf = v_ctx.astype(jnp.float32)
    if k_scale is not None:
        kcf = kcf * k_scale.astype(jnp.float32)[..., None]
        vcf = vcf * v_scale.astype(jnp.float32)[..., None]
    kf = jnp.concatenate([kcf, k_new.astype(jnp.float32)], axis=1)
    vf = jnp.concatenate([vcf, v_new.astype(jnp.float32)], axis=1)
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   kf) * scale
    s_ctx = k_ctx.shape[1]
    k_pos = jnp.arange(s_ctx + c)[None, None, None, :]
    q_pos = jnp.arange(c)[None, None, :, None]
    cl = ctx_len.astype(jnp.int32)[:, None, None, None]
    in_ctx = jnp.logical_and(k_pos < s_ctx, k_pos < cl)
    in_chunk = jnp.logical_and(k_pos >= s_ctx,
                               k_pos - s_ctx <= q_pos)
    s = jnp.where(jnp.logical_or(in_ctx, in_chunk), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, vf).astype(q.dtype)


def _ctx_blockwise_jnp(q, k, v, ctx_len, scale, block_k,
                       k_scale=None, v_scale=None):
    """Non-causal blockwise attention of C query rows against a
    context masked by a DYNAMIC per-sequence length: the chunk's
    context half.  Operands are (bh, ...)-merged like the forward
    fallback; returns (out, lse)."""
    bh, t_q, d = q.shape
    t_kv = k.shape[1]
    n_blocks = t_kv // block_k
    qf = q.astype(jnp.float32) * scale
    kb = jnp.swapaxes(k.reshape(bh, n_blocks, block_k, d), 0, 1)
    vb = jnp.swapaxes(v.reshape(bh, n_blocks, block_k, d), 0, 1)
    scan_over = [jnp.arange(n_blocks), kb, vb]
    quantized = k_scale is not None
    if quantized:
        scan_over.append(jnp.swapaxes(
            k_scale.reshape(bh, n_blocks, block_k), 0, 1))
        scan_over.append(jnp.swapaxes(
            v_scale.reshape(bh, n_blocks, block_k), 0, 1))

    def body(carry, inp):
        m, l, acc = carry
        if quantized:
            j, kj, vj, ksj, vsj = inp
            kjf = kj.astype(jnp.float32) * ksj[..., None]
            vjf = vj.astype(jnp.float32) * vsj[..., None]
        else:
            j, kj, vj = inp
            kjf = kj.astype(jnp.float32)
            vjf = vj.astype(jnp.float32)
        s = jnp.einsum('bqd,bkd->bqk', qf, kjf)
        k_pos = j * block_k + jnp.arange(block_k)
        s = jnp.where(k_pos[None, None, :] < ctx_len[:, None, None],
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum('bqk,bkd->bqd',
                                                  p, vjf)
        return (m_new, l, acc), None

    m0 = jnp.full((bh, t_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, t_q), jnp.float32)
    acc0 = jnp.zeros((bh, t_q, d), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, acc0),
                              tuple(scan_over))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    return out, m + jnp.log(l_safe)


def flash_attention_chunk(q, k_new, v_new, k_ctx, v_ctx, ctx_len,
                          scale=None, k_scale=None, v_scale=None,
                          block_q=None, block_k=None):
    """Prefill-chunk attention: C fresh query rows at absolute
    positions ``ctx_len + [0, C)`` against the banked context plus
    causal self-attention within the chunk.

    q/k_new/v_new: (B, C, H, D); k_ctx/v_ctx: (B, S, H, D) gathered
    cache rows (int8 with ``k_scale``/``v_scale`` (B, S, H) in int8-KV
    mode -- the CHUNK half always attends the fresh un-quantized K/V,
    exactly like the whole-prompt prefill); ctx_len: (B,) int32
    dynamic.  Context positions ``>= ctx_len[b]`` are masked out, so
    a fixed-capacity gathered buffer (the page table's full span) is
    safe to pass regardless of how much of it is banked.

    Computed as two blockwise online-softmax passes -- the causal
    in-chunk half through the SAME forward path as
    :func:`flash_attention` (Pallas kernel or jnp fallback), the
    context half through a dynamic-length jnp scan -- merged exactly
    via their logsumexps.  With ``ctx_len == 0`` the merge is the
    identity and the result is bitwise the plain causal forward,
    which is what pins single-chunk (unchunked) paged prefill to the
    slot engine's prefill (``tests/test_transformer.py``).
    """
    if block_q is None:
        block_q = _LANES
    if block_k is None:
        block_k = _LANES
    b, c, h, d = q.shape
    s_ctx = k_ctx.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError('int8 KV context needs BOTH k_scale and '
                         'v_scale (or neither)')
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, max(c, 1))
    block_ctx = min(block_k, max(s_ctx, 1))
    block_k = min(block_k, max(c, 1))

    def merge(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    def merge_scale(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1])

    qm = merge(q)
    km_new, vm_new = merge(k_new), merge(v_new)
    pad_q = (-c) % block_q
    pad_k = (-c) % block_k
    qm_p = jnp.pad(qm, ((0, 0), (0, pad_q), (0, 0))) if pad_q else qm
    if pad_k:
        km_new = jnp.pad(km_new, ((0, 0), (0, pad_k), (0, 0)))
        vm_new = jnp.pad(vm_new, ((0, 0), (0, pad_k), (0, 0)))
    # in-chunk causal half: the forward kernel/fallback, with lse
    if pallas_mode() == 'fallback':
        out_c, lse_c = _fwd_blockwise_jnp(qm_p, km_new, vm_new, True,
                                          scale, c, block_k)
    else:
        out_c, lse_c = _fwd_pallas(qm_p, km_new, vm_new, True, scale,
                                   c, block_q, block_k)
        lse_c = lse_c[:, 0]
    out_c, lse_c = out_c[:, :c], lse_c[:, :c]

    # context half: dynamic-length blockwise scan over banked rows
    km_ctx, vm_ctx = merge(k_ctx), merge(v_ctx)
    ksm = merge_scale(k_scale) if k_scale is not None else None
    vsm = merge_scale(v_scale) if v_scale is not None else None
    pad_ctx = (-s_ctx) % block_ctx
    if pad_ctx:
        km_ctx = jnp.pad(km_ctx, ((0, 0), (0, pad_ctx), (0, 0)))
        vm_ctx = jnp.pad(vm_ctx, ((0, 0), (0, pad_ctx), (0, 0)))
        if ksm is not None:
            ksm = jnp.pad(ksm, ((0, 0), (0, pad_ctx)))
            vsm = jnp.pad(vsm, ((0, 0), (0, pad_ctx)))
    ctx_bh = jnp.repeat(ctx_len.astype(jnp.int32), h)
    out_x, lse_x = _ctx_blockwise_jnp(qm, km_ctx, vm_ctx, ctx_bh,
                                      scale, block_ctx, ksm, vsm)

    # exact logsumexp merge; empty context (lse_x -> -inf) reduces to
    # the chunk half bitwise (w_c = exp(0) = 1, w_x = 0)
    m_tot = jnp.maximum(lse_c, lse_x)
    w_c = jnp.exp(lse_c - m_tot)[..., None]
    w_x = jnp.exp(lse_x - m_tot)[..., None]
    out = (out_c.astype(jnp.float32) * w_c
           + out_x.astype(jnp.float32) * w_x) / (w_c + w_x)
    out = out.astype(q.dtype)
    return jnp.swapaxes(out.reshape(b, h, c, d), 1, 2)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None, window=None):
    """Fused attention. q: (B, Tq, H, D), k/v: (B, Tkv, Hkv, D);
    ``v`` may be (B, Tkv, Hkv, Dv) with a width of its own (latent
    attention's 192 / 128), the output is then (B, Tq, H, Dv); the
    backward gives dQ / dK at the key width and dV at the value width.

    Sequence lengths are padded to kernel block multiples internally
    (padded keys are masked out; padded query rows are dropped); with
    ``causal=True``, Tq must equal Tkv (self-attention).

    Grouped K/V heads: ``Hkv`` may divide ``H``; query head ``i`` then
    reads K/V head ``i // (H / Hkv)`` through the kernel's index map
    (no repeated copy of K/V).  ``window`` (causal only): the query at
    position ``p`` sees the ``window`` keys ``p - window + 1 .. p``;
    key blocks wholly outside are neither fetched nor computed.  Both
    are FORWARD-ONLY (serving): the backward kernels know neither, so
    such a call has no gradient.

    The tiles are a function of the shapes (:func:`_flash_blocks`, the
    forward's and each backward kernel's own).  An explicit
    ``block_q`` / ``block_k`` is used by all three kernels instead
    (one left out is 128).  bfloat16 inputs go to the MXU as stored,
    and the probabilities are rounded to bfloat16 for their products;
    the softmax statistics and every accumulator are float32.  float32
    inputs keep float32 products.
    """
    b, t_q, h, d = q.shape
    t_kv, h_kv = k.shape[1:3]
    if causal and t_q != t_kv:
        raise ValueError('causal attention requires t_q == t_kv, got '
                         '%d vs %d' % (t_q, t_kv))
    if h % h_kv:
        raise ValueError('%d K/V heads do not divide %d query heads'
                         % (h_kv, h))
    if window is not None and not causal:
        raise ValueError('a window bounds CAUSAL attention; pass '
                         'causal=True')
    group = h // h_kv
    if scale is None:
        scale = d ** -0.5
    if block_q is None and block_k is None:
        blocks = None
        pad_q = _padded_len(t_q) - t_q
        pad_k = _padded_len(t_kv) - t_kv
    else:
        blocks = (min(block_q or _LANES, max(t_q, 1)),
                  min(block_k or _LANES, max(t_kv, 1)))
        pad_q = (-t_q) % blocks[0]
        pad_k = (-t_kv) % blocks[1]

    def merge(x):
        # (B, T, H, D) -> (B*H, T, D)
        return jnp.swapaxes(x, 1, 2).reshape(-1, x.shape[1], x.shape[3])

    qm, km, vm = merge(q), merge(k), merge(v)
    if pad_q:
        qm = jnp.pad(qm, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        km = jnp.pad(km, ((0, 0), (0, pad_k), (0, 0)))
        vm = jnp.pad(vm, ((0, 0), (0, pad_k), (0, 0)))
    if group == 1 and window is None:
        out = _flash(qm, km, vm, causal, scale, t_kv, blocks)
    elif pallas_mode() == 'fallback':
        out, _ = _fwd_blockwise_jnp(
            qm, km, vm, causal, scale, t_kv,
            _scan_block(blocks, km.shape[1]), group, window)
    else:
        block_q, block_k = blocks or _flash_blocks(
            t_q, t_kv, max(d, v.shape[3]), q.dtype, window)
        out, _ = _fwd_pallas(qm, km, vm, causal, scale, t_kv, block_q,
                             block_k, group, window)
    out = out[:, :t_q]
    return jnp.swapaxes(out.reshape(b, h, t_q, v.shape[3]), 1, 2)
