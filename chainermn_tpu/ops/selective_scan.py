"""The selective scan (Mamba, Gu & Dao, arXiv:2312.00752): a state-space
layer's recurrence, with an input-dependent step ``delta`` and
input-dependent ``B``, ``C`` over a DIAGONAL state transition ``A``.

Per channel ``d`` of ``Di`` and state index ``n`` of ``N``, with a state
``h`` that is zero before the sequence::

    h_t[n, d] = exp(delta_t[d] * A[d, n]) * h_{t-1}[n, d]
                + B_t[n] * delta_t[d] * x_t[d]
    m_t[d]    = sum_n C_t[n] * h_t[n, d] + D[d] * x_t[d]

(``A < 0``: every decay lies in (0, 1]; ``delta``, the decays and the
state are float32.)  The state is held TRANSPOSED, ``(N, Di)``: the
channels on the 128 lanes, the ``N`` state values of a channel on the
sublanes, so that a state of 16 x 5120 is 80 whole vector registers
(``(5120, 16)`` would pad its minor dim to 128 lanes, eight times the
bytes).

Three forms of it, as :mod:`chainermn_tpu.ops.gated_delta` has of its
rule:

- :func:`selective_scan_reference`: the recurrence as written, a
  ``lax.scan`` over positions.  The oracle of the tests.
- :func:`selective_scan`: a whole prompt in CHUNKS of positions; only
  the ``(N, Di)`` state goes from chunk to chunk and no ``T x Di x N``
  tensor exists anywhere (335 MB a layer at 1,024 positions of 5120 x
  16).  On the chip one Pallas kernel: a grid step takes ``CHUNK``
  positions of a block of channels, the block's state in registers from
  position to position and in VMEM from chunk to chunk.  Elsewhere the
  chunk is solved by an associative scan over its positions.  Positions
  at or past ``length`` are the identity (``delta = 0``): a prompt
  padded to its bucket leaves the state as its last real token did.
- :func:`selective_scan_step`: one token a row for decode.  Each row's
  state is read and written where it lies in the ``(rows, 1, N, Di)``
  leaf (``ops.state_shape(rows, 1, N, Di)``: one "head" of ``N x Di``):
  on the chip a Pallas kernel whose grid step takes one row's state
  through VMEM, the leaf its aliased output; elsewhere a gather, the
  update and a scatter.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops._common import interpret_flag, pallas_mode

#: positions one grid step of the prompt kernel takes (the ``B^T`` /
#: ``C^T`` block's lanes: a whole tile) and channels it takes (its
#: state is then 8 registers, carried through the chunk's positions)
CHUNK = 128
_BLOCK = 512
#: positions the jnp form solves at once (it holds ``chunk x N x Di``)
_JNP_CHUNK = 32


def _operands(x, delta, A, B, C, D):
    """Everything float32, ``A`` transposed to the state's ``(N, Di)``."""
    f32 = jnp.float32
    return (x.astype(f32), delta.astype(f32), A.astype(f32).T,
            B.astype(f32), C.astype(f32), D.astype(f32))


def _state0(state0, at):
    """``state0`` in float32, zeros where none is given."""
    if state0 is None:
        return jnp.zeros(at.shape, jnp.float32)
    return state0.astype(jnp.float32)


def selective_scan_reference(x, delta, A, B, C, D, state0=None):
    """``x`` / ``delta`` (T, Di), ``A`` (Di, N), ``B`` / ``C`` (T, N),
    ``D`` (Di,), ``state0`` (N, Di) or zeros: ``(m (T, Di), state (N,
    Di))`` in float32, one position at a time."""
    x, delta, at, B, C, D = _operands(x, delta, A, B, C, D)

    def body(h, step):
        x_t, dt, b_t, c_t = step
        h = jnp.exp(dt * at) * h + b_t[:, None] * (dt * x_t)
        return h, jnp.sum(h * c_t[:, None], axis=0) + D * x_t

    state, m = lax.scan(body, _state0(state0, at), (x, delta, B, C))
    return m, state


def _scan_chunks_jnp(x, delta, at, B, C, D, state0):
    """The prompt in chunks of ``_JNP_CHUNK`` positions: inside a chunk
    the pairs ``(decay, input)`` compose associatively, ``(a1, b1) then
    (a2, b2) = (a1 a2, a2 b1 + b2)``; between chunks the state."""
    t, di = x.shape
    c = min(_JNP_CHUNK, t)
    pad = -t % c

    def chunks(a):
        a = jnp.pad(a, ((0, pad), (0, 0)))
        return a.reshape((t + pad) // c, c, a.shape[1])

    def compose(first, second):
        return (first[0] * second[0], second[0] * first[1] + second[1])

    def body(h, chunk):
        x_c, dt_c, b_c, c_c = chunk
        decay, held = lax.associative_scan(
            compose, (jnp.exp(dt_c[:, None, :] * at),
                      b_c[:, :, None] * (dt_c * x_c)[:, None, :]))
        hs = decay * h + held                          # (c, N, Di)
        return hs[-1], jnp.einsum('cnd,cn->cd', hs, c_c) + D * x_c

    state, m = lax.scan(body, state0,
                        tuple(chunks(a) for a in (x, delta, B, C)))
    return m.reshape(t + pad, di)[:t], state


def _scan_kernel(x_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, s0_ref,
                 m_ref, s_ref, *, chunk):
    """``chunk`` positions of one block of channels.  The state block
    is the kernel's own output, resident over the chunks of its
    channels: set from ``state0`` at the first, carried after."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    at, d = at_ref[...], d_ref[...]
    h = s_ref[...]
    for t in range(chunk):
        dt, x = dt_ref[t:t + 1, :], x_ref[t:t + 1, :]
        h = jnp.exp(dt * at) * h + bt_ref[:, t:t + 1] * (dt * x)
        m_ref[t:t + 1, :] = jnp.sum(h * ct_ref[:, t:t + 1], axis=0,
                                    keepdims=True) + d * x
    s_ref[...] = h


def _scan_pallas(x, delta, at, B, C, D, state0):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, di = x.shape
    n = at.shape[0]
    f32 = jnp.float32
    chunk = CHUNK if t > CHUNK else -(-t // 8) * 8
    pad = -t % chunk
    block = _BLOCK if di % _BLOCK == 0 else di

    def rows(a):                    # (T, .) -> (T + pad, .), zeros
        return jnp.pad(a, ((0, pad), (0, 0)))

    wide = pl.BlockSpec((chunk, block), lambda i, j: (j, i))
    cols = pl.BlockSpec((n, chunk), lambda i, j: (0, j))
    held = pl.BlockSpec((n, block), lambda i, j: (0, i))
    m, state = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=(di // block, (t + pad) // chunk),
        in_specs=[wide, wide, held, cols, cols,
                  pl.BlockSpec((1, block), lambda i, j: (0, i)), held],
        out_specs=[wide, held],
        out_shape=[jax.ShapeDtypeStruct((t + pad, di), f32),
                   jax.ShapeDtypeStruct((n, di), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret_flag(),
        name='selective_scan',
    )(rows(x), rows(delta), at, rows(B).T, rows(C).T, D[None], state0)
    return m[:t], state


def selective_scan(x, delta, A, B, C, D, state0=None, length=None):
    """The same function as :func:`selective_scan_reference`, in
    chunks; positions at or past ``length`` change nothing (their ``m``
    is what the final state gives their ``C``, plus ``D x``)."""
    x, delta, at, B, C, D = _operands(x, delta, A, B, C, D)
    if length is not None:
        delta = jnp.where((jnp.arange(x.shape[0]) < length)[:, None],
                          delta, 0.0)
    scan = (_scan_chunks_jnp if pallas_mode() == 'fallback'
            else _scan_pallas)
    return scan(x, delta, at, B, C, D, _state0(state0, at))


# ----------------------------------------------------------------------
# one token a row, the state where it lies
# ----------------------------------------------------------------------

def _step_kernel(rows_ref, x_ref, dt_ref, bc_ref, at_ref, d_ref, s_ref,
                 m_ref, s_out_ref, *, block):
    """One row, a block of channels at a time (a block's state is a
    few registers): ``bc`` holds the row's ``B`` and ``C`` as two
    columns, broadcast over the lanes."""
    del rows_ref
    b, c = bc_ref[0, :, 0:1], bc_ref[0, :, 1:2]
    for at in range(0, s_ref.shape[3], block):
        lanes = slice(at, at + block)
        dt, x = dt_ref[0, :, lanes], x_ref[0, :, lanes]
        h = jnp.exp(dt * at_ref[:, lanes]) * s_ref[0, 0, :, lanes] \
            + b * (dt * x)
        m_ref[0, :, lanes] = jnp.sum(h * c, axis=0, keepdims=True) \
            + d_ref[:, lanes] * x
        s_out_ref[0, 0, :, lanes] = h


def _step_pallas(state, rows, x, delta, at, B, C, D):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_rows, di = x.shape
    n = at.shape[0]
    f32 = jnp.float32
    block = 2 * _BLOCK if di % (2 * _BLOCK) == 0 else di

    row = pl.BlockSpec((1, 1, di), lambda i, rows: (i, 0, 0))
    leaf = pl.BlockSpec((1, 1, n, di),
                        lambda i, rows: (rows[i], 0, 0, 0))
    m, state = pl.pallas_call(
        functools.partial(_step_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_rows,),
            in_specs=[row, row,
                      pl.BlockSpec((1, n, 2), lambda i, rows: (i, 0, 0)),
                      pl.BlockSpec((n, di), lambda i, rows: (0, 0)),
                      pl.BlockSpec((1, di), lambda i, rows: (0, 0)),
                      leaf],
            out_specs=[row, leaf]),
        out_shape=[jax.ShapeDtypeStruct((n_rows, 1, di), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the prefetched rows: the state is 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret_flag(),
        name='selective_scan_step',
    )(rows.astype(jnp.int32), x[:, None], delta[:, None],
      jnp.stack([B, C], axis=-1), at, D[None], state)
    return m[:, 0], state


def selective_scan_step(state, rows, x, delta, A, B, C, D):
    """One position a row.  ``state``: the leaf ``(R, 1, N, Di)``
    float32; ``rows`` (n,) the row of each sequence in it; ``x`` /
    ``delta`` (n, Di), ``A`` (Di, N), ``B`` / ``C`` (n, N), ``D``
    (Di,).  Returns ``(m (n, Di) float32, state)``: the rows updated in
    place, nothing else of the leaf moved.  Rows that share a state row
    (idle rows on row 0) overwrite each other, as a scatter's would."""
    x, delta, at, B, C, D = _operands(x, delta, A, B, C, D)
    if pallas_mode() != 'fallback':
        return _step_pallas(state, rows, x, delta, at, B, C, D)
    h = jnp.exp(delta[:, None, :] * at) * state[rows, 0] \
        + B[:, :, None] * (delta * x)[:, None, :]
    m = jnp.einsum('rnd,rn->rd', h, C) + D * x
    return m, state.at[rows, 0].set(h)
