"""Dropless sparse experts: a grouped SwiGLU over rows sorted by expert.

``parallel/moe.py`` is the switch-style layer (a capacity per expert,
tokens over it dropped, one expert a device).  This is the other kind:
every one of a token's ``k`` assignments is computed, however the load
falls.  The ``T * k`` assignments are sorted by expert (the stable sort
``parallel.moe.sort_dispatch`` uses), which makes each expert's rows one
contiguous GROUP of the sorted matrix; :func:`grouped_swiglu` runs

    y[r] = (silu(x[r] @ w1[e]) * (x[r] @ w3[e])) @ w2[e],   r in group e

as ONE Pallas kernel over ``(row tile, group)`` VISITS: a row tile that
spans several groups is visited once per group and writes only that
group's rows (the megablox idea).  The visit list is scalar-prefetched,
so the weight block of a visit is fetched by expert id straight from
the ``(E, ...)`` weights; consecutive visits of one expert reuse the
block, and AN EXPERT NO ROW CHOSE HAS NO VISIT AND IS NEVER READ.  That
is what decode needs (a few rows on most of the experts: the time is
each touched expert's weights streamed once) and what prefill needs
(hundreds of rows an expert: the three products on the MXU while the
next expert's weights arrive).

Training: :func:`grouped_swiglu` is a ``jax.custom_vjp`` whose backward
is two more kernels over the same kind of schedule
(``grouped_swiglu_bwd_dx``: the data gradient; ``grouped_swiglu_bwd_dw``:
the weight gradient, a grouped product whose CONTRACTION runs over a
group's rows).  On a backend without Mosaic the op is
:func:`grouped_swiglu_reference` and its gradient ``lax.ragged_dot``'s
own.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chainermn_tpu.ops._common import interpret_flag, pallas_mode

#: scoped VMEM the kernel asks for: one expert's three matrices twice
#: (the pipeline's two buffers), a row tile and its products
_VMEM_LIMIT = 96 * 1024 * 1024


def _visits(group_sizes, n_tiles, tile_m):
    """The kernel's schedule.  Per visit its group and its row tile,
    plus the groups' row ranges and the live visit count; the list has
    the static length ``n_tiles + E - 1`` (every boundary between
    groups can add one visit to the tiles) and its dead tail repeats
    the last live visit, so nothing new is fetched for it."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tile_m
    per = jnp.where(group_sizes > 0,
                    (ends - 1) // tile_m - first + 1, 0)
    visit_end = jnp.cumsum(per)
    total = visit_end[-1]
    at = jnp.minimum(jnp.arange(n_tiles + n_groups - 1, dtype=jnp.int32),
                     jnp.maximum(total - 1, 0))
    group = jnp.searchsorted(visit_end, at, side='right').astype(
        jnp.int32)
    group = jnp.minimum(group, n_groups - 1)
    tile = first[group] + at - (visit_end - per)[group]
    i32 = jnp.int32
    return (group, tile.astype(i32), starts.astype(i32),
            ends.astype(i32), total.astype(i32)[None])


def _mine(group_ref, tile_ref, start_ref, end_ref, visit, shape, tile_m):
    """Which rows of the visit's tile belong to the visit's group."""
    group = group_ref[visit]
    rows = (tile_ref[visit] * tile_m
            + lax.broadcasted_iota(jnp.int32, shape, 0))
    return jnp.logical_and(rows >= start_ref[group],
                           rows < end_ref[group])


def _kernel(group_ref, tile_ref, start_ref, end_ref, total_ref, x_ref,
            w1_ref, w3_ref, w2_ref, o_ref, *, tile_m):
    import jax.experimental.pallas as pl

    visit = pl.program_id(0)

    @pl.when(visit < total_ref[0])
    def _():
        # one MXU pass in the operands' own dtype, whatever the
        # process-wide default precision says (Mosaic refuses
        # 'highest' on bfloat16 operands)
        dot = functools.partial(jnp.dot, precision=lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
        x = x_ref[...]                                 # (tile_m, d)
        gate = dot(x, w1_ref[0])
        hidden = (gate * jax.nn.sigmoid(gate)
                  * dot(x, w3_ref[0])).astype(x.dtype)
        y = dot(hidden, w2_ref[0])
        mine = _mine(group_ref, tile_ref, start_ref, end_ref, visit,
                     y.shape, tile_m)
        # the other rows of the tile belong to the visits before and
        # after this one; on a tile's first visit they hold whatever
        # the buffer held, and their own visit overwrites them
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _grouped_swiglu_pallas(x, w1, w3, w2, group_sizes, tile_m):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    n_groups, _, f = w1.shape
    pad = -n % tile_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_tiles = (n + pad) // tile_m
    schedule = _visits(group_sizes.astype(jnp.int32), n_tiles, tile_m)

    def rows(v, group, tile, *_):
        return (tile[v], 0)

    def expert(v, group, *_):
        return (group[v], 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_tiles + n_groups - 1,),
            in_specs=[pl.BlockSpec((tile_m, d), rows),
                      pl.BlockSpec((1, d, f), expert),
                      pl.BlockSpec((1, d, f), expert),
                      pl.BlockSpec((1, f, d), expert)],
            out_specs=pl.BlockSpec((tile_m, d), rows)),
        out_shape=jax.ShapeDtypeStruct((n + pad, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_flag(),
        name='grouped_swiglu',
    )(*schedule, x, w1, w3, w2)
    return out[:n]


def _bwd_dx_kernel(group_ref, tile_ref, start_ref, end_ref, total_ref,
                   x_ref, dy_ref, w1_ref, w3_ref, w2_ref, dx_ref,
                   dgate_ref, dup_ref, hidden_ref, *, tile_m):
    """The data gradient of one visit: ``gate`` / ``up`` RECOMPUTED
    from ``x`` (two products the forward made too), then ``dhidden =
    dy w2^T``, through ``silu(gate) * up``, and ``dx = dgate w1^T +
    dup w3^T``.  ``dgate``, ``dup`` and ``hidden`` leave as (N, f)
    side results for the weight gradient."""
    import jax.experimental.pallas as pl

    visit = pl.program_id(0)

    @pl.when(visit < total_ref[0])
    def _():
        def dot(a, b, dims=(((1,), (0,)), ((), ()))):
            return lax.dot_general(a, b, dims,
                                   precision=lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32)
        nt = (((1,), (1,)), ((), ()))                  # a @ b.T
        x, dy = x_ref[...], dy_ref[...]                # (tile_m, d)
        w1, w3 = w1_ref[0], w3_ref[0]
        gate, up = dot(x, w1), dot(x, w3)              # (tile_m, f)
        sig = jax.nn.sigmoid(gate)
        silu = gate * sig
        dhidden = dot(dy, w2_ref[0], nt)
        dgate = (dhidden * up * (sig * (1.0 + gate * (1.0 - sig)))
                 ).astype(x.dtype)
        dup = (dhidden * silu).astype(x.dtype)
        dx = dot(dgate, w1, nt) + dot(dup, w3, nt)
        def mine(shape):
            return _mine(group_ref, tile_ref, start_ref, end_ref, visit,
                         shape, tile_m)
        dx_ref[...] = jnp.where(mine(dx.shape), dx.astype(x.dtype),
                                dx_ref[...])
        narrow = mine(gate.shape)
        for ref, value in ((dgate_ref, dgate), (dup_ref, dup),
                           (hidden_ref, (silu * up).astype(x.dtype))):
            ref[...] = jnp.where(narrow, value, ref[...])


def _bwd_dw_kernel(group_ref, tile_ref, start_ref, end_ref, total_ref,
                   x_ref, dy_ref, dgate_ref, dup_ref, hidden_ref, *refs,
                   tile_m):
    """The weight gradient: per visit the tile's rows of the visit's
    group add ``x^T dgate``, ``x^T dup`` and ``hidden^T dy`` to float32
    accumulators in VMEM; a group's last visit writes its three blocks,
    once.  (``refs``: the three zero-filled arrays the results alias,
    then the results, then the accumulators.)"""
    import jax.experimental.pallas as pl

    dw1_ref, dw3_ref, dw2_ref, acc1, acc3, acc2 = refs[3:]
    visit = pl.program_id(0)
    total = total_ref[0]
    last = pl.num_programs(0) - 1
    group = group_ref[visit]
    live = visit < total

    @pl.when(jnp.logical_and(live, jnp.logical_or(
        visit == 0, group_ref[jnp.maximum(visit - 1, 0)] != group)))
    def _first():
        acc1[...] = jnp.zeros_like(acc1)
        acc3[...] = jnp.zeros_like(acc3)
        acc2[...] = jnp.zeros_like(acc2)

    @pl.when(live)
    def _accumulate():
        tn = (((0,), (0,)), ((), ()))                  # a.T @ b
        dot = functools.partial(lax.dot_general, dimension_numbers=tn,
                                precision=lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
        x = x_ref[...]
        mine = _mine(group_ref, tile_ref, start_ref, end_ref, visit,
                     dgate_ref.shape, tile_m)

        def own(ref):
            # rows of other groups, and pad rows nothing ever wrote
            return jnp.where(mine, ref[...], jnp.zeros_like(ref))
        acc1[...] += dot(x, own(dgate_ref))
        acc3[...] += dot(x, own(dup_ref))
        acc2[...] += dot(own(hidden_ref), dy_ref[...])

    @pl.when(jnp.logical_and(live, jnp.logical_or(
        visit == total - 1,
        group_ref[jnp.minimum(visit + 1, last)] != group)))
    def _write():
        dw1_ref[0] = acc1[...].astype(dw1_ref.dtype)
        dw3_ref[0] = acc3[...].astype(dw3_ref.dtype)
        dw2_ref[0] = acc2[...].astype(dw2_ref.dtype)

    @pl.when(jnp.logical_and(visit == 0, total == 0))
    def _no_row_at_all():
        # the grid's one resident block is copied out at its end
        # whether or not a visit wrote it
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        dw3_ref[...] = jnp.zeros_like(dw3_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)


def _padded_rows(arrays, tile_m):
    pad = -arrays[0].shape[0] % tile_m
    if pad:
        arrays = [jnp.pad(a, ((0, pad), (0, 0))) for a in arrays]
    return arrays, arrays[0].shape[0] // tile_m


def _grouped_swiglu_bwd_pallas(x, w1, w3, w2, group_sizes, dy, tile_m):
    """``(dx, dw1, dw3, dw2)``.  ``gate`` / ``up`` are recomputed from
    ``x`` in the data-gradient kernel (2 of its 5 products; saving them
    in the forward would cost 2 * N * f floats a call and a second
    forward kernel for training); that kernel hands ``dgate``, ``dup``
    and ``hidden`` (3 * N * f values in ``x``'s dtype: 28 MB at 6,144
    rows x 768) to the weight-gradient kernel, which so makes three
    products a visit and no recomputation.  The weight gradients alias
    zero-filled arrays: an expert no row chose has no visit, is never
    read or written, and keeps its zeros.  ``group_sizes`` may sum to
    less than N (a layer that holds a share of the experts): the rows
    past the total get a zero ``dx``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    n_groups, _, f = w1.shape
    sizes = group_sizes.astype(jnp.int32)
    (xp, dyp), n_tiles = _padded_rows([x, dy.astype(x.dtype)], tile_m)
    schedule = _visits(sizes, n_tiles, tile_m)
    grid = (n_tiles + n_groups - 1,)

    def rows(v, group, tile, *_):
        return (tile[v], 0)

    def expert(v, group, *_):
        return (group[v], 0, 0)

    wide = pl.BlockSpec((tile_m, d), rows)
    narrow = pl.BlockSpec((tile_m, f), rows)
    up_w = pl.BlockSpec((1, d, f), expert)
    down_w = pl.BlockSpec((1, f, d), expert)
    params = pltpu.CompilerParams(dimension_semantics=('arbitrary',),
                                  vmem_limit_bytes=_VMEM_LIMIT)
    side = jax.ShapeDtypeStruct((xp.shape[0], f), x.dtype)
    dx, dgate, dup, hidden = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=grid,
            in_specs=[wide, wide, up_w, up_w, down_w],
            out_specs=[wide, narrow, narrow, narrow]),
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype), side, side,
                   side],
        compiler_params=params, interpret=interpret_flag(),
        name='grouped_swiglu_bwd_dx',
    )(*schedule, xp, dyp, w1, w3, w2)

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    dw1, dw3, dw2 = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=grid,
            in_specs=[wide, wide, narrow, narrow, narrow, anywhere,
                      anywhere, anywhere],
            out_specs=[up_w, up_w, down_w],
            scratch_shapes=[pltpu.VMEM((d, f), jnp.float32),
                            pltpu.VMEM((d, f), jnp.float32),
                            pltpu.VMEM((f, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype)
                   for w in (w1, w3, w2)],
        # operands count the five prefetched scalars and the five row
        # operands: the first zero-filled array is operand 10
        input_output_aliases={10: 0, 11: 1, 12: 2},
        compiler_params=params, interpret=interpret_flag(),
        name='grouped_swiglu_bwd_dw',
    )(*schedule, xp, dyp, dgate, dup, hidden,
      *[jnp.zeros_like(w) for w in (w1, w3, w2)])
    # rows past the groups' total belong to no visit: nothing wrote them
    held = jnp.arange(n)[:, None] < jnp.sum(sizes)
    return jnp.where(held, dx[:n], jnp.zeros_like(x)), dw1, dw3, dw2


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped_swiglu_kernels(x, w1, w3, w2, group_sizes, tile_m):
    return _grouped_swiglu_pallas(x, w1, w3, w2, group_sizes, tile_m)


def _grouped_swiglu_fwd(x, w1, w3, w2, group_sizes, tile_m):
    return (_grouped_swiglu_pallas(x, w1, w3, w2, group_sizes, tile_m),
            (x, w1, w3, w2, group_sizes))


def _grouped_swiglu_bwd(tile_m, res, dy):
    x, w1, w3, w2, group_sizes = res
    # a backward's visit computes 5 (dx) and 3 (dw) products over its
    # whole tile: never the decode's 16-row tile
    grads = _grouped_swiglu_bwd_pallas(x, w1, w3, w2, group_sizes, dy,
                                       max(tile_m, 128))
    return grads + (np.zeros(group_sizes.shape, jax.dtypes.float0),)


_grouped_swiglu_kernels.defvjp(_grouped_swiglu_fwd, _grouped_swiglu_bwd)


def grouped_swiglu_reference(x, w1, w3, w2, group_sizes):
    """The same rows through ``lax.ragged_dot``: the kernel's oracle
    and the path of a backend without Mosaic."""
    sizes = group_sizes.astype(jnp.int32)
    gate = lax.ragged_dot(x, w1, sizes,
                          preferred_element_type=jnp.float32)
    up = lax.ragged_dot(x, w3, sizes, preferred_element_type=jnp.float32)
    hidden = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
    return lax.ragged_dot(hidden, w2, sizes,
                          preferred_element_type=jnp.float32
                          ).astype(x.dtype)


def grouped_swiglu(x, w1, w3, w2, group_sizes, tile_m=None):
    """``x`` (N, d): rows sorted by group; ``w1``/``w3`` (E, d, f) and
    ``w2`` (E, f, d): a SwiGLU per group; ``group_sizes`` (E,) int32,
    summing to N.  Returns (N, d) in ``x``'s dtype, products
    accumulated in float32.  ``tile_m`` rows a tile: by default 16 up
    to 512 rows (decode: a tile's products are nothing beside its
    expert's 3 * d * f weights) and 128 above (prefill: a visit
    computes its whole tile, so a smaller tile wastes less on the
    boundaries between groups).  Differentiable in ``x`` and the
    three weights (``_grouped_swiglu_bwd_pallas``)."""
    if tile_m is None:
        tile_m = 16 if x.shape[0] <= 512 else 128
    if pallas_mode() == 'fallback':
        return grouped_swiglu_reference(x, w1, w3, w2, group_sizes)
    return _grouped_swiglu_kernels(x, w1, w3, w2, group_sizes, tile_m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _permute_rows(x, perm, inverse, k):
    """Row ``perm[i] // k`` of ``x`` for every ``i``: ``perm`` a
    permutation of ``k`` assignments a row of ``x``, ``inverse`` its
    inverse (``k`` 1: ``x[perm]``).  The gradient is a gather too
    (``g[inverse]``, a row's ``k`` summed), where autodiff's would be a
    scatter of every row."""
    return jnp.take(x, perm // k if k > 1 else perm, axis=0)


def _permute_rows_fwd(x, perm, inverse, k):
    return _permute_rows(x, perm, inverse, k), (perm, inverse)


def _permute_rows_bwd(k, res, g):
    perm, inverse = res
    g = jnp.take(g, inverse, axis=0)
    if k > 1:
        g = jnp.sum(g.reshape(-1, k, g.shape[1]).astype(jnp.float32),
                    axis=1).astype(g.dtype)
    none = np.zeros(perm.shape, jax.dtypes.float0)
    return g, none, none


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def dropless_experts(x, experts, selected, weights, tile_m=None,
                     first=None):
    """The routed half of a dropless expert layer.  ``x`` (T, d);
    ``selected`` (T, k) int32 expert ids and ``weights`` (T, k) float32
    gates, from the caller's router; ``experts`` ``{'w1', 'w3', 'w2'}``
    stacked over E.  Returns ``(sum_j weights[:, j] * expert_{selected
    [:, j]}(x)`` in float32 (T, d), ``group_sizes`` (E,))``: no
    assignment is dropped whatever the load.

    ``first`` (an int): the layer holds a SHARE of the experts the
    router chose among: ``experts`` are the ids ``first .. first + E -
    1``.  An assignment to an absent expert carries no row into any
    group (it sorts behind every held one, where no visit of the
    kernels goes) and adds nothing; ``group_sizes`` then sum to the
    assignments held.  The sorted matrix keeps all ``T * k`` rows:
    every one of them MAY be held.

    Two bodies, chosen by the caller from its shapes (``models/
    _experts.py``): a share's masks are dead weight where every expert
    is here, and cost 5.5% of this call at 16,384 rows of 3,584 (6,277
    -> 6,625 us on a v5e, a ``xing4`` prefill; 0.1-0.3% at a decode
    tick's rows: ``PERF.md`` section 6, PR 41)."""
    tokens, k = selected.shape
    n_experts = experts['w1'].shape[0]
    flat = selected.reshape(-1)
    if first is not None:
        flat = flat - first
        held = jnp.logical_and(flat >= 0, flat < n_experts)
        flat = jnp.where(held, flat, n_experts)
        order = jnp.argsort(flat, stable=True)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(
            1)[:n_experts]
        y = grouped_swiglu(
            _permute_rows(x, order, back, k), experts['w1'],
            experts['w3'], experts['w2'], sizes, tile_m)
        held = held.reshape(tokens, k)
        # an absent assignment's row is one no visit wrote
        y = jnp.where(held[..., None],
                      _permute_rows(y, back, order, 1).reshape(
                          tokens, k, -1).astype(jnp.float32), 0.0)
        return jnp.einsum('tkd,tk->td', y, jnp.where(
            held, weights.astype(jnp.float32), 0.0)), sizes
    order = jnp.argsort(flat, stable=True)             # (T*k,)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    y = grouped_swiglu(jnp.take(x, order // k, axis=0), experts['w1'],
                       experts['w3'], experts['w2'], sizes, tile_m)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(tokens, k, -1)
    return jnp.einsum('tkd,tk->td', y.astype(jnp.float32),
                      weights.astype(jnp.float32)), sizes
