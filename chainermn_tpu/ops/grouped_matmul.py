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
"""

import functools

import jax
import jax.numpy as jnp
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
        group = group_ref[visit]
        rows = (tile_ref[visit] * tile_m
                + lax.broadcasted_iota(jnp.int32, y.shape, 0))
        mine = jnp.logical_and(rows >= start_ref[group],
                               rows < end_ref[group])
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
    boundaries between groups)."""
    if tile_m is None:
        tile_m = 16 if x.shape[0] <= 512 else 128
    if pallas_mode() == 'fallback':
        return grouped_swiglu_reference(x, w1, w3, w2, group_sizes)
    return _grouped_swiglu_pallas(x, w1, w3, w2, group_sizes, tile_m)


def dropless_experts(x, experts, selected, weights, tile_m=None):
    """The routed half of a dropless expert layer.  ``x`` (T, d);
    ``selected`` (T, k) int32 expert ids and ``weights`` (T, k) float32
    gates, from the caller's router; ``experts`` ``{'w1', 'w3', 'w2'}``
    stacked over E.  Returns ``(sum_j weights[:, j] * expert_{selected
    [:, j]}(x)`` in float32 (T, d), ``group_sizes`` (E,))``: no
    assignment is dropped whatever the load."""
    tokens, k = selected.shape
    n_experts = experts['w1'].shape[0]
    flat = selected.reshape(-1)
    order = jnp.argsort(flat, stable=True)             # (T*k,)
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    y = grouped_swiglu(jnp.take(x, order // k, axis=0), experts['w1'],
                       experts['w3'], experts['w2'], sizes, tile_m)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(tokens, k, -1)
    return jnp.einsum('tkd,tk->td', y.astype(jnp.float32),
                      weights.astype(jnp.float32)), sizes
