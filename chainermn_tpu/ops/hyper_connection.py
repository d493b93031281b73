"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): the per-token coefficients of a
residual path that carries ``n`` streams.

A layer's state is ``X`` (n, d) a token.  Around a sub-layer ``F``::

    xbar   = RMSNorm(vec(X))                      (n*d,), no weight
    h      = xbar @ phi.T                         (n + n + n*n,)
    H_pre  = sigmoid(a_pre * h[:n] + b_pre)       which streams F reads
    H_post = 2 sigmoid(a_post * h[n:2n] + b_post)  where F's output goes
    H_res  = SK(clip(a_res * h[2n:] + b_res))     (n, n), doubly stochastic
    X'     = H_res X + H_post^T F(RMSNorm(H_pre X))

``SK``: ``exp`` then ``iters`` rounds of ``M / (rowsum + eps)``, ``M /
(colsum + eps)`` (Sinkhorn-Knopp).  :func:`mhc_coefficients` computes
the three of them for every token in ONE kernel over tiles of 128
tokens: unrolled into XLA's own operations the 20 rounds are ~40 small
reductions a solve, and a decode call makes two solves a layer.

Inside the kernel the tokens lie along the LANES: ``h`` is ``(rows,
tokens)``, so a coefficient is a row and the Sinkhorn rounds are
element-wise over sixteen rows.  The norm has no weight, so its scalar
leaves the product: ``h = (X @ phi.T) * rsqrt(mean(X^2) + eps)``.  With
bfloat16 streams ``phi`` (float32) is split into three bfloat16 parts
whose products with ``X`` are exact in float32 and which ride one MXU
pass stacked on the sublanes; float32 streams take a ``highest``
product.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chainermn_tpu.ops._common import interpret_flag, pallas_mode

#: tokens a grid step: one lane tile
_TILE = 128
_VMEM_LIMIT = 32 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def mhc_rows(n):
    """Rows of ``phi``: ``n`` read gates, ``n`` write gates, ``n * n``
    entries of the mixing matrix (row-major)."""
    return n * (n + 2)


def _sinkhorn(m, iters, eps, rows, cols):
    """``iters`` rounds on ``m`` whose axes ``rows`` / ``cols`` index
    the matrix."""
    def body(_, m):
        m = m / (jnp.sum(m, axis=cols, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=rows, keepdims=True) + eps)
    return lax.fori_loop(0, iters, body, m)


def mhc_coefficients_reference(x, phi, alpha, b, n, iters, eps, clamp,
                               norm_eps):
    """The kernel's oracle and the path of a backend without Mosaic:
    ``x`` (T, n*d) -> ``(pre (T, n), post (T, n), res (T, n, n))``
    float32."""
    xf = x.astype(jnp.float32)
    xbar = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                          + norm_eps)
    h = jnp.dot(xbar, phi.astype(jnp.float32).T,
                precision=lax.Precision.HIGHEST)
    alpha, b = alpha.astype(jnp.float32), b.astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * h[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * h[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * h[:, 2 * n:] + b[2 * n:], *clamp))
    res = _sinkhorn(res.reshape(-1, n, n), iters, eps, rows=-2, cols=-1)
    return pre, post, res


def _mhc_kernel(x_ref, phi_ref, ab_ref, o_ref, *, n, d, iters, eps,
                clamp, norm_eps):
    tile = x_ref.shape[0]
    rows = mhc_rows(n)
    # the norm's scalar, a stream at a time (a float32 copy of the
    # whole tile would be twice the tile)
    ss = jnp.zeros((tile, 1), jnp.float32)
    for i in range(n):
        xi = x_ref[:, i * d:(i + 1) * d].astype(jnp.float32)
        ss = ss + jnp.sum(xi * xi, axis=-1, keepdims=True)
    rs = lax.rsqrt(ss / (n * d) + norm_eps)            # (tile, 1)
    rs = jnp.broadcast_to(rs, (tile, _TILE)).T[:1, :]  # (1, tile)
    x, phi = x_ref[...], phi_ref[...]                  # phi (rows, n*d)
    if x.dtype == jnp.bfloat16:
        hi = phi.astype(jnp.bfloat16)
        rest = phi - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        parts = lax.dot_general(
            jnp.concatenate([hi, mid, low], axis=0), x, _NT,
            precision=lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)        # (3 rows, tile)
        h = parts[:rows] + parts[rows:2 * rows] + parts[2 * rows:]
    else:
        h = lax.dot_general(phi, x.astype(jnp.float32), _NT,
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    ab = ab_ref[...]                                   # (rows, 2)
    g = ab[:, 0:1] * (h * rs) + ab[:, 1:2]             # (rows, tile)
    # read gates and write gates: rows [0, 2n), the write gates doubled
    gates = jax.nn.sigmoid(g[:2 * n])
    which = lax.broadcasted_iota(jnp.int32, gates.shape, 0)
    o_ref[:2 * n, :] = jnp.where(which < n, gates, 2.0 * gates)
    # the mixing matrix, an entry a (1, tile) row: every round is
    # element-wise over the sixteen of them
    m = [[jnp.exp(jnp.clip(g[2 * n + i * n + j:2 * n + i * n + j + 1],
                           *clamp)) for j in range(n)] for i in range(n)]

    def body(_, m):
        m = [[e / (sum(row) + eps) for e in row] for row in m]
        cols = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
        return [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]

    m = lax.fori_loop(0, iters, body, m)
    for i in range(n):
        for j in range(n):
            at = 2 * n + i * n + j
            o_ref[at:at + 1, :] = m[i][j]


@functools.partial(jax.jit, static_argnames=(
    'n', 'iters', 'eps', 'clamp', 'norm_eps', 'interpret'))
def _mhc_pallas(x, phi, alpha, b, n, iters, eps, clamp, norm_eps,
                interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, nd = x.shape
    rows = mhc_rows(n)
    # a row's scale and shift, side by side: (rows, 2)
    counts = np.asarray([n, n, n * n])      # noqa: shardlint (static)
    ab = jnp.stack([jnp.repeat(alpha.astype(jnp.float32), counts,
                               total_repeat_length=rows),
                    b.astype(jnp.float32)], axis=1)
    out = pl.pallas_call(
        functools.partial(_mhc_kernel, n=n, d=nd // n, iters=iters,
                          eps=eps, clamp=clamp, norm_eps=norm_eps),
        grid=(-(-t // _TILE),),
        in_specs=[pl.BlockSpec((_TILE, nd), lambda i: (i, 0)),
                  pl.BlockSpec((rows, nd), lambda i: (0, 0)),
                  pl.BlockSpec((rows, 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rows, _TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='mhc_coefficients',
    )(x, phi.astype(jnp.float32), ab)
    out = out.T                                        # (T, rows)
    return (out[:, :n], out[:, n:2 * n],
            out[:, 2 * n:].reshape(t, n, n))


def mhc_coefficients(x, phi, alpha, b, n=4, iters=20, eps=1e-6,
                     clamp=(-30.0, 30.0), norm_eps=1e-6):
    """The residual path's coefficients of every token.

    ``x`` (T, n*d): a token's ``n`` streams side by side, in the
    activation dtype; ``phi`` (n*(n+2), n*d), ``alpha`` (3,) and ``b``
    (n*(n+2),) float32, rows ordered read gates, write gates, mixing
    matrix (row-major).  Returns ``(H_pre (T, n), H_post (T, n), H_res
    (T, n, n))`` float32; ``H_res`` is doubly stochastic to what
    ``iters`` Sinkhorn rounds reach."""
    clamp = (float(clamp[0]), float(clamp[1]))
    if pallas_mode() == 'fallback':
        return mhc_coefficients_reference(x, phi, alpha, b, n, iters, eps,
                                          clamp, norm_eps)
    return _mhc_pallas(x, phi, alpha, b, n=n, iters=int(iters),
                       eps=float(eps), clamp=clamp,
                       norm_eps=float(norm_eps),
                       interpret=interpret_flag())
