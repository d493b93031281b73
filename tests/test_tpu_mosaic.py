"""Pallas kernels under REAL Mosaic on the TPU.

The suite normally runs on the 8-virtual-device CPU mesh
(``JAX_PLATFORMS=cpu``), where the Pallas kernels execute in interpret
mode or fall back to jnp, and ``tests/test_chip_compile.py`` only
COMPILES them for a described chip.  This module is the on-chip half:
every fused op is pinned against its jnp oracle ON DEVICE, fwd and
bwd.  Run it on a machine with a TPU, nothing else set:

    python -m pytest tests/test_tpu_mosaic.py -v

(with ``JAX_PLATFORMS`` unset conftest leaves JAX on the chip).
Skipped automatically when the backend is not TPU, so the CPU suite
stays green.

Parity anchor: these kernels are the repo's native hot path, the role
the reference's hand-written NCCL/Cython layer plays
(``/root/reference/chainermn/nccl/nccl.pyx:153-199``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != 'tpu',
    reason='Mosaic lowering checks need the real TPU backend')


def _close(a, b, rtol=2e-2, name=''):
    a = np.asarray(jax.device_get(a), np.float32)
    b = np.asarray(jax.device_get(b), np.float32)
    err = float(np.max(np.abs(a - b) / (np.abs(b) + 1.0)))
    assert err < rtol, '%s rel err %g' % (name, err)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_attention_mosaic(causal):
    from chainermn_tpu import ops
    from chainermn_tpu.ops.flash_attention import mha_reference
    rng = np.random.RandomState(0)
    b, s, h, d = 2, 512, 4, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.5

    out = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=causal))(q, k, v)
    _close(out, mha_reference(q, k, v, causal=causal), name='fwd')

    def lp(q, k, v):
        return (ops.flash_attention(q, k, v, causal=causal) ** 2).sum()

    def lr(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    gp = jax.jit(jax.grad(lp, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip(('dq', 'dk', 'dv'), gp, gr):
        _close(a, b_, name=name)


def _decode_case(rng, dtype, int8):
    """Serve-phase widths: 32 slots x 8 heads x 64, cache depth 512,
    per-slot lengths spread over the whole depth."""
    from chainermn_tpu.precision import quantize_kv
    b, s, h, d = 32, 512, 8, 64
    q = jnp.asarray(rng.randn(b, h, d), dtype) * 0.5
    k = jnp.asarray(rng.randn(b, s, h, d), dtype) * 0.5
    v = jnp.asarray(rng.randn(b, s, h, d), dtype) * 0.5
    lengths = jnp.asarray(rng.randint(1, s + 1, b), jnp.int32)
    lengths = lengths.at[0].set(1).at[1].set(s)
    scales = {}
    if int8:
        k, ks = quantize_kv(k)
        v, vs = quantize_kv(v)
        scales = dict(k_scale=ks, v_scale=vs)
    return q, k, v, lengths, scales


@pytest.mark.parametrize('int8', [False, True], ids=['bf16', 'int8'])
def test_decode_slab_mosaic(int8):
    from chainermn_tpu import ops
    q, k, v, lengths, scales = _decode_case(
        np.random.RandomState(5), jnp.bfloat16, int8)
    out = jax.jit(lambda *a, **kw: ops.flash_attention_decode(
        *a, **kw))(q, k, v, lengths, **scales)
    ref = ops.decode_attention_reference(q, k, v, lengths, **scales)
    assert out.shape == q.shape and out.dtype == q.dtype
    _close(out, ref, name='slab decode')


# the two serving cells' paged decode calls (rows, pool, table width,
# group, window), each with rows of length 1, ending mid-page and
# mid-step, full, and (the ring) past the window
_CELL_CALLS = {
    # the cell's float pool since PR 44: two 64-wide heads a 128-lane
    # row, head-major, each query one of its row's group of 2
    'gpt2m_packed': (32, (2049, 8, 16, 128), 64, 2, None, True),
    # what an int8 pool of the same model still reads (here in bf16)
    'gpt2m_page_major': (32, (2049, 16, 16, 128), 64, 1, None, False),
    'trinity_full': (64, (4097, 4, 64, 128), 64, 8, None, True),
    'trinity_ring': (64, (2113, 4, 64, 128), 33, 8, 2048, True),
}


@pytest.mark.parametrize('case', sorted(_CELL_CALLS))
def test_decode_paged_cells_mosaic(case):
    """The kernel at the benchmark cells' exact shapes, several pages
    a grid step by the rule, against the jnp twin on the device."""
    import importlib
    fa = importlib.import_module('chainermn_tpu.ops.flash_attention')
    rows, pool, n_max, group, window, head_major = _CELL_CALLS[case]
    ps = pool[2] if head_major else pool[1]
    pages = fa._paged_pages_per_step(
        pool[1:], jnp.bfloat16, n_max, head_major=head_major)
    assert pages == 16 if case == 'gpt2m_packed' else pages > 1
    rng = np.random.RandomState(7)
    top = n_max * ps if window is None else 4096
    lengths = rng.randint(1, top + 1, rows)
    lengths[:6] = [1, ps, ps + 3, 9 * ps + 5, top, top - 1]
    heads = (pool[1] if head_major else pool[2]) * group
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (rows, heads, pool[-1]), jnp.bfloat16)
    k = jax.random.normal(keys[1], pool, jnp.bfloat16)
    v = jax.random.normal(keys[2], pool, jnp.bfloat16)
    tables = jnp.asarray(np.stack([
        1 + rng.permutation(pool[0] - 1)[:n_max] for _ in range(rows)]),
        jnp.int32)
    args = (q, k, v, tables, jnp.asarray(lengths, jnp.int32))
    static = dict(scale=pool[-1] ** -0.5, group=group, window=window,
                  head_major=head_major)
    out = jax.jit(lambda *a: fa._decode_paged_pallas(*a, **static))(*args)
    ref = jax.jit(lambda *a: fa._decode_paged_blockwise_jnp(
        *a, **static))(*args)
    _close(out, ref, name=case)


@pytest.mark.parametrize('int8', [False, True], ids=['bf16', 'int8'])
@pytest.mark.parametrize('page', [16, 128])
def test_decode_paged_mosaic(page, int8):
    """The slab case re-cut into pages scattered over a pool (page 0
    is scratch): same numbers through the page table."""
    from chainermn_tpu import ops
    rng = np.random.RandomState(6)
    q, k, v, lengths, scales = _decode_case(rng, jnp.bfloat16, int8)
    b, s = k.shape[:2]
    n_max = s // page
    perm = 1 + rng.permutation(b * n_max)
    tables = jnp.asarray(perm.reshape(b, n_max), jnp.int32)

    def pool(x):
        pages = x.reshape((b * n_max, page) + x.shape[2:])
        out = jnp.zeros((1 + b * n_max,) + pages.shape[1:], x.dtype)
        return out.at[tables.reshape(-1)].set(pages)

    pooled = {name: pool(val) for name, val in scales.items()}
    out = jax.jit(lambda *a, **kw: ops.flash_attention_decode_paged(
        *a, **kw))(q, pool(k), pool(v), tables, lengths, **pooled)
    ref = ops.decode_attention_reference(q, k, v, lengths, **scales)
    _close(out, ref, name='paged decode')
    if page == 128:
        # page == the slab key block: identical arithmetic by
        # construction, so identical bits
        slab = jax.jit(lambda *a, **kw: ops.flash_attention_decode(
            *a, **kw))(q, k, v, lengths, **scales)
        assert bool(jnp.all(out == slab))


def test_flash_two_widths_mosaic():
    """Keys of 192, values of 128 (expanded latent attention) at the
    ``xing4`` cell's head count, a prompt of 1,000."""
    from chainermn_tpu import ops
    rng = np.random.RandomState(3)
    t, h = 1000, 32
    q = jnp.asarray(rng.randn(1, t, h, 192), jnp.bfloat16) * 0.5
    k = jnp.asarray(rng.randn(1, t, h, 192), jnp.bfloat16) * 0.5
    v = jnp.asarray(rng.randn(1, t, h, 128), jnp.bfloat16) * 0.5
    out = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, scale=0.1))(q, k, v)
    f = lambda x: x.astype(jnp.float32)                 # noqa: E731
    s = jnp.einsum('bqhd,bkhd->bhqk', f(q), f(k),
                   precision='highest') * 0.1
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, -1), f(v),
                      precision='highest')
    assert out.shape == (1, t, h, 128)
    _close(out, want, name='fwd 192/128')


def test_decode_paged_latent_mosaic():
    """The absorbed form at the ``xing4`` cell's shapes: 48 rows, ONE
    leaf of 640-lane rows in pages of 64, values its first 512 lanes,
    against the dense oracle."""
    from chainermn_tpu import ops
    rng = np.random.RandomState(4)
    b, h, n_max, ps = 48, 32, 120, 64
    pool = jnp.asarray(rng.randn(1 + b * 16, 1, ps, 640),
                       jnp.bfloat16) * 0.5
    q = jnp.asarray(rng.randn(b, h, 640), jnp.bfloat16) * 0.5
    lengths = rng.randint(1, 16 * ps + 1, b).astype(np.int32)
    lengths[:3] = [1, ps, 16 * ps]
    tables = np.zeros((b, n_max), np.int32)
    tables[:, :16] = 1 + rng.permutation(b * 16).reshape(b, 16)
    out = ops.flash_attention_decode_paged(
        q, pool, None, jnp.asarray(tables), jnp.asarray(lengths),
        scale=0.05, group=h, head_major=True, value_lanes=512)
    rows = jnp.repeat(jnp.swapaxes(pool, 1, 2), h, axis=2)
    want = ops.decode_attention_paged_reference(
        q, rows, rows, jnp.asarray(tables[:, :16]), jnp.asarray(lengths),
        scale=0.05)[..., :512]
    assert out.shape == (b, h, 512)
    _close(out, want, name='latent decode')


@pytest.mark.parametrize('rows', [48, 1000])
def test_mhc_coefficients_mosaic(rows):
    from chainermn_tpu import ops
    key = jax.random.PRNGKey(rows)
    x = jax.random.normal(key, (rows, 4 * 3584), jnp.bfloat16)
    phi = 0.02 * jax.random.normal(jax.random.fold_in(key, 1),
                                   (24, 4 * 3584), jnp.float32)
    alpha = jnp.ones((3,), jnp.float32)
    b = 0.02 * jax.random.normal(jax.random.fold_in(key, 2), (24,),
                                 jnp.float32)
    got = ops.mhc_coefficients(x, phi, alpha, b)
    want = ops.mhc_coefficients_reference(
        x, phi, alpha, b, 4, 20, 1e-6, (-30.0, 30.0), 1e-6)
    for g, w, name in zip(got, want, ('pre', 'post', 'res')):
        _close(g, w, rtol=1e-4, name=name)


def _scan_operands(t, di=5120, n=16):
    rng = np.random.RandomState(3)
    f32 = jnp.float32
    return (jnp.asarray(rng.randn(t, di), jnp.bfloat16),
            jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                           (t, di))), f32),
            -jnp.asarray(np.tile(np.arange(1, n + 1), (di, 1)), f32),
            jnp.asarray(rng.randn(t, n), f32),
            jnp.asarray(rng.randn(t, n), f32),
            jnp.asarray(rng.randn(di), f32))


@pytest.mark.parametrize('t, length', [(64, 64), (100, 93), (1024, 1000)])
def test_selective_scan_mosaic(t, length):
    """The prompt kernel at the ``phi4-mini-flash`` cell's widths: under
    a chunk of 128, over one and a whole bucket, pad positions past
    ``length`` leaving the state alone."""
    from chainermn_tpu import ops
    operands = _scan_operands(t)
    m, state = jax.jit(lambda *a: ops.selective_scan(*a, length=length))(
        *operands)
    want_m, want_state = jax.jit(ops.selective_scan_reference)(
        *(a[:length] if a.shape[0] == t else a for a in operands))
    _close(m[:length], want_m, rtol=1e-4, name='m')
    _close(state, want_state, rtol=1e-4, name='state')


def test_selective_scan_step_mosaic():
    """The decode kernel at the cell's widths: 96 rows of a 97-row leaf
    in place, pad rows sharing row 0, the other rows untouched."""
    from chainermn_tpu import ops
    di, n, n_rows = 5120, 16, 96
    x, delta, a, b, c, d = _scan_operands(n_rows)
    rng = np.random.RandomState(4)
    leaf = jnp.asarray(rng.randn(*ops.state_shape(97, 1, n, di)),
                       jnp.float32)
    rows = np.zeros((n_rows,), np.int32)
    rows[:60] = rng.permutation(np.arange(1, 97))[:60]
    m, out = jax.jit(ops.selective_scan_step, donate_argnums=(0,))(
        jnp.array(leaf), jnp.asarray(rows), x, delta, a, b, c, d)
    xf = x.astype(jnp.float32)
    h = jnp.exp(delta[:, None, :] * a.T) * leaf[rows, 0] \
        + b[:, :, None] * (delta * xf)[:, None, :]
    want_m = jnp.einsum('rnd,rn->rd', h, c) + d * xf
    _close(m[:60], want_m[:60], rtol=1e-4, name='m')
    _close(out[rows[:60], 0], h[:60], rtol=1e-4, name='state')
    idle = np.setdiff1d(np.arange(1, 97), rows)
    np.testing.assert_array_equal(np.asarray(out[idle]),
                                  np.asarray(leaf[idle]))


def test_layer_norm_mosaic():
    from chainermn_tpu import ops
    from chainermn_tpu.ops.layer_norm import layer_norm_reference
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(256, 512), jnp.float32)
    g = jnp.asarray(rng.randn(512), jnp.float32)
    b = jnp.asarray(rng.randn(512), jnp.float32)
    _close(jax.jit(ops.layer_norm)(x, g, b),
           layer_norm_reference(x, g, b), rtol=1e-3, name='ln fwd')
    gp = jax.jit(jax.grad(
        lambda x, g, b: (ops.layer_norm(x, g, b) ** 2).sum(),
        argnums=(0, 1, 2)))(x, g, b)
    gr = jax.grad(
        lambda x, g, b: (layer_norm_reference(x, g, b) ** 2).sum(),
        argnums=(0, 1, 2))(x, g, b)
    for name, a, b_ in zip(('dx', 'dg', 'db'), gp, gr):
        _close(a, b_, rtol=1e-2, name='ln ' + name)


@pytest.mark.parametrize('rows,d', [
    (8192, 1024),      # gpt2m-train-1k's call: 16 tiles of 512 rows
    (1031, 1024),      # a ragged last tile of 7 rows
    (96, 2560),        # a decode call: one tile
])
def test_layer_norm_mosaic_bf16_tiles(rows, d):
    """bfloat16 rows under float32 ``gamma`` / ``beta``, as the
    transformer family calls it.  Gradients are compared relative to
    their largest entry: a column sum over 8,192 rows is no nearer."""
    from chainermn_tpu import ops
    from chainermn_tpu.ops.layer_norm import layer_norm_reference
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(rows, d), jnp.bfloat16)
    g = jnp.asarray(rng.randn(d), jnp.float32)
    b = jnp.asarray(rng.randn(d), jnp.float32)
    _close(jax.jit(ops.layer_norm)(x, g, b),
           layer_norm_reference(x, g, b), name='ln fwd')

    # float32 from the output on: what is compared is the arithmetic,
    # not where bfloat16 rounds the loss's cotangent
    def loss(ln):
        return lambda x, g, b: (ln(x, g, b).astype(jnp.float32)
                                ** 2).mean()

    gp = jax.jit(jax.grad(loss(ops.layer_norm), argnums=(0, 1, 2)))(
        x, g, b)
    gr = jax.grad(loss(layer_norm_reference), argnums=(0, 1, 2))(x, g, b)
    for name, a, b_ in zip(('dx', 'dg', 'db'), gp, gr):
        scale = float(jnp.max(jnp.abs(b_.astype(jnp.float32))))
        _close(a.astype(jnp.float32) / scale,
               b_.astype(jnp.float32) / scale, name='ln ' + name)


def test_cross_entropy_mosaic():
    from chainermn_tpu import ops
    from chainermn_tpu.ops.cross_entropy import (
        softmax_cross_entropy_reference)
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(256, 1000), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, 256), jnp.int32)
    _close(jax.jit(ops.softmax_cross_entropy)(logits, labels),
           softmax_cross_entropy_reference(logits, labels),
           rtol=1e-3, name='ce fwd')
    gp = jax.jit(jax.grad(lambda l: ops.softmax_cross_entropy(
        l, labels).sum()))(logits)
    gr = jax.grad(lambda l: softmax_cross_entropy_reference(
        l, labels).sum())(logits)
    _close(gp, gr, rtol=1e-2, name='ce dlogits')


def test_fused_sgd_mosaic():
    from chainermn_tpu import ops
    rng = np.random.RandomState(3)
    params = {'w': jnp.asarray(rng.randn(128, 512), jnp.float32),
              'b': jnp.asarray(rng.randn(512), jnp.float32)}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape), jnp.float32), params)
    vel = jax.tree_util.tree_map(jnp.zeros_like, params)
    new_p, new_v = jax.jit(lambda p, g, v: ops.momentum_sgd(
        p, g, v, 0.1, 0.9))(params, grads, vel)
    ref_v = jax.tree_util.tree_map(lambda g, v: 0.9 * v + g, grads, vel)
    ref_p = jax.tree_util.tree_map(lambda p, v: p - 0.1 * v, params,
                                   ref_v)
    for k in params:
        _close(new_p[k], ref_p[k], rtol=1e-5, name='p.' + k)
        _close(new_v[k], ref_v[k], rtol=1e-5, name='v.' + k)


def test_transformer_step_mosaic():
    """Full TransformerLM train-step numerics: Pallas kernels vs the
    jnp-oracle build of the same model, same params, on device."""
    import os

    from chainermn_tpu.models.transformer import TransformerLM, lm_loss

    model = TransformerLM(vocab_size=1024, d_model=256, n_heads=4,
                          n_layers=2, d_ff=1024, max_len=256)
    rng = np.random.RandomState(4)
    toks = jnp.asarray(rng.randint(0, 1024, (4, 256)), jnp.int32)
    tgts = jnp.asarray(rng.randint(0, 1024, (4, 256)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)['params']
    loss_fn = lm_loss(lambda p, t: model.apply({'params': p}, t))

    def run():
        val, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, toks, tgts)[0]))(params)
        gn = sum(float(np.asarray(jax.device_get(
            (g.astype('float32') ** 2).sum())))
            for g in jax.tree_util.tree_leaves(grads))
        return float(np.asarray(jax.device_get(val))), gn ** 0.5

    # force the kernel arm ON even if the ambient env disabled Pallas
    # (oracle-vs-oracle would pass vacuously); restore afterwards
    prior = os.environ.pop('CHAINERMN_TPU_PALLAS', None)
    try:
        l_pallas, g_pallas = run()
        os.environ['CHAINERMN_TPU_PALLAS'] = '0'
        l_oracle, g_oracle = run()
    finally:
        if prior is None:
            os.environ.pop('CHAINERMN_TPU_PALLAS', None)
        else:
            os.environ['CHAINERMN_TPU_PALLAS'] = prior
    assert abs(l_pallas - l_oracle) / max(abs(l_oracle), 1e-6) < 2e-2
    assert abs(g_pallas - g_oracle) / max(abs(g_oracle), 1e-6) < 5e-2


def test_s2d_stem_equivalence_on_tpu():
    """The space-to-depth stem must stay an exact weight-mapped
    equivalent of the 7x7/2 stem when XLA:TPU compiles both conv
    forms (layout/tiling differences must not change the math beyond
    f32 roundoff)."""
    from chainermn_tpu.models import ResNet
    from chainermn_tpu.models.resnet50 import convert_stem_variables

    kw = dict(stage_sizes=[1], num_classes=10, width=16,
              dtype=jnp.float32)
    std = ResNet(stem='standard', **kw)
    s2d = ResNet(stem='space_to_depth', **kw)
    x = jnp.asarray(
        np.random.RandomState(0).rand(2, 64, 64, 3), jnp.float32)
    v_std = std.init({'params': jax.random.PRNGKey(0)}, x,
                     train=False)
    # true-f32 conv passes: at DEFAULT precision XLA:TPU uses bf16
    # multiply passes, and the differently-shaped stems accumulate in
    # different tap order -- the equivalence claim is about f32 math
    with jax.default_matmul_precision('float32'):
        out_std = jax.jit(
            lambda v, xx: std.apply(v, xx, train=False))(v_std, x)
        out_s2d = jax.jit(
            lambda v, xx: s2d.apply(v, xx, train=False))(
                convert_stem_variables(v_std), x)
    _close(out_s2d, out_std, rtol=1e-3, name='s2d stem')
