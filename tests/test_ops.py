"""Pallas op layer: numerics vs pure-jnp oracles, fwd and bwd.

Runs each op both on the default (fallback) path and, via the
``interpret`` fixture param, through the actual Pallas kernels in
interpreter mode -- the CPU-side analogue of compiling the Mosaic
kernels on TPU.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu import ops
from chainermn_tpu.ops import _common

# the module: ``ops`` re-exports a function under the same name
_fa = importlib.import_module('chainermn_tpu.ops.flash_attention')


@pytest.fixture(params=['fallback', 'interpret'])
def mode(request, monkeypatch):
    if request.param == 'interpret':
        monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    else:
        monkeypatch.delenv('CHAINERMN_TPU_PALLAS_INTERPRET',
                           raising=False)
    assert _common.pallas_mode() == request.param
    return request.param


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize('causal', [False, True])
    def test_matches_reference(self, mode, causal):
        q = _rand((2, 64, 2, 16), 0)
        k = _rand((2, 64, 2, 16), 1)
        v = _rand((2, 64, 2, 16), 2)
        out = ops.flash_attention(q, k, v, causal=causal,
                                  block_q=32, block_k=32)
        ref = ops.mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_unpadded_lengths(self, mode):
        # T not a multiple of the block: padded keys must get no mass
        q = _rand((1, 40, 1, 8), 3)
        k = _rand((1, 72, 1, 8), 4)
        v = _rand((1, 72, 1, 8), 5)
        out = ops.flash_attention(q, k, v, block_q=32, block_k=32)
        ref = ops.mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_cross_attention_lengths(self, mode):
        q = _rand((2, 16, 2, 8), 6)
        k = _rand((2, 48, 2, 8), 7)
        v = _rand((2, 48, 2, 8), 8)
        out = ops.flash_attention(q, k, v, block_q=16, block_k=16)
        ref = ops.mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize('causal', [False, True])
    def test_gradients(self, mode, causal):
        q = _rand((1, 32, 2, 8), 9)
        k = _rand((1, 32, 2, 8), 10)
        v = _rand((1, 32, 2, 8), 11)

        def f(q, k, v):
            return jnp.sum(ops.flash_attention(
                q, k, v, causal=causal, block_q=16, block_k=16) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(ops.mha_reference(q, k, v, causal=causal) ** 2)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_causal_requires_square(self, mode):
        q = _rand((1, 16, 1, 8), 0)
        k = _rand((1, 32, 1, 8), 1)
        with pytest.raises(ValueError):
            ops.flash_attention(q, k, k, causal=True)

    def test_derived_tiles_bf16_causal(self, mode):
        """Forward and gradients at the tiles ``_flash_blocks`` derives
        (no explicit block), bfloat16, causal, long enough that every
        kernel visits at least two key tiles and two query tiles: a
        dropped tile or a frontier off by one tile moves an output row
        by O(0.1), the rounding of bfloat16 by O(0.01).  The backward
        reads ``lse`` / ``delta`` in their (B*H, 1, T) row layout."""
        t, d = 2048, 64
        for kernel in ('fwd', 'dq', 'dkv'):
            bq, bk = _fa._flash_blocks(t, t, d, jnp.bfloat16,
                                       kernel=kernel)
            assert t // bq >= 2 and t // bk >= 2, (kernel, bq, bk)
        q, k, v = (_rand((1, t, 1, d), i, jnp.bfloat16) * 0.5
                   for i in (20, 21, 22))
        w = _rand((1, t, 1, d), 23)

        def f(attn):
            return lambda q, k, v: jnp.sum(
                attn(q, k, v, causal=True).astype(jnp.float32) * w)

        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        ref = ops.mha_reference(*f32, causal=True)
        out = ops.flash_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(jnp.float32), ref,
                                   atol=2e-2, rtol=2e-2)
        g = jax.grad(f(ops.flash_attention), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f(ops.mha_reference), argnums=(0, 1, 2))(*f32)
        for name, a, b in zip('qkv', g, g_ref):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b)
            # per-row: late query rows average ~2,000 keys and carry
            # small gradients, early ones few keys and large ones
            assert np.max(np.abs(a - b)) < 0.03 * np.max(np.abs(b)), name
            assert (np.linalg.norm(a - b)
                    < 0.02 * np.linalg.norm(b)), name

    def test_window_and_groups_at_derived_tiles(self, mode):
        """The serving forward (grouped K/V heads, a window) at derived
        tiles, over more than one key tile and past the window."""
        t, d, window = 640, 128, 256
        q = _rand((1, t, 4, d), 30) * 0.5
        k = _rand((1, t, 2, d), 31) * 0.5
        v = _rand((1, t, 2, d), 32) * 0.5
        out = ops.flash_attention(q, k, v, causal=True, window=window)
        kr, vr = (jnp.repeat(x, 2, axis=2) for x in (k, v))
        s = jnp.einsum('bqhd,bkhd->bhqk', q, kr) * d ** -0.5
        rel = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        s = jnp.where((rel >= 0) & (rel < window), s, -1e30)
        ref = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, -1), vr)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestDecodeAttention:
    """ISSUE 11: the single-query decode variant -- oracle parity in
    fallback AND interpret modes, per-slot dynamic lengths, int8-KV
    dequant, dtype pins, and the one-cache-read jaxpr pin."""

    def _qkv(self, b=3, s=64, h=2, d=16):
        q = _rand((b, h, d), 0)
        k = _rand((b, s, h, d), 1)
        v = _rand((b, s, h, d), 2)
        lengths = jnp.asarray([5, s, s // 2 + 1], jnp.int32)[:b]
        return q, k, v, lengths

    def test_matches_reference(self, mode):
        q, k, v, lengths = self._qkv()
        out = ops.flash_attention_decode(q, k, v, lengths, block_k=16)
        ref = ops.decode_attention_reference(q, k, v, lengths)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_reference_matches_full_causal_row(self, mode):
        """The oracle's own pin: decoding position t equals row t of
        full causal attention."""
        b, t, h, d = 2, 24, 2, 8
        q = _rand((b, t, h, d), 3)
        k = _rand((b, t, h, d), 4)
        v = _rand((b, t, h, d), 5)
        full = ops.mha_reference(q, k, v, causal=True)
        pos = t - 1
        out = ops.flash_attention_decode(
            q[:, pos], k, v, jnp.full((b,), pos + 1, jnp.int32),
            block_k=8)
        np.testing.assert_allclose(out, full[:, pos], atol=2e-5,
                                   rtol=2e-5)

    def test_stale_rows_beyond_length_ignored(self, mode):
        """Slot-reuse safety: garbage past ``lengths`` (a previous
        occupant's K/V) must receive no probability mass."""
        q, k, v, lengths = self._qkv()
        k_dirty = k.at[:, 40:].set(100.0)
        v_dirty = v.at[:, 40:].set(-100.0)
        lengths = jnp.minimum(lengths, 40)
        out = ops.flash_attention_decode(q, k_dirty, v_dirty, lengths,
                                         block_k=16)
        ref = ops.decode_attention_reference(q, k, v, lengths)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_int8_kv(self, mode):
        from chainermn_tpu.precision import quantize_kv
        q, k, v, lengths = self._qkv()
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ref_f32 = ops.decode_attention_reference(q, k, v, lengths)
        ref_i8 = ops.decode_attention_reference(
            q, kq, vq, lengths, k_scale=ks, v_scale=vs)
        out = ops.flash_attention_decode(
            q, kq, vq, lengths, k_scale=ks, v_scale=vs, block_k=16)
        # kernel matches its own int8 oracle tightly...
        np.testing.assert_allclose(out, ref_i8, atol=2e-5, rtol=2e-5)
        # ...and the f32 answer within the documented 5e-2
        np.testing.assert_allclose(out, ref_f32, atol=5e-2, rtol=5e-2)

    def test_scale_args_must_pair(self, mode):
        from chainermn_tpu.precision import quantize_kv
        q, k, v, lengths = self._qkv()
        kq, ks = quantize_kv(k)
        with pytest.raises(ValueError, match='BOTH'):
            ops.flash_attention_decode(q, kq, v, lengths, k_scale=ks)

    def test_dtype_pin_bf16(self, mode):
        q, k, v, lengths = self._qkv()
        out = ops.flash_attention_decode(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16), lengths, block_k=16)
        assert out.dtype == jnp.bfloat16
        ref = ops.decode_attention_reference(q, k, v, lengths)
        np.testing.assert_allclose(out.astype(jnp.float32), ref,
                                   atol=5e-2, rtol=5e-2)

    def test_unpadded_cache_length(self, mode):
        # S not a block multiple: padded keys must get no mass
        q = _rand((2, 2, 8), 6)
        k = _rand((2, 40, 2, 8), 7)
        v = _rand((2, 40, 2, 8), 8)
        lengths = jnp.asarray([40, 17], jnp.int32)
        out = ops.flash_attention_decode(q, k, v, lengths, block_k=16)
        ref = ops.decode_attention_reference(q, k, v, lengths)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_jaxpr_one_cache_read_no_full_materialization(self):
        """The acceptance pin: the decode step consumes each cache
        operand ONCE (a single streamed HBM pass) and materializes no
        full-sequence score/probability row in f32 -- every softmax
        intermediate is a (block_k,)-tile."""
        b, s, h, d = 2, 128, 2, 16
        block_k = 32

        def step(q, k, v, lengths):
            return ops.flash_attention_decode(q, k, v, lengths,
                                              block_k=block_k)

        jaxpr = jax.make_jaxpr(step)(
            jnp.zeros((b, h, d)), jnp.zeros((b, s, h, d)),
            jnp.zeros((b, s, h, d)), jnp.zeros((b,), jnp.int32))
        _, k_var, v_var, _ = jaxpr.jaxpr.invars
        for var in (k_var, v_var):
            readers = [e for e in jaxpr.jaxpr.eqns
                       if var in e.invars]
            assert len(readers) == 1, (
                'cache operand consumed %d times' % len(readers))

        def walk(jx):
            for e in jx.eqns:
                for ov in e.outvars:
                    shape = getattr(ov.aval, 'shape', ())
                    dtype = getattr(ov.aval, 'dtype', None)
                    if (len(shape) >= 2 and shape[-1] == s
                            and str(dtype) == 'float32'):
                        raise AssertionError(
                            'full-sequence f32 row materialized: '
                            '%s %r' % (e.primitive, shape))
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

        walk(jaxpr.jaxpr)


class TestDecodePagedAttention:
    """Paged decode (this PR's tentpole kernel): the cache is a POOL
    of fixed-size pages read through per-sequence int32 page tables
    -- oracle parity (fallback AND interpret), equivalence with the
    contiguous decode oracle on a gathered cache, int8-KV page
    dequant, stale-page safety, and the one-pool-read jaxpr pin."""

    def _pool(self, b=3, n_pages=14, ps=8, n_max=4, h=2, d=16):
        q = _rand((b, h, d), 20)
        k = _rand((n_pages, ps, h, d), 21)
        v = _rand((n_pages, ps, h, d), 22)
        # distinct non-scratch pages, deliberately NON-contiguous and
        # shared-free so the contiguous-gather oracle is well defined
        rng = np.random.RandomState(0)
        perm = 1 + rng.permutation(n_pages - 1)[:b * n_max]
        tables = jnp.asarray(perm.reshape(b, n_max), jnp.int32)
        lengths = jnp.asarray([5, n_max * ps, ps + 3], jnp.int32)[:b]
        return q, k, v, tables, lengths

    def test_matches_reference(self, mode):
        q, k, v, tables, lengths = self._pool()
        out = ops.flash_attention_decode_paged(q, k, v, tables,
                                               lengths)
        ref = ops.decode_attention_paged_reference(q, k, v, tables,
                                                   lengths)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_matches_contiguous_decode_oracle(self, mode):
        """Cross-oracle pin: gathering the table rows into a private
        contiguous cache and running the NON-paged decode oracle must
        give the same answer -- paging is pure addressing."""
        q, k, v, tables, lengths = self._pool()
        b, n_max = tables.shape
        ps = k.shape[1]
        kc = jnp.take(k, tables.reshape(-1), axis=0).reshape(
            (b, n_max * ps) + k.shape[2:])
        vc = jnp.take(v, tables.reshape(-1), axis=0).reshape(
            (b, n_max * ps) + v.shape[2:])
        out = ops.flash_attention_decode_paged(q, k, v, tables,
                                               lengths)
        ref = ops.decode_attention_reference(q, kc, vc, lengths)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_stale_pages_beyond_length_ignored(self, mode):
        """Page-reuse safety: a table may still name pages past the
        sequence's live frontier (reclaimed, or the scratch page);
        their contents must get no probability mass."""
        q, k, v, tables, lengths = self._pool()
        ps = k.shape[1]
        lengths = jnp.minimum(lengths, ps + 1)   # <= 2 live pages
        dirty = np.asarray(tables)[:, 2:].reshape(-1)   # dead entries
        k_dirty = k.at[dirty].set(100.0).at[0].set(100.0)
        v_dirty = v.at[dirty].set(-100.0).at[0].set(-100.0)
        out = ops.flash_attention_decode_paged(q, k_dirty, v_dirty,
                                               tables, lengths)
        ref = ops.decode_attention_paged_reference(q, k, v, tables,
                                                   lengths)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_int8_kv(self, mode):
        from chainermn_tpu.precision import quantize_kv
        q, k, v, tables, lengths = self._pool()
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ref_f32 = ops.decode_attention_paged_reference(
            q, k, v, tables, lengths)
        ref_i8 = ops.decode_attention_paged_reference(
            q, kq, vq, tables, lengths, k_scale=ks, v_scale=vs)
        out = ops.flash_attention_decode_paged(
            q, kq, vq, tables, lengths, k_scale=ks, v_scale=vs)
        # kernel matches its own int8 oracle tightly...
        np.testing.assert_allclose(out, ref_i8, atol=2e-5, rtol=2e-5)
        # ...and the f32 answer within the documented 5e-2
        np.testing.assert_allclose(out, ref_f32, atol=5e-2,
                                   rtol=5e-2)

    def test_scale_args_must_pair(self, mode):
        from chainermn_tpu.precision import quantize_kv
        q, k, v, tables, lengths = self._pool()
        kq, ks = quantize_kv(k)
        with pytest.raises(ValueError, match='BOTH'):
            ops.flash_attention_decode_paged(q, kq, v, tables,
                                             lengths, k_scale=ks)

    def test_jaxpr_one_pool_read_no_full_materialization(self):
        """The paged twin of the decode jaxpr pin: each pool operand
        is consumed ONCE at the top level (one streamed pass over the
        table-named pages) and no f32 score/probability row spanning
        the whole table extent is ever materialized."""
        b, n_pages, ps, n_max, h, d = 2, 16, 8, 4, 2, 16
        s_virt = n_max * ps

        def step(q, k, v, tables, lengths):
            return ops.flash_attention_decode_paged(q, k, v, tables,
                                                    lengths)

        jaxpr = jax.make_jaxpr(step)(
            jnp.zeros((b, h, d)), jnp.zeros((n_pages, ps, h, d)),
            jnp.zeros((n_pages, ps, h, d)),
            jnp.zeros((b, n_max), jnp.int32),
            jnp.zeros((b,), jnp.int32))
        _, k_var, v_var, _, _ = jaxpr.jaxpr.invars
        for var in (k_var, v_var):
            readers = [e for e in jaxpr.jaxpr.eqns
                       if var in e.invars]
            assert len(readers) == 1, (
                'pool operand consumed %d times' % len(readers))

        def walk(jx):
            for e in jx.eqns:
                for ov in e.outvars:
                    shape = getattr(ov.aval, 'shape', ())
                    dtype = getattr(ov.aval, 'dtype', None)
                    if (len(shape) >= 2 and shape[-1] == s_virt
                            and str(dtype) == 'float32'):
                        raise AssertionError(
                            'full-extent f32 row materialized: '
                            '%s %r' % (e.primitive, shape))
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

        walk(jaxpr.jaxpr)


def _paged_oracle(q, k, v, tables, lengths, window=None,
                  head_major=False, k_scale=None, v_scale=None):
    """Dense numpy oracle of the paged decode call with every static
    argument: each row's live positions gathered through its table (a
    ring where there is a window), plain softmax in float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    if k_scale is not None:
        k = k * np.asarray(k_scale, np.float64)[..., None]
        v = v * np.asarray(v_scale, np.float64)[..., None]
    if head_major:                        # -> (P, ps, Hkv, D)
        k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)
    ps, group = k.shape[1], q.shape[1] // k.shape[2]
    tables, out = np.asarray(tables), []
    for b, length in enumerate(np.asarray(lengths)):
        pos = np.arange(0 if window is None else max(length - window, 0),
                        length)
        column = pos // ps
        if window is not None:
            column = column % tables.shape[1]
        pages = tables[b, column]
        kk = np.repeat(k[pages, pos % ps], group, axis=1)   # (n, H, D)
        vv = np.repeat(v[pages, pos % ps], group, axis=1)
        s = np.einsum('hd,khd->hk', q[b], kk) * q.shape[-1] ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append(np.einsum('hk,khd->hd', p / p.sum(-1, keepdims=True),
                             vv))
    return np.stack(out)


# layout -> (pool's page shape after the pool axis, group, window,
# table width, lengths).  Page size 4 (8 in the head-major layouts: a
# float32 sublane tile, below which that layout is one page a step).
# The lengths hit each edge at every pages-a-step the rule can return:
# a row of length 1; a length ending mid-page and mid-step; a row whose
# steps are all live; a table whose width no step count divides; and,
# in the ring, rows whose first live column (2, 5, 7, 8, 9, 10) puts
# the wrap inside a step of 2, of 4 and of 8 pages.
_PAGED_LAYOUTS = {
    'page_major': ((4, 2, 16), 1, None, 11, [1, 11, 44, 16, 21, 32]),
    'page_major_int8': ((4, 2, 16), 1, None, 11, [1, 11, 44, 16, 21, 32]),
    'head_major_group': ((2, 8, 16), 4, None, 11, [1, 19, 88, 32, 41, 64]),
    'head_major_ring': ((2, 8, 16), 4, 80, 11,
                        [1, 80, 99, 123, 139, 147, 154, 163]),
}


@pytest.mark.parametrize('pages', [1, 2, 4, 8])
@pytest.mark.parametrize('layout', sorted(_PAGED_LAYOUTS))
def test_paged_decode_pages_a_step(mode, monkeypatch, layout, pages):
    """The paged decode kernel at every number of pages a grid step,
    forced through the rule's own input (what a step may fetch), in
    each layout: rows of every edge (above), two rows sharing a page,
    and dirty pages past every live prefix."""
    fa = _fa
    page, group, window, n_max, lengths = _PAGED_LAYOUTS[layout]
    head_major = layout.startswith('head_major')
    int8 = layout.endswith('int8')
    ps = page[1] if head_major else page[0]
    b, n_pages = len(lengths), 1 + len(lengths) * n_max
    rng = np.random.RandomState(3)
    q = _rand((b, (page[0] if head_major else page[1]) * group,
               page[2]), 40)
    k = _rand((n_pages,) + page, 41)
    v = _rand((n_pages,) + page, 42)
    tables = 1 + rng.permutation(n_pages - 1).reshape(b, n_max)
    if window is None:
        tables[1, :2] = tables[2, :2]     # a shared two-page prefix
    else:
        tables[3] = tables[4]             # two rows on one ring
    scales = {}
    if int8:
        from chainermn_tpu.precision import quantize_kv
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        scales = dict(k_scale=ks, v_scale=vs)
    # dirty what no row may see: the scratch page and every page no
    # live position of any row lies in
    live = set()
    for row, length in zip(tables, lengths):
        first = 0 if window is None else max(length - window, 0) // ps
        live |= {row[j % n_max] for j in range(first,
                                               (length - 1) // ps + 1)}
    dead = np.asarray(sorted(set(range(n_pages)) - live))
    k_dirty = k.at[dead].set(jnp.asarray(100, k.dtype))
    v_dirty = v.at[dead].set(jnp.asarray(-100, v.dtype))
    # the rule's input: a step may fetch this many pages' K + V
    monkeypatch.setattr(fa, '_PAGED_STEP_BYTES', pages * (
        2 * fa._vmem_bytes(page, k.dtype)
        + (2 * fa._vmem_bytes(page[:-1] + (1,), jnp.float32)
           if int8 else 0)))
    assert fa._paged_pages_per_step(page, k.dtype, n_max, int8,
                                    head_major) == pages
    assert n_max % pages or pages == 1
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    out = ops.flash_attention_decode_paged(
        q, k_dirty, v_dirty, tables, lengths, group=group, window=window,
        head_major=head_major, **scales)
    want = _paged_oracle(q, k, v, tables, lengths, window, head_major,
                         **scales)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    if not head_major:
        np.testing.assert_allclose(
            out, ops.decode_attention_paged_reference(
                q, k, v, tables, lengths, **scales),
            atol=2e-5, rtol=2e-5)


# (page shape, dtype, table width, int8, head-major) -> pages a step
_PAGED_RULE = {
    'gpt2m_cell': (((16, 16, 128), jnp.bfloat16, 64, False, False), 8),
    'trinity_full': (((4, 64, 128), jnp.bfloat16, 64, False, True), 8),
    'trinity_ring': (((4, 64, 128), jnp.bfloat16, 33, False, True), 8),
    # 30 K/V heads of 128: a page of 64 is 983 KB of K + V, one a step;
    # the ``olmo-hybrid-7b`` cell's pages of 32 go two a step
    'olmo_page64': (((30, 64, 128), jnp.bfloat16, 64, False, True), 1),
    'olmo_cell_page32': (((30, 32, 128), jnp.bfloat16, 128, False, True),
                         2),
    'one_page_wide_table': (((16, 16, 128), jnp.bfloat16, 1, False,
                             False), 1),
    'table_of_three': (((16, 16, 128), jnp.bfloat16, 3, False, False), 2),
    'page_of_1mb': (((256, 16, 128), jnp.bfloat16, 64, False, False), 1),
    'page_of_256kb': (((64, 16, 128), jnp.bfloat16, 64, False, False), 2),
    'int8_scales_ride': (((16, 16, 128), jnp.int8, 64, True, False), 2),
    'tp_shard_of_4_heads': (((16, 4, 128), jnp.bfloat16, 64, False,
                             False), 8),
    'head_major_off_the_sublanes': (((4, 8, 128), jnp.bfloat16, 64,
                                     False, True), 1),
}


@pytest.mark.parametrize('case', sorted(_PAGED_RULE))
def test_paged_pages_per_step_rule(case):
    """Pages a grid step are a function of the shapes: a power of two,
    at most the table's width, a step's K + V inside what a step may
    fetch and its scratch and working set inside the VMEM the kernel
    asks for; 1 where a page is that large already."""
    fa = _fa
    (page, dtype, n_max, int8, head_major), want = _PAGED_RULE[case]
    pages = fa._paged_pages_per_step(page, dtype, n_max, int8,
                                     head_major)
    assert pages == want
    assert pages & (pages - 1) == 0 and 1 <= pages <= n_max
    fetched, held = fa._paged_step_vmem(pages, page, dtype, int8,
                                        head_major)
    assert held <= fa._VMEM_LIMIT
    assert pages == 1 or fetched <= fa._PAGED_STEP_BYTES


def test_paged_pages_per_step_rule_holds_the_vmem_bound(monkeypatch):
    """With no bound on what a step fetches, the VMEM the kernel asks
    for is what stops the doubling."""
    fa = _fa
    monkeypatch.setattr(fa, '_PAGED_STEP_BYTES', 1 << 40)
    page = (16, 16, 128)
    pages = fa._paged_pages_per_step(page, jnp.bfloat16, 4096)
    held = lambda n: fa._paged_step_vmem(   # noqa: E731
        n, page, jnp.bfloat16, False, False)[1]
    assert 1 < pages < 4096
    assert held(pages) <= fa._VMEM_LIMIT < held(2 * pages)


def test_decode_paged_grid_counts_pages_and_steps():
    """What the engine hangs on its ``serve_decode`` span: the pages
    the kernel's copies fetch (a row's live pages, the window's in a
    ring, one for a row of length 0 or 1) and the steps of its grid:
    each row's live steps, none dead."""
    fa = _fa
    page = (16, 16, 128)
    read, steps = fa.decode_paged_grid([1, 16, 17, 230, 1024], page,
                                       jnp.bfloat16, 64)
    assert fa._paged_pages_per_step(page, jnp.bfloat16, 64) == 8
    assert read == 1 + 1 + 2 + 15 + 64
    assert steps == 1 + 1 + 1 + 2 + 8     # live steps only
    # a ring of 33 pages of 64 under a window of 2,048
    ring = (4, 64, 128)
    read, steps = fa.decode_paged_grid(
        [1, 2048, 2049, 3000], ring, jnp.bfloat16, 33, window=2048,
        head_major=True)
    assert read == 1 + 32 + 33 + (2999 // 64 - 952 // 64 + 1)
    assert steps == 1 + 4 + 5 + 5


@pytest.mark.parametrize('pages', [1, 2, 4, 8])
def test_paged_decode_over_a_latent_leaf(mode, monkeypatch, pages):
    """ONE leaf whose rows are keys and values at once (absorbed latent
    attention): scores over all 256 lanes of a row, values its first
    128, all 4 query heads on the one K/V "head", at every number of
    pages a grid step; dirty pages past every live prefix (the kernel
    zeroes its own slots: a dead page's 0 x garbage must stay 0)."""
    fa = _fa
    page, n_max = (1, 8, 256), 11
    lengths = [1, 19, 88, 32, 41, 64]
    b, n_pages = len(lengths), 1 + len(lengths) * n_max
    rng = np.random.RandomState(5)
    q = _rand((b, 4, 256), 50)
    pool = _rand((n_pages,) + page, 51)
    tables = 1 + rng.permutation(n_pages - 1).reshape(b, n_max)
    tables[1, :2] = tables[2, :2]         # a shared two-page prefix
    live = set()
    for row, length in zip(tables, lengths):
        live |= {row[j] for j in range((length - 1) // 8 + 1)}
    dead = np.asarray(sorted(set(range(n_pages)) - live))
    dirty = pool.at[dead].set(jnp.asarray(100.0, pool.dtype))
    monkeypatch.setattr(fa, '_PAGED_STEP_BYTES',
                        pages * fa._vmem_bytes(page, pool.dtype))
    assert fa._paged_pages_per_step(page, pool.dtype, n_max, False, True,
                                    shared=True) == pages
    out = ops.flash_attention_decode_paged(
        q, dirty, None, jnp.asarray(tables), jnp.asarray(lengths),
        group=4, head_major=True, value_lanes=128)
    assert out.shape == (b, 4, 128)
    want = _paged_oracle(q, pool, pool, tables, lengths,
                         head_major=True)[..., :128]
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    # the step rule counts the one leaf once: twice the pages of a
    # K + V pool of the same page
    assert fa._paged_step_vmem(4, page, pool.dtype, False, True,
                               shared=True)[0] * 2 \
        == fa._paged_step_vmem(4, page, pool.dtype, False, True)[0]


def test_latent_leaf_at_the_cells_shape_goes_eight_pages_a_step():
    """``xing4-serve-closed48-long``: pages of 64 rows of 640 lanes in
    bfloat16 are 81,920 bytes; eight are 655 KB, sixteen would pass the
    step's 1 MiB."""
    fa = _fa
    page = (1, 64, 640)
    assert fa._vmem_bytes(page, jnp.bfloat16) == 64 * 640 * 2
    assert fa._paged_pages_per_step(page, jnp.bfloat16, 120, False, True,
                                    shared=True) == 8
    read, steps = fa.decode_paged_grid(
        [1, 64, 65, 4000, 7680], page, jnp.bfloat16, 120,
        head_major=True, shared=True)
    assert read == 1 + 1 + 2 + 63 + 120
    assert steps == 1 + 1 + 1 + 8 + 15


@pytest.mark.parametrize('bad', [
    dict(v='pool', value_lanes=128), dict(v=None, value_lanes=None),
    dict(v=None, value_lanes=100), dict(v=None, value_lanes=512),
    dict(v=None, value_lanes=128, head_major=False)])
def test_latent_decode_refuses_what_it_cannot_read(bad):
    pool = jnp.zeros((3, 1, 8, 256))
    q = jnp.zeros((2, 4, 256))
    kw = dict(dict(group=4, head_major=True), **bad)
    v = pool if kw.pop('v') == 'pool' else None
    with pytest.raises(ValueError):
        ops.flash_attention_decode_paged(
            q, pool, v, jnp.zeros((2, 2), jnp.int32),
            jnp.ones((2,), jnp.int32), **kw)


def test_append_into_one_pool(mode):
    """``paged_kv_append`` with ONE pool: the token's row lands at
    ``[page, :, offset]`` and nothing else of the leaf moves."""
    pool = _rand((5, 1, 8, 256), 60)
    new = _rand((3, 1, 256), 61)
    pages, offsets = jnp.asarray([2, 4, 1]), jnp.asarray([0, 7, 3])
    got, none = ops.paged_kv_append(pool, None, new, None, pages, offsets)
    assert none is None
    want = np.array(pool)
    for i, (page, at) in enumerate(zip([2, 4, 1], [0, 7, 3])):
        want[page, :, at] = np.asarray(new[i])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize('t, dtype, atol', [
    (200, jnp.float32, 2e-5), (130, jnp.bfloat16, 2e-2),
    (16, jnp.float32, 2e-5)])
def test_flash_forward_with_a_value_width_of_its_own(mode, t, dtype, atol):
    """Keys of 192 and values of 128 (expanded latent attention): the
    forward kernel's output and accumulator take the value width; the
    tiles are still ``_flash_blocks``' (of the wider of the two)."""
    fa = _fa
    q = _rand((2, t, 4, 192), 70, dtype)
    k = _rand((2, t, 4, 192), 71, dtype)
    v = _rand((2, t, 4, 128), 72, dtype)
    out = ops.flash_attention(q, k, v, causal=True, scale=0.11)
    assert out.shape == (2, t, 4, 128) and out.dtype == dtype
    f = lambda x: np.asarray(x, np.float64)             # noqa: E731
    s = np.einsum('bqhd,bkhd->bhqk', f(q), f(k)) * 0.11
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum('bhqk,bkhd->bqhd', p / p.sum(-1, keepdims=True),
                     f(v))
    np.testing.assert_allclose(f(out), want, atol=atol)
    # one width as ever: the same tiles as before the second existed
    assert fa._flash_blocks(1024, 1024, 64, jnp.bfloat16) == (1024, 1024)
    assert fa._flash_vmem_bytes('fwd', 1024, 1024, 64, 2) \
        == fa._flash_vmem_bytes('fwd', 1024, 1024, 128, 2)
    assert fa._flash_vmem_bytes('fwd', 512, 512, 192, 2) \
        == fa._flash_vmem_bytes('fwd', 512, 512, 256, 2)


class TestChunkAttention:
    """Chunked prefill's attention: a C-token chunk attends causally
    within itself AND to ``ctx_len`` banked context tokens, merged
    exactly via logsumexps -- oracle parity, the rows-of-full-causal
    pin, the bitwise ctx=0 degeneration, and int8 context pages."""

    def _operands(self, b=2, c=16, s_ctx=24, h=2, d=16):
        q = _rand((b, c, h, d), 30)
        k_new = _rand((b, c, h, d), 31)
        v_new = _rand((b, c, h, d), 32)
        k_ctx = _rand((b, s_ctx, h, d), 33)
        v_ctx = _rand((b, s_ctx, h, d), 34)
        ctx_len = jnp.asarray([s_ctx, s_ctx // 2 + 1], jnp.int32)[:b]
        return q, k_new, v_new, k_ctx, v_ctx, ctx_len

    def test_matches_reference(self, mode):
        q, k_new, v_new, k_ctx, v_ctx, ctx_len = self._operands()
        out = ops.flash_attention_chunk(q, k_new, v_new, k_ctx,
                                        v_ctx, ctx_len,
                                        block_q=8, block_k=8)
        ref = ops.chunk_attention_reference(q, k_new, v_new, k_ctx,
                                            v_ctx, ctx_len)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_matches_full_causal_rows(self, mode):
        """The strong pin: chunk attention over (banked ctx, chunk)
        equals rows [ctx_len:ctx_len+C] of FULL causal attention on
        the concatenated sequence -- chunking is a schedule, not an
        approximation."""
        b, c, s_ctx, h, d = 1, 8, 16, 2, 8
        q_full = _rand((b, s_ctx + c, h, d), 40)
        k_full = _rand((b, s_ctx + c, h, d), 41)
        v_full = _rand((b, s_ctx + c, h, d), 42)
        full = ops.mha_reference(q_full, k_full, v_full, causal=True)
        out = ops.flash_attention_chunk(
            q_full[:, s_ctx:], k_full[:, s_ctx:], v_full[:, s_ctx:],
            k_full[:, :s_ctx], v_full[:, :s_ctx],
            jnp.full((b,), s_ctx, jnp.int32), block_q=8, block_k=8)
        np.testing.assert_allclose(out, full[:, s_ctx:], atol=2e-5,
                                   rtol=2e-5)

    def test_ctx_zero_bitwise_equals_causal(self, mode):
        """The first chunk of a prompt (no banked context yet) must
        degenerate to plain causal attention BITWISE: the merge
        weight of an all-masked context half is exactly 0.0."""
        q, k_new, v_new, k_ctx, v_ctx, _ = self._operands()
        ctx0 = jnp.zeros((q.shape[0],), jnp.int32)
        out = ops.flash_attention_chunk(q, k_new, v_new, k_ctx,
                                        v_ctx, ctx0,
                                        block_q=8, block_k=8)
        base = ops.flash_attention(q, k_new, v_new, causal=True,
                                   block_q=8, block_k=8)
        assert np.array_equal(np.asarray(out), np.asarray(base))

    def test_int8_ctx(self, mode):
        from chainermn_tpu.precision import quantize_kv
        q, k_new, v_new, k_ctx, v_ctx, ctx_len = self._operands()
        kq, ks = quantize_kv(k_ctx)
        vq, vs = quantize_kv(v_ctx)
        ref_f32 = ops.chunk_attention_reference(
            q, k_new, v_new, k_ctx, v_ctx, ctx_len)
        ref_i8 = ops.chunk_attention_reference(
            q, k_new, v_new, kq, vq, ctx_len, k_scale=ks, v_scale=vs)
        out = ops.flash_attention_chunk(
            q, k_new, v_new, kq, vq, ctx_len, k_scale=ks,
            v_scale=vs, block_q=8, block_k=8)
        np.testing.assert_allclose(out, ref_i8, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(out, ref_f32, atol=5e-2,
                                   rtol=5e-2)

    def test_scale_args_must_pair(self, mode):
        from chainermn_tpu.precision import quantize_kv
        q, k_new, v_new, k_ctx, v_ctx, ctx_len = self._operands()
        kq, ks = quantize_kv(k_ctx)
        with pytest.raises(ValueError, match='BOTH'):
            ops.flash_attention_chunk(q, k_new, v_new, kq, v_ctx,
                                      ctx_len, k_scale=ks)


class TestCrossEntropy:
    def test_matches_reference(self, mode):
        logits = _rand((20, 33), 0)
        labels = jnp.arange(20) % 33
        loss = ops.softmax_cross_entropy(logits, labels)
        ref = ops.softmax_cross_entropy_reference(logits, labels)
        np.testing.assert_allclose(loss, ref, atol=1e-5, rtol=1e-5)

    def test_gradients(self, mode):
        logits = _rand((8, 16), 1)
        labels = jnp.arange(8) % 16

        def f(l):
            return jnp.mean(ops.softmax_cross_entropy(l, labels))

        def f_ref(l):
            return jnp.mean(
                ops.softmax_cross_entropy_reference(l, labels))

        np.testing.assert_allclose(
            jax.grad(f)(logits), jax.grad(f_ref)(logits),
            atol=1e-5, rtol=1e-5)


#: ``x``'s shape and dtype (``gamma`` and ``beta`` are float32), then
#: the forward's and the gradients' tolerance.  The two small float32
#: cases are the ones these tests always had, at ``atol = rtol = tol``;
#: the others (``scaled``) compare against the largest entry, ``atol =
#: tol * max|want|``: a column sum over a thousand rows carries the
#: rounding of its largest terms, and bfloat16 rounds a float32 result
#: once on each side.  At 1,024 columns a tile is 512 rows and at 2,560
#: it is 192 (float32: 200), so 520, 1,031 and 200 rows end in a ragged
#: tile and 5 or 96 are one tile that is no multiple of the sublane
#: packing
_LN_CASES = [
    ((3, 7, 32), jnp.float32, 1e-5, 1e-4, False),
    ((5, 16), jnp.float32, 1e-5, 1e-4, False),
    ((5, 1024), jnp.float32, 1e-4, 1e-4, True),
    ((520, 1024), jnp.bfloat16, 2.0 ** -7, 2.0 ** -7, True),
    ((1031, 1024), jnp.float32, 1e-4, 1e-4, True),
    ((96, 2560), jnp.bfloat16, 2.0 ** -7, 2.0 ** -7, True),
    ((200, 2560), jnp.bfloat16, 2.0 ** -7, 2.0 ** -7, True),
]
_ln = importlib.import_module('chainermn_tpu.ops.layer_norm')


def _ln_operands(shape, dtype, key):
    d = shape[-1]
    return (_rand(shape, key).astype(dtype),
            1.0 + 0.1 * _rand((d,), key + 1), 0.1 * _rand((d,), key + 2))


def _ln_close(got, want, tol, scaled):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    np.testing.assert_allclose(
        got, want, rtol=tol,
        atol=tol * np.abs(want).max() if scaled else tol)


@pytest.mark.parametrize('shape,dtype,fwd_tol,grad_tol,scaled', _LN_CASES)
class TestLayerNorm:
    def test_matches_reference(self, mode, shape, dtype, fwd_tol,
                               grad_tol, scaled):
        x, gamma, beta = _ln_operands(shape, dtype, 2)
        out = ops.layer_norm(x, gamma, beta)
        assert out.dtype == x.dtype and out.shape == x.shape
        _ln_close(out, ops.layer_norm_reference(x, gamma, beta),
                  fwd_tol, scaled)

    def test_gradients(self, mode, shape, dtype, fwd_tol, grad_tol,
                       scaled):
        x, gamma, beta = _ln_operands(shape, dtype, 5)

        def loss(ln):
            return lambda x, g, b: jnp.sum(
                ln(x, g, b).astype(jnp.float32) ** 2)

        got = jax.grad(loss(ops.layer_norm), argnums=(0, 1, 2))(
            x, gamma, beta)
        want = jax.grad(loss(ops.layer_norm_reference),
                        argnums=(0, 1, 2))(x, gamma, beta)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            _ln_close(a, b, grad_tol, scaled)


@pytest.mark.parametrize('rows,d,dtype,tile', [
    (8192, 1024, jnp.bfloat16, 512),     # gpt2m-train-1k: 16 grid steps
    (32, 1024, jnp.bfloat16, 32),        # decode calls: one step
    (96, 2560, jnp.bfloat16, 96),
    (5, 1024, jnp.float32, 5),
    (1031, 1024, jnp.float32, 512),
    (8192, 2560, jnp.bfloat16, 192),
    (4096, 2500, jnp.float32, 200),      # a row pads to 2,560 lanes
    (64, 65536, jnp.bfloat16, 16),       # never under the packing
])
def test_layer_norm_tile_is_sized_in_bytes(rows, d, dtype, tile):
    assert _ln._rows_tile(rows, d, dtype) == tile


class TestFusedSGD:
    def test_matches_optax(self, mode):
        params = {'w': _rand((13, 7), 0), 'b': _rand((7,), 1)}
        opt_ref = optax.sgd(0.1, momentum=0.9)
        state_ref = opt_ref.init(params)
        opt = ops.fused_momentum_sgd(0.1, momentum=0.9)
        state = opt.init(params)
        p_ref, p = params, params
        for step in range(3):
            grads = jax.tree_util.tree_map(
                lambda x: jnp.cos(x + step), params)
            upd_ref, state_ref = opt_ref.update(grads, state_ref, p_ref)
            p_ref = optax.apply_updates(p_ref, upd_ref)
            upd, state = opt.update(grads, state, p)
            p = optax.apply_updates(p, upd)
        for key in params:
            np.testing.assert_allclose(p[key], p_ref[key],
                                       atol=1e-6, rtol=1e-6)

    def test_functional_api(self, mode):
        params = {'w': _rand((9, 5), 2)}
        vel = jax.tree_util.tree_map(jnp.zeros_like, params)
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        new_p, new_v = ops.momentum_sgd(params, grads, vel, lr=0.5,
                                        momentum=0.0)
        np.testing.assert_allclose(new_p['w'], params['w'] - 0.5,
                                   atol=1e-6)
        np.testing.assert_allclose(new_v['w'], 1.0, atol=1e-6)

    def test_bf16_grads_keep_f32_velocity(self, mode):
        """Velocity keeps its own f32 state dtype even with bf16
        params/grads on the kernel path (ADVICE r1: the native path
        used to downcast momentum state to the gradient dtype)."""
        params = {'w': _rand((9, 5), 3).astype(jnp.bfloat16)}
        opt = ops.fused_momentum_sgd(0.1, momentum=0.9)
        state = opt.init(params)
        grads = jax.tree_util.tree_map(
            lambda x: jnp.ones_like(x, jnp.bfloat16), params)
        for _ in range(2):
            upd, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, upd)
        vel = jax.tree_util.tree_leaves(state)
        assert all(v.dtype == jnp.float32 for v in vel
                   if hasattr(v, 'dtype') and v.ndim), state
        assert params['w'].dtype == jnp.bfloat16


# what the benchmark's cells send: (t_q = t_kv, d, dtype, window)
_FLASH_SHAPES = {
    'train_t1024_d64': (1024, 64, jnp.bfloat16, None),
    'prefill_bucket16_d64': (16, 64, jnp.bfloat16, None),
    'prefill_bucket64_d64': (64, 64, jnp.bfloat16, None),
    'prefill_bucket512_d64': (512, 64, jnp.bfloat16, None),
    'prefill_t2048_d128_window2048': (2048, 128, jnp.bfloat16, 2048),
    'prefill_t3072_d128_window2048': (3072, 128, jnp.bfloat16, 2048),
    'prefill_t3072_d128_full': (3072, 128, jnp.bfloat16, None),
    'no_multiple_of_a_tile_t1000': (1000, 64, jnp.bfloat16, None),
    'odd_multiple_of_128_t1664': (1664, 64, jnp.bfloat16, None),
    'under_128_t100': (100, 64, jnp.bfloat16, None),
    'float32_t4096_d128': (4096, 128, jnp.float32, None),
    'narrow_window_t1024': (1024, 64, jnp.bfloat16, 200),
}


@pytest.mark.parametrize('kernel', ['fwd', 'dq', 'dkv'])
@pytest.mark.parametrize('case', sorted(_FLASH_SHAPES))
def test_flash_blocks_rule(case, kernel):
    """The tiles are a function of the shapes: they divide the padded
    length, never exceed it, keep a step's working set inside the VMEM
    the kernels ask for, and a windowed layer's key tile is no wider
    than its window rounded up to the lanes."""
    fa = _fa
    t, d, dtype, window = _FLASH_SHAPES[case]
    if window is not None and kernel != 'fwd':
        pytest.skip('the windowed call has no backward')
    bq, bk = fa._flash_blocks(t, t, d, dtype, window, kernel=kernel)
    padded = fa._padded_len(t)
    assert 0 <= padded - t < max(128, t // 4)
    for block in (bq, bk):
        assert 0 < block <= padded and padded % block == 0
        assert block == padded or block % 128 == 0
    assert fa._flash_vmem_bytes(
        kernel, bq, bk, d, jnp.dtype(dtype).itemsize) <= fa._VMEM_LIMIT
    if window is not None:
        assert bk <= max(128, -(-window // 128) * 128)
    if t >= 1024:
        # the point of the rule: a step that carries work
        assert bq * bk >= 256 * 256


# -- the selective scan (ops/selective_scan.py) ------------------------

def _scan_operands(t, di=128, n=16, seed=0):
    """``x``, ``delta`` in (0, ~1), ``A`` < 0, ``B``, ``C``, ``D``."""
    x, delta, a, b, c, d = (_rand(shape, seed + i) for i, shape in
                            enumerate([(t, di), (t, di), (di, n),
                                       (t, n), (t, n), (di,)]))
    return x, jax.nn.softplus(delta - 2.0), -jnp.exp(a), b, c, d


@pytest.mark.parametrize('t', [1, 7, 32, 33, 150])
def test_selective_scan_is_the_per_token_recurrence(mode, t):
    """One position, under a chunk, a whole chunk, over its boundary
    (32 in the jnp form), and over the kernel's (128)."""
    operands = _scan_operands(t)
    m, state = ops.selective_scan(*operands)
    want_m, want_state = ops.selective_scan_reference(*operands)
    np.testing.assert_allclose(m, want_m, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)


def test_selective_scan_continues_from_a_state(mode):
    operands = _scan_operands(40, seed=3)
    _, mid = ops.selective_scan(*(a[:25] if a.shape[0] == 40 else a
                                  for a in operands))
    m, state = ops.selective_scan(
        *(a[25:] if a.shape[0] == 40 else a for a in operands),
        state0=mid)
    want_m, want_state = ops.selective_scan_reference(*operands)
    np.testing.assert_allclose(m, want_m[25:], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)


def test_selective_scan_step_moves_its_rows_alone(mode):
    di, n = 256, 16
    x, delta, a, b, c, d = _scan_operands(3, di=di, seed=5)
    leaf = _rand(ops.state_shape(6, 1, n, di), 9)
    rows = jnp.asarray([4, 1, 2], jnp.int32)
    m, out = ops.selective_scan_step(leaf, rows, x, delta, a, b, c, d)
    assert out.shape == leaf.shape == (6, 1, n, di)
    for i, row in enumerate([4, 1, 2]):
        want_m, want = ops.selective_scan_reference(
            x[i:i + 1], delta[i:i + 1], a, b[i:i + 1], c[i:i + 1], d,
            state0=leaf[row, 0])
        np.testing.assert_allclose(m[i], want_m[0], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(out[row, 0], want, atol=2e-5,
                                   rtol=2e-5)
    for row in (0, 3, 5):
        np.testing.assert_array_equal(out[row], leaf[row])


def test_convolution_step_adds_its_bias(mode):
    taps, c = 4, 200
    w, bias, x = _rand((taps, c), 0), _rand((c,), 1), _rand((2, c), 2)
    tail = jnp.zeros(ops.tail_shape(3, taps, c, jnp.float32),
                     jnp.float32)
    rows = jnp.asarray([2, 1], jnp.int32)
    plain, _ = ops.causal_conv_step(tail, rows, x, w)
    biased, _ = ops.causal_conv_step(tail, rows, x, w, bias)
    np.testing.assert_allclose(biased, plain + bias, atol=1e-6)


# -- training: the two-width flash backward, the grouped SwiGLU's VJP ---

def _causal_attention(q, k, v):
    """Plain causal softmax attention, ``v`` of its own width."""
    t = q.shape[1]
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize('d,dv,t', [(24, 16, 200), (192, 128, 256),
                                    (16, 24, 72)])
def test_flash_backward_with_a_value_width_of_its_own(mode, d, dv, t):
    """dQ / dK at the key width, dV at the value width, against
    autodiff of a jnp attention: the Mosaic kernels in the interpreter
    and the blockwise fallback."""
    q, k = _rand((2, t, 3, d), 0), _rand((2, t, 3, d), 1)
    v, w = _rand((2, t, 3, dv), 2), _rand((2, t, 3, dv), 3)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * w), (0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
    for a, b, width in zip(got, grads(_causal_attention), (d, d, dv)):
        assert a.shape[-1] == width
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize('d,dv', [(64, 64), (192, 128)])
def test_flash_residual_names_are_inert_without_a_checkpoint(
        mode, monkeypatch, d, dv):
    """``_flash_fwd`` names its residuals for a caller's checkpoint
    policy (``RESIDUAL_NAMES``).  With no checkpoint around the call
    the value, the gradient and the jaxpr's equations but for the five
    ``name`` ones are what the kernel gives with no name at all."""
    from chainermn_tpu.analysis import walker
    q, k = _rand((1, 160, 2, d), 0), _rand((1, 160, 2, d), 1)
    v, w = _rand((1, 160, 2, dv), 2), _rand((1, 160, 2, dv), 3)

    def both():
        fn = jax.value_and_grad(lambda *a: jnp.sum(
            ops.flash_attention(*a, causal=True) * w), (0, 1, 2))
        eqns = [(e.primitive.name, e.params.get('name'),
                 [str(o.aval) for o in e.outvars])
                for e, _ in walker.iter_eqns(jax.make_jaxpr(fn)(q, k, v))]
        return fn(q, k, v), eqns

    named, named_eqns = both()
    assert sorted(name for prim, name, _ in named_eqns
                  if prim == 'name') == sorted(_fa.RESIDUAL_NAMES)
    monkeypatch.setattr(_fa, 'checkpoint_name', lambda x, name: x)
    jax.clear_caches()       # the forward rule's trace is cached
    try:
        plain, plain_eqns = both()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not any(prim == 'name' for prim, _, _ in plain_eqns)
    assert [e for e in named_eqns if e[0] != 'name'] == plain_eqns
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(a, b)
    # and the bytes a caller's policy would keep, at the padded length
    assert sum(_fa.residual_bytes(1, 160, 2, d, dv, jnp.float32
                                  ).values()) == \
        2 * 256 * (2 * d + 2 * dv + 1) * 4


def _grouped_case(sizes, extra, d=32, f=48):
    sizes = jnp.asarray(sizes, jnp.int32)
    n, e = int(sizes.sum()) + extra, sizes.shape[0]
    x, c = _rand((n, d), 0), _rand((n, d), 4)
    weights = [0.2 * _rand(shape, key) for key, shape in
               ((1, (e, d, f)), (2, (e, d, f)), (3, (e, f, d)))]
    # rows past the groups' total take no part: nothing flows into them
    return sizes, x, weights, c.at[n - extra:].set(0.0)


@pytest.mark.parametrize('sizes,extra', [
    ([5, 0, 37, 16, 1, 0, 70, 3], 0),     # uneven, two empty groups
    ([5, 0, 37, 16, 1, 0, 70, 3], 200),   # a share: rows held by nobody
    ([0, 0, 0, 0], 77),                   # no assignment is held
    ([0, 130, 0], 0),                     # one group over several tiles
    ([16, 16, 16, 16], 0)])               # every boundary on a tile's
def test_grouped_swiglu_vjp_against_ragged_dots(mode, sizes, extra):
    sizes, x, weights, c = _grouped_case(sizes, extra)

    def grads(fn):
        return jax.grad(lambda x, *w: jnp.sum(fn(x, *w, sizes) * c),
                        (0, 1, 2, 3))(x, *weights)

    got = grads(lambda *a: ops.grouped_swiglu(*a, tile_m=16))
    for a, b in zip(got, grads(ops.grouped_swiglu_reference)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    # an expert no row chose: exact zeros, and the rows past the total
    empty = np.asarray(sizes) == 0
    for g in got[1:]:
        assert not np.asarray(g)[empty].any()
    if extra:
        assert not np.asarray(got[0])[-extra:].any()


@pytest.mark.parametrize('first', [0, 4, 12])
def test_dropless_experts_over_a_share(mode, first):
    """A layer told which experts it holds: the held experts' part of
    the sum over a token's chosen experts, absent ones adding nothing,
    and its gradient by the rows and the weights."""
    tokens, k, router, held, d, f = 40, 3, 16, 4, 16, 24
    x = _rand((tokens, d), 0)
    experts = {name: 0.3 * _rand(shape, key) for key, (name, shape) in
               enumerate((('w1', (held, d, f)), ('w3', (held, d, f)),
                          ('w2', (held, f, d))), 1)}
    selected = jnp.argsort(_rand((tokens, router), 5), -1)[:, :k]
    gates = jax.nn.softmax(_rand((tokens, k), 6), -1)

    def dense(x, experts):
        out = jnp.zeros_like(x)
        for e in range(held):
            y = (jax.nn.silu(x @ experts['w1'][e]) * (x @ experts['w3'][e])
                 ) @ experts['w2'][e]
            g = jnp.sum(jnp.where(selected == first + e, gates, 0.0), -1)
            out = out + g[:, None] * y
        return out

    def sparse(x, experts):
        return ops.dropless_experts(x, experts, selected, gates,
                                    tile_m=8, first=first)[0]

    np.testing.assert_allclose(sparse(x, experts), dense(x, experts),
                               atol=3e-5, rtol=3e-5)
    _, sizes = ops.dropless_experts(x, experts, selected, gates, tile_m=8,
                                    first=first)
    want = [(np.asarray(selected) == first + e).sum() for e in range(held)]
    assert np.asarray(sizes).tolist() == want

    def loss(fn):
        return jax.grad(lambda x, w: jnp.sum(fn(x, w) ** 2), (0, 1))(
            x, experts)

    for a, b in zip(jax.tree_util.tree_leaves(loss(sparse)),
                    jax.tree_util.tree_leaves(loss(dense))):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
