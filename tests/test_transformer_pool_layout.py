"""The layout of ``TransformerLM``'s paged K/V pool (out of
``tests/test_transformer.py``, a file of its own so that it is a unit
of ``--dist loadfile``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import TransformerLM


class TestPagedPoolLayout:
    """The float paged pool's layout (PR 44): head-major, ``pack``
    heads side by side in a 128-lane row, ``pack`` from the shapes
    alone; what every paged entry point reads and writes through it
    equals the slot cache's path, which it does not touch."""

    @pytest.mark.parametrize('d_head,heads,tp,page', [
        (64, 16, 1, (8, 16, 128)),      # gpt2-medium: two a row
        (32, 16, 1, (4, 16, 128)),      # four a row
        (128, 16, 1, (16, 16, 128)),    # a head fills the lanes
        (96, 16, 1, (16, 16, 128)),     # 96 does not divide 128: padded
        (64, 3, 1, (3, 16, 128)),       # an odd number of heads: padded
        (64, 16, 2, (4, 16, 128)),      # tp 2 of 16 heads: 8 local
        (64, 16, 16, (1, 16, 128)),     # one local head: nothing to pair
    ])
    def test_pack_comes_from_the_shapes(self, d_head, heads, tp, page):
        from chainermn_tpu.models import init_paged_kv_cache
        model = TransformerLM(vocab_size=64, d_model=d_head * heads,
                              n_heads=heads, n_layers=1, d_ff=64,
                              max_len=64, dtype=jnp.bfloat16)
        cache = jax.eval_shape(
            lambda: init_paged_kv_cache(model, 5, 16, tp=tp))
        # the layout is in the cache itself: an entry with no leaf
        assert set(cache) == {'k', 'v', 'head_major'}
        assert len(jax.tree_util.tree_leaves(cache)) == 2
        assert cache['k'][0].shape == cache['v'][0].shape == (5,) + page
        # the GLOBAL pool that shards ``tp`` ways: ``tp`` such pools
        # side by side on the head axis, the rows laid out for a shard
        if heads % tp == 0:
            whole = jax.eval_shape(
                lambda: init_paged_kv_cache(model, 5, 16, shards=tp))
            assert whole['k'][0].shape == (5, tp * page[0]) + page[1:]
        # an int8 pool: page-major, every head padded to the lanes
        cache = jax.eval_shape(
            lambda: init_paged_kv_cache(model, 5, 16, tp=tp,
                                        int8_kv=True))
        assert 'head_major' not in cache
        assert cache['k'][0].shape == (5, 16, heads // tp, 128)
        assert cache['k_scale'][0].shape == (5, 16, heads // tp)

    @pytest.mark.parametrize('dtype,page_size,head_major', [
        (jnp.bfloat16, 16, True), (jnp.bfloat16, 32, True),
        (jnp.bfloat16, 8, False), (jnp.bfloat16, 24, False),
        (jnp.float32, 8, True), (jnp.float32, 4, False)])
    def test_a_page_off_the_sublane_tile_stays_page_major(
            self, dtype, page_size, head_major):
        """The kernel's head-major branch carries ONE page a grid step
        where the page is not whole sublane tiles of the pool's dtype
        (16 rows of bfloat16, 8 of float32) and its page-major branch
        eight: such a float pool keeps the page-major layout, and
        ``decode_paged_grid`` counts that branch's steps."""
        import importlib

        from chainermn_tpu import ops
        from chainermn_tpu.models import init_paged_kv_cache, kv_cache_specs
        fa = importlib.import_module('chainermn_tpu.ops.flash_attention')
        model = TransformerLM(vocab_size=64, d_model=1024, n_heads=16,
                              n_layers=2, d_ff=64, max_len=1024,
                              dtype=dtype)
        cache = jax.eval_shape(
            lambda: init_paged_kv_cache(model, 9, page_size))
        assert ('head_major' in cache) == head_major
        assert cache['k'][0].shape == (
            (9, 8, page_size, 128) if head_major
            else (9, page_size, 16, 128))
        assert model.kv_lanes(cache) == ((128, 128) if head_major
                                         else (64, 128))
        assert kv_cache_specs(cache, 'm')['k'][0] == (
            P(None, 'm', None, None) if head_major
            else P(None, None, 'm', None))
        n_max = 1024 // page_size
        page = cache['k'][0].shape[1:]
        assert fa._paged_pages_per_step(
            page, dtype, n_max, head_major=head_major) > 1
        lengths = [1, 100, 1000]
        assert model.decode_paged_grid(cache, lengths, n_max) == tuple(
            2 * n for n in ops.decode_paged_grid(
                lengths, page, dtype, n_max, head_major=head_major))

    def test_pool_bytes_halve_at_d_head_64(self):
        """gpt2-medium's pool: 48 leaves of bf16[2049,8,16,128], half
        the bytes of the lane-padded (2049, 16, 16, 128); the slot
        cache keeps its layout."""
        from chainermn_tpu.models import (init_kv_cache,
                                          init_paged_kv_cache)
        model = TransformerLM(vocab_size=64, d_model=1024, n_heads=16,
                              n_layers=24, d_ff=64, max_len=1024,
                              dtype=jnp.bfloat16)
        cache = jax.eval_shape(
            lambda: init_paged_kv_cache(model, 2049, 16))
        leaves = jax.tree_util.tree_leaves(cache)
        assert {(leaf.shape, leaf.dtype.name) for leaf in leaves} == {
            ((2049, 8, 16, 128), 'bfloat16')}
        nbytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
        assert nbytes == 48 * 2049 * 16 * 16 * 64 * 2
        assert 2 * nbytes == 48 * 2049 * 16 * 16 * 128 * 2
        assert model.kv_lanes(cache) == (128, 128)
        int8 = jax.eval_shape(
            lambda: init_paged_kv_cache(model, 2049, 16, int8_kv=True))
        assert model.kv_lanes(int8) == (64, 128)
        slot = jax.eval_shape(lambda: init_kv_cache(model, 2, 32))
        assert slot['k'][0].shape == (2, 32, 16, 128)

    @pytest.mark.parametrize('d_model,heads,pack,ps', [
        (64, 2, 1, 8), (128, 2, 2, 8), (128, 4, 4, 8), (192, 2, 1, 8),
        (128, 2, None, 4), (64, 2, None, 4)])
    def test_every_paged_entry_point_equals_the_slot_oracle(
            self, d_model, heads, pack, ps):
        """Three rows through both caches: a prompt banked whole, one
        in two chunks (``pos0 > 0``, the first chunk ending mid-page),
        decode steps across a page boundary, then a verify window: the
        paged logits are the slot cache's.  ``pack`` None: pages of 4
        float32 positions, a float pool that stays page-major."""
        from chainermn_tpu import models as M
        model = TransformerLM(vocab_size=97, d_model=d_model,
                              n_heads=heads, n_layers=2, d_ff=128,
                              max_len=64, dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))['params']
        n_max = 32 // ps
        cache = M.init_paged_kv_cache(model, 1 + 3 * n_max, ps)
        assert cache['k'][0].shape == (
            (1 + 3 * n_max, ps, heads, 128) if pack is None
            else (1 + 3 * n_max, heads // pack, ps, 128))
        slot = M.init_kv_cache(model, 3, 32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (3, 22), 0, 97)
        tables = jnp.asarray(1 + np.random.RandomState(0).permutation(
            3 * n_max).reshape(3, n_max), jnp.int32)

        def close(a, b):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)

        for i in range(3):
            want, slot = M.prefill(
                model, params, slot,
                jnp.pad(toks[i:i + 1, :11], ((0, 0), (0, 5))), 11, i)
            if i == 0:
                got, cache = M.prefill_paged(
                    model, params, cache,
                    jnp.pad(toks[:1, :11], ((0, 0), (0, 5))), 11,
                    tables[0], 0)
            else:
                # 7 + 4: the first chunk ends mid-page
                _, cache = M.prefill_paged(
                    model, params, cache,
                    jnp.pad(toks[i:i + 1, :7], ((0, 0), (0, 1))), 7,
                    tables[i], 0)
                got, cache = M.prefill_paged(
                    model, params, cache,
                    jnp.pad(toks[i:i + 1, 7:11], ((0, 0), (0, 4))), 4,
                    tables[i], 7)
            close(got, want)
        for p in range(11, 18):
            at = jnp.full((3,), p, jnp.int32)
            want, slot = M.decode_step(model, params, slot, toks[:, p],
                                       at)
            got, cache = M.decode_step_paged(model, params, cache,
                                             toks[:, p], at, tables)
            close(got, want)
        at = jnp.full((3,), 18, jnp.int32)
        want, _ = M.spec_verify(model, params, slot, toks[:, 18:22], at)
        got, _ = M.spec_verify_paged(model, params, cache,
                                     toks[:, 18:22], at, tables)
        close(got, want)

    def test_decode_paged_grid_is_the_kernels_own(self):
        """The engine's counters at the cell's shapes: pages read and
        grid steps are those of the kernel's head-major call on the
        packed page, 16 pages a step; an int8 pool's those of the
        page-major call at its own rule's pages."""
        import importlib

        from chainermn_tpu import ops
        from chainermn_tpu.models import init_paged_kv_cache
        fa = importlib.import_module('chainermn_tpu.ops.flash_attention')
        model = TransformerLM(vocab_size=64, d_model=1024, n_heads=16,
                              n_layers=24, d_ff=64, max_len=1024,
                              dtype=jnp.bfloat16)
        lengths = [1, 16, 17, 255, 256, 257, 700, 1024]
        for int8_kv, page, dtype in (
                (False, (8, 16, 128), jnp.bfloat16),
                (True, (16, 16, 128), jnp.int8)):
            cache = jax.eval_shape(lambda: init_paged_kv_cache(
                model, 2049, 16, int8_kv=int8_kv))
            pages = fa._paged_pages_per_step(page, dtype, 64, int8_kv,
                                             not int8_kv)
            assert int8_kv or pages == 16
            read, steps = ops.decode_paged_grid(
                lengths, page, dtype, 64, quantized=int8_kv,
                head_major=not int8_kv)
            assert model.decode_paged_grid(cache, lengths, 64) == (
                24 * read, 24 * steps)
            assert read == sum(-(-n // 16) for n in lengths)
            assert steps == sum(-(-n // (16 * pages)) for n in lengths)
            # under tp 2 a chip holds half the heads of every page
            half = page[:int8_kv] + (page[int8_kv] // 2,) \
                + page[int8_kv + 1:]
            assert model.decode_paged_grid(
                cache, lengths, 64, tp=2) == tuple(
                    24 * n for n in ops.decode_paged_grid(
                        lengths, half, dtype, 64, quantized=int8_kv,
                        head_major=not int8_kv))
