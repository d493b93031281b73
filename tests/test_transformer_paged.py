"""``TransformerLM``'s paged decode against the slot oracle (out of
``tests/test_transformer.py``, a file of its own so that it is a unit
of ``--dist loadfile``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import TransformerLM


class TestPagedDecode:
    """Paged-KV parity pins (this PR's tentpole): prefill_paged /
    decode_step_paged through a pooled cache addressed by page tables
    must reproduce the full-sequence causal forward -- f32 rtol 1e-5,
    int8-KV 5e-2 -- with non-contiguous tables, across chunked
    prefill, across page REUSE (dirty pages from a previous
    occupant), across a shared-prefix table (two sequences reading
    the same physical pages), and composed with tp=2 shard_map."""

    PS = 8

    #: the pool's layouts: name -> (d_model, n_heads, int8_kv, rtol).
    #: A float pool is head-major with ``pack`` heads a 128-lane row
    #: (1: a head of 8 padded to the lanes; 2: two of 64; 4: four of
    #: 32); an int8 pool stays page-major with its scale leaves.
    LAYOUTS = {'pack1': (32, 4, False, 1e-5),
               'pack2': (128, 2, False, 1e-5),
               'pack4': (128, 4, False, 1e-5),
               'int8': (32, 4, True, 5e-2)}

    @pytest.fixture(params=sorted(LAYOUTS))
    def layout(self, request):
        d_model, n_heads, int8_kv, rtol = self.LAYOUTS[request.param]
        model = self._model(d_model=d_model, n_heads=n_heads)
        packs = {'pack1': 1, 'pack2': 2, 'pack4': 4}
        if not int8_kv:
            leaf = self._cache(model, False, n_pages=2)['k'][0]
            assert leaf.shape == (
                2, n_heads // packs[request.param], self.PS, 128)
        return model, int8_kv, rtol

    def _model(self, dtype=jnp.float32, max_len=64, d_model=32,
               n_heads=4):
        return TransformerLM(vocab_size=64, d_model=d_model,
                             n_heads=n_heads, n_layers=2, d_ff=64,
                             max_len=max_len, dtype=dtype)

    def _cache(self, model, int8_kv, n_pages):
        from chainermn_tpu.models import init_paged_kv_cache
        return init_paged_kv_cache(model, n_pages=n_pages,
                                   page_size=self.PS, int8_kv=int8_kv)

    def _stepwise(self, model, params, cache, toks, t_pre, table,
                  chunk=None, start=0):
        """Prefill ``toks[start:t_pre]`` in ``chunk``-token pieces
        (whole remainder when None) through ``table``, then
        teacher-force the rest via decode_step_paged; returns
        (logits at each position >= t_pre - 1, cache)."""
        from chainermn_tpu.models import (decode_step_paged,
                                          prefill_paged)
        width = chunk or (t_pre - start)
        out = {}
        pos = start
        while pos < t_pre:
            n = min(width, t_pre - pos)
            pad = np.zeros((1, width), np.int32)
            pad[0, :n] = toks[pos:pos + n]
            lg, cache = prefill_paged(
                model, params, cache, jnp.asarray(pad),
                jnp.asarray(n, jnp.int32),
                jnp.asarray(table, jnp.int32),
                jnp.asarray(pos, jnp.int32))
            pos += n
        out[t_pre - 1] = np.asarray(lg)
        for p in range(t_pre, len(toks)):
            lg, cache = decode_step_paged(
                model, params, cache,
                jnp.asarray([toks[p]], jnp.int32),
                jnp.asarray([p], jnp.int32),
                jnp.asarray([table], jnp.int32))
            out[p] = np.asarray(lg[0])
        return out, cache

    def test_matches_full_forward(self, layout):
        model, int8_kv, rtol = layout
        rng = np.random.RandomState(10)
        toks = rng.randint(0, 64, size=20).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([toks]))['params']
        full = np.asarray(model.apply({'params': params},
                                      jnp.asarray([toks])))[0]
        cache = self._cache(model, int8_kv, n_pages=9)
        # deliberately non-contiguous, non-monotone table
        table = np.array([5, 2, 7, 1, 3, 8, 4, 6], np.int32)
        got, _ = self._stepwise(model, params, cache, toks,
                                t_pre=6, table=table)
        for p, lg in got.items():
            np.testing.assert_allclose(lg, full[p], rtol=rtol,
                                       atol=rtol)

    def test_chunked_prefill_identical_logits(self, layout):
        """Chunking is a schedule, not an approximation: prefilling
        in 4-token chunks (every other one ends mid-page) must
        produce the SAME first-token logits and decode trajectory as
        one monolithic prefill (an int8 pool: to its rounding, a later
        chunk reads the earlier ones' K/V dequantized)."""
        model, int8_kv, rtol = layout
        rng = np.random.RandomState(11)
        toks = rng.randint(0, 64, size=18).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([toks]))['params']
        table = np.array([3, 1, 4, 2, 5], np.int32)
        mono, _ = self._stepwise(
            model, params, self._cache(model, int8_kv, 6), toks,
            t_pre=13, table=table)
        chunked, _ = self._stepwise(
            model, params, self._cache(model, int8_kv, 6), toks,
            t_pre=13, table=table, chunk=4)
        tol = rtol if int8_kv else 1e-6
        for p in mono:
            np.testing.assert_allclose(chunked[p], mono[p],
                                       rtol=tol, atol=tol)

    def test_parity_across_page_reuse(self, layout):
        """Reclaim safety: sequence B prefilled through pages A just
        DIRTIED (no zeroing) must reproduce B's fresh-pool logits
        exactly -- reads mask by live length, never by page history."""
        model, int8_kv, _ = layout
        rng = np.random.RandomState(12)
        tok_a = rng.randint(0, 64, size=20).astype(np.int32)
        tok_b = rng.randint(0, 64, size=11).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([tok_a]))['params']
        cache = self._cache(model, int8_kv, n_pages=4)
        table = np.array([2, 1, 3], np.int32)
        _, cache = self._stepwise(model, params, cache, tok_a,
                                  t_pre=7, table=table)
        got_b, _ = self._stepwise(model, params, cache, tok_b,
                                  t_pre=5, table=table)
        fresh = self._cache(model, int8_kv, n_pages=4)
        want_b, _ = self._stepwise(model, params, fresh, tok_b,
                                   t_pre=5, table=table)
        for p in got_b:
            np.testing.assert_allclose(got_b[p], want_b[p],
                                       rtol=1e-6, atol=1e-6)

    def test_shared_prefix_pages_reproduce(self, layout):
        """Prefix sharing numerics: sequence B's table points at the
        pages sequence A banked for their common 2-page prefix; B
        prefills ONLY its suffix (pos0 = 16) into private pages.
        B's logits must match its own full forward -- reading a
        neighbor's physical pages is invisible to the math."""
        model, int8_kv, rtol = layout
        rng = np.random.RandomState(13)
        shared = rng.randint(0, 64, size=16).astype(np.int32)
        tok_a = np.concatenate(
            [shared, rng.randint(0, 64, size=6).astype(np.int32)])
        tok_b = np.concatenate(
            [shared, rng.randint(0, 64, size=8).astype(np.int32)])
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([tok_a]))['params']
        cache = self._cache(model, int8_kv, n_pages=6)
        table_a = np.array([1, 2, 3], np.int32)
        _, cache = self._stepwise(model, params, cache, tok_a,
                                  t_pre=20, table=table_a)
        # B: A's prefix pages 1,2 + a private tail page 4
        table_b = np.array([1, 2, 4], np.int32)
        got_b, _ = self._stepwise(model, params, cache, tok_b,
                                  t_pre=20, table=table_b, start=16)
        full_b = np.asarray(model.apply({'params': params},
                                        jnp.asarray([tok_b])))[0]
        for p, lg in got_b.items():
            np.testing.assert_allclose(lg, full_b[p], rtol=rtol,
                                       atol=rtol)

    def test_copied_tail_page_then_divergent_decode(self, layout):
        """Copy-on-write numerics: B shares A's 12-token prefix, whose
        second page is HALF full.  The engine's page copy (every
        leaf's page ``src`` to ``dst``, the first axis in both
        layouts) gives B its own tail page; B banks its suffix from
        ``pos0 = 12``, mid-page, and both decode on, each matching its
        own full forward: B's writes keep what the copied page held
        before ``pos0`` and never reach A's."""
        model, int8_kv, rtol = layout
        rng = np.random.RandomState(15)
        shared = rng.randint(0, 64, size=12).astype(np.int32)
        tok_a = np.concatenate(
            [shared, rng.randint(0, 64, size=9).astype(np.int32)])
        tok_b = np.concatenate(
            [shared, rng.randint(0, 64, size=10).astype(np.int32)])
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([tok_a]))['params']
        cache = self._cache(model, int8_kv, n_pages=7)
        table_a = np.array([1, 2, 3], np.int32)
        _, cache = self._stepwise(model, params, cache, tok_a,
                                  t_pre=12, table=table_a)
        cache = jax.tree_util.tree_map(
            lambda leaf: leaf.at[5].set(leaf[2]), cache)
        table_b = np.array([1, 5, 6], np.int32)
        got_b, cache = self._stepwise(model, params, cache, tok_b,
                                      t_pre=17, table=table_b,
                                      start=12)
        got_a, _ = self._stepwise(model, params, cache, tok_a,
                                  t_pre=15, table=table_a, start=12)
        for toks, got in ((tok_a, got_a), (tok_b, got_b)):
            full = np.asarray(model.apply({'params': params},
                                          jnp.asarray([toks])))[0]
            for p, lg in got.items():
                np.testing.assert_allclose(lg, full[p], rtol=rtol,
                                           atol=rtol)

    @pytest.mark.slow
    @pytest.mark.parametrize('int8_kv,rtol', [(False, 1e-5),
                                              (True, 5e-2)])
    def test_tp_paged_decode_matches_oracle(self, int8_kv, rtol):
        """The paged x tp composition pin: prefill_paged +
        decode_step_paged under shard_map tp=2 must match the
        unsharded f32 full forward, with int8 pages within the int8
        5e-2 budget (kv_cache_specs shards either pool on its head
        axis: axis 1 of a float leaf, axis 2 of an int8 one)."""
        from chainermn_tpu.models import (
            decode_step_paged, init_paged_kv_cache, kv_cache_specs,
            prefill_paged, tp_param_specs)
        from chainermn_tpu.parallel.meshplan import MeshPlan
        if jax.device_count() < 2:
            pytest.skip('needs 2 devices')
        plan = MeshPlan.create(tp=2)
        model = self._model().clone(tp_axis=plan.model_axis)
        oracle = self._model()
        rng = np.random.RandomState(14)
        toks = rng.randint(0, 64, size=(1, 14)).astype(np.int32)
        params = oracle.init(jax.random.PRNGKey(1),
                             jnp.asarray(toks))['params']
        full = np.asarray(oracle.apply({'params': params},
                                       jnp.asarray(toks)))[0]
        specs = tp_param_specs(params, plan.model_axis)
        cache = init_paged_kv_cache(oracle, n_pages=4,
                                    page_size=self.PS, int8_kv=int8_kv)
        cspecs = kv_cache_specs(cache, plan.model_axis)
        assert cspecs['k'][0] == (P(None, None, plan.model_axis, None)
                                  if int8_kv else
                                  P(None, plan.model_axis, None, None))
        pp = jax.device_put(params, plan.param_shardings(specs))
        cd = jax.device_put(cache, plan.param_shardings(cspecs))
        pre = jax.shard_map(
            lambda p, c, t, n, tab, o: prefill_paged(
                model, p, c, t, n, tab, o),
            mesh=plan.mesh,
            in_specs=(specs, cspecs, P(), P(), P(), P()),
            out_specs=(P(), cspecs), check_vma=False)
        dec = jax.shard_map(
            lambda p, c, t, pos, tab: decode_step_paged(
                model, p, c, t, pos, tab),
            mesh=plan.mesh,
            in_specs=(specs, cspecs, P(), P(), P()),
            out_specs=(P(), cspecs), check_vma=False)
        table = np.array([2, 1, 3], np.int32)
        lg, cd = pre(pp, cd, jnp.asarray(toks[:, :9]),
                     jnp.asarray(9, jnp.int32),
                     jnp.asarray(table, jnp.int32),
                     jnp.asarray(0, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg), full[8],
                                   rtol=rtol, atol=rtol)
        for p in range(9, 14):
            lg, cd = dec(pp, cd, jnp.asarray(toks[:, p]),
                         jnp.full((1,), p, jnp.int32),
                         jnp.asarray(table[None], jnp.int32))
            np.testing.assert_allclose(np.asarray(lg)[0], full[p],
                                       rtol=rtol, atol=rtol)
