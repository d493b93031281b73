"""Transformer LM + sequence parallelism integration.

The load-bearing test is distributed-vs-local equivalence: the model
run with its sequence dim sharded over a 4-device mesh axis (ring
attention) must match the same model run unsharded on one device --
the transformer analogue of the reference's model-parallel-vs-replica
test (``tests/functions_tests/test_point_to_point_communication.py:
62-104``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.models import TransformerLM, lm_loss


def _tiny(seq_axis=None, sp_scheme='ring'):
    return TransformerLM(vocab_size=64, d_model=32, n_heads=2,
                         n_layers=2, d_ff=64, max_len=128,
                         dtype=jnp.float32, sequence_axis=seq_axis,
                         sp_scheme=sp_scheme)


def _inlined(jaxpr, rename=None):
    """``jaxpr``'s equations with every nested ``jit`` call inlined
    and its variables renamed to the caller's, so that a consumer
    inside ``jnp.take`` counts as a consumer of the outer value.  A
    jitted function that every layer calls is ONE inner jaxpr with one
    set of variables: each call's results get variables of their own,
    so that a value inside one layer's call is not read by another's."""
    rename = {} if rename is None else rename

    def outer(v):
        return rename.get(id(v), v)

    class Var:
        def __init__(self, aval):
            self.aval = aval

    class Eqn:
        def __init__(self, eqn):
            self.primitive = eqn.primitive
            self.invars = [outer(v) for v in eqn.invars]
            self.outvars = [Var(v.aval) for v in eqn.outvars]
            rename.update(zip(map(id, eqn.outvars), self.outvars))

        def __repr__(self):
            return self.primitive.name

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'jit':
            inner = eqn.params['jaxpr'].jaxpr
            inside = dict(rename)
            for v_in, v_out in zip(inner.invars, eqn.invars):
                inside[id(v_in)] = outer(v_out)
            out.extend(_inlined(inner, inside))
            for v_in, v_out in zip(inner.outvars, eqn.outvars):
                rename[id(v_out)] = inside.get(id(v_in), v_in)
        else:
            out.append(Eqn(eqn))
    return out


@pytest.fixture(scope='module')
def setup():
    model = _tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)['params']
    return model, params, tokens


class TestTransformerLM:
    def test_forward_shape_finite(self, setup):
        model, params, tokens = setup
        logits = model.apply({'params': params}, tokens)
        assert logits.shape == (2, 32, 64)
        assert bool(jnp.all(jnp.isfinite(logits)))

    @pytest.mark.slow
    def test_loss_and_grads_finite(self, setup):
        model, params, tokens = setup
        targets = jnp.roll(tokens, -1, axis=1)
        loss_fn = lm_loss(
            lambda p, t: model.apply({'params': p}, t))
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, targets)
        assert np.isfinite(float(loss))
        assert np.isfinite(float(metrics['perp']))
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)

    def test_padding_mask(self, setup):
        model, params, tokens = setup
        loss_fn = lm_loss(
            lambda p, t: model.apply({'params': p}, t), pad_id=0)
        targets = jnp.where(jnp.arange(32) < 16,
                            jnp.roll(tokens, -1, axis=1), 0)
        loss, _ = loss_fn(params, tokens, targets)
        assert np.isfinite(float(loss))

    def test_causality(self, setup):
        # future tokens must not influence current logits
        model, params, tokens = setup
        logits = model.apply({'params': params}, tokens)
        perturbed = tokens.at[:, -1].set((tokens[:, -1] + 1) % 64)
        logits_p = model.apply({'params': params}, perturbed)
        np.testing.assert_allclose(logits[:, :-1], logits_p[:, :-1],
                                   atol=1e-5)


class TestSequenceParallel:
    def test_matches_single_device(self, setup):
        _, params, tokens = setup
        n_sp = 4
        if jax.device_count() < n_sp:
            pytest.skip('needs 4 devices')
        local = _tiny()
        ref = local.apply({'params': params}, tokens)

        sp_model = _tiny(seq_axis='sp')
        mesh = Mesh(np.array(jax.devices()[:n_sp]), ('sp',))

        def fwd(params, tokens):
            return sp_model.apply({'params': params}, tokens)

        sharded = jax.jit(jax.shard_map(
            fwd, mesh=mesh, in_specs=(P(), P(None, 'sp')),
            out_specs=P(None, 'sp', None), check_vma=False))
        out = sharded(params, tokens)
        np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize('scheme', ['ring', 'ulysses'])
    @pytest.mark.slow
    def test_sp_training_step(self, setup, scheme):
        """Differentiate OUTSIDE shard_map (the supported pattern, see
        parallel/__init__ AUTODIFF CAVEAT: grad INSIDE mis-transposes
        the attention collectives) and pin the sharded gradients
        against the unsharded model before training."""
        _, params, tokens = setup
        n_sp = 2  # both schemes (2 heads): ulysses needs H % sp == 0
        if jax.device_count() < n_sp:
            pytest.skip('needs 2 devices')
        sp_model = _tiny(seq_axis='sp', sp_scheme=scheme)
        mesh = Mesh(np.array(jax.devices()[:n_sp]), ('sp',))
        targets = jnp.roll(tokens, -1, axis=1)
        loss_fn = lm_loss(
            lambda p, t: sp_model.apply({'params': p}, t))

        from chainermn_tpu.parallel import mapped_global_loss
        mapped_loss = mapped_global_loss(loss_fn, mesh, P(None, 'sp'))

        # first-step gradient equivalence vs the unsharded model --
        # this is the check that catches grad-inside-shard_map
        local_loss_fn = lm_loss(
            lambda p, t: _tiny().apply({'params': p}, t))
        g_ref = jax.grad(
            lambda p: local_loss_fn(p, tokens, targets)[0])(params)
        g_sp = jax.jit(jax.grad(mapped_loss))(params, tokens, targets)
        for a, r in zip(jax.tree_util.tree_leaves(g_sp),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=5e-3, atol=5e-4)

        opt = optax.adam(1e-3)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state, tokens, targets):
            loss, grads = jax.value_and_grad(mapped_loss)(
                params, tokens, targets)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        p1, s1, loss1 = step(params, opt_state, tokens, targets)
        p2, _, loss2 = step(p1, s1, tokens, targets)
        assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
        assert float(loss2) < float(loss1)


def test_sp_token_weighted_loss_exact_under_uneven_padding(setup):
    """ADVICE r3: pmean of per-shard mean losses is Jensen-weighted
    when padding is uneven across sequence shards; the
    token_weighted=True path (psum(sum)/psum(count)) must equal the
    unsharded masked loss exactly, and the default path must
    demonstrably differ on the same batch (or this test proves
    nothing)."""
    from chainermn_tpu.models.transformer import lm_loss_sum
    from chainermn_tpu.parallel import mapped_global_loss

    _, params, tokens = setup
    n_sp = 2
    if jax.device_count() < n_sp:
        pytest.skip('needs 2 devices')
    pad = 0
    targets = jnp.roll(tokens, -1, axis=1)
    # mask out the trailing 10 of 32 positions: shard 0 keeps all 16,
    # shard 1 only 6 -- maximally uneven
    targets = targets.at[:, -10:].set(pad)

    sp_model = _tiny(seq_axis='sp')
    mesh = Mesh(np.array(jax.devices()[:n_sp]), ('sp',))

    ref_loss_fn = lm_loss(
        lambda p, t: _tiny().apply({'params': p}, t), pad_id=pad)
    ref = float(ref_loss_fn(params, tokens, targets)[0])

    weighted = mapped_global_loss(
        lm_loss_sum(lambda p, t: sp_model.apply({'params': p}, t),
                    pad_id=pad),
        mesh, P(None, 'sp'), token_weighted=True)
    got = float(jax.jit(weighted)(params, tokens, targets))
    np.testing.assert_allclose(got, ref, rtol=1e-5)

    plain = mapped_global_loss(
        lm_loss(lambda p, t: sp_model.apply({'params': p}, t),
                pad_id=pad),
        mesh, P(None, 'sp'))
    jensen = float(jax.jit(plain)(params, tokens, targets))
    assert abs(jensen - ref) > 1e-4, (
        'pmean-of-means coincides with the weighted mean; pick a more '
        'uneven mask so the test has teeth (ref=%f jensen=%f)'
        % (ref, jensen))

    # gradients of the weighted path match the unsharded masked loss
    g_ref = jax.grad(lambda p: ref_loss_fn(p, tokens, targets)[0])(
        params)
    g_sp = jax.jit(jax.grad(weighted))(params, tokens, targets)
    for a, r in zip(jax.tree_util.tree_leaves(g_sp),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=5e-3, atol=5e-4)


class TestTensorParallel:
    """ISSUE 7 acceptance: ``TransformerLM(tp_axis='model')`` on
    (1, 2) and (2, 2) CPU meshes matches the unsharded oracle's loss
    AND grads -- rtol 1e-5 f32 / 5e-2 bf16 -- with gradients taken
    INSIDE shard_map (the updater's mode; the tp_copy/tp_reduce
    conjugate pair makes the transposes exact there), and the forward
    jaxpr carries exactly one model-axis psum per Megatron half-block
    (attention, MLP) plus one each for the vocab-sharded embedding
    and the row-parallel head."""

    def _mesh(self, dp, tp):
        devs = np.array(jax.devices()[:dp * tp]).reshape(dp, tp)
        return Mesh(devs, ('data', 'model'))

    def _models(self, dtype):
        kw = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                  d_ff=64, max_len=128, dtype=dtype)
        return (TransformerLM(**kw),
                TransformerLM(tp_axis='model', **kw))

    @pytest.mark.parametrize('shape', [(1, 2), (2, 2)])
    @pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
    def test_matches_oracle(self, shape, dtype):
        from chainermn_tpu.models import tp_param_specs

        dp, tp = shape
        if jax.device_count() < dp * tp:
            pytest.skip('needs %d devices' % (dp * tp))
        rtol = 1e-5 if dtype == 'float32' else 5e-2
        atol = 1e-6 if dtype == 'float32' else 5e-3
        oracle, tp_model = self._models(jnp.dtype(dtype))
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2 * dp, 32),
                                    0, 64)
        targets = jnp.roll(tokens, -1, axis=1)
        params = oracle.init(jax.random.PRNGKey(1), tokens)['params']
        mesh = self._mesh(dp, tp)
        specs = tp_param_specs(params, 'model')

        ref_fn = lm_loss(lambda p, t: oracle.apply({'params': p}, t))
        (l_ref, _), g_ref = jax.value_and_grad(
            ref_fn, has_aux=True)(params, tokens, targets)

        tp_fn = lm_loss(lambda p, t: tp_model.apply({'params': p}, t))

        def step(p, tok, tgt):
            (loss, _), grads = jax.value_and_grad(
                tp_fn, has_aux=True)(p, tok, tgt)
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, 'data'), grads)
            return jax.lax.pmean(loss, ('data', 'model')), grads

        l_tp, g_tp = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(specs, P(('data',)), P(('data',))),
            out_specs=(P(), specs), check_vma=False))(
                params, tokens, targets)
        np.testing.assert_allclose(float(l_tp), float(l_ref),
                                   rtol=rtol)
        for (kp, a), (_, r) in zip(
                jax.tree_util.tree_leaves_with_path(g_tp),
                jax.tree_util.tree_leaves_with_path(g_ref)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(r, np.float32),
                rtol=rtol, atol=atol,
                err_msg=jax.tree_util.keystr(kp))

    def test_one_psum_per_half_block(self):
        from chainermn_tpu.analysis import walker
        from chainermn_tpu.models import tp_param_specs

        oracle, tp_model = self._models(jnp.float32)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32),
                                    0, 64)
        params = oracle.init(jax.random.PRNGKey(1), tokens)['params']
        mesh = self._mesh(1, 2)
        specs = tp_param_specs(params, 'model')
        fwd = jax.shard_map(
            lambda p, t: tp_model.apply({'params': p}, t),
            mesh=mesh, in_specs=(specs, P(('data',))),
            out_specs=P(('data',)), check_vma=False)
        jaxpr = jax.make_jaxpr(fwd)(params, tokens)
        n = sum(1 for eqn, _ in walker.iter_eqns(jaxpr)
                if eqn.primitive.name == 'psum'
                and 'model' in walker.eqn_axes(eqn))
        # one per attention half-block + one per MLP half-block
        # (2 per layer) + embedding + lm head
        assert n == 2 * tp_model.n_layers + 2, n

    def test_tp_and_sequence_axis_mutually_exclusive(self):
        model = TransformerLM(vocab_size=64, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, tp_axis='model',
                              sequence_axis='sp')
        with pytest.raises(ValueError):
            model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))

    def test_tp_oracle_round_trip(self):
        from chainermn_tpu.models import tp_oracle
        _, tp_model = self._models(jnp.float32)
        assert tp_oracle(tp_model).tp_axis is None
        assert tp_oracle(tp_model).d_model == tp_model.d_model


class TestIncrementalDecode:
    """ISSUE 11 parity pin: the slot-addressed KV-cache decode path
    (prefill + decode_step) reproduces the full-sequence causal
    forward's logits -- f32 rtol 1e-5, bf16 / int8-KV 5e-2 --
    including across a slot-REFILL boundary (a second prompt through
    a used slot must not see the previous occupant's rows)."""

    def _model(self, dtype, max_len=64):
        return TransformerLM(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=max_len,
                             dtype=dtype)

    def _stepwise_logits(self, model, params, cache, toks, t_pre,
                         slot):
        """Prefill ``toks[:t_pre]`` into ``slot`` then teacher-force
        the remainder through decode_step; returns (logits at each
        position >= t_pre - 1, cache)."""
        from chainermn_tpu.models import decode_step, prefill
        pad = np.zeros((1, t_pre), np.int32)
        pad[0] = toks[:t_pre]
        out = {}
        lg, cache = prefill(model, params, cache, jnp.asarray(pad),
                            jnp.asarray(t_pre), jnp.asarray(slot))
        out[t_pre - 1] = np.asarray(lg)
        for p in range(t_pre, len(toks)):
            lg, cache = decode_step(
                model, params, cache,
                jnp.asarray([toks[p]], jnp.int32),
                jnp.asarray([p], jnp.int32),
                slots=jnp.asarray([slot], jnp.int32))
            out[p] = np.asarray(lg[0])
        return out, cache

    @pytest.mark.parametrize('dtype,rtol', [('float32', 1e-5),
                                            ('bfloat16', 5e-2)])
    def test_matches_full_forward(self, dtype, rtol):
        from chainermn_tpu.models import init_kv_cache
        model = self._model(jnp.dtype(dtype))
        rng = np.random.RandomState(0)
        toks = rng.randint(0, 64, size=12).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([toks]))['params']
        full = np.asarray(model.apply({'params': params},
                                      jnp.asarray([toks])))[0]
        cache = init_kv_cache(model, n_slots=2)
        got, _ = self._stepwise_logits(model, params, cache, toks,
                                       t_pre=4, slot=1)
        for p, lg in got.items():
            np.testing.assert_allclose(lg, full[p], rtol=rtol,
                                       atol=rtol)

    def test_int8_kv_cache_parity(self):
        from chainermn_tpu.models import init_kv_cache
        model = self._model(jnp.float32)
        rng = np.random.RandomState(2)
        toks = rng.randint(0, 64, size=10).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([toks]))['params']
        full = np.asarray(model.apply({'params': params},
                                      jnp.asarray([toks])))[0]
        cache = init_kv_cache(model, n_slots=1, int8_kv=True)
        assert all(leaf.dtype == jnp.int8 for leaf in cache['k'])
        assert len(cache['k']) == model.n_layers
        got, _ = self._stepwise_logits(model, params, cache, toks,
                                       t_pre=3, slot=0)
        for p, lg in got.items():
            np.testing.assert_allclose(lg, full[p], rtol=5e-2,
                                       atol=5e-2)

    def test_parity_across_slot_refill_boundary(self):
        """The continuous-batching numerics pin: after sequence A
        used slot 0, prefilling sequence B into the SAME slot (no
        zeroing) must reproduce B's fresh-cache logits exactly --
        stale rows beyond B's length are masked, not read."""
        from chainermn_tpu.models import init_kv_cache
        model = self._model(jnp.float32)
        rng = np.random.RandomState(3)
        tok_a = rng.randint(0, 64, size=12).astype(np.int32)
        tok_b = rng.randint(0, 64, size=7).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([tok_a]))['params']
        cache = init_kv_cache(model, n_slots=1, max_len=32)
        _, cache = self._stepwise_logits(model, params, cache, tok_a,
                                         t_pre=5, slot=0)
        # refill: B through the USED slot vs B through a fresh cache
        got_b, _ = self._stepwise_logits(model, params, cache, tok_b,
                                         t_pre=3, slot=0)
        fresh = init_kv_cache(model, n_slots=1, max_len=32)
        want_b, _ = self._stepwise_logits(model, params, fresh, tok_b,
                                          t_pre=3, slot=0)
        for p in got_b:
            np.testing.assert_allclose(got_b[p], want_b[p],
                                       rtol=1e-6, atol=1e-6)
        full = np.asarray(model.apply({'params': params},
                                      jnp.asarray([tok_b])))[0]
        for p in got_b:
            np.testing.assert_allclose(got_b[p], full[p], rtol=1e-5,
                                       atol=1e-5)

    @pytest.mark.parametrize('kv', ['bf16', 'bf16-page8', 'int8'])
    @pytest.mark.parametrize('fn', ['decode_step', 'decode_step_paged',
                                    'prefill', 'prefill_paged',
                                    'spec_verify_paged'])
    def test_full_bucket_decode_reads_cache_in_place(self, fn, kv,
                                                     monkeypatch):
        """The in-place jaxpr pin at the model layer, for every
        serving function whose cache read is a kernel or a gather:
        each cache leaf (one array per layer) has exactly ONE
        consumer, its write; the write's result reaches the kernel
        (full-slot and paged decode) or the context gather (paged
        prefill / verify) with nothing that cuts, turns or copies it
        in between; and nothing but the writes makes a value of a
        leaf's shape.  A float paged pool of 16-position pages is
        head-major: its decode write is the append kernel, and a
        chunk's write reads the pages it can touch (a gather, never
        leaf-shaped) before it scatters them back whole; one of
        8-position pages (off bfloat16's sublane tile) is page-major,
        as an int8 pool is.

        This proves there is no gather, slice or copy of the cache IN
        THE PROGRAM.  What XLA materialises for a custom call's
        operand on the TPU no jaxpr shows: that is
        ``chip_smoke.serving_pool_check`` on the chip and
        ``tests/test_chip_compile.py`` for a described one."""
        import importlib

        from chainermn_tpu import models as M
        model = self._model(jnp.bfloat16)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))['params']
        # the kernels' path, as on the chip: traced, never lowered
        fa = importlib.import_module('chainermn_tpu.ops.flash_attention')
        monkeypatch.setattr(fa, 'pallas_mode', lambda: 'native')
        monkeypatch.setattr(fa, 'interpret_flag', lambda: False)
        int8 = kv == 'int8'
        i32 = jnp.int32
        if fn in ('decode_step', 'prefill'):
            cache = M.init_kv_cache(model, n_slots=4, int8_kv=int8)
        else:
            cache = M.init_paged_kv_cache(
                model, n_pages=9, page_size=8 if kv == 'bf16-page8'
                else 16, int8_kv=int8)
        rows, tables = jnp.zeros((4,), i32), jnp.zeros((4, 8), i32)
        call, operands = {
            'decode_step': (M.decode_step, (rows, rows)),
            'decode_step_paged': (M.decode_step_paged,
                                  (rows, rows, tables)),
            'prefill': (M.prefill, (jnp.zeros((1, 16), i32),
                                    jnp.asarray(5, i32),
                                    jnp.asarray(1, i32))),
            'prefill_paged': (M.prefill_paged,
                              (jnp.zeros((1, 16), i32),
                               jnp.asarray(5, i32), tables[0],
                               jnp.asarray(8, i32))),
            'spec_verify_paged': (M.spec_verify_paged,
                                  (jnp.zeros((4, 3), i32), rows,
                                   tables)),
        }[fn]
        jaxpr = jax.make_jaxpr(
            lambda cache, *a: call(model, params, cache, *a))(
                cache, *operands)
        leaves = jax.tree_util.tree_leaves(cache)
        assert len(leaves) == model.n_layers * (4 if int8 else 2)
        head_major = 'paged' in fn and kv == 'bf16'
        assert head_major == ('head_major' in cache)
        eqns = _inlined(jaxpr.jaxpr)
        writes = set()
        for var in jaxpr.jaxpr.invars[:len(leaves)]:
            readers = sorted((e for e in eqns if var in e.invars),
                             key=lambda e: e.primitive.name)
            assert [e.primitive.name for e in readers] in (
                [['pallas_call'] if 'decode' in fn
                 else ['gather', 'scatter']] if head_major
                else [['scatter'], ['dynamic_update_slice']]), (
                    'cache leaf %s consumed by %r' % (var.aval, readers))
            write = readers[-1]
            writes.add(id(write))
            # from the written leaf to its reader: a reshape (slab ->
            # pages, scale -> scale tile) at most, then the kernel or
            # the gather; a whole-prompt slot prefill reads nothing
            frontier = [v for v in write.outvars
                        if v.aval.shape == var.aval.shape]
            reads = []
            while frontier:
                var = frontier.pop()
                for e in eqns:
                    if var not in e.invars:
                        continue
                    name = e.primitive.name
                    assert name in ('pallas_call', 'gather', 'reshape',
                                    'broadcast_in_dim'), (
                        '%s between the write and the read of a cache '
                        'leaf' % name)
                    if name in ('pallas_call', 'gather'):
                        reads.append(name)
                    else:
                        frontier.extend(e.outvars)
            assert set(reads) == (set() if fn == 'prefill' else
                                  {'pallas_call'} if 'decode' in fn
                                  else {'gather'}), reads
            assert len(reads) <= (2 if head_major and 'decode' in fn
                                  else 1), reads
        shapes = {leaf.shape for leaf in leaves}
        made = [e for e in eqns if id(e) not in writes
                and any(v.aval.shape in shapes for v in e.outvars)]
        assert not made, 'leaf-shaped values made by %r' % (made,)

    def test_compacted_vs_full_bucket_same_logits(self):
        from chainermn_tpu.models import (decode_step, init_kv_cache,
                                          prefill)
        model = self._model(jnp.float32)
        rng = np.random.RandomState(4)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))['params']
        cache = init_kv_cache(model, n_slots=4)
        toks = rng.randint(0, 64, size=(4, 6)).astype(np.int32)
        for s in range(2):
            _, cache = prefill(model, params, cache,
                               jnp.asarray(toks[s:s + 1]),
                               jnp.asarray(6), jnp.asarray(s))
        nxt = jnp.asarray([1, 2], jnp.int32)
        pos = jnp.asarray([6, 6], jnp.int32)
        lg_c, _ = decode_step(model, params, cache, nxt, pos,
                              slots=jnp.asarray([0, 1], jnp.int32))
        # full bucket: same tokens at rows 0/1, padding rows 2/3
        lg_f, _ = decode_step(
            model, params, cache,
            jnp.asarray([1, 2, 0, 0], jnp.int32),
            jnp.asarray([6, 6, 0, 0], jnp.int32))
        np.testing.assert_allclose(np.asarray(lg_c),
                                   np.asarray(lg_f)[:2], rtol=1e-6,
                                   atol=1e-6)

    @pytest.mark.slow
    def test_tp_decode_matches_oracle(self):
        """Decode under shard_map tp=2: same psum structure as the
        tp forward, logits match the unsharded full forward."""
        from chainermn_tpu.models import (decode_step, init_kv_cache,
                                          kv_cache_specs, prefill,
                                          tp_param_specs)
        from chainermn_tpu.parallel.meshplan import MeshPlan
        if jax.device_count() < 2:
            pytest.skip('needs 2 devices')
        plan = MeshPlan.create(tp=2)
        model = self._model(jnp.float32).clone(
            tp_axis=plan.model_axis)
        oracle = self._model(jnp.float32)
        rng = np.random.RandomState(5)
        toks = rng.randint(0, 64, size=(2, 9)).astype(np.int32)
        params = oracle.init(jax.random.PRNGKey(1),
                             jnp.asarray(toks))['params']
        full = np.asarray(oracle.apply({'params': params},
                                       jnp.asarray(toks)))
        specs = tp_param_specs(params, plan.model_axis)
        cache = init_kv_cache(model, n_slots=2)
        cspecs = kv_cache_specs(cache, plan.model_axis)
        pp = jax.device_put(params, plan.param_shardings(specs))
        cd = jax.device_put(cache, plan.param_shardings(cspecs))
        pre = jax.shard_map(
            lambda p, c, t, n, s: prefill(model, p, c, t, n, s),
            mesh=plan.mesh,
            in_specs=(specs, cspecs, P(), P(), P()),
            out_specs=(P(), cspecs), check_vma=False)
        dec = jax.shard_map(
            lambda p, c, t, pos: decode_step(model, p, c, t, pos),
            mesh=plan.mesh, in_specs=(specs, cspecs, P(), P()),
            out_specs=(P(), cspecs), check_vma=False)
        for s in range(2):
            lg, cd = pre(pp, cd, jnp.asarray(toks[s:s + 1, :6]),
                         jnp.asarray(6), jnp.asarray(s))
            np.testing.assert_allclose(np.asarray(lg), full[s, 5],
                                       rtol=1e-5, atol=1e-5)
        for p in range(6, 9):
            lg, cd = dec(pp, cd, jnp.asarray(toks[:, p]),
                         jnp.full((2,), p, jnp.int32))
            np.testing.assert_allclose(np.asarray(lg), full[:, p],
                                       rtol=1e-5, atol=1e-5)


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


class TestSpecVerify:
    """Speculative-decoding verify twin (ISSUE 19): ``spec_verify`` /
    ``spec_verify_paged`` score a k-token window in ONE pass and must
    reproduce the sequential teacher-forced ``decode_step`` /
    ``decode_step_paged`` trajectory over the same tokens -- logits
    close, ARGMAX exactly equal (the accept rule compares argmaxes,
    so argmax parity, not a logit tolerance, is what exact greedy
    equivalence rests on).  int8-KV included: the verify pass
    quantize-roundtrips its fresh K/V so in-window attention reads
    bitwise-match what the oracle wrote to the cache."""

    PS = 8
    K = 4

    def _model(self, max_len=64):
        return TransformerLM(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=max_len,
                             dtype=jnp.float32)

    @pytest.mark.parametrize('paged', [False, True])
    @pytest.mark.parametrize('int8_kv', [False, True])
    def test_window_matches_sequential_decode(self, paged, int8_kv):
        from chainermn_tpu.models import (
            decode_step, decode_step_paged, init_kv_cache,
            init_paged_kv_cache, prefill, prefill_paged, spec_verify,
            spec_verify_paged)
        model = self._model()
        rng = np.random.RandomState(20)
        toks = rng.randint(0, 64, size=6 + self.K).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([toks]))['params']
        t_pre = 6
        pad = np.zeros((1, t_pre), np.int32)
        pad[0] = toks[:t_pre]
        table = np.array([2, 1, 3, 4], np.int32)
        if paged:
            mk = lambda: init_paged_kv_cache(  # noqa: E731
                model, n_pages=5, page_size=self.PS, int8_kv=int8_kv)
            c_seq = c_win = mk()
            _, c_seq = prefill_paged(
                model, params, c_seq, jnp.asarray(pad),
                jnp.asarray(t_pre, jnp.int32),
                jnp.asarray(table, jnp.int32),
                jnp.asarray(0, jnp.int32))
            _, c_win = prefill_paged(
                model, params, mk(), jnp.asarray(pad),
                jnp.asarray(t_pre, jnp.int32),
                jnp.asarray(table, jnp.int32),
                jnp.asarray(0, jnp.int32))
        else:
            mk = lambda: init_kv_cache(  # noqa: E731
                model, n_slots=2, int8_kv=int8_kv)
            _, c_seq = prefill(model, params, mk(), jnp.asarray(pad),
                               jnp.asarray(t_pre), jnp.asarray(1))
            _, c_win = prefill(model, params, mk(), jnp.asarray(pad),
                               jnp.asarray(t_pre), jnp.asarray(1))
        # oracle: teacher-force the window one decode step at a time
        want = []
        for j in range(self.K):
            p = t_pre + j
            if paged:
                lg, c_seq = decode_step_paged(
                    model, params, c_seq,
                    jnp.asarray([toks[p]], jnp.int32),
                    jnp.asarray([p], jnp.int32),
                    jnp.asarray([table], jnp.int32))
            else:
                lg, c_seq = decode_step(
                    model, params, c_seq,
                    jnp.asarray([toks[p]], jnp.int32),
                    jnp.asarray([p], jnp.int32),
                    slots=jnp.asarray([1], jnp.int32))
            want.append(np.asarray(lg[0]))
        # one verify pass over the same window
        win = jnp.asarray([toks[t_pre:t_pre + self.K]], jnp.int32)
        base = jnp.asarray([t_pre], jnp.int32)
        if paged:
            got, c_win = spec_verify_paged(
                model, params, c_win, win, base,
                jnp.asarray([table], jnp.int32))
        else:
            got, c_win = spec_verify(model, params, c_win, win, base,
                                     slots=jnp.asarray([1],
                                                       jnp.int32))
        got = np.asarray(got)[0]
        for j in range(self.K):
            np.testing.assert_allclose(got[j], want[j], rtol=1e-5,
                                       atol=1e-5)
            assert int(got[j].argmax()) == int(want[j].argmax()), j
        # the verify WRITES the window into the cache: continuing
        # with plain decode from either cache must agree (the engine's
        # full-acceptance path never re-writes accepted positions)
        p = t_pre + self.K
        nxt = jnp.asarray([int(got[-1].argmax())], jnp.int32)
        if paged:
            lg_a, _ = decode_step_paged(
                model, params, c_seq, nxt,
                jnp.asarray([p], jnp.int32),
                jnp.asarray([table], jnp.int32))
            lg_b, _ = decode_step_paged(
                model, params, c_win, nxt,
                jnp.asarray([p], jnp.int32),
                jnp.asarray([table], jnp.int32))
        else:
            lg_a, _ = decode_step(
                model, params, c_seq, nxt,
                jnp.asarray([p], jnp.int32),
                slots=jnp.asarray([1], jnp.int32))
            lg_b, _ = decode_step(
                model, params, c_win, nxt,
                jnp.asarray([p], jnp.int32),
                slots=jnp.asarray([1], jnp.int32))
        np.testing.assert_allclose(np.asarray(lg_b), np.asarray(lg_a),
                                   rtol=1e-6, atol=1e-6)

    def test_full_bucket_variant_matches_compacted(self):
        """The full-slot verify executable (cache read in place, no
        slots operand) must produce the same logits as the compacted
        variant for the same live rows."""
        from chainermn_tpu.models import (init_kv_cache, prefill,
                                          spec_verify)
        model = self._model()
        rng = np.random.RandomState(21)
        toks = rng.randint(0, 64, size=10).astype(np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            jnp.asarray([toks]))['params']
        pad = np.zeros((1, 6), np.int32)
        pad[0] = toks[:6]
        c_a = c_b = None
        _, c_a = prefill(model, params,
                         init_kv_cache(model, n_slots=2),
                         jnp.asarray(pad), jnp.asarray(6),
                         jnp.asarray(0))
        _, c_b = prefill(model, params,
                         init_kv_cache(model, n_slots=2),
                         jnp.asarray(pad), jnp.asarray(6),
                         jnp.asarray(0))
        win = jnp.asarray([toks[6:10]], jnp.int32)
        base = jnp.asarray([6], jnp.int32)
        lg_c, _ = spec_verify(model, params, c_a, win, base,
                              slots=jnp.asarray([0], jnp.int32))
        win2 = jnp.asarray([toks[6:10], np.zeros(4, np.int32)],
                           jnp.int32)
        lg_f, _ = spec_verify(model, params, c_b, win2,
                              jnp.asarray([6, 0], jnp.int32))
        np.testing.assert_allclose(np.asarray(lg_f)[0],
                                   np.asarray(lg_c)[0],
                                   rtol=1e-6, atol=1e-6)


def test_ulysses_matches_single_device():
    """sp_scheme='ulysses' (all_to_all head resharding) must also
    reproduce the unsharded model: 2 heads over 2 devices."""
    model = _tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)['params']
    ref = model.apply({'params': params}, tokens)

    n_sp = 2
    sp_model = _tiny(seq_axis='sp', sp_scheme='ulysses')
    mesh = Mesh(np.array(jax.devices()[:n_sp]), ('sp',))
    out = jax.jit(jax.shard_map(
        lambda p, t: sp_model.apply({'params': p}, t),
        mesh=mesh, in_specs=(P(), P(None, 'sp')),
        out_specs=P(None, 'sp', None), check_vma=False))(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)
