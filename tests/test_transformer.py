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
