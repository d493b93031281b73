"""Serving subsystem tests (ISSUE 10): dynamic batching determinism,
AOT warm-start / no-recompile pins, typed overload shedding, int8
parity vs the f32 oracle, MeshPlan-sharded serving, elastic-checkpoint
loading, and the telemetry doctor's serve-capture recognition.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import precision, serving
from chainermn_tpu.serving import (InferenceEngine, OverloadError,
                                   RequestQueue, bucket_edges,
                                   bucket_of, pack_sizes)
from chainermn_tpu.utils import chaos
from conftest import mlp_setup as _mlp_setup, tiny_lm as _tiny_lm


# ---------------------------------------------------------------------
# buckets + packing

class TestBuckets:
    def test_edges_power_of_two_up_to_max(self):
        assert bucket_edges(32) == (1, 2, 4, 8, 16, 32)
        # non-pow2 cap: the top edge IS the cap
        assert bucket_edges(24) == (1, 2, 4, 8, 16, 24)
        assert bucket_edges(1) == (1,)

    def test_bucket_of_smallest_fit(self):
        edges = bucket_edges(16)
        assert bucket_of(1, edges) == 1
        assert bucket_of(3, edges) == 4
        assert bucket_of(16, edges) == 16

    def test_bucket_of_oversize_typed(self):
        with pytest.raises(ValueError, match='exceeds the largest'):
            bucket_of(17, bucket_edges(16))

    def test_bucket_of_degenerate(self):
        with pytest.raises(ValueError):
            bucket_of(0, bucket_edges(16))


class TestPackingDeterminism:
    def test_distinct_sizes_any_order_identical_assignment(self):
        """Same mix of DISTINCT sizes in different arrival orders:
        identical per-size bucket assignment and padded shapes."""
        edges = bucket_edges(16)
        mix = [7, 3, 5, 1, 9, 2]
        ref = None
        for perm in ([0, 1, 2, 3, 4, 5], [5, 4, 3, 2, 1, 0],
                     [2, 0, 5, 1, 4, 3]):
            sizes = [mix[i] for i in perm]
            packed = pack_sizes(sizes, 16, edges)
            # map each SIZE to its group's bucket (sizes distinct)
            assign = {sizes[i]: bucket
                      for bucket, members in packed for i in members}
            shapes = sorted(b for b, _ in packed)
            if ref is None:
                ref = (assign, shapes)
            assert (assign, shapes) == ref

    def test_equal_sizes_identical_shape_multiset(self):
        """Interchangeable equal-size requests: the multiset of
        bucket shapes is order-invariant."""
        edges = bucket_edges(8)
        for order in ([4, 4, 4], [4, 4, 4]):
            packed = pack_sizes(order, 8, edges)
            assert sorted(b for b, _ in packed) == [4, 8]

    def test_one_request_degenerate(self):
        packed = pack_sizes([3], 16, bucket_edges(16))
        assert packed == [(4, [0])]

    def test_over_max_typed(self):
        with pytest.raises(ValueError, match='exceeds max_batch'):
            pack_sizes([17], 16, bucket_edges(16))

    def test_groups_never_exceed_max_batch(self):
        rng = np.random.RandomState(0)
        edges = bucket_edges(16)
        for _ in range(20):
            sizes = list(rng.randint(1, 17, size=12))
            for bucket, members in pack_sizes(sizes, 16, edges):
                total = sum(sizes[i] for i in members)
                assert total <= 16
                assert bucket == bucket_of(total, edges)

    def test_padded_shapes_and_signatures_order_invariant(self):
        """The end-to-end determinism pin: same mix, two arrival
        orders, through the REAL queue -> identical padded shapes and
        identical jit signature hashes (the engine's no-recompile
        guard vocabulary)."""
        from chainermn_tpu.analysis.walker import abstract_signature

        mix = [5, 2, 7, 1, 3]

        def shapes_for(order):
            q = RequestQueue(max_batch=16, max_wait=0.0, max_queue=64)
            for n in order:
                q.submit(np.zeros((n, 6), np.float32))
            out = []
            for pb in q.take(timeout=0.5):
                x, mask = pb.collate()
                assert x.shape[0] == pb.bucket
                assert mask.sum() == pb.total
                out.append(abstract_signature((x,)))
            return sorted(out)

        assert shapes_for(mix) == shapes_for(list(reversed(mix)))


# ---------------------------------------------------------------------
# queue admission

class TestRequestQueue:
    def test_coalesces_into_buckets(self):
        q = RequestQueue(max_batch=8, max_wait=0.0, max_queue=64)
        for n in (3, 2):
            q.submit(np.ones((n, 4), np.float32))
        batches = q.take(timeout=0.5)
        assert len(batches) == 1
        assert batches[0].bucket == 8 and batches[0].total == 5
        x, mask = batches[0].collate()
        assert x.shape == (8, 4)
        assert mask.tolist() == [1, 1, 1, 1, 1, 0, 0, 0]

    def test_bounded_queue_sheds_typed(self):
        q = RequestQueue(max_batch=4, max_wait=10.0, max_queue=4)
        for _ in range(4):
            q.submit(np.zeros((1, 2), np.float32))
        with pytest.raises(OverloadError) as ei:
            q.submit(np.zeros((1, 2), np.float32))
        assert ei.value.reason == 'queue_full'
        assert ei.value.queue_depth == 4
        assert q.shed_queue_full == 1

    def test_deadline_expired_sheds_typed_at_drain(self):
        clock = [0.0]
        q = RequestQueue(max_batch=4, max_wait=0.0, max_queue=16,
                         clock=lambda: clock[0])
        req = q.submit(np.zeros((1, 2), np.float32), deadline=0.5)
        live = q.submit(np.zeros((1, 2), np.float32))
        clock[0] = 1.0
        batches = q.take(timeout=0.1)
        assert req.done()
        with pytest.raises(OverloadError) as ei:
            req.result(timeout=0)
        assert ei.value.reason == 'deadline'
        assert sum(len(b.requests) for b in batches) == 1
        assert batches[0].requests[0] is live

    def test_oversize_submit_rejected_before_queueing(self):
        q = RequestQueue(max_batch=4, max_queue=16)
        with pytest.raises(ValueError, match='exceeds the largest'):
            q.submit(np.zeros((5, 2), np.float32))
        assert q.depth() == 0

    def test_close_sheds_pending_shutdown(self):
        q = RequestQueue(max_batch=8, max_wait=60.0, max_queue=16)
        req = q.submit(np.zeros((1, 2), np.float32))
        q.close()
        with pytest.raises(OverloadError) as ei:
            req.result(timeout=0)
        assert ei.value.reason == 'shutdown'
        with pytest.raises(OverloadError):
            q.submit(np.zeros((1, 2), np.float32))

    def test_max_wait_triggers_partial_batch(self):
        q = RequestQueue(max_batch=64, max_wait=0.01, max_queue=128)
        q.submit(np.zeros((2, 3), np.float32))
        t0 = time.monotonic()
        batches = q.take(timeout=1.0)
        assert batches and batches[0].total == 2
        assert time.monotonic() - t0 < 0.5


class TestServeBurstChaos:
    def teardown_method(self):
        chaos.uninstall()

    def test_burst_amplifies_through_bounded_admission(self):
        chaos.install(chaos.FaultInjector('serve_burst=@0:8'))
        q = RequestQueue(max_batch=4, max_wait=10.0, max_queue=6)
        req = q.submit(np.zeros((1, 2), np.float32))
        # the real request was admitted; the burst filled the queue
        # to capacity and the overflow was shed inside submit
        assert not req.done()
        assert q.depth() == 6
        with pytest.raises(OverloadError):
            q.submit(np.zeros((1, 2), np.float32))

    def test_burst_saturation_degrades_gracefully(self):
        """serve_burst on every submit at 4x: the queue keeps
        serving admitted work; excess sheds typed."""
        chaos.install(chaos.FaultInjector('serve_burst=*:4'))
        _model, params, apply_fn, example = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, example, max_batch=8,
                              aot=False)
        eng.warmup()
        q = RequestQueue(max_batch=8, max_wait=0.001, max_queue=16)
        rep = serving.open_loop(eng, q, rate=2000.0, n_requests=40,
                                seed=3)
        assert rep['served'] + rep['shed_submit'] \
            + rep['shed_deadline'] + rep['errored'] == 40
        assert rep['served'] > 0  # admitted work still served


# ---------------------------------------------------------------------
# engine: AOT, warm start, signature guard, fallback

class TestInferenceEngine:
    def test_warmup_compiles_every_bucket_aot(self):
        _m, params, apply_fn, example = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, example, max_batch=8)
        aot = eng.warmup()
        assert sorted(aot) == [1, 2, 4, 8]
        assert all(aot.values())  # this jax has the AOT surface
        assert eng.compile_count == 4
        assert eng.trace_count == 4

    def test_warm_start_avoids_retracing(self):
        """The acceptance pin: after warmup, traffic across every
        bucket adds ZERO traces and ZERO compiles."""
        _m, params, apply_fn, example = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, example, max_batch=8)
        eng.warmup()
        traces0, compiles0 = eng.trace_count, eng.compile_count
        for bucket in eng.edges:
            for _ in range(3):
                y = eng.infer(np.ones((bucket, 48), np.float32))
                assert np.asarray(y).shape == (bucket, 10)
        assert eng.trace_count == traces0
        assert eng.compile_count == compiles0
        assert eng.executions == 3 * len(eng.edges)

    def test_signature_guard_refuses_off_bucket_shape(self):
        _m, params, apply_fn, example = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, example, max_batch=8)
        eng.warmup()
        with pytest.raises(RuntimeError, match='not a bucket edge'):
            eng.infer(np.ones((3, 48), np.float32))
        with pytest.raises(RuntimeError, match='no-recompile guard'):
            eng.guard_signature(np.ones((3, 48), np.float32))

    def test_plain_jit_when_aot_off(self):
        """``aot=False`` is the explicit way to ask for plain jit:
        the engine serves identically, and warmup still forces every
        compile so traffic never traces."""
        _m, params, apply_fn, example = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, example, max_batch=4,
                              aot=False)
        aot = eng.warmup()
        assert not any(aot.values())
        y = eng.infer(np.ones((4, 48), np.float32))
        assert np.asarray(y).shape == (4, 10)
        t0 = eng.trace_count
        eng.infer(np.ones((4, 48), np.float32))
        assert eng.trace_count == t0

    def test_aot_lowering_error_propagates(self):
        """No silent fall to plain jit: a forward that cannot lower
        fails warmup."""
        _m, params, _apply, example = _mlp_setup()

        def broken(p, x):
            raise TypeError('cannot lower this')

        eng = InferenceEngine(broken, params, example, max_batch=2)
        with pytest.raises(TypeError, match='cannot lower'):
            eng.warmup()

    def test_persistent_cache_writes_executables(self, tmp_path,
                                                 monkeypatch):
        from jax.experimental.compilation_cache import (
            compilation_cache)
        from chainermn_tpu.utils import platform
        # the engine's cache lives where the environment says, else
        # in the checkout: stand a scratch checkout in for this test
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        monkeypatch.setattr(platform, '_CHECKOUT', str(tmp_path))
        was_dir = jax.config.jax_compilation_cache_dir
        was_min = jax.config.jax_persistent_cache_min_compile_time_secs
        jax.config.update(
            'jax_persistent_cache_min_compile_time_secs', 0.0)
        try:
            _m, params, apply_fn, example = _mlp_setup()
            eng = InferenceEngine(apply_fn, params, example,
                                  max_batch=4)
            cache = str(tmp_path / '.jax_compile_cache')
            assert eng.cache_dir == cache
            eng.warmup()
            entries = [f for f in os.listdir(cache)
                       if f.endswith('-cache')]
            assert len(entries) >= len(eng.edges)
            # a second engine (cold start simulation) warms up
            # against the SAME cache dir and serves identically
            eng2 = InferenceEngine(apply_fn, params, example,
                                   max_batch=4)
            eng2.warmup()
            x = np.ones((4, 48), np.float32)
            np.testing.assert_allclose(np.asarray(eng.infer(x)),
                                       np.asarray(eng2.infer(x)),
                                       rtol=1e-6)
        finally:
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs', was_min)
            jax.config.update('jax_compilation_cache_dir', was_dir)
            compilation_cache.reset_cache()

    def test_policy_bf16_casts_params_and_outputs_f32(self):
        _m, params, apply_fn, example = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, example, max_batch=4,
                              policy=precision.Policy.bf16())
        eng.warmup()
        leaf = jax.tree_util.tree_leaves(eng.params)[0]
        assert leaf.dtype == jnp.bfloat16
        y = eng.infer(np.ones((4, 48), np.float32))
        assert np.asarray(y).dtype == np.float32


# ---------------------------------------------------------------------
# int8 policy

class TestInt8Policy:
    def test_quantize_eligibility(self):
        tree = {'w': np.random.RandomState(0).randn(64, 32)
                .astype(np.float32),
                'b': np.zeros((32,), np.float32),
                'n': np.arange(4, dtype=np.int32)}
        qt = precision.quantize_int8(tree)
        assert precision.is_quantized(qt['w'])
        assert qt['w'].q.dtype == jnp.int8
        assert qt['w'].scale.shape == (32,)
        assert not precision.is_quantized(qt['b'])  # under size floor
        assert not precision.is_quantized(qt['n'])  # integer

    def test_roundtrip_error_small(self):
        w = np.random.RandomState(1).randn(128, 64).astype(np.float32)
        qt = precision.quantize_int8({'w': w})
        err = precision.quantization_error({'w': w}, qt)
        assert 0 < err < 0.02  # per-channel int8 symmetric

    def test_dequant_matmul_matches_reference(self):
        from chainermn_tpu import ops
        rng = np.random.RandomState(2)
        w = rng.randn(48, 16).astype(np.float32)
        x = rng.randn(8, 48).astype(np.float32)
        qt = precision.quantize_int8({'w': w}, min_elems=0)['w']
        got = ops.dequant_matmul(jnp.asarray(x), qt.q, qt.scale)
        want = ops.dequant_matmul_reference(jnp.asarray(x), qt.q,
                                            qt.scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # and both approximate the unquantized matmul
        np.testing.assert_allclose(np.asarray(got), x @ w, rtol=0.2,
                                   atol=0.1)

    def test_int8_engine_parity_vs_f32_oracle(self):
        """The acceptance pin: int8-policy logits match the f32
        oracle within the documented tolerance (rtol <= 5e-2)."""
        _m, params, apply_fn, example = _mlp_setup(n_units=64)
        oracle = InferenceEngine(apply_fn, params, example,
                                 max_batch=8)
        quant = InferenceEngine(apply_fn, params, example,
                                max_batch=8,
                                policy=precision.Int8Policy())
        oracle.warmup()
        quant.warmup()
        assert quant.quantized
        x = np.random.RandomState(3).rand(8, 48).astype(np.float32)
        y_f32 = np.asarray(oracle.infer(x))
        y_i8 = np.asarray(quant.infer(x))
        np.testing.assert_allclose(y_i8, y_f32, rtol=5e-2, atol=5e-2)

    def test_int8_under_tp_specs_typed_refusal(self):
        from chainermn_tpu.parallel.meshplan import MeshPlan
        from jax.sharding import PartitionSpec as P
        _m, params, apply_fn, example = _mlp_setup()
        with pytest.raises(NotImplementedError):
            InferenceEngine(apply_fn, params, example, max_batch=8,
                            policy=precision.Int8Policy(),
                            plan=MeshPlan.create(tp=2),
                            param_specs=jax.tree_util.tree_map(
                                lambda _: P(), params))


# ---------------------------------------------------------------------
# MeshPlan serving + elastic checkpoint loading

class TestShardedServing:
    def test_plan_serving_matches_single_device(self):
        from chainermn_tpu.parallel.meshplan import MeshPlan
        _m, params, apply_fn, example = _mlp_setup()
        plain = InferenceEngine(apply_fn, params, example,
                                max_batch=16)
        plan = MeshPlan.create(tp=1)  # pure data-parallel serving
        sharded = InferenceEngine(apply_fn, params, example,
                                  max_batch=16, plan=plan)
        # buckets not divisible over the data axes were dropped
        assert all(b % plan.data_size == 0 for b in sharded.edges)
        plain.warmup()
        sharded.warmup()
        b = sharded.edges[-1]
        x = np.random.RandomState(4).rand(b, 48).astype(np.float32)
        np.testing.assert_allclose(np.asarray(sharded.infer(x)),
                                   np.asarray(plain.infer(x)),
                                   rtol=1e-5, atol=1e-5)

    def test_from_elastic_checkpoint(self, tmp_path):
        """Engine loads params topology-portably from a PR 5 npz
        snapshot (crc-verified, prefix 'params')."""
        from chainermn_tpu import serializers
        model, params, apply_fn, example = _mlp_setup()
        path = serializers.save_npz(
            str(tmp_path / 'snap'), {'params': params, 'iteration': 7})
        eng = InferenceEngine.from_checkpoint(
            str(path), model, {'params': params}, example, max_batch=4)
        eng.warmup()
        x = np.random.RandomState(5).rand(4, 48).astype(np.float32)
        want = np.asarray(model.apply({'params': params},
                                      jnp.asarray(x)))
        np.testing.assert_allclose(np.asarray(eng.infer(x)), want,
                                   rtol=1e-5, atol=1e-5)

    def test_corrupt_checkpoint_typed(self, tmp_path):
        from chainermn_tpu import serializers
        from chainermn_tpu.utils import failure
        model, params, apply_fn, example = _mlp_setup()
        path = serializers.save_npz(str(tmp_path / 'snap'),
                                    {'params': params})
        size = os.path.getsize(path)
        with open(path, 'r+b') as f:
            f.truncate(size // 2)
        with pytest.raises(failure.CheckpointCorruptError):
            serving.load_params(path, params)


# ---------------------------------------------------------------------
# end-to-end open loop + acceptance

class TestOpenLoopEndToEnd:
    def test_overload_sheds_typed_and_serves_the_rest(self):
        """ISSUE 10 acceptance: open-loop generator above capacity ->
        typed OverloadError shedding, p50/p99 from telemetry
        histograms, bucket hit-rate > 0, no retracing during
        traffic."""
        _m, params, apply_fn, example = _mlp_setup(n_units=64)
        eng = InferenceEngine(apply_fn, params, example, max_batch=16)
        eng.warmup()
        # tiny bounded queue + absurd offered rate = guaranteed
        # saturation
        q = RequestQueue(max_batch=16, max_wait=0.005, max_queue=16)
        rep = serving.open_loop(eng, q, rate=50000.0, n_requests=300,
                                seed=7)
        assert rep['served'] > 0
        assert rep['shed_submit'] > 0  # overload shed, not wedged
        assert rep['shed_fraction'] > 0
        assert rep['served'] + rep['shed_submit'] \
            + rep['shed_deadline'] + rep['errored'] == 300
        assert rep['latency_p50_ms'] is not None
        assert rep['latency_p99_ms'] >= rep['latency_p50_ms']
        assert rep['pad_waste_fraction'] is not None
        assert rep['bucket_hit_rate'] > 0
        # AOT warm start: zero traffic-time compiles
        assert rep['compile_count'] == len(eng.edges)

    def test_open_loop_deterministic_mix(self):
        _m, params, apply_fn, example = _mlp_setup()
        reports = []
        for _ in range(2):
            eng = InferenceEngine(apply_fn, params, example,
                                  max_batch=8, aot=False)
            eng.warmup()
            q = RequestQueue(max_batch=8, max_wait=0.001,
                             max_queue=64)
            reports.append(serving.open_loop(
                eng, q, rate=400.0, n_requests=30, seed=11))
        assert reports[0]['offered'] == reports[1]['offered']
        assert reports[0]['served'] == reports[1]['served'] == 30


# ---------------------------------------------------------------------
# telemetry doctor serve recognition (ISSUE 10 satellite)

class TestDoctorServeRecognition:
    def _serve_capture(self, tmp_path):
        _m, params, apply_fn, example = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, example, max_batch=8,
                              aot=False)
        eng.warmup()
        q = RequestQueue(max_batch=8, max_wait=0.001, max_queue=64)
        cap = str(tmp_path / 'cap')
        serving.open_loop(eng, q, rate=500.0, n_requests=20,
                          capture_dir=cap)
        return cap

    def test_quick_verdict_not_empty_on_serve_window(self, tmp_path):
        from chainermn_tpu.telemetry import diagnosis
        cap = self._serve_capture(tmp_path)
        diag = diagnosis.quick_verdict(cap)
        assert diag is not None
        assert diag['serve']['requests'] == 20
        assert diag['serve']['latency_ms']['p50'] is not None
        assert any('serving capture' in s
                   for s in diag['verdict']['summary'])

    def test_doctor_cli_exit_0_on_metrics_only_serve_window(
            self, tmp_path):
        """The regression pin: a serve capture holding ONLY metrics
        (no event log) must not be reported as EMPTY (exit 2)."""
        from chainermn_tpu.telemetry import diagnosis
        cap = self._serve_capture(tmp_path)
        only = tmp_path / 'metrics_only'
        only.mkdir()
        data = json.load(open(os.path.join(cap, 'metrics-rank0.json')))
        with open(only / 'metrics-rank0.json', 'w') as f:
            json.dump(data, f)
        assert diagnosis.quick_verdict(str(only)) is not None
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        for sub in ('doctor', 'report'):
            p = subprocess.run(
                [sys.executable, '-m', 'chainermn_tpu.telemetry', sub,
                 str(only)], capture_output=True, text=True, env=env)
            assert p.returncode == 0, (sub, p.stdout, p.stderr)
            assert 'serving' in p.stdout

    def test_truly_empty_capture_still_exit_2(self, tmp_path):
        empty = tmp_path / 'empty'
        empty.mkdir()
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        p = subprocess.run(
            [sys.executable, '-m', 'chainermn_tpu.telemetry',
             'doctor', str(empty)], capture_output=True, text=True,
            env=env)
        assert p.returncode == 2

    def test_serve_execute_spans_feed_anomaly_scan(self, tmp_path):
        """serve_execute spans carry iteration=batch index, so the
        doctor's within-run anomaly machinery sees serve batches the
        way it sees training steps."""
        from chainermn_tpu.telemetry import diagnosis
        spans = [
            {'type': 'span', 'name': 'serve_execute', 'kind': 'serve',
             't0': i * 0.01, 't1': i * 0.01 + (0.5 if i == 9
                                               else 0.002),
             'iteration': i, 'rank': 0}
            for i in range(12)]
        rows = diagnosis.step_anomalies(spans)
        assert rows and rows[0]['phase'] == 'serve_execute'
        assert rows[0]['iteration'] == 9


# ---------------------------------------------------------------------
# autoregressive generation (ISSUE 11): continuous batching over the
# prefill/decode AOT split

class TestGenerationQueue:
    def test_bounded_queue_sheds_typed(self):
        q = serving.GenerationQueue(max_prompt_len=8, max_queue=2)
        q.submit([1, 2], 4)
        q.submit([3], 4)
        with pytest.raises(OverloadError) as ei:
            q.submit([4], 4)
        assert ei.value.reason == 'queue_full'
        assert q.shed_queue_full == 1

    def test_over_length_prompt_client_error(self):
        q = serving.GenerationQueue(max_prompt_len=4)
        with pytest.raises(ValueError, match='exceeds'):
            q.submit([1, 2, 3, 4, 5], 4)
        assert q.depth() == 0

    def test_close_sheds_shutdown(self):
        q = serving.GenerationQueue(max_prompt_len=8)
        req = q.submit([1], 4)
        q.close()
        with pytest.raises(OverloadError) as ei:
            req.result(timeout=0)
        assert ei.value.reason == 'shutdown'
        with pytest.raises(OverloadError):
            q.submit([1], 4)

    def test_pop_sheds_expired_deadline_typed(self):
        clock = [0.0]
        q = serving.GenerationQueue(max_prompt_len=8,
                                    clock=lambda: clock[0])
        dead = q.submit([1], 4, deadline=0.5)
        live = q.submit([2], 4)
        clock[0] = 1.0
        out = q.pop(2)
        assert [r is live for r in out] == [True]
        with pytest.raises(OverloadError) as ei:
            dead.result(timeout=0)
        assert ei.value.reason == 'deadline'
        assert q.shed_deadline == 1

    def test_serve_burst_amplifies_through_bounded_admission(self):
        chaos.install(chaos.FaultInjector('serve_burst=@0:8'))
        try:
            q = serving.GenerationQueue(max_prompt_len=8, max_queue=4)
            req = q.submit([1, 2], 4)
            assert not req.done()
            assert q.depth() == 4  # burst filled to capacity, rest shed
        finally:
            chaos.uninstall()


class TestContinuousBatching:
    def test_finished_slot_serves_new_request_next_decode_step(self):
        """THE acceptance observable: sequence B finishes while A is
        still generating; B's cache slot serves request C at the NEXT
        decode step -- not at batch end -- and the decode executable
        never retraces across the refill."""
        model, params = _tiny_lm()
        eng = serving.GenerationEngine(model, params, n_slots=2,
                                       max_prompt_len=4)
        eng.warmup()
        traces0 = eng.stats()['decode_trace_count']
        compiles0 = eng.stats()['compile_count']
        q = serving.GenerationQueue(max_prompt_len=4)
        a = q.submit([1, 2], 8)
        b = q.submit([3], 2)
        c = q.submit([4, 5], 3)
        eng.step(q)           # A+B prefill (C waits), call 1 goes out
        assert not b.done()   # ... and is in flight: B ends in it
        eng.step(q)           # a foreseen end: read, emit, hand back
        assert b.done()       # B: prefill token + 1 decoded = 2
        assert not a.done()
        assert len(eng._free) == 1
        freed = eng._free[0]
        eng.step(q)           # the refill step
        assert not a.done()   # A is still mid-generation: token-level
        assert eng._slots[freed].request is c   # admission, not batch
        st = eng.stats()
        assert st['decode_trace_count'] == traces0
        assert st['compile_count'] == compiles0
        # drain everything
        for _ in range(20):
            if a.done() and c.done():
                break
            eng.step(q)
        assert len(a.result()) == 8 and len(c.result()) == 3

    def test_deadline_expiry_mid_generation_frees_slot_typed(self):
        model, params = _tiny_lm()
        eng = serving.GenerationEngine(model, params, n_slots=1,
                                       max_prompt_len=4)
        eng.warmup()
        clock = [0.0]
        q = serving.GenerationQueue(max_prompt_len=4,
                                    clock=lambda: clock[0])
        doomed = q.submit([1], 100, deadline=5.0)
        waiting = q.submit([2], 5)
        eng.step(q, clock=lambda: clock[0])   # doomed occupies slot 0
        assert not doomed.done()
        clock[0] = 10.0                       # deadline passes
        eng.step(q, clock=lambda: clock[0])   # expire -> refill
        with pytest.raises(OverloadError) as ei:
            doomed.result(timeout=0)
        assert ei.value.reason == 'deadline'
        assert eng._slots and eng._slots[0].request is waiting
        assert eng.cancelled == 1

    def test_serve_cancel_chaos_site(self):
        chaos.install(chaos.FaultInjector('serve_cancel=@1'))
        try:
            model, params = _tiny_lm()
            eng = serving.GenerationEngine(model, params, n_slots=2,
                                           max_prompt_len=4)
            eng.warmup()
            q = serving.GenerationQueue(max_prompt_len=4)
            victim = q.submit([1], 50)
            eng.step(q)   # occurrence 0: no fire
            eng.step(q)   # occurrence 1: forced mid-generation cancel
            assert victim.done()
            with pytest.raises(OverloadError) as ei:
                victim.result(timeout=0)
            assert ei.value.reason == 'deadline'
            assert eng.stats()['cancelled'] == 1
            assert len(eng._free) == 2   # slot freed, never leaked
        finally:
            chaos.uninstall()

    def test_greedy_matches_reference_loop(self):
        model, params = _tiny_lm(n_layers=2)
        eng = serving.GenerationEngine(model, params, n_slots=2,
                                       max_prompt_len=8)
        eng.warmup()
        prompt = np.asarray([3, 7, 11, 2], np.int32)
        toks = list(prompt)
        want = []
        for _ in range(5):
            logits = model.apply({'params': params},
                                 jnp.asarray([toks], jnp.int32))
            tok = int(jnp.argmax(logits[0, -1]))
            want.append(tok)
            toks.append(tok)
        q = serving.GenerationQueue(max_prompt_len=8)
        req = q.submit(prompt, 5)
        for _ in range(10):
            if req.done():
                break
            eng.step(q)
        assert [int(t) for t in req.result()] == want

    def test_full_bucket_decode_with_free_mid_slot_keeps_parity(self):
        """Regression: 3 of 4 active slots bucket UP to the full-slot
        executable (decode edges [1, 2, 4]), whose cache read is in
        place -- row i IS slot i.  A middle slot freed mid-flight must
        not shift the survivors onto each other's KV rows: every
        remaining request still matches the full-forward greedy
        reference across the non-identity full-bucket steps."""
        model, params = _tiny_lm(n_layers=2)

        def reference(prompt, n_new):
            toks = [int(t) for t in prompt]
            out = []
            for _ in range(n_new):
                logits = model.apply({'params': params},
                                     jnp.asarray([toks], jnp.int32))
                tok = int(jnp.argmax(logits[0, -1]))
                out.append(tok)
                toks.append(tok)
            return out

        eng = serving.GenerationEngine(model, params, n_slots=4,
                                       max_prompt_len=8)
        eng.warmup()
        traces0 = eng.stats()['decode_trace_count']
        q = serving.GenerationQueue(max_prompt_len=8)
        prompts = ([3, 7, 11], [2, 9], [13, 1, 4, 6], [8, 8, 5])
        n_new = (6, 2, 6, 6)   # slot 1 finishes after one decode step
        reqs = [q.submit(p, n) for p, n in zip(prompts, n_new)]
        eng.step(q)            # four prefills + identity decode step
        eng.step(q)            # ... read a tick later: slot 1 ends in it
        assert reqs[1].done()
        assert eng._free == [1]   # a MIDDLE slot freed, 0/2/3 live
        for _ in range(10):
            if all(r.done() for r in reqs):
                break
            eng.step(q)        # k=3 -> bucket=4: the in-place path
        for req, p, n in zip(reqs, prompts, n_new):
            assert [int(t) for t in req.result()] == reference(p, n)
        assert eng.stats()['decode_trace_count'] == traces0

    def test_eos_stops_early(self):
        model, params = _tiny_lm(n_layers=2)
        # find what the model emits first, then declare it EOS
        probe = serving.GenerationEngine(model, params, n_slots=1,
                                         max_prompt_len=4)
        probe.warmup()
        q = serving.GenerationQueue(max_prompt_len=4)
        req = q.submit([5], 1)
        while not req.done():
            probe.step(q)
        eos = int(req.result()[0])
        eng = serving.GenerationEngine(model, params, n_slots=1,
                                       max_prompt_len=4, eos_id=eos)
        eng.warmup()
        q2 = serving.GenerationQueue(max_prompt_len=4)
        req2 = q2.submit([5], 50)
        while not req2.done():
            eng.step(q2)
        out = [int(t) for t in req2.result()]
        assert out[-1] == eos
        assert len(out) < 50

    def test_signature_guard_refuses_off_bucket(self):
        model, params = _tiny_lm()
        eng = serving.GenerationEngine(model, params, n_slots=2,
                                       max_prompt_len=4)
        eng.warmup()
        bogus = (jax.ShapeDtypeStruct((3,), jnp.int32),)
        with pytest.raises(RuntimeError, match='no-recompile guard'):
            eng.guard_signature(bogus)

    def test_int8_weights_under_tp_specs_typed_refusal(self):
        from jax.sharding import PartitionSpec as P
        from chainermn_tpu.parallel.meshplan import MeshPlan
        plan = MeshPlan.create(tp=2)
        model, params = _tiny_lm()
        model = model.clone(tp_axis=plan.model_axis)
        with pytest.raises(NotImplementedError):
            serving.GenerationEngine(
                model, params, n_slots=2, max_prompt_len=4,
                policy=precision.Int8Policy(), plan=plan,
                param_specs=jax.tree_util.tree_map(lambda _: P(),
                                                   params))


class TestOpenLoopGenerate:
    def test_report_fields_and_accounting(self):
        model, params = _tiny_lm()
        eng = serving.GenerationEngine(model, params, n_slots=2,
                                       max_prompt_len=4)
        eng.warmup()
        traces0 = eng.stats()['decode_trace_count']
        q = serving.GenerationQueue(max_prompt_len=4, max_queue=8)
        rep = serving.open_loop_generate(
            eng, q, rate=300.0, n_requests=10, seed=3,
            prompt_len_range=(1, 4), max_new_tokens=4)
        assert rep['served'] + rep['shed_submit'] \
            + rep['shed_deadline'] + rep['errored'] == 10
        assert rep['served'] > 0
        assert rep['tokens_served'] == 4 * rep['served']
        assert rep['tokens_per_s'] > 0
        assert rep['ttft_p50_ms'] is not None
        assert rep['ttft_p99_ms'] >= rep['ttft_p50_ms']
        assert rep['intertoken_p50_ms'] is not None
        assert rep['decode_trace_count'] == traces0  # no retrace
        assert rep['n_slots'] == 2

    def test_int8_kv_arm_serves(self):
        model, params = _tiny_lm()
        eng = serving.GenerationEngine(model, params, n_slots=2,
                                       max_prompt_len=4,
                                       int8_kv=True)
        eng.warmup()
        q = serving.GenerationQueue(max_prompt_len=4)
        rep = serving.open_loop_generate(
            eng, q, rate=300.0, n_requests=6, seed=4,
            prompt_len_range=(1, 4), max_new_tokens=3)
        assert rep['served'] == 6
        assert rep['int8_kv'] is True


# ---------------------------------------------------------------------
# shardlint decode_forward target (ISSUE 11 satellite)

class TestDecodeForwardLintTarget:
    @pytest.mark.slow
    def test_decode_forward_swept_and_clean(self):
        from chainermn_tpu.analysis import runner, targets
        t = targets.decode_forward_target()
        assert t.name == 'step:decode_forward'
        assert t.plan_axes == ('model',)
        # iteration-independent signature: the SL007 static twin of
        # the flat-trace-count pin
        assert targets.LintTarget  # imported symbol sanity
        import chainermn_tpu.analysis.walker as walker
        s1 = walker.abstract_signature(t.make_args(1))
        s2 = walker.abstract_signature(t.make_args(7))
        assert s1 == s2
        findings = runner.lint_target(t)
        errors = [f for f in findings if f.severity == 'error']
        assert not errors, errors
        multi = [f for f in findings
                 if f.rule_id in ('SL010', 'SL011', 'SL012')]
        assert not multi, multi
        assert {f.rule_id for f in findings} <= {'SL008'}

    @pytest.mark.slow
    def test_decode_forward_in_default_step_sweep(self):
        from chainermn_tpu.analysis import targets
        names = [t.name for t in targets.step_targets(
            include_resnet50=False)]
        assert 'step:decode_forward' in names


# ---------------------------------------------------------------------
# shardlint serve_forward target (ISSUE 10 satellite)

class TestServeForwardLintTarget:
    @pytest.mark.slow
    def test_serve_forward_swept_and_clean(self):
        from chainermn_tpu.analysis import runner, targets
        t = targets.serve_forward_target()
        assert t.name == 'step:serve_forward'
        assert t.plan_axes == ('model',)
        findings = runner.lint_target(t)
        errors = [f for f in findings if f.severity == 'error']
        assert not errors, errors
        multi = [f for f in findings
                 if f.rule_id in ('SL010', 'SL011', 'SL012')]
        assert not multi, multi
        # the one pinned warning: the lm head's deliberate f32
        # contraction (models/transformer.py vocab-head numerics)
        assert {f.rule_id for f in findings} <= {'SL008'}

    @pytest.mark.slow
    def test_serve_forward_in_default_step_sweep(self):
        from chainermn_tpu.analysis import targets
        names = [t.name for t in targets.step_targets(
            include_resnet50=False)]
        assert 'step:serve_forward' in names


# ---------------------------------------------------------------------
# live weight hot-swap (ISSUE 13): the fleet's per-replica primitive


class TestWeightSwap:
    def test_swap_no_retrace_and_output_changes(self):
        model, params, apply_fn, item = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, item, max_batch=4,
                              label='rep-0', version=3)
        eng.warmup()
        x = np.random.RandomState(0).rand(4, 48).astype(np.float32)
        y1 = np.asarray(eng.infer(x))
        traces = eng.trace_count
        scaled = jax.tree_util.tree_map(lambda a: a * 1.5, params)
        assert eng.swap_params(scaled, version=7) == 7
        y2 = np.asarray(eng.infer(x))
        # shape-keyed executables: the swap never retraces, and the
        # new weights demonstrably serve
        assert eng.trace_count == traces
        assert eng.param_version == 7
        assert not np.allclose(y1, y2)

    def test_swap_nonfinite_refused_typed_incumbent_serves(self):
        from chainermn_tpu.utils.failure import WeightSwapError
        model, params, apply_fn, item = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, item, max_batch=2)
        eng.warmup()
        x = np.random.RandomState(0).rand(2, 48).astype(np.float32)
        y1 = np.asarray(eng.infer(x))
        poisoned = jax.tree_util.tree_map(
            lambda a: np.full_like(np.asarray(a), np.nan), params)
        with pytest.raises(WeightSwapError):
            eng.swap_params(poisoned, version=9)
        # validation failed BEFORE cutover: version and outputs intact
        assert eng.param_version == 0
        np.testing.assert_allclose(np.asarray(eng.infer(x)), y1)

    def test_swap_from_checkpoint_roundtrip(self, tmp_path):
        from chainermn_tpu import serializers
        model, params, apply_fn, item = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, item, max_batch=2)
        eng.warmup()
        scaled = jax.tree_util.tree_map(
            lambda a: np.asarray(a) * 2.0, params)
        path = serializers.save_npz(str(tmp_path / 'snapshot_iter_8'),
                                    {'params': scaled})
        assert eng.swap_from_checkpoint(path, version=8) == 8
        x = np.random.RandomState(1).rand(2, 48).astype(np.float32)
        ref = model.apply({'params': scaled}, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(eng.infer(x)),
                                   np.asarray(ref), rtol=1e-5)

    def test_generation_swap_refused_while_slots_live(self):
        from chainermn_tpu.serving.generate import (GenerationEngine,
                                                    GenerationQueue)
        from chainermn_tpu.utils.failure import WeightSwapError
        model, params = _tiny_lm()
        eng = GenerationEngine(model, params, n_slots=2,
                               max_prompt_len=4)
        eng.warmup()
        q = GenerationQueue(4)
        q.submit([1, 2], 8)
        eng.step(q)   # prompt admitted: a live slot now holds KV
        assert eng._slots
        with pytest.raises(WeightSwapError):
            eng.swap_params(params, version=5)
        assert eng.param_version == 0
        # drain (finish the sequence), then the swap goes through
        # with a FLAT decode trace count -- the roll's no-retrace pin
        while eng._slots:
            eng.step(q)
        traces = eng.decode_trace_count
        scaled = jax.tree_util.tree_map(lambda a: a * 1.01, params)
        assert eng.swap_params(scaled, version=5) == 5
        req = q.submit([3, 1], 4)
        while not req.done():
            eng.step(q)
        assert len(req.result(timeout=5)) == 4
        assert eng.decode_trace_count == traces

    def test_request_id_passthrough_both_queues(self):
        from chainermn_tpu.serving.generate import GenerationQueue
        q = RequestQueue(max_batch=4)
        assert q.submit(np.zeros((1, 3), np.float32),
                        request_id='r777').request_id == 'r777'
        g = GenerationQueue(8)
        assert g.submit([1], 2,
                        request_id='r778').request_id == 'r778'

    def test_version_labels_on_serve_records(self):
        from chainermn_tpu import telemetry
        model, params, apply_fn, item = _mlp_setup()
        eng = InferenceEngine(apply_fn, params, item, max_batch=2,
                              label='rep-7', version=4)
        eng.warmup()
        installed = telemetry.active() is None
        if installed:
            telemetry.enable()
        try:
            q = RequestQueue(max_batch=2, max_wait=0.001,
                             label='rep-7')
            req = q.submit(np.zeros((1, 48), np.float32))
            for pb in q.take(timeout=1.0):
                eng.serve_packed(pb)
            req.result(timeout=5)
            recs = [r for r in list(telemetry.active().events)
                    if r.get('replica') == 'rep-7']
            assert recs, 'no replica-labeled records'
            assert {r.get('version') for r in recs} == {4}
            stages = {r.get('name') for r in recs
                      if r.get('kind') == 'request'}
            assert {'queue_wait', 'bucket_pack',
                    'execute'} <= stages
        finally:
            if installed:
                telemetry.disable()
