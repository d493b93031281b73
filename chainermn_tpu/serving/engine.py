"""AOT-compiled inference engine over bucketed batch shapes.

The training stack compiles one step and reuses it; a serving path
faces the opposite shape economy -- every request mix is a new batch
shape, and an XLA retrace mid-traffic is a multi-second p99 cliff.
The engine closes that hole with three mechanisms:

- **Pre-lowered per-bucket executables.**  For every bucket edge the
  batcher can emit, the forward-only ``apply`` is compiled ONCE --
  ahead of time, ``jax.jit(...).lower(...).compile()`` (a lowering
  error is an error; ``aot=False`` asks for plain ``jit``) -- and
  stored keyed on the bucket.  ``warmup()`` compiles all buckets
  eagerly so no request ever pays a trace or a compile.
- **Persistent compilation cache.**  Every engine turns jax's
  persistent compilation cache on
  (:func:`~chainermn_tpu.utils.platform.enable_compilation_cache`:
  ``JAX_COMPILATION_CACHE_DIR`` where set, else the checkout's
  ``.jax_compile_cache``), so a RESTARTED engine's warmup
  deserializes executables instead of re-running XLA's compile
  (tracing and lowering are paid again).  The cache layout is jax's
  own (one
  ``...-cache`` entry per executable fingerprint);
  ``docs/serving.md`` documents it.
- **No-recompile runtime guard.**  The SL007 recompilation rule's
  signature machinery (:func:`chainermn_tpu.analysis.walker.
  abstract_signature` -- what jit keys its cache on) doubles as a
  runtime pin: the engine precomputes the signature of every bucket
  shape and REFUSES any batch whose signature is not in that set
  (typed ``RuntimeError``) instead of silently retracing.  The
  static twin is the ``step:serve_forward`` shardlint target.

Sharded serving composes with the PR 7 :class:`~chainermn_tpu.
parallel.MeshPlan`: pass ``plan=`` (and ``param_specs=`` for
tensor-parallel weights) and the forward runs shard_mapped over the
plan mesh -- the batch sharded over ``data``, tensor-parallel psums
over ``model`` inserted by the model itself.  Quantized serving
composes with :class:`~chainermn_tpu.precision.Int8Policy`: weights
are stored int8 + per-channel scales and dequantized IN the compiled
graph (:mod:`chainermn_tpu.ops.int8_matmul`).

Telemetry (PR 6 registry): per-batch ``serve_queue_wait`` /
``serve_h2d`` / ``serve_execute`` spans, raw-sample histograms of the
same phases plus per-request ``serve_latency_seconds`` and per-batch
``serve_pad_waste`` -- p50/p99 come from the histograms, never from
averaged percentiles.
"""

import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.analysis.walker import abstract_signature
from chainermn_tpu.serving.batcher import bucket_edges
from chainermn_tpu.utils import chaos as _chaos
from chainermn_tpu.utils.platform import enable_compilation_cache


def load_params(path, template, prefix='params'):
    """Topology-portable parameter load from an elastic-resume
    checkpoint (PR 5): the npz snapshots the preemption handler and
    the snapshot extension write carry collectively regathered,
    crc-verified leaves, so ANY process layout can read them back --
    a serving replica needs no knowledge of the training topology.
    Integrity failures raise the typed ``CheckpointCorruptError``
    chain unchanged."""
    from chainermn_tpu import serializers
    by_key, _manifest = serializers.read_npz(path)
    return serializers._fetch_tree(by_key, template, prefix, path)


class InferenceEngine:
    """Forward-only serving executable set for one model.

    Args:
      apply_fn: ``apply_fn(params, x) -> y`` -- the forward pass
        (e.g. ``lambda p, x: model.apply({'params': p}, x)``).
      params: the parameter pytree (host or device).
      example: ONE item (no batch dim) as array/ShapeDtypeStruct --
        the shape template bucket executables are lowered against.
      max_batch / edges: bucket geometry (power-of-two by default,
        ``edges`` overrides; the engine serves exactly these shapes).
      policy: optional :class:`~chainermn_tpu.precision.Policy`.
        A float policy casts params + inputs to its compute dtype; an
        :class:`~chainermn_tpu.precision.Int8Policy` quantizes the
        params at load and dequantizes in-graph.
      plan / param_specs: optional MeshPlan sharded serving (batch
        over the data axes, params per ``param_specs`` or
        replicated).  Buckets not divisible by the data-axis size are
        dropped (a shard_map batch must split evenly).
      aot: compile bucket executables ahead of time (default);
        ``aot=False`` serves from plain ``jit`` instead.
      label / version: fleet identity.  ``label`` names this engine
        as a replica; when set, every serve-path record (spans,
        request stage spans, complete/shed events) carries
        ``replica``/``version`` attributes so a per-replica,
        per-version SLO monitor can filter one engine's traffic out
        of a shared recorder stream.  ``version`` is the parameter
        version served at boot (:meth:`swap_params` advances it).
    """

    def __init__(self, apply_fn, params, example, max_batch=32,
                 edges=None, policy=None, plan=None, param_specs=None,
                 aot=True, label=None, version=0):
        _telemetry.maybe_enable_from_env()
        _telemetry.install_compile_log()
        self.apply_fn = apply_fn
        self.policy = policy
        self.plan = plan
        self.label = label
        self.param_version = int(version)
        self._boot_version = self.param_version
        self.max_batch = int(max_batch)
        edges = tuple(edges) if edges else bucket_edges(max_batch)
        if plan is not None:
            kept = tuple(e for e in edges if e % plan.data_size == 0)
            if not kept:
                raise ValueError(
                    'no bucket edge in %r divides over the data axes '
                    '(size %d); raise max_batch or pass edges'
                    % (edges, plan.data_size))
            edges = kept
        self.edges = edges
        self.cache_dir = enable_compilation_cache()
        self.aot_requested = bool(aot)

        ex = (example if hasattr(example, 'shape')
              else np.asarray(example))
        self._item_shape = tuple(ex.shape)
        in_dtype = np.dtype(getattr(ex, 'dtype', np.float32))
        if policy is not None and np.issubdtype(in_dtype, np.floating):
            in_dtype = np.dtype(policy.compute_dtype)
        self._in_dtype = in_dtype

        if param_specs is not None and plan is None:
            raise ValueError('param_specs requires a plan')
        self.param_specs = param_specs

        # load-time parameter transform: quantize (int8 policy) or
        # cast to compute dtype (float policy; an inference engine
        # holds no f32 masters -- there is no optimizer to feed)
        quantize = getattr(policy, 'quantize', None)
        if quantize is not None and param_specs is not None:
            raise NotImplementedError(
                'int8 weights under tensor-parallel param_specs '
                'are not wired yet: quantize per shard after '
                'resharding, or serve the tp model in bf16')
        self.quantized = quantize is not None
        # structure/shape template of the UNtransformed host tree --
        # what checkpoint loads for later hot-swaps validate against
        # (shapes only; no host copy is retained)
        self._params_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                np.shape(x), np.asarray(x).dtype
                if not hasattr(x, 'dtype') else x.dtype), params)
        self.params = self._place_params(params)

        self._compiled = {}   # bucket -> callable(params, x)
        self._aot = {}        # bucket -> True when AOT-compiled
        self._signatures = {} # bucket -> abstract signature
        self._lock = threading.Lock()
        self.trace_count = 0  # incremented INSIDE the traced forward
        self.compile_count = 0
        self.executions = 0
        self._batch_index = 0
        self._mapped = self._build_mapped(param_specs)

    # -- forward construction ------------------------------------------
    def _param_sharding(self):
        if self.plan is None:
            return jax.devices()[0]
        if self.param_specs is None:
            return self.plan.replicated()
        return self.plan.param_shardings(self.param_specs)

    def _place_params(self, params):
        """The load-time parameter transform (quantize under an int8
        policy, cast under a float one) + device placement -- shared
        by construction and every later hot-swap, so a swapped tree
        goes through the identical pipeline the boot tree did."""
        if self.quantized:
            return jax.device_put(self.policy.quantize(params),
                                  self._param_sharding())
        host = params
        if self.policy is not None:
            from chainermn_tpu.precision import cast_floating
            host = cast_floating(host, self.policy.compute_dtype)
        return jax.device_put(host, self._param_sharding())

    def _ident(self):
        """Replica/version attrs stamped on serve-path records when
        the engine has a fleet identity (empty otherwise, keeping
        single-engine record schemas unchanged)."""
        if self.label is None:
            return {}
        return {'replica': self.label, 'version': self.param_version}

    def _forward(self, params, x):
        # tracing-only counter: the body runs at trace time, so this
        # increments exactly once per compilation -- the warm-start /
        # no-retrace assertion tests pin it
        self.trace_count += 1
        policy = self.policy
        if self.quantized:
            params = policy.dequantize(params)
        y = self.apply_fn(params, x)
        if policy is not None:
            from chainermn_tpu.precision import cast_floating
            y = cast_floating(y, policy.output_dtype
                              or policy.compute_dtype)
        return y

    def _build_mapped(self, param_specs):
        if self.plan is None:
            return self._forward
        from jax.sharding import PartitionSpec as P
        plan = self.plan
        in_specs = (param_specs if param_specs is not None else P(),
                    plan.batch_spec())
        return jax.shard_map(
            self._forward, mesh=plan.mesh, in_specs=in_specs,
            out_specs=plan.batch_spec(), check_vma=False)

    def traceable_forward(self, bucket=None):
        """``(fn, args)`` for ``jax.make_jaxpr`` -- the EXACT mapped
        callable the engine compiles, on a zeros batch of ``bucket``
        items: the shardlint ``step:serve_forward`` target traces
        production code, not a test double."""
        bucket = bucket or self.edges[-1]
        x = jnp.zeros((bucket,) + self._item_shape, self._in_dtype)
        return self._mapped, (self.params, x)

    def _batch_struct(self, bucket):
        return jax.ShapeDtypeStruct((bucket,) + self._item_shape,
                                    self._in_dtype)

    def _compile_bucket(self, bucket):
        exe = jax.jit(self._mapped)
        if self.aot_requested:
            exe = exe.lower(self.params,
                            self._batch_struct(bucket)).compile()
        # aot=False: plain jit -- first call traces+compiles, later
        # calls hit the jit cache; results identical
        self._aot[bucket] = self.aot_requested
        self._compiled[bucket] = exe
        self._signatures[bucket] = abstract_signature(
            (self._batch_struct(bucket),))
        self.compile_count += 1
        return exe

    # -- public surface ------------------------------------------------
    def warmup(self):
        """Compile (or cache-load) every bucket executable eagerly,
        largest first (the largest compile dominates; failing fast on
        it beats discovering the OOM at traffic time).  Returns
        ``{bucket: aot?}``."""
        reg = _telemetry.registry()
        for bucket in sorted(self.edges, reverse=True):
            if bucket in self._compiled:
                continue
            with _telemetry.span('serve_warmup', kind='serve',
                                 bucket=bucket):
                t0 = time.perf_counter()
                exe = self._compile_bucket(bucket)
                if not self._aot[bucket]:
                    # plain jit: force the compile NOW -- warmup
                    # exists so traffic never traces
                    x = jnp.zeros((bucket,) + self._item_shape,
                                  self._in_dtype)
                    jax.block_until_ready(exe(self.params, x))
                if reg is not None:
                    reg.histogram(
                        'serve_warmup_seconds',
                        help='per-bucket warmup compile/load time'
                    ).observe(time.perf_counter() - t0)
        return dict(self._aot)

    # -- live weight hot-swap (fleet roll) -----------------------------
    def swap_params(self, params, version=None, validate=True):
        """Hot-swap the served parameter tree WITHOUT recompiling.

        The bucket executables are keyed on shapes, not values, so a
        same-shape tree slots straight in: the new tree is placed
        through :meth:`_place_params` (double-buffered -- both
        versions live on device from here), optionally validated by
        running the largest compiled bucket on zeros and checking the
        output finite, and only then CUT OVER by rebinding
        ``self.params`` (in-flight executions keep the old reference
        they already loaded; the old buffers are freed when the last
        of them completes).  ``trace_count`` stays flat across a swap
        -- the no-retrace property the fleet's roll depends on.

        Raises :class:`~chainermn_tpu.utils.failure.WeightSwapError`
        (engine unchanged, still serving the old version) when
        validation fails."""
        from chainermn_tpu.utils.failure import WeightSwapError
        new = self._place_params(params)
        if validate and self._compiled:
            bucket = max(self._compiled)
            x = jnp.zeros((bucket,) + self._item_shape, self._in_dtype)
            try:
                y = jax.block_until_ready(
                    self._compiled[bucket](new, x))
            except Exception as e:
                raise WeightSwapError(
                    'swap validation forward failed (%s: %s) -- '
                    'keeping the incumbent parameters'
                    % (type(e).__name__, e), version=version) from e
            probe = y[0] if isinstance(y, (tuple, list)) else y
            if not bool(np.isfinite(
                    np.asarray(jax.device_get(probe))).all()):
                raise WeightSwapError(
                    'swap validation produced non-finite outputs -- '
                    'refusing cutover to version %r' % (version,),
                    version=version)
        old = self.params
        self.params = new
        self.param_version = (int(version) if version is not None
                              else self.param_version + 1)
        del old  # the double buffer: freed after cutover
        return self.param_version

    def swap_from_checkpoint(self, path, version=None, validate=True):
        """:meth:`swap_params` fed from an elastic-resume checkpoint:
        the crc-verified ``params`` subtree is loaded against the
        boot tree's shape template (a changed architecture fails
        typed, before any cutover) and hot-swapped in."""
        return self.swap_params(
            load_params(path, self._params_template), version=version,
            validate=validate)

    def allowed_signatures(self):
        return set(self._signatures.values())

    def guard_signature(self, x):
        """The SL007 machinery as a runtime pin: refuse any batch
        whose jit signature is not one of the precompiled bucket
        signatures -- serving a shape outside the bucket set would
        retrace mid-traffic, exactly the hazard the static rule
        flags on training steps."""
        sig = abstract_signature((x,))
        if sig not in self.allowed_signatures():
            raise RuntimeError(
                'no-recompile guard: batch signature %r is outside '
                'the precompiled bucket set %r -- the batcher and '
                'engine disagree on bucket geometry'
                % (sig, sorted(self._signatures)))
        return sig

    def infer(self, x):
        """Run one already-padded batch (leading dim must be a bucket
        edge).  Compiles on first use of a bucket if ``warmup`` was
        skipped; after warmup this never traces (``trace_count``
        pins it)."""
        x = np.asarray(x)
        bucket = x.shape[0]
        exe = self._compiled.get(bucket)
        if exe is None:
            with self._lock:
                exe = self._compiled.get(bucket)
                if exe is None:
                    if bucket not in self.edges:
                        raise RuntimeError(
                            'batch of %d items is not a bucket edge '
                            '%r' % (bucket, list(self.edges)))
                    exe = self._compile_bucket(bucket)
        if x.dtype != self._in_dtype and np.issubdtype(
                x.dtype, np.floating):
            x = x.astype(self._in_dtype)
        self.guard_signature(x)
        if _chaos._active is not None:
            _chaos.on_serve_slow(
                self.param_version != self._boot_version)
        with _telemetry.span('serve_h2d', kind='h2d', bucket=bucket):
            xd = jax.device_put(
                x, self.plan.batch_sharding() if self.plan is not None
                else jax.devices()[0])
        with _telemetry.span('serve_execute', kind='serve',
                             bucket=bucket,
                             iteration=self._batch_index,
                             **self._ident()) as sp:
            y = exe(self.params, xd)
            y = jax.block_until_ready(y)
            sp.set(aot=self._aot.get(bucket, False))
        self.executions += 1
        self._batch_index += 1
        return y

    def serve_packed(self, pb, clock=None):
        """Execute one :class:`~chainermn_tpu.serving.batcher.
        PackedBatch`: collate+pad host-side (policy compute dtype),
        run the bucket executable, split the output rows back to the
        member requests, and record the serve telemetry (phase
        histograms + per-request latency + per-request trace stages
        ``queue_wait`` -> ``bucket_pack`` -> ``execute`` ->
        ``complete``, tiled so the stage budgets sum to the
        end-to-end latency)."""
        clock = clock or time.monotonic
        rec = _telemetry.live()
        reg = _telemetry.registry()
        ident = self._ident()
        t_exec0 = clock()
        queue_wait = t_exec0 - min(r.t_submit for r in pb.requests)
        # queue wait is PASSIVE time that already elapsed, so it is
        # recorded as an event + histogram, not a wrapping span
        _telemetry.event('serve_queue_wait', kind='serve',
                         seconds=queue_wait, bucket=pb.bucket,
                         iteration=self._batch_index)
        t_pack0 = rec.now() if rec is not None else None
        if rec is not None:
            pad = pb.pad_waste()
            for req in pb.requests:
                # stage 1: the wait that already elapsed, from the
                # admission stamp (or reconstructed when telemetry
                # came up mid-flight) to this drain
                t0 = req.t_trace0
                if t0 is None:
                    t0 = t_pack0 - (clock() - req.t_submit)
                rec.child_span(req.request_id, 'queue_wait', t0,
                               t_pack0, seq=req.seq, **ident)
        try:
            x, _mask = pb.collate(
                dtype=self.policy.compute_dtype
                if self.policy is not None else None)
            t_h2d0 = clock()
            t_exe0 = rec.now() if rec is not None else None
            if rec is not None:
                for req in pb.requests:
                    rec.child_span(req.request_id, 'bucket_pack',
                                   t_pack0, t_exe0, bucket=pb.bucket,
                                   pad_fraction=round(pad, 4),
                                   items=req.n, **ident)
            y = self.infer(x)
            t_done = clock()
            y_host = np.asarray(
                jax.device_get(y if not isinstance(y, (tuple, list))
                               else y[0]))
            off = 0
            for req in pb.requests:
                req.set_result(y_host[off:off + req.n])
                off += req.n
            if rec is not None:
                t_done_tele = rec.now()
                for req in pb.requests:
                    rec.child_span(req.request_id, 'execute', t_exe0,
                                   t_done_tele, bucket=pb.bucket,
                                   **ident)
                    rec.event('complete', kind='request',
                              request_id=req.request_id,
                              bucket=pb.bucket, **ident)
        except Exception as e:
            for req in pb.requests:
                if not req.done():
                    req.set_error(e)
                    if rec is not None:
                        rec.event('error', kind='request',
                                  request_id=req.request_id,
                                  error=type(e).__name__, **ident)
            raise
        if reg is not None:
            reg.histogram(
                'serve_queue_wait',
                help='oldest-request queue wait per served batch (s)'
            ).observe(queue_wait)
            reg.histogram(
                'serve_h2d',
                help='host collation + device placement + execute '
                     'dispatch per batch (s)').observe(t_h2d0 - t_exec0)
            reg.histogram(
                'serve_execute',
                help='bucket executable run-to-completion per batch '
                     '(s)').observe(t_done - t_h2d0)
            reg.histogram(
                'serve_pad_waste',
                help='padding fraction of each served batch'
            ).observe(pb.pad_waste())
            reg.histogram(
                'serve_batch_items',
                help='valid items per served batch').observe(pb.total)
            lat = reg.histogram(
                'serve_latency_seconds',
                help='submit-to-response latency per request (s)')
            now = clock()
            for req in pb.requests:
                lat.observe(now - req.t_submit)
            reg.counter('serve_requests_total',
                        help='requests answered with a result'
                        ).inc(len(pb.requests))
            reg.counter('serve_batches_total',
                        help='bucket executions').inc()
        return y_host

    def run(self, queue, stop=None, take_timeout=0.05):
        """Drain ``queue`` until ``stop`` is set and the queue is
        empty -- the serving worker loop (a daemon thread in the
        bench/load generator; errors land on the affected requests,
        never kill the loop)."""
        while True:
            batches = queue.take(timeout=take_timeout)
            if not batches:
                if stop is not None and stop.is_set() \
                        and queue.depth() == 0:
                    return
                continue
            for pb in batches:
                try:
                    self.serve_packed(pb)
                except Exception:
                    continue  # requests already carry the error

    def stats(self):
        return {
            'buckets': sorted(self._compiled),
            'edges': list(self.edges),
            'label': self.label,
            'param_version': self.param_version,
            'aot': dict(self._aot),
            'aot_requested': self.aot_requested,
            'cache_dir': self.cache_dir,
            'quantized': self.quantized,
            'trace_count': self.trace_count,
            'compile_count': self.compile_count,
            'executions': self.executions,
        }

    # -- constructors --------------------------------------------------
    @classmethod
    def for_model(cls, model, variables, example, apply_kwargs=None,
                  **kw):
        """Engine over a flax zoo module: ``variables`` is the full
        ``model.init`` result (params + any BatchNorm state -- the
        non-param collections ride along un-quantized and the forward
        runs them in eval mode via ``apply_kwargs``, e.g.
        ``{'train': False}`` for the conv zoo)."""
        apply_kwargs = dict(apply_kwargs or {})

        def apply_fn(vars_, x):
            return model.apply(vars_, x, **apply_kwargs)

        return cls(apply_fn, dict(variables), example, **kw)

    @classmethod
    def from_checkpoint(cls, path, model, variables_template, example,
                        apply_kwargs=None, **kw):
        """Engine loaded from an elastic-resume training checkpoint
        (:func:`load_params`): ``variables_template`` supplies
        structure/shapes (an ``eval_shape``-style init is enough);
        the npz's crc-verified ``params`` subtree replaces the
        template's."""
        variables = dict(variables_template)
        variables['params'] = load_params(
            path, variables_template['params'])
        return cls.for_model(model, variables, example,
                             apply_kwargs=apply_kwargs, **kw)
