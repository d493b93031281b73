"""Standard updater: the jitted SPMD train step.

The reference's hot loop is ``StandardUpdater.update`` ->
``_MultiNodeOptimizer.update`` -> forward/backward, allreduce, step
(``multi_node_optimizer.py:11-29``, SURVEY call stack 3.2).  Here the
whole of that -- loss, grad, strategy-specific gradient reduction,
optimizer step, metric averaging -- is ONE compiled program per mesh:
``jax.jit(shard_map(step))`` with donated buffers, so XLA overlaps the
backward pass with gradient collectives and there is no per-iteration
Python work beyond feeding the next batch.
"""

import functools
import weakref

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.training.convert import collate
from chainermn_tpu.utils import chaos as _chaos


class _held_weakly:
    """A method whose bound form holds its updater weakly.

    The updater owns gigabytes on the device, and whoever drops the
    last reference to it expects them back at once.  A caller that
    stores a wrapper of ``upd.update_core`` on ``upd`` itself (a
    profiler shim does: ``upd.update_core = spanned(upd.update_core)``)
    would otherwise close a reference cycle through the bound method,
    and the device state would wait for the interpreter's next FULL
    collection -- which a process holding millions of long-lived
    objects (a loaded profiler trace) puts off past the next thing that
    needs the memory (PERF.md section 6, PR 28)."""

    def __init__(self, fn):
        self._fn = fn
        functools.update_wrapper(self, fn)

    def __get__(self, obj, owner=None):
        if obj is None:
            return self._fn
        ref, fn = weakref.ref(obj), self._fn

        @functools.wraps(fn)
        def bound(*args, **kwargs):
            return fn(ref(), *args, **kwargs)
        return bound


class StandardUpdater:
    """Owns params/optimizer state and advances one iteration per call.

    Args:
      iterator: batch iterator (items collated via ``concat_examples``).
      optimizer: an ``optax.GradientTransformation`` -- typically the
        result of :func:`chainermn_tpu.create_multi_node_optimizer`.
      loss_fn: ``loss_fn(params, *batch) -> loss`` or
        ``-> (loss, metrics_dict)``.
      params: initial parameter pytree (host or device).
      comm: communicator whose mesh the step is mapped over.
      donate: donate param/opt-state buffers to the step (HBM reuse).
    """

    def __init__(self, iterator, optimizer, loss_fn, params, comm,
                 has_aux=False, donate=True, model_state=None, rng=None,
                 zero=False, accum_steps=1, zero_check=True,
                 zero_reduce_dtype=None, device_prefetch=0,
                 policy=None, param_specs=None, remat=False):
        """``model_state``: optional non-trainable collections (e.g.
        BatchNorm running stats).  When given, ``loss_fn`` must have
        the extended signature
        ``loss_fn(params, model_state, rng, *batch) ->
        (loss, (metrics, new_model_state))`` -- gradients are taken
        w.r.t. ``params`` only, the returned state is mean-synced
        across the mesh (cross-replica BatchNorm statistics), and
        ``rng`` (defaulting to PRNGKey(0)) is folded per iteration and
        per device for dropout-style randomness.

        ``zero=True`` shards the optimizer state over the mesh
        (ZeRO-1; see :mod:`chainermn_tpu.parallel.zero`): gradients
        are mean-reduce-scattered, the update runs on each device's
        shard, parameter deltas are all-gathered.  Pass the RAW optax
        optimizer here -- the first-update-broadcast semantics of the
        multi-node wrapper are applied internally (wrapping twice
        would average shards that are intentionally different).

        ONLY ELEMENTWISE optimizers (sgd/momentum, adam, adamw, ...)
        preserve the replicated trajectory under zero=True: the
        transformation sees flat 1-D per-device shards, so anything
        that reads cross-element structure -- clip_by_global_norm,
        per-layer trust ratios (LARS/LAMB), adafactor's shape-based
        factoring -- computes over shards instead of true leaves and
        silently diverges from zero=False.  This is ENFORCED at
        construction by a behavioral probe
        (:func:`chainermn_tpu.parallel.zero.check_elementwise`);
        ``zero_check=False`` bypasses it.  The common non-elementwise
        case -- global-norm clipping -- IS supported via the
        mesh-aware transform:
        ``zero.chain(zero.clip_by_global_norm(c), optax.adam(...))``
        completes its norm with a psum of per-shard sums and matches
        the zero=False + ``optax.clip_by_global_norm`` trajectory.

        ``zero_reduce_dtype`` (e.g. ``'bfloat16'``): cast gradients
        to a narrower dtype for the ZeRO reduce-scatter and back for
        the optimizer update -- the zero=True twin of the multi-node
        optimizer's ``allreduce_dtype`` (which does not compose with
        zero because zero takes the raw optax optimizer).

        ``accum_steps=k`` splits each per-device batch into k
        micro-batches processed by ``lax.scan`` with gradients
        averaged before the (single) optimizer step -- k-times larger
        effective batch at 1/k activation memory.

        ``device_prefetch=N`` (N >= 1) wraps the iterator in a
        :class:`~chainermn_tpu.training.DevicePrefetchIterator`: a
        worker thread collates and ``device_put``s up to N batches
        ahead, so host input work and the host->device transfer
        overlap the running step instead of serializing between
        steps (pair with ``update(sync=False)`` /
        ``Trainer(async_metrics=True)`` for a gap-free device).  The
        collation it runs is ONE pass (``training/convert.py``): each
        column allocated once at the dtype it ships at, the rows of a
        large one written by a few pool threads beside the producer.

        ``policy`` (a :class:`chainermn_tpu.precision.Policy`, e.g.
        ``Policy.bf16()``): mixed-precision training with master
        weights.  Params are STORED in ``param_dtype`` (f32) and cast
        to ``compute_dtype`` INSIDE the differentiated loss, so the
        forward and backward run narrow while gradient cotangents
        upcast to the master dtype at the cast boundary for the f32
        optimizer update.  The policy's ``reduce_dtype`` is imposed on
        the communicator's ``allreduce_grad`` (or on the ZeRO
        reduce-scatter, subsuming ``zero_reduce_dtype``), batches are
        cast to compute dtype on the HOST in :meth:`shard_batch`, in
        the very assignment that collates them (no float32 batch is
        ever built; halved H2D traffic; the prefetch iterator inherits
        this), and
        BatchNorm statistics plus metric averages are pinned to f32.
        A policy with a ``loss_scale`` (``Policy.f16()``) scales the
        loss before the backward pass, unscales gradients before the
        optimizer, SKIPS the update when any device's unscaled
        gradients are non-finite (verdict made replica-uniform with a
        pmin, so no device can diverge), and adjusts the scale --
        metrics then carry ``loss_scale`` and ``grads_finite``.
        See ``docs/mixed_precision.md``.

        ``param_specs`` (a ``PartitionSpec`` pytree over ``params``,
        e.g. :func:`chainermn_tpu.models.tp_param_specs`): per-leaf
        parameter sharding for composed-mesh training
        (``docs/mesh_parallelism.md``) -- pair with a
        :class:`chainermn_tpu.parallel.MeshPlan` communicator
        (``plan.communicator()``).  Params and optimizer state are
        PLACED with the specs (optimizer moments inherit their
        weight's spec via structure matching), the jitted step maps
        them with the same in/out specs (donation aliases shard to
        shard, policy casts run on the local shards), gradient
        reduction and the batch shard span the communicator's
        ``data_axes`` only, and the loss runs inside ``shard_map``
        with the plan's axes bound -- a ``tp_axis`` model's
        collectives just work.  ``zero=True`` composes with
        REPLICATED specs (the partitioning then spans the data axes
        only); ZeRO of a model-SHARDED leaf is not implemented.

        ``remat=True`` wraps the differentiated loss in
        ``jax.checkpoint``: the backward recomputes the forward
        instead of holding its activations -- the PERF.md knob #6
        memory lever, paired with ``donate=True`` by
        ``bench.py --donate``.
        """
        _telemetry.maybe_enable_from_env()
        _telemetry.install_compile_log()
        self.iterator = iterator
        self.optimizer = optimizer
        self.comm = comm
        self.loss_fn = loss_fn
        self._has_aux = has_aux
        #: aux keys the loss marks as the step's counters
        #: (``loss_fn.span_counters``): ``update()`` hangs their
        #: synchronised values on its ``train_update`` span
        self._span_counters = tuple(getattr(loss_fn, 'span_counters',
                                            ()))
        self._has_state = model_state is not None
        self._zero = zero
        self._zero_reduce_dtype = (jnp.dtype(zero_reduce_dtype)
                                   if zero_reduce_dtype is not None
                                   else None)
        if self._zero_reduce_dtype is not None and not zero:
            raise ValueError('zero_reduce_dtype requires zero=True '
                             '(use allreduce_dtype on the multi-node '
                             'optimizer for the plain path)')
        if accum_steps < 1:
            raise ValueError('accum_steps must be >= 1')
        self._accum_steps = accum_steps
        self._policy = policy
        self._loss_scale = (policy.loss_scale
                            if policy is not None else None)
        if policy is not None:
            if zero_reduce_dtype is not None:
                raise ValueError(
                    'zero_reduce_dtype is subsumed by the policy: set '
                    'Policy(reduce_dtype=...) instead of passing both')
            from chainermn_tpu.precision import cast_floating
            # master weights live in param_dtype (f32); compute-dtype
            # copies exist only inside the step
            params = cast_floating(params, policy.param_dtype)
            if (policy.reduce_dtype is not None and not zero
                    and getattr(comm, 'reduce_dtype', None) is None):
                # impose the policy's reduce dtype on the strategy's
                # allreduce_grad (an explicitly-constructed
                # communicator reduce_dtype wins); the ZeRO path
                # narrows its own reduce-scatter instead
                comm.reduce_dtype = policy.reduce_dtype
        from chainermn_tpu.training.placement import owned_device_put

        # data-parallel axes: the whole mesh for classic strategies,
        # the plan's `data` axes for a MeshPlan communicator -- batch
        # sharding, gradient reduction and ZeRO partitioning all key
        # off this (docs/mesh_parallelism.md)
        from chainermn_tpu.communicators.mesh_utility import AXES
        self._data_axes = tuple(getattr(comm, 'data_axes', AXES))
        self._param_specs = param_specs
        self._remat = bool(remat)
        sharded_params = param_specs is not None and any(
            tuple(s) for s in jax.tree_util.tree_leaves(
                param_specs,
                is_leaf=lambda x: isinstance(x, P)))

        # replicate + donation-aliasing guard in one placement: copies
        # exactly the would-alias leaves (see placement.py)
        _repl = NamedSharding(comm.mesh, P())
        if param_specs is None:
            param_shardings = _repl
        else:
            param_shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(comm.mesh, spec),
                param_specs)
        self.params = owned_device_put(params, param_shardings, donate)
        self.model_state = (owned_device_put(model_state, _repl, donate)
                            if self._has_state else None)
        if zero:
            from chainermn_tpu.multi_node_optimizer import (
                MultiNodeOptimizerState)
            from chainermn_tpu.parallel import zero as zero_mod
            if sharded_params:
                raise NotImplementedError(
                    'zero=True with model-sharded param_specs is not '
                    'implemented: the ZeRO stacked-state layout has '
                    'no host-level representation for leaves that '
                    'also vary over the model axis.  Under a '
                    'MeshPlan, ZeRO partitions along the data axes '
                    'of a REPLICATED parameter tree only.')
            local_state = optimizer.init(
                zero_mod.shard_templates(params, comm.size))
            if isinstance(local_state, MultiNodeOptimizerState):
                raise ValueError(
                    'zero=True needs the raw optax optimizer, not the '
                    'multi-node wrapper (broadcast-first is built in)')
            if zero_check:
                zero_mod.check_elementwise(optimizer)
            self._zero_specs = zero_mod.state_specs(local_state,
                                                    self._data_axes)
            stacked = zero_mod.expand_state(local_state, comm.size)
            shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(comm.mesh, spec),
                self._zero_specs)
            # protect=params: the state tree is internal, but state
            # embedding the caller's params (lookahead) must not be
            # donated aliased (see placement.py)
            self.opt_state = owned_device_put(stacked, shardings,
                                              donate, protect=params)
        else:
            opt_state = optimizer.init(params)
            if param_specs is None:
                self._opt_specs = P()
                opt_shardings = _repl
            else:
                # optimizer moments inherit their weight's spec
                # (structure matching; see meshplan.state_specs)
                from chainermn_tpu.parallel.meshplan import (
                    broadcast_specs_to_state)
                self._opt_specs = broadcast_specs_to_state(
                    param_specs, params, opt_state)
                opt_shardings = jax.tree_util.tree_map(
                    lambda spec: NamedSharding(comm.mesh, spec),
                    self._opt_specs)
            self.opt_state = owned_device_put(opt_state, opt_shardings,
                                              donate, protect=params)
        self.iteration = 0
        #: distinct compilations of the jitted step (bumped at trace
        #: time) -- the no-retrace pin shared with the pipeline
        #: updaters: a stable loop keeps this at 1 across iterations
        self.trace_count = 0
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.scale_state = (comm.replicate(self._loss_scale.init())
                            if self._loss_scale is not None else None)
        self._step = self._build_step(donate)
        self._device_prefetch = bool(device_prefetch)
        if device_prefetch:
            from chainermn_tpu.training.iterators import (
                DevicePrefetchIterator)
            self.iterator = DevicePrefetchIterator(
                iterator, self.shard_batch, depth=device_prefetch)

    def _build_step(self, donate):
        comm = self.comm
        optimizer = self.optimizer
        loss_fn = self.loss_fn
        has_aux = self._has_aux

        from chainermn_tpu import precision as precision_mod
        has_state = self._has_state
        is_zero = self._zero
        policy = self._policy
        loss_scale = self._loss_scale
        remat = self._remat
        reduce_dtype = self._zero_reduce_dtype
        if policy is not None and policy.reduce_dtype is not None:
            # the policy subsumes zero_reduce_dtype (enforced in
            # __init__); the non-zero path narrows inside the
            # communicator's allreduce_grad instead
            reduce_dtype = policy.reduce_dtype
        axes = self._data_axes

        accum = self._accum_steps

        def grads_and_metrics_once(params, model_state, rng, scale,
                                   *batch):
            # ``scale`` (loss-scale scalar or None) multiplies the
            # DIFFERENTIATED output only; the reported loss rides the
            # aux dict unscaled.  The policy's compute-dtype cast sits
            # inside the differentiated function, so the
            # convert_element_type transpose upcasts gradient
            # cotangents back to the master dtype for free.
            if has_state:
                dev_rng = jax.random.fold_in(rng, comm.axis_rank())

                def wrapped(p):
                    if policy is not None:
                        p = policy.cast_to_compute(p)
                    loss, (metrics, new_state) = loss_fn(
                        p, model_state, dev_rng, *batch)
                    sloss = (loss * scale.astype(loss.dtype)
                             if scale is not None else loss)
                    return sloss, (dict(metrics, loss=loss), new_state)
                if remat:
                    # backward recomputes the forward instead of
                    # holding its activations (PERF.md knob #6)
                    wrapped = jax.checkpoint(wrapped)
                (_, (metrics, new_state)), grads = jax.value_and_grad(
                    wrapped, has_aux=True)(params)
                if policy is not None:
                    # BatchNorm statistics stay in the master state
                    # dtype (f32): a compute-dtype model must not
                    # narrow the running stats it emits
                    new_state = jax.tree_util.tree_map(
                        lambda n, o: n.astype(jnp.result_type(o)),
                        new_state, model_state)
                # cross-replica sync of running statistics
                new_state = comm.allreduce(new_state, op='mean')
            else:
                def wrapped(p):
                    if policy is not None:
                        p = policy.cast_to_compute(p)
                    out = loss_fn(p, *batch)
                    loss, metrics = out if has_aux else (out, {})
                    sloss = (loss * scale.astype(loss.dtype)
                             if scale is not None else loss)
                    return sloss, dict(metrics, loss=loss)
                if remat:
                    wrapped = jax.checkpoint(wrapped)
                (_, metrics), grads = jax.value_and_grad(
                    wrapped, has_aux=True)(params)
                new_state = model_state
            return grads, metrics, new_state

        def grads_and_metrics(params, model_state, rng, scale, *batch):
            if accum == 1:
                return grads_and_metrics_once(params, model_state, rng,
                                              scale, *batch)

            # micro-batch scan: (B, ...) -> (accum, B/accum, ...);
            # grads/metrics averaged, model_state threaded through
            micro = tuple(
                b.reshape((accum, b.shape[0] // accum) + b.shape[1:])
                for b in batch)

            def body(carry, mb):
                state_c, rng_c = carry
                g, m, new_state = grads_and_metrics_once(
                    params, state_c, rng_c, scale, *mb)
                rng_c = (jax.random.fold_in(rng_c, 1)
                         if has_state else rng_c)
                return (new_state, rng_c), (g, m)

            (new_state, _), (gs, ms) = jax.lax.scan(
                body, (model_state, rng), micro)
            grads = jax.tree_util.tree_map(
                lambda g: jnp.mean(g, axis=0), gs)
            metrics = jax.tree_util.tree_map(
                lambda m: jnp.mean(m, axis=0), ms)
            return grads, metrics, new_state

        def finish_metrics(metrics):
            if policy is not None:
                # metric averages stay f32 regardless of the compute
                # dtype (a bf16 loss mean would quantize the logs)
                metrics = jax.tree_util.tree_map(
                    lambda m: (m.astype(jnp.float32)
                               if jnp.issubdtype(jnp.result_type(m),
                                                 jnp.floating) else m),
                    metrics)
            return comm.allreduce(metrics, op='mean')

        def unscale_and_check(grads, scale_state):
            """Unscaled gradients + a REPLICA-UNIFORM finiteness
            verdict.  Gradients here are local (pre-reduction), so one
            overflowing device must veto the update everywhere --
            otherwise devices take different branches and params
            silently diverge."""
            grads = loss_scale.unscale(grads, scale_state)
            local = precision_mod.all_finite(grads)
            finite = comm.allreduce(local.astype(jnp.float32),
                                    op='min') > 0.5
            return grads, finite

        def step_core(params, model_state, opt_state, rng, scale_state,
                      *batch):
            scale = (scale_state.scale if scale_state is not None
                     else None)
            grads, metrics, new_state = grads_and_metrics(
                params, model_state, rng, scale, *batch)
            if loss_scale is None:
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
                metrics = finish_metrics(metrics)
                return params, new_state, opt_state, metrics
            grads, finite = unscale_and_check(grads, scale_state)
            # zero the grads (not the branch: collectives inside
            # optimizer.update must still be issued in lockstep), then
            # discard the poisoned update and state on overflow
            safe = jax.tree_util.tree_map(
                lambda g: jnp.where(finite, g, jnp.zeros_like(g)),
                grads)
            updates, new_opt = optimizer.update(safe, opt_state,
                                                params)
            updates = jax.tree_util.tree_map(
                lambda u: jnp.where(finite, u, jnp.zeros_like(u)),
                updates)
            opt_state = precision_mod.tree_select(finite, new_opt,
                                                  opt_state)
            params = optax.apply_updates(params, updates)
            new_scale = loss_scale.adjust(scale_state, finite)
            metrics = finish_metrics(dict(
                metrics, loss_scale=scale_state.scale,
                grads_finite=finite.astype(jnp.float32)))
            return params, new_state, opt_state, new_scale, metrics

        def zero_step_core(params, model_state, opt_state, rng,
                           scale_state, needs_bcast, *batch):
            from jax import lax
            from chainermn_tpu.parallel import zero as z
            scale = (scale_state.scale if scale_state is not None
                     else None)
            grads, metrics, new_state = grads_and_metrics(
                params, model_state, rng, scale, *batch)
            finite = None
            if loss_scale is not None:
                grads, finite = unscale_and_check(grads, scale_state)
            n = comm.size
            rank = comm.axis_rank()

            def first_call(_):
                # initial weight sync, no step (reference
                # multi_node_optimizer.py:23-26)
                synced = comm.broadcast_data(params)
                return synced, opt_state

            def later_call(_):
                g = grads
                if reduce_dtype is not None:
                    # narrow-dtype reduce-scatter: halves the bytes on
                    # the wire; the mean lands in the narrow dtype and
                    # is widened back for the optimizer update
                    g = jax.tree_util.tree_map(
                        lambda x: x.astype(reduce_dtype), g)
                with jax.named_scope('grad_allreduce'):
                    g_sh = jax.tree_util.tree_map(
                        lambda g_: z.scatter_grad_leaf(g_, n, axes), g)
                if reduce_dtype is not None:
                    g_sh = jax.tree_util.tree_map(
                        lambda r, g0: r.astype(g0.dtype), g_sh, grads)
                p_sh = jax.tree_util.tree_map(
                    lambda p: z.param_shard_leaf(p, n, rank), params)
                opt_local = z.squeeze_state(opt_state)
                # mesh-aware transforms (zero.clip_by_global_norm,
                # zero.scale_by_trust_ratio) complete their statistics
                # over the mesh: every element of every leaf lives on
                # exactly one device along `axes`, so both the whole-
                # tree and the per-leaf global sq-norms are psums of
                # per-shard sums
                with z.mesh_norm_scope(
                        lambda t: z.axes_sumsq(t, axes),
                        leaf_sumsq=lambda x: z.axes_sumsq(x, axes)), \
                        jax.named_scope('optimizer_update'):
                    updates, new_opt = optimizer.update(
                        g_sh, opt_local, p_sh)
                upd_full = jax.tree_util.tree_map(
                    lambda u, p: z.gather_update_leaf(u, p, axes),
                    updates, params)
                return (optax.apply_updates(params, upd_full),
                        z.unsqueeze_state(new_opt))

            new_params, new_opt_state = lax.cond(
                needs_bcast, first_call, later_call, operand=None)
            if loss_scale is None:
                metrics = finish_metrics(metrics)
                return new_params, new_state, new_opt_state, metrics
            # skip-on-nonfinite -- but never revert the first-call
            # broadcast: it is a weight SYNC, not an update, and
            # reverting it would leave replicas permanently unsynced
            keep = jnp.logical_or(finite, needs_bcast)
            new_params = precision_mod.tree_select(keep, new_params,
                                                   params)
            new_opt_state = precision_mod.tree_select(
                keep, new_opt_state, opt_state)
            new_scale = loss_scale.adjust(scale_state, finite)
            metrics = finish_metrics(dict(
                metrics, loss_scale=scale_state.scale,
                grads_finite=finite.astype(jnp.float32)))
            return (new_params, new_state, new_opt_state, new_scale,
                    metrics)

        # fixed-arity entry points: the leading-args layout is
        # (params, model_state, opt_state, rng[, scale_state]
        #  [, needs_bcast], *batch) -- scale only under a loss-scaled
        # policy, needs_bcast only under zero -- with matching specs
        scaled = loss_scale is not None
        if is_zero and scaled:
            def core(params, model_state, opt_state, rng, scale_state,
                     needs_bcast, *batch):
                return zero_step_core(params, model_state, opt_state,
                                      rng, scale_state, needs_bcast,
                                      *batch)
        elif is_zero:
            def core(params, model_state, opt_state, rng, needs_bcast,
                     *batch):
                return zero_step_core(params, model_state, opt_state,
                                      rng, None, needs_bcast, *batch)
        elif scaled:
            def core(params, model_state, opt_state, rng, scale_state,
                     *batch):
                return step_core(params, model_state, opt_state, rng,
                                 scale_state, *batch)
        else:
            def core(params, model_state, opt_state, rng, *batch):
                return step_core(params, model_state, opt_state, rng,
                                 None, *batch)

        opt_specs = self._zero_specs if is_zero else self._opt_specs
        # per-leaf param specs under a MeshPlan (P() replicated
        # otherwise); in == out so donated shards alias shard to shard
        pspecs = (self._param_specs if self._param_specs is not None
                  else P())
        lead_specs = ((pspecs, P(), opt_specs, P())
                      + ((P(),) if scaled else ())
                      + ((P(),) if is_zero else ()))
        out_specs = ((pspecs, P(), opt_specs)
                     + ((P(),) if scaled else ()) + (P(),))
        n_lead = len(lead_specs)

        # arity of in_specs depends on the batch tuple; resolved at
        # trace time (jit caches per shape signature).  The name is
        # the executable's (``jit_train_step`` on the profiler's
        # ``XLA Modules`` line): what a trace reduction keys on
        me = weakref.ref(self)   # the step must not keep its updater

        def train_step(*args):
            me().trace_count += 1  # fires per compilation, not per step
            n_batch = len(args) - n_lead
            fn = jax.shard_map(
                core, mesh=comm.mesh,
                in_specs=lead_specs + (comm.batch_spec(),) * n_batch,
                out_specs=out_specs, check_vma=False)
            return fn(*args)

        jit_kwargs = {'donate_argnums': (0, 1, 2)} if donate else {}
        # what the strategy's collectives need from the compiler (the
        # `xla` strategy on several TPU chips: all-reduces that run
        # under the backward), asked for HERE and by no flag
        options = comm.step_compiler_options()
        if options:
            jit_kwargs['compiler_options'] = options
        return jax.jit(train_step, static_argnums=(), **jit_kwargs)

    @_held_weakly
    def shard_batch(self, batch):
        """Collate a list of examples and place it sharded on the mesh
        (under a policy, floating columns are written at compute dtype
        on the HOST, in the one pass that collates them, halving the
        host->device bytes).  The ``host_batch_prep`` span says how
        wide the collate ran (``collate_workers``) and over how many
        shipped bytes (``collate_bytes``)."""
        with _telemetry.span('host_batch_prep', kind='host',
                             iteration=self.iteration) as span:
            arrays, workers, nbytes = collate(
                batch, dtype=(self._policy.compute_dtype
                              if self._policy is not None else None))
            span.set(collate_workers=workers, collate_bytes=nbytes)
            if isinstance(arrays, dict):
                arrays = tuple(arrays.values())
            if _chaos._active is not None:  # nan_batch fault injection
                arrays = _chaos.corrupt_batch(arrays)
            n = arrays[0].shape[0]
            if n % (self.comm.size * self._accum_steps):
                raise ValueError(
                    'global batch size %d must be divisible by mesh '
                    'size %d x accum_steps %d'
                    % (n, self.comm.size, self._accum_steps))
        # comm.shard_batch records its own 'h2d' span; tag the step
        # index on a sibling so the timeline groups H2D per iteration
        with _telemetry.span('h2d', kind='h2d',
                             iteration=self.iteration):
            return self.comm.shard_batch(arrays)

    def _step_args(self, arrays, iteration=None):
        """The exact argument tuple one train-step call receives at
        the given iteration (default: the next real one).  Single
        source of truth for ``update_core``,
        ``compiled_cost_analysis`` and ``traceable_step`` -- the
        static analyzer must see the very signature the hot loop
        compiles under."""
        it = self.iteration if iteration is None else iteration
        # stateless path reuses the cached key (the step ignores it)
        step_rng = (jax.random.fold_in(self._rng, it)
                    if self._has_state else self._rng)
        args = (self.params, self.model_state, self.opt_state,
                step_rng)
        if self._loss_scale is not None:
            args += (self.scale_state,)
        if self._zero:
            args += (jnp.asarray(it == 0),)
        return args + tuple(arrays)

    def traceable_step(self, arrays, iteration=None):
        """``(fn, args)`` of the jitted train step for jaxpr-level
        static analysis (:mod:`chainermn_tpu.analysis`): ``fn`` is the
        compiled-step callable (donation marks intact) and ``args``
        the concrete argument tuple iteration ``iteration`` would
        pass.  Tracing ``jax.make_jaxpr(fn)(*args)`` performs no
        device computation."""
        return self._step, self._step_args(arrays, iteration)

    @_held_weakly
    def update_core(self, arrays):
        """Advance one iteration on already-sharded device arrays;
        returns device-resident metrics (no host sync -- steps can
        overlap)."""
        if _chaos._active is not None:  # sigterm_step / kill_step
            _chaos.on_step(self.iteration)
        # measures DISPATCH: completion is on the device's own lines
        # of the profiler's trace, under ``jit_train_step``
        with _telemetry.span('jitted_step', kind='compute',
                             iteration=self.iteration):
            out = self._step(*self._step_args(arrays))
        if self._loss_scale is not None:
            (self.params, self.model_state, self.opt_state,
             self.scale_state, metrics) = out
        else:
            self.params, self.model_state, self.opt_state, metrics = \
                out
        self.iteration += 1
        return metrics

    def update(self, sync=True):
        """Advance one iteration.  ``sync=True`` (default) returns host
        floats -- which BLOCKS on the device step and costs a full
        host-device round trip per iteration.  ``sync=False`` returns
        the device-resident metric arrays so the Python loop can run
        ahead and the device never idles between steps; convert with
        ``float()`` only where a value is actually consumed (see
        ``Trainer(async_metrics=True)``)."""
        iteration = self.iteration
        with _telemetry.span('train_update', kind='step',
                             iteration=iteration) as step_span:
            # the consumer's wait for a batch: what the producer
            # threads (``batch_fetch``; ``host_batch_prep`` and ``h2d``
            # under ``device_prefetch``) did not hide
            with _telemetry.span('input_wait', kind='host',
                                 iteration=iteration):
                batch = next(self.iterator)
            metrics = self.update_core(
                batch if self._device_prefetch
                else self.shard_batch(batch))
            if not sync:
                return dict(metrics)
            # the host-device round trip the sync=True contract pays
            with _telemetry.span('metrics_sync', kind='host',
                                 iteration=iteration):
                out = {k: float(v) for k, v in metrics.items()}
            if self._span_counters and step_span is not _telemetry.NULL_SPAN:
                # what the loss names as the step's counters, already
                # on the host: attributes of this step's span
                step_span.set(**{k: out[k] for k in self._span_counters
                                 if k in out})
            return out

    def compiled_cost_analysis(self, arrays):
        """XLA cost analysis (flops etc.) of the compiled train step
        for the given sharded batch."""
        lowered = self._step.lower(*self._step_args(arrays))
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost or {})

    def declared_reduce_dtypes(self):
        """Dtype names reductions in this updater's compiled step may
        legitimately narrow to (the shardlint SL004 introspection
        hook): the policy's compute/reduce dtypes, the ZeRO reduce
        dtype, and the communicator's own declaration."""
        out = set()
        if self._policy is not None:
            out |= self._policy.declared_dtypes()
        if self._zero_reduce_dtype is not None:
            out.add(str(self._zero_reduce_dtype))
        hook = getattr(self.comm, 'declared_reduce_dtypes', None)
        if hook is not None:
            out |= set(hook())
        return out

    # epoch accounting is delegated to the iterator
    @property
    def epoch(self):
        return getattr(self.iterator, 'epoch', 0)

    @property
    def epoch_detail(self):
        return getattr(self.iterator, 'epoch_detail', 0.0)

    @property
    def is_new_epoch(self):
        return getattr(self.iterator, 'is_new_epoch', False)
