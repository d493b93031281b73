"""Training THROUGH the pipeline (VERDICT r2 item 5).

The reference actually *trains* its 2-stage sequential pipeline --
``MultiNodeChainList`` is driven by a normal updater/optimizer loop
(``/root/reference/examples/mnist/train_mnist_model_parallel.py:66``).
This module gives :class:`chainermn_tpu.parallel.Pipeline` the same
status: a drop-in updater whose single jitted program runs the GPipe
schedule forward, lets JAX autodiff produce the reverse schedule (the
backward ``ppermute`` runs opposite the forward rotation -- the
reference's Send/Recv backward pairing at scale), reduces gradients
over the data axis, and applies the optimizer -- loss computed on the
LAST stage only and broadcast so every host observes the same metrics.

Mesh layout: 2-D ``(data, stage)`` -- or 3-D ``(data, stage, tp)``
with ``pipeline_mesh(n_tp=...)`` + ``param_specs``, where each
stage's weights are additionally Megatron-sharded over ``tp``.
Both are the COMPATIBILITY-SHIM surface now: the unified path is
:class:`MeshPipelineUpdater` over a 3-D
:class:`chainermn_tpu.parallel.MeshPlan` ``(data, model, pipe)``
mesh, which runs the same schedules with the plan's axis names
(``docs/mesh_parallelism.md``).
Parameters are stacked per stage
(:func:`~chainermn_tpu.parallel.pipeline.stack_stage_params`) and
sharded ``P('stage', ...)`` -- each device holds ONLY its stage's
(tp-shard of) weights, the memory/compute scaling the SPMD
``MultiNodeChainList`` mode deliberately does not attempt
(``link.py:33-38``).  Gradients need no collective over ``stage``
(disjoint ownership); they are ``pmean``'d over ``data``.

Memory profile (why GPipe-via-scan, not 1F1B): differentiating the
scheduling ``lax.scan`` stores one carry per tick, i.e.
``n_micro + n_stages - 1`` stage-activations per device.  1F1B caps
the in-flight count at ``n_stages`` instead, a win only when
``n_micro >> n_stages`` AND activations dominate HBM.  At that point
pass ``remat=True``: the stage body is rematerialized in the backward
pass, the stored carry shrinks to the inter-stage boundary activation
(exactly what 1F1B keeps), and peak memory matches 1F1B's schedule to
within the boundary buffer -- with none of the hand-written backward
bookkeeping XLA cannot fuse across.  See
``tests/test_pipeline_training.py::test_remat_matches`` for the
equivalence pin.
"""

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.parallel import zero as zero_helpers
from chainermn_tpu.parallel.pipeline import (
    Pipeline, assert_collective_free, microbatch, pipeline_1f1b_grads)
from chainermn_tpu.training.convert import collate
from chainermn_tpu.training.placement import owned_device_put


def _assert_1f1b_safe(loss_probe, loss_args, stage_fn, p_local,
                      act_micro, prologue=None, extra=None, x=None,
                      allowed_axes=()):
    """Trace-time probes: the 1f1b schedule takes per-device vjps of
    the stage body, loss and prologue, so any of them containing a
    collective in a DIFFERENTIATED output would train on silently
    mis-transposed gradients (e.g.
    ``models.transformer.pipeline_parts``'s loss psums over the data
    axis -- that composition needs gpipe).  Fail loudly instead.
    ``loss_probe(*loss_args)`` must return the loss scalar only
    (metrics are aux, never differentiated, and may psum freely).

    ``allowed_axes`` names the tensor-parallel axis whose collectives
    ride the conjugate custom-vjp discipline (exact per-device
    transposes) -- the unified dp x tp x pp composition
    (:class:`MeshPipelineUpdater`); see
    :func:`chainermn_tpu.parallel.pipeline.assert_collective_free`."""
    assert_collective_free("loss_on_last under schedule='1f1b'",
                           loss_probe, *loss_args,
                           allowed_axes=allowed_axes)
    assert_collective_free(
        "stage_fn under schedule='1f1b'", stage_fn, p_local,
        act_micro, allowed_axes=allowed_axes)
    if prologue is not None:
        assert_collective_free(
            "prologue under schedule='1f1b'", prologue, extra, x,
            allowed_axes=allowed_axes)

AXIS_DATA = 'data'
AXIS_STAGE = 'stage'


AXIS_TP = 'tp'


def pipeline_mesh(n_stages, devices=None, n_tp=1):
    """A ``(data, stage)`` mesh -- or ``(data, stage, tp)`` when
    ``n_tp > 1`` -- using all local devices: the trailing
    (fastest-varying, most ICI-local) axes carry the stage boundary
    ``ppermute`` and the per-block tensor-parallel ``psum`` so that
    traffic rides neighbor links."""
    import numpy as np
    if n_tp < 1 or n_stages < 1:
        raise ValueError('n_stages and n_tp must be >= 1, got %d, %d'
                         % (n_stages, n_tp))
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % (n_stages * n_tp):
        raise ValueError('%d devices not divisible into %d stages x '
                         '%d tp' % (n, n_stages, n_tp))
    arr = np.asarray(devices, dtype=object)  # noqa: shardlint
    if n_tp > 1:
        return Mesh(arr.reshape(n // (n_stages * n_tp), n_stages,
                                n_tp),
                    (AXIS_DATA, AXIS_STAGE, AXIS_TP))
    return Mesh(arr.reshape(n // n_stages, n_stages),
                (AXIS_DATA, AXIS_STAGE))


class PipelineUpdater:
    """Drop-in updater (same surface as ``StandardUpdater``) that runs
    a micro-batched pipeline-parallel train step.

    Args:
      iterator: batch iterator (or ``iter([])`` when driving
        ``update_core`` directly).
      optimizer: raw ``optax.GradientTransformation`` -- applied to the
        stage-local shard; elementwise optimizers keep per-stage
        trajectories identical to the unpipelined model.
      stage_fn: ``stage_fn(stage_params, x) -> y``; homogeneous
        activation shapes between stages.
      loss_on_last: ``loss_on_last(outputs, y_micro) -> (loss, metrics)``
        evaluated on the last stage's emitted micro-batch stack
        ``(n_micro, micro_b, ...)``.
      params_stacked: pytree whose leaves have leading dim
        ``n_stages`` (see ``stack_stage_params``).
      mesh: a ``(data, stage)`` mesh (``pipeline_mesh``).
      n_micro: number of micro-batches per step.
      remat: rematerialize the stage body in the backward pass
        (gpipe schedule only; see module docstring).
      schedule: ``'gpipe'`` (default; differentiated scan) or
        ``'1f1b'`` (:func:`~chainermn_tpu.parallel.pipeline.
        pipeline_1f1b_grads`): one-forward-one-backward with
        hand-propagated cotangents -- in-flight activations bounded by
        ``2 * n_stages`` regardless of ``n_micro``, recompute built in.
        1f1b requires a collective-free ``stage_fn`` and a
        ``loss_on_last`` that decomposes as a mean over micro-batches
        (standard mean losses do; NONLINEAR metrics differ between
        schedules by Jensen -- gpipe evaluates them once on the full
        micro-batch stack, 1f1b averages per-micro values, so e.g.
        perplexity reads slightly higher under 1f1b).  GRADIENTS are
        identical between
        schedules (``tests/test_pipeline_training.py``); identical
        PARAMETER trajectories additionally require an ELEMENTWISE
        optimizer -- under 1f1b the optimizer sees each stage's local
        tree, so cross-element transformations (clip_by_global_norm,
        LARS/LAMB trust ratios) would compute per-stage statistics
        instead of the stacked-tree statistics gpipe uses.  This is
        ENFORCED by a behavioral probe
        (:func:`chainermn_tpu.parallel.zero.check_elementwise`);
        ``schedule_check=False`` bypasses it.  Global-norm clipping
        IS supported through the mesh-aware
        ``zero.chain(zero.clip_by_global_norm(c), ...)``: the updater
        completes its squared norm across stages (psum over the stage
        axis; replicated ``extra_params`` counted once), so the 1f1b
        trajectory matches gpipe's with ``optax.clip_by_global_norm``.
      schedule_check: verify the optimizer is elementwise when
        ``schedule='1f1b'`` (see above).
      prologue: ``prologue(extra_params, x) -> activations``, run
        replicated on the full local batch BEFORE micro-batching
        (embedding/positional lookup); its output feeds stage 0.
        Requires ``extra_params``.
      extra_params: replicated parameter pytree for the heterogeneous
        ends of a real model (embedding table, final norm, head),
        trained jointly with the stage-stacked body; ``loss_on_last``
        then takes ``(extra, outputs, y_micro)``.  Works under BOTH
        schedules; under 1f1b the loss and prologue must be
        collective-free like the stage body (their vjps are taken
        per device -- a loss that psums over the data axis, such as
        :func:`~chainermn_tpu.models.transformer.pipeline_parts`'s,
        needs gpipe).
      param_specs: optional pytree of ``PartitionSpec`` (matching
        ``params_stacked``, every spec leading with ``'stage'``) that
        ADDS sharded axes beyond the stage axis -- e.g.
        ``P('stage', None, 'tp')`` for Megatron-sharded stage weights
        on a ``pipeline_mesh(n_stages, n_tp=...)``.  ``stage_fn`` is
        then responsible for the matching collectives (``tp_mlp``'s
        psum) and must return activations REPLICATED over the extra
        axes.  Optimizer state mirroring a params leaf inherits its
        full spec.  gpipe schedule only.
      opt_state_specs: optional LEAF-EXACT pytree of ``PartitionSpec``
        for the optimizer state, overriding the built-in placement
        heuristic.  The heuristic stage-shards any >=2-D state leaf
        whose leading dim equals ``n_stages`` (and inherits param
        specs on shape/keypath matches) -- correct for every stock
        optax transform, but a semantically REPLICATED buffer that
        coincidentally has that shape would be sliced ``a[0]`` per
        stage under 1f1b (the trace-time shape guard catches most,
        not all, such corruptions).  Exotic optimizers can state
        their placement here, as ``param_specs`` does for parameters.
    """

    def __init__(self, iterator, optimizer, stage_fn, loss_on_last,
                 params_stacked, mesh, n_micro, remat=False,
                 donate=True, schedule='gpipe', schedule_check=True,
                 prologue=None, extra_params=None, param_specs=None,
                 opt_state_specs=None, policy=None,
                 data_axis=AXIS_DATA, stage_axis=AXIS_STAGE,
                 tp_axis=None):
        """``policy`` (a :class:`chainermn_tpu.precision.Policy`):
        mixed-precision training with f32 master weights, same
        contract as ``StandardUpdater(policy=...)``.  Stage (and
        extra) parameters are stored in ``param_dtype`` and cast to
        ``compute_dtype`` inside the differentiated stage/loss/
        prologue bodies, so gradient cotangents upcast to the master
        dtype at the cast boundary; batches are cast host-side in
        :meth:`shard_batch`, in the one pass that collates them (each
        floating column written at compute dtype); loss and metrics
        are pinned to f32 before their cross-stage psums.
        ``reduce_dtype`` narrows the
        1f1b schedule's explicit data-axis gradient pmean
        (cast-before, upcast-after); the gpipe schedule's data-axis
        reduction lives inside the shard_map transpose and runs at
        the master dtype -- the boundary cast upcasts cotangents
        before they cross devices.  Loss-scaled policies
        (``Policy.f16()``) are not supported here: bf16 -- the
        TPU-native compute dtype -- needs no scaling, and the
        schedule's per-stage backward has no single point to apply
        the skip-on-nonfinite contract; use ``Policy.bf16()``.

        ``data_axis`` / ``stage_axis`` / ``tp_axis``: the mesh axis
        names the schedule binds -- the classic ``(data, stage)``
        mesh by default; :class:`MeshPipelineUpdater` rebinds them to
        a 3-D :class:`chainermn_tpu.parallel.MeshPlan`'s
        ``(data, pipe)`` (+ ``model`` for tensor parallelism inside a
        stage).  With ``tp_axis`` set, ``param_specs`` may shard stage
        weights over that axis UNDER BOTH SCHEDULES: the 1f1b
        collective guard then exempts collectives acting only over
        ``tp_axis`` (the conjugate custom-vjp discipline of
        ``parallel/tensor.py`` makes their per-device transposes
        exact), and mesh-aware ``zero.*`` norm transforms are NOT
        supported (their stage-axis statistics would miss the model
        shards).

        DEPRECATION NOTE: direct construction over a bare
        ``pipeline_mesh`` ``(data, stage)`` mesh is retained as a
        compatibility shim; new code should compose the pipeline into
        a 3-D plan (``MeshPlan.create(tp=..., pp=...)``) and use
        :class:`MeshPipelineUpdater` -- same machinery, one mesh for
        every axis (``docs/mesh_parallelism.md``).
        """
        if schedule not in ('gpipe', '1f1b'):
            raise ValueError("schedule must be 'gpipe' or '1f1b'")
        if policy is not None and policy.loss_scale is not None:
            raise ValueError(
                'PipelineUpdater does not support loss-scaled '
                'policies (use Policy.bf16(), whose f32-range '
                'exponent needs no scaling, or StandardUpdater for '
                'f16 with dynamic loss scaling)')
        if param_specs is not None:
            spec_leaves = jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda v: isinstance(v, P))
            bad = [
                sp for sp in spec_leaves
                if not (isinstance(sp, P) and len(sp) >= 1
                        and sp[0] == stage_axis)]
            if bad:
                raise ValueError(
                    'every param spec must lead with the stage axis '
                    "(P(%r, ...)), got %r" % (stage_axis, bad[:3]))
            if schedule == '1f1b':
                # specs that only restate the stage placement are
                # fine under 1f1b; EXTRA sharded axes imply
                # collectives inside stage_fn, whose per-device
                # transposes are exact only through the declared
                # tp_axis's conjugate custom-vjp discipline
                stray = [
                    sp for sp in spec_leaves
                    if any(e not in (None, tp_axis)
                           for e in tuple(sp)[1:])]
                if stray:
                    raise ValueError(
                        "param_specs under schedule='1f1b' may shard "
                        'non-stage dims only over a declared tp_axis '
                        '(the conjugate-discipline axis; got tp_axis='
                        '%r, stray specs %r).  Other axes need the '
                        'gpipe schedule.' % (tp_axis, stray[:3]))
            n_p = len(jax.tree_util.tree_leaves(params_stacked))
            if len(spec_leaves) != n_p:
                # a pytree PREFIX would device_put/shard_map fine but
                # silently mis-pair the per-leaf spec table the
                # optimizer-state placement is derived from
                raise ValueError(
                    'param_specs must be LEAF-EXACT (one PartitionSpec '
                    'per params leaf): got %d specs for %d leaves -- '
                    'expand the prefix with jax.tree_util.tree_map'
                    % (len(spec_leaves), n_p))
        extra_used = extra_params is not None
        if prologue is not None and not extra_used:
            raise ValueError('prologue requires extra_params (pass an '
                             'empty dict if it is parameter-free)')
        if schedule == '1f1b':
            if remat:
                raise ValueError(
                    "remat=True has no effect under schedule='1f1b' "
                    '(its backward recomputes by construction); drop '
                    'the flag')
            if schedule_check:
                from chainermn_tpu.parallel import zero as zero_mod
                try:
                    zero_mod.check_elementwise(optimizer)
                except ValueError as e:
                    raise ValueError(
                        "schedule='1f1b' requires an elementwise "
                        'optimizer: under 1f1b the optimizer sees '
                        "each stage's local tree, so cross-element "
                        'transforms compute per-stage statistics and '
                        "silently diverge from gpipe's stacked-tree "
                        'trajectory.  For global-norm clipping use '
                        'zero.chain(zero.clip_by_global_norm(c), ...) '
                        '-- its norm is completed across stages.  '
                        'Trust ratios (LARS/LAMB, incl. zero.lars and '
                        'zero.lamb) '
                        'are NOT available under 1f1b: stage sharding '
                        'admits no per-leaf norm rule.  The gpipe '
                        'schedule runs them, with pipeline-native '
                        'semantics: one ratio per STACKED leaf (all '
                        'stages sharing a layer name together), not '
                        'per layer of the unstacked model.  '
                        'Probe result: %s  Pass schedule_check=False '
                        'to bypass.' % e) from e
        _telemetry.maybe_enable_from_env()
        _telemetry.install_compile_log()
        self.iterator = iterator
        self.optimizer = optimizer
        self.mesh = mesh
        self.n_micro = n_micro
        # the mesh axes this instance binds (MeshPipelineUpdater
        # rebinds them onto a 3-D plan; closures below use the locals)
        ax_d, ax_s = data_axis, stage_axis
        self._axis_data = ax_d
        self._axis_stage = ax_s
        self._tp_axis = tp_axis
        self.n_stages = mesh.shape[stage_axis]
        n_data = int(mesh.shape[data_axis])
        self.iteration = 0
        #: distinct compilations of the jitted step (bumped at trace
        #: time): the whole schedule lives inside ONE jit, so this
        #: stays 1 across steps -- the no-retrace acceptance pin
        self.trace_count = 0
        self._policy = policy
        if policy is not None:
            from chainermn_tpu.precision import cast_floating
            # master weights live in param_dtype (f32); compute-dtype
            # copies exist only inside the step
            params_stacked = cast_floating(params_stacked,
                                           policy.param_dtype)
            if extra_params is not None:
                extra_params = cast_floating(extra_params,
                                             policy.param_dtype)

        p_specs = (param_specs if param_specs is not None
                   else jax.tree_util.tree_map(
                       lambda _: P(stage_axis), params_stacked))
        self.params = owned_device_put(
            params_stacked,
            jax.tree_util.tree_map(
                lambda sp: NamedSharding(mesh, sp), p_specs,
                is_leaf=lambda v: isinstance(v, P)),
            donate)
        # heterogeneous ends: replicated prologue/epilogue parameters
        # (embedding table, head, final norm) trained alongside the
        # stage-stacked body
        self.extra = (owned_device_put(
            extra_params, NamedSharding(mesh, P()), donate)
            if extra_used else None)
        # optimizer state mirrors the stage-stacked params leafwise
        # (elementwise transformations update stacked leaves exactly as
        # they would per stage); scalar leaves (step counts) replicate
        opt_tree0 = ({'stages': params_stacked, 'extra': extra_params}
                     if extra_used else params_stacked)
        opt_state0 = optimizer.init(opt_tree0)
        # per-leaf specs: a state leaf is stage-stacked iff it is
        # >=2-D with leading dim n_stages (params-shaped state --
        # momentum/EMA under any key name -- AND per-stage factored
        # state like adafactor row/col moments; every params leaf is
        # >=2-D stacked except per-stage scalars) or it is a 1-D leaf
        # that mirrors a (n_stages,) params leaf (stacked per-stage
        # scalar) by keypath suffix.  Other 1-D length-n_stages
        # vectors REPLICATE: a schedule/coefficient buffer sharded
        # over stages would silently hand each stage a different
        # scalar.  Shared by placement AND the 1f1b shard_map specs.
        _p_sigs = [
            (jax.tree_util.keystr(kp), getattr(v, 'shape', None), sp)
            for (kp, v), sp in zip(
                jax.tree_util.tree_flatten_with_path(
                    params_stacked)[0],
                jax.tree_util.tree_leaves(
                    p_specs, is_leaf=lambda v: isinstance(v, P)))]

        def _leaf_spec(kp, leaf):
            ks = jax.tree_util.keystr(kp)
            if extra_used:
                # a leaf belongs to the replicated 'extra' branch iff
                # "['extra']" is the FIRST of the two top-level branch
                # keys on its path -- a bare substring test would
                # false-positive on a BODY param key named 'extra'
                # (path "...['stages']['extra']...")
                si = ks.find("['stages']")
                ei = ks.find("['extra']")
                if ei != -1 and (si == -1 or ei < si):
                    return P()  # replicated prologue/epilogue state
            shape = getattr(leaf, 'shape', None)
            if shape is None:
                return P()
            # mirror state (momentum/EMA): same keypath suffix and
            # shape as a params leaf -> inherit that leaf's FULL spec
            # (stage + any extra tensor-parallel axes)
            for pk, s, sp in _p_sigs:
                if shape == s and ks.endswith(pk):
                    return sp
            if len(shape) >= 2 and shape[0] == self.n_stages:
                # renamed-key or factored per-stage state: shape-only
                # match inherits the spec; otherwise stage-shard the
                # leading dim (correct for e.g. adafactor row/col
                # moments, whose trailing dims match no params leaf)
                for pk, s, sp in _p_sigs:
                    if shape == s:
                        return sp
                return P(stage_axis)
            return P()

        if opt_state_specs is not None:
            # explicit escape hatch (ADVICE r3): the heuristic below
            # infers stage sharding from shapes/keypaths, and a
            # semantically REPLICATED state leaf that happens to be
            # >=2-D with leading dim n_stages would be mis-sliced per
            # stage under 1f1b.  Exotic optimizers can state their
            # placement outright, mirroring param_specs.
            n_s = len(jax.tree_util.tree_leaves(opt_state0))
            spec_leaves = jax.tree_util.tree_leaves(
                opt_state_specs, is_leaf=lambda v: isinstance(v, P))
            if (len(spec_leaves) != n_s
                    or not all(isinstance(sp, P)
                               for sp in spec_leaves)):
                raise ValueError(
                    'opt_state_specs must be LEAF-EXACT (one '
                    'PartitionSpec per optimizer-state leaf): got %d '
                    'specs for %d leaves'
                    % (len(spec_leaves), n_s))

            def _canon(sp):
                # strip trailing Nones: the 1f1b squeeze/re-stack
                # compares specs by equality with P('stage'), and
                # P('stage', None) != P('stage') even though the
                # placement is identical
                t = tuple(sp)
                while t and t[-1] is None:
                    t = t[:-1]
                return P(*t)

            opt_specs = jax.tree_util.tree_map(
                _canon, opt_state_specs,
                is_leaf=lambda v: isinstance(v, P))
        else:
            opt_specs = jax.tree_util.tree_map_with_path(
                _leaf_spec, opt_state0)
        # protect=opt_tree0 (the caller's trees): opt_state0 is
        # internal (aliasing within it is harmless), but state that
        # embeds the caller's params (lookahead slow weights) must not
        # be donated aliased
        self.opt_state = owned_device_put(
            opt_state0,
            jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec), opt_specs),
            donate, protect=opt_tree0)

        body = stage_fn if not remat else jax.checkpoint(stage_fn)
        pipe = Pipeline(body, self.n_stages, axis=stage_axis)
        n_stages = self.n_stages
        n_micro_ = n_micro
        updater_self = self

        def _mark_schedule():
            """Trace-time telemetry (fires once per compilation, like
            the strategies' collective-issue marks): the schedule's
            static bubble accounting -- what `telemetry report` turns
            into the per-stage bubble fraction -- and the trace
            counter behind the flat-trace acceptance pin."""
            from chainermn_tpu.parallel.pipeline import schedule_ticks
            updater_self.trace_count += 1
            if _telemetry.live() is None:
                return
            _telemetry.event(
                'pipeline:schedule', kind='pipeline',
                schedule=schedule, n_micro=n_micro_,
                n_stages=n_stages,
                total_ticks=schedule_ticks(n_micro_, n_stages,
                                           schedule),
                axes=[ax_s])

        # IMPORTANT: differentiate OUTSIDE the shard_map.  With
        # ``check_vma=False`` (which the ragged metrics outputs need),
        # ``jax.grad`` INSIDE shard_map mis-transposes programs whose
        # value crosses devices (the pipeline's ppermute chain): the
        # replication-tracking rewrite that makes collective transposes
        # correct is disabled, and gradients come out wrong (verified
        # empirically; the error is large, not roundoff).  Taking the
        # grad of the whole mapped loss lets JAX transpose the
        # shard_map itself, which is the supported path -- and is also
        # how ``tests/test_parallel.py::test_pipeline_backward`` pins
        # the schedule's reverse pairing.

        policy = self._policy

        def device_loss(params, extra, x, y):
            p_local = jax.tree_util.tree_map(lambda a: a[0], params)
            if policy is not None:
                # compute-dtype cast INSIDE the differentiated
                # function: the transpose upcasts cotangents back to
                # the master dtype before they cross the shard_map
                # boundary (where the data-axis psum happens)
                p_local = policy.cast_to_compute(p_local)
                extra = policy.cast_to_compute(extra)
                x = policy.cast_to_compute(x)
            acts = prologue(extra, x) if prologue is not None else x
            outs = pipe(p_local, microbatch(acts, n_micro_))
            stage = lax.axis_index(ax_s)
            onlast = stage == n_stages - 1
            # mask the ACTIVATIONS fed to the loss, not just the loss
            # value: loss_fn on a non-last stage's raw activations can
            # overflow to inf/NaN, and while the where on the loss
            # below protects the forward psum, the where TRANSPOSE
            # delivers a zero cotangent that still multiplies the
            # loss_fn jacobian in the backward pass -- 0 * inf = NaN
            # in the non-last stage's parameter gradients.  Evaluating
            # the loss at zeros keeps both directions finite.
            outs_safe = jax.tree_util.tree_map(
                lambda o: jnp.where(onlast, o, jnp.zeros_like(o)),
                outs)
            y_micro = microbatch(y, n_micro_)
            if extra_used:
                loss, metrics = loss_on_last(extra, outs_safe, y_micro)
            else:
                loss, metrics = loss_on_last(outs_safe, y_micro)
            if policy is not None:
                # metric averages stay f32 regardless of the compute
                # dtype (and their cross-stage psums run widened)
                loss = loss.astype(jnp.float32)
                metrics = jax.tree_util.tree_map(
                    lambda m: m.astype(jnp.float32), metrics)
            # garbage on non-last stages is masked with where, NOT
            # multiplication: the garbage loss can be inf/NaN (loss_fn
            # on raw activations) and inf * 0 = NaN would poison the
            # psum on every stage.  psum then broadcasts the real value.
            loss = lax.pmean(
                lax.psum(jnp.where(onlast, loss, 0.0), ax_s),
                ax_d)
            metrics = jax.tree_util.tree_map(
                lambda m: lax.pmean(
                    lax.psum(jnp.where(onlast, m,
                                       jnp.zeros_like(m)), ax_s),
                    ax_d), metrics)
            return loss, metrics

        def mapped_loss(params, extra, x, y):
            return jax.shard_map(
                device_loss, mesh=mesh,
                in_specs=(p_specs, P(), P(ax_d),
                          P(ax_d)),
                out_specs=(P(), P()), check_vma=False)(
                    params, extra, x, y)

        def pipeline_train_step(params, extra, opt_state, x, y):
            _mark_schedule()
            (loss, metrics), grads = jax.value_and_grad(
                mapped_loss, argnums=(0, 1), has_aux=True)(
                    params, extra, x, y)
            if extra_used:
                tree = {'stages': params, 'extra': extra}
                gtree = {'stages': grads[0], 'extra': grads[1]}
            else:
                tree, gtree = params, grads[0]
            updates, opt_state = optimizer.update(gtree, opt_state,
                                                  tree)
            tree = optax.apply_updates(tree, updates)
            if extra_used:
                params, extra = tree['stages'], tree['extra']
            else:
                params = tree
            return params, extra, opt_state, dict(metrics, loss=loss)

        # 1F1B: gradients are hand-propagated per stage inside the
        # shard_map (no autodiff through collectives, so the
        # grad-inside caveat above does not apply), and the optimizer
        # runs on each stage's complete local tree in the same program.
        def _stage_leading(sp):
            """An optimizer-state leaf is stage-stacked iff its spec
            LEADS with the stage axis (possibly followed by tp axes
            under the composed plan)."""
            t = tuple(sp)
            return bool(t) and t[0] == stage_axis

        def _pmean_data(g_tree):
            """Data-axis gradient mean, narrowed to the policy's
            reduce dtype on the wire (cast-before, upcast-after) --
            the 1f1b twin of the communicator reduce-dtype plumbing."""
            rd = policy.reduce_dtype if policy is not None else None
            if rd is None:
                return lax.pmean(g_tree, ax_d)
            narrowed = jax.tree_util.tree_map(
                lambda g: g.astype(rd), g_tree)
            return jax.tree_util.tree_map(
                lambda r, g: r.astype(g.dtype),
                lax.pmean(narrowed, ax_d), g_tree)

        def _reduce_extra(g_tree):
            """Stage-sum + data-mean of the extra-params gradients as
            ONE multi-axis psum (a stage-psum feeding a data-pmean is
            the disjoint-axis reduce chain SL011 flags: two
            serialized launches moving the same bytes), narrowed like
            :func:`_pmean_data`."""
            rd = policy.reduce_dtype if policy is not None else None
            if rd is None:
                return jax.tree_util.tree_map(
                    lambda g: lax.psum(g, (ax_s, ax_d)) / n_data,
                    g_tree)
            narrowed = jax.tree_util.tree_map(
                lambda g: g.astype(rd), g_tree)
            red = jax.tree_util.tree_map(
                lambda g: lax.psum(g, (ax_s, ax_d))
                / jnp.asarray(n_data, g.dtype), narrowed)
            return jax.tree_util.tree_map(
                lambda r, g: r.astype(g.dtype), red, g_tree)

        def _last_stage_mean(v, onlast):
            """Last-stage value averaged over data replicas in one
            multi-axis psum (values on non-last stages are masked
            zeros, so the (stage, data) sum / n_data IS the data
            mean of the last stage's value -- no SL011 chain)."""
            return lax.psum(
                jnp.where(onlast, v, jnp.zeros_like(v)),
                (ax_s, ax_d)) / n_data

        def device_step_1f1b(params, extra, opt_state, x, y):
            p_local = jax.tree_util.tree_map(lambda a: a[0], params)
            # squeeze only the stage-stacked optimizer leaves; scalar
            # leaves (replicated, spec P()) pass through untouched
            s_local = jax.tree_util.tree_map(
                lambda a, sp: a[0] if _stage_leading(sp) else a,
                opt_state, opt_specs)

            if policy is None:
                stage_body = stage_fn
                cast = lambda t: t  # noqa: E731
            else:
                # casts INSIDE the vjp'd bodies: masters stay f32 and
                # the cast transpose upcasts every gradient for free
                cast = policy.cast_to_compute

                def stage_body(p, a):
                    return stage_fn(cast(p), a)

                x = cast(x)

            if extra_used:
                y_m = microbatch(y, n_micro_)

                def per_micro_loss(e, yy, ym):
                    return loss_on_last(cast(e), yy[None], ym[None])

                if prologue is not None:
                    # ONE prologue forward: jax.vjp's primal IS the
                    # activation stack fed to the pipeline (no
                    # reliance on CSE to dedupe a second trace)
                    acts_m, vjp_pro = jax.vjp(
                        lambda e: microbatch(prologue(cast(e), x),
                                             n_micro_), extra)
                else:
                    acts_m = microbatch(x, n_micro_)
                _assert_1f1b_safe(
                    lambda e, yy, ym: per_micro_loss(e, yy, ym)[0],
                    (extra, acts_m[0], y_m[0]), stage_body, p_local,
                    acts_m[0], prologue=prologue, extra=extra, x=x,
                    allowed_axes=((tp_axis,) if tp_axis else ()))
                loss, metrics, grads, g_extra, dx_buf = \
                    pipeline_1f1b_grads(
                        stage_body, per_micro_loss, p_local,
                        acts_m, y_m, n_stages, axis=ax_s,
                        extra=extra,
                        collect_input_cotangents=prologue is not None)
                if prologue is not None:
                    # complete the embedding backward: the scan
                    # collected d(loss)/d(pipeline input micro) on
                    # stage 0 (zeros elsewhere)
                    (g_pro,) = vjp_pro(dx_buf.astype(acts_m.dtype))
                    g_extra = jax.tree_util.tree_map(
                        lambda a, b: a + b, g_extra, g_pro)
                # head grads live on the last stage, prologue grads
                # on stage 0, zeros elsewhere: psum over stage sums
                # the disjoint contributions, pmean over data averages
                g_extra = _reduce_extra(g_extra)
                grads = _pmean_data(grads)
                tree = {'stages': p_local, 'extra': extra}
                gtree = {'stages': grads, 'extra': g_extra}
            else:
                def per_micro_loss(yy, ym):
                    return loss_on_last(yy[None], ym[None])

                x_m = microbatch(x, n_micro_)
                y_m = microbatch(y, n_micro_)
                _assert_1f1b_safe(
                    lambda yy, ym: per_micro_loss(yy, ym)[0],
                    (x_m[0], y_m[0]), stage_body, p_local, x_m[0],
                    allowed_axes=((tp_axis,) if tp_axis else ()))
                loss, metrics, grads = pipeline_1f1b_grads(
                    stage_body, per_micro_loss, p_local, x_m, y_m,
                    n_stages, axis=ax_s)
                grads = _pmean_data(grads)
                tree, gtree = p_local, grads
            if policy is not None:
                # metric averages stay f32 (same pin as device_loss)
                loss = loss.astype(jnp.float32)
                metrics = jax.tree_util.tree_map(
                    lambda m: m.astype(jnp.float32), metrics)

            # mesh-aware transforms (zero.clip_by_global_norm) finish
            # their statistic across stages: stage leaves are disjoint
            # along the stage axis (psum), extra leaves are replicated
            # on every device (count once, no psum); everything is
            # already identical along the data axis (grads pmean'd)
            def gnorm_sq_1f1b(t):
                if extra_used:
                    return (zero_helpers.axes_sumsq(
                        t['stages'], ax_s)
                        + zero_helpers.tree_sumsq(t['extra']))
                return zero_helpers.axes_sumsq(t, ax_s)

            with zero_helpers.mesh_norm_scope(gnorm_sq_1f1b):
                updates, s_local = optimizer.update(gtree, s_local,
                                                    tree)
            new_tree = optax.apply_updates(tree, updates)
            # trace-time guard: a mis-sharded optimizer-state leaf
            # (e.g. a replicated vector broadcasting against
            # stage-local scalars) corrupts param shapes silently --
            # fail loudly instead
            bad = [
                (a.shape, b.shape) for a, b in zip(
                    jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(new_tree))
                if a.shape != b.shape]
            if bad:
                raise ValueError(
                    'optimizer update changed param shapes %s -- an '
                    'optimizer-state leaf is sharded inconsistently '
                    'with the stage axis (see the opt_specs rule in '
                    'PipelineUpdater.__init__)' % (bad,))
            if extra_used:
                p_local = new_tree['stages']
                new_extra = new_tree['extra']
            else:
                p_local, new_extra = new_tree, extra
            onlast = lax.axis_index(ax_s) == n_stages - 1
            # last-stage value -> data mean as ONE (stage, data) psum
            # (the SL011-clean form; see _last_stage_mean)
            loss = _last_stage_mean(loss, onlast)
            metrics = jax.tree_util.tree_map(
                lambda m: _last_stage_mean(m, onlast), metrics)
            p_out = jax.tree_util.tree_map(lambda a: a[None], p_local)
            s_out = jax.tree_util.tree_map(
                lambda a, sp: a[None] if _stage_leading(sp) else a,
                s_local, opt_specs)
            return p_out, new_extra, s_out, dict(metrics, loss=loss)

        def pipeline_train_step_1f1b(params, extra, opt_state, x, y):
            _mark_schedule()
            return jax.shard_map(
                device_step_1f1b, mesh=mesh,
                in_specs=(p_specs, P(), opt_specs,
                          P(ax_d), P(ax_d)),
                out_specs=(p_specs, P(), opt_specs, P()),
                check_vma=False)(params, extra, opt_state, x, y)

        if donate:
            kw = {'donate_argnums': (0, 1, 2) if extra_used
                  else (0, 2)}
        else:
            kw = {}
        # the raw (unjitted, undonated) step: bench scan makers wrap
        # it in their own outer jit to run k steps as one program
        self._raw_step = (pipeline_train_step if schedule == 'gpipe'
                          else pipeline_train_step_1f1b)
        self._step = jax.jit(self._raw_step, **kw)
        # forward-only path for evaluation: same pipeline schedule and
        # loss, NO gradient/optimizer (params not donated)
        self._eval = jax.jit(mapped_loss)

    def shard_batch(self, batch):
        """Collate and place a batch sharded over the data axis.
        Dict examples flatten in INSERTION order -- the positional
        (x, y) contract of the train step follows that order (same
        convention as ``StandardUpdater.shard_batch``, including the
        one-pass collate that writes floating columns at compute dtype
        on the host under a policy, and the ``collate_workers`` /
        ``collate_bytes`` attributes of the span)."""
        with _telemetry.span('host_batch_prep', kind='host',
                             iteration=self.iteration) as span:
            arrays, workers, nbytes = collate(
                batch, dtype=(self._policy.compute_dtype
                              if self._policy is not None else None))
            span.set(collate_workers=workers, collate_bytes=nbytes)
            if isinstance(arrays, dict):
                arrays = tuple(arrays.values())
        data_sharding = NamedSharding(self.mesh, P(self._axis_data))
        with _telemetry.span('h2d', kind='h2d',
                             iteration=self.iteration):
            return tuple(jax.device_put(a, data_sharding)
                         for a in arrays)

    def traceable_step(self, arrays, iteration=None):
        """``(fn, args)`` of the jitted pipeline train step for
        jaxpr-level static analysis (:mod:`chainermn_tpu.analysis`)
        -- same contract as ``StandardUpdater.traceable_step``.  The
        pipeline step's signature carries no iteration-dependent
        arguments, so ``iteration`` only exists for interface
        uniformity."""
        del iteration
        return self._step, (self.params, self.extra,
                            self.opt_state) + tuple(arrays)

    def update_core(self, arrays):
        with _telemetry.span('jitted_step', kind='compute',
                             iteration=self.iteration):
            out = self._step(self.params, self.extra, self.opt_state,
                             *arrays)
        self.params, self.extra, self.opt_state, metrics = out
        self.iteration += 1
        return metrics

    def update(self, sync=True):
        """Advance one iteration.  Same protocol as
        ``StandardUpdater.update``: ``sync=False`` returns the
        device-resident metric arrays (no host round trip) for
        ``Trainer(async_metrics=True)``."""
        iteration = self.iteration
        with _telemetry.span('train_update', kind='step',
                             iteration=iteration):
            with _telemetry.span('input_wait', kind='host',
                                 iteration=iteration):
                batch = next(self.iterator)
            metrics = self.update_core(self.shard_batch(batch))
            if not sync:
                return dict(metrics)
            with _telemetry.span('metrics_sync', kind='host',
                                 iteration=iteration):
                return {k: float(v) for k, v in metrics.items()}

    def evaluate(self, arrays):
        """Forward-only metrics on already-sharded arrays: runs the
        pipeline schedule and the loss but neither gradients nor the
        optimizer -- use this for validation batches (a train step on
        eval data would fit the validation set)."""
        loss, metrics = self._eval(self.params, self.extra, *arrays)
        return {k: float(v) for k, v in
                dict(metrics, loss=loss).items()}

    def compiled_cost_analysis(self, arrays):
        """XLA cost analysis (flops etc.) of the compiled pipeline
        step for the given sharded batch (mirrors
        ``StandardUpdater.compiled_cost_analysis`` -- the bench's
        flops cross-check)."""
        lowered = self._step.lower(self.params, self.extra,
                                   self.opt_state, *arrays)
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost or {})

    def declared_reduce_dtypes(self):
        """Dtype names reductions in this updater's compiled step may
        legitimately narrow to (the shardlint SL004 introspection
        hook, mirroring ``StandardUpdater``)."""
        if self._policy is None:
            return set()
        return set(self._policy.declared_dtypes())

    @property
    def epoch(self):
        return getattr(self.iterator, 'epoch', 0)

    @property
    def epoch_detail(self):
        return getattr(self.iterator, 'epoch_detail', 0.0)

    @property
    def is_new_epoch(self):
        return getattr(self.iterator, 'is_new_epoch', False)


class MeshPipelineUpdater(PipelineUpdater):
    """The unified plan-based pipeline path (ROADMAP item 2): the
    same schedule machinery as :class:`PipelineUpdater`, rebound onto
    ONE 3-D :class:`chainermn_tpu.parallel.MeshPlan` mesh --
    ``(data, model, pipe)`` -- so the pipeline composes with the rest
    of the training stack instead of owning a side mesh:

    - stage parameters live on their ``pipe`` coordinate
      (``plan.stage_specs``; pass ``param_specs`` with Megatron
      ``model``-axis entries -- e.g.
      :func:`chainermn_tpu.models.pipeline_stage_specs` -- for tensor
      parallelism INSIDE each stage, riding the conjugate custom-vjp
      discipline of ``parallel/tensor.py``);
    - micro-batch activations and activation-grads hand off between
      stages via ``lax.ppermute`` over ``pipe`` (SL002 lints the ring
      bijective; the whole warmup/steady/cooldown ladder is one
      ``lax.scan`` inside ONE jitted ``shard_map`` step --
      ``trace_count`` stays 1 across steps);
    - gradients pmean over ``data`` at the end, exactly as
      ``StandardUpdater(param_specs=...)``'s plan communicator
      reduces them (``data_axes = ('data',)``), so dp composes
      unchanged.

    Defaults to ``schedule='1f1b'`` -- the in-flight-bounded schedule
    the composition was built for; ``'gpipe'`` remains available.
    The static bubble accounting (``parallel.pipeline.
    bubble_fraction``) is stamped on the telemetry stream at trace
    time and surfaced per stage by ``telemetry report``.
    """

    def __init__(self, iterator, optimizer, stage_fn, loss_on_last,
                 params_stacked, plan, n_micro, schedule='1f1b',
                 param_specs=None, **kw):
        if getattr(plan, 'pipe_axis', None) is None:
            raise ValueError(
                'MeshPipelineUpdater needs a plan with a pipeline '
                'axis: build it with MeshPlan.create(tp=..., pp=...)')
        if len(plan.data_axes) != 1:
            raise ValueError('the pipeline schedule expects a single '
                             'data axis, got %r' % (plan.data_axes,))
        tp_axis = (plan.model_axis
                   if plan.model_axis is not None
                   and plan.model_size > 1 else None)
        self.plan = plan
        super().__init__(
            iterator, optimizer, stage_fn, loss_on_last,
            params_stacked, plan.mesh, n_micro, schedule=schedule,
            param_specs=param_specs, data_axis=plan.data_axes[0],
            stage_axis=plan.pipe_axis, tp_axis=tp_axis, **kw)
