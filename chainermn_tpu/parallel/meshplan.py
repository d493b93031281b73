"""MeshPlan: composed named-axis device meshes (data x model).

Every parallelism axis in the repo used to run alone -- the 9
data-parallel strategies span the whole ``(inter, intra)`` mesh, ZeRO
partitions over it, the pipeline owns its own ``(data, stage)`` mesh.
``MeshPlan`` is the composition layer: ONE mesh with named roles --
``data`` (batch sharding + gradient reduction + ZeRO partitioning) and
``model`` (Megatron tensor parallelism: attention heads / MLP columns
and rows, :mod:`chainermn_tpu.parallel.tensor`) -- built from the same
TPU/CPU topology discovery as the communicators
(:mod:`chainermn_tpu.communicators.mesh_utility`), handing out
``NamedSharding``/``PartitionSpec`` trees for params, optimizer state
and batches (the SNIPPETS [2] named-2-D-mesh pattern, GSPMD-style: the
specs declare placement, the compiler inserts the collectives the
specs imply).

Degradation is graceful and SHAPE-ONLY (the SNIPPETS [2] contract):
both axes always exist with stable names; on small device counts the
requested tp clamps to the largest divisor of the device count, so
1 device -> ``(1, 1)``, tp >= n -> ``(1, n)``, tp = 1 -> ``(n, 1)`` --
a ``psum`` over a size-1 axis is the identity and the same program
runs unchanged.

The composition is 3-D: ``MeshPlan.create(tp=N, pp=K)`` binds a
``pipe`` axis (minor, so the 1F1B stage-boundary ``ppermute`` rides
neighbor links) whose coordinates own the pipeline stages'
parameters (:meth:`MeshPlan.stage_specs`), trained through
:class:`chainermn_tpu.training.MeshPipelineUpdater` -- the unified
plan-based pipeline path (``docs/mesh_parallelism.md``).
``MeshPlan.create(ep=N)`` is the expert-axis on-ramp: a
``(data, expert)`` mesh whose ``expert`` axis carries the
:class:`chainermn_tpu.parallel.MoELayer` ``all_to_all``
(:meth:`MeshPlan.expert_param_specs`).

Threading: ``plan.communicator()`` returns a
:class:`MeshPlanCommunicator` -- the updater-facing adapter whose
gradient reduction, batch sharding and ZeRO partitioning span the
``data`` axes ONLY (tensor-parallel leaves are sharded, not
replicated, over ``model``; reducing them across it would be wrong) --
and ``StandardUpdater(param_specs=...)`` takes the per-leaf spec tree
(e.g. :func:`chainermn_tpu.models.tp_param_specs`) through placement,
the mesh-aware jitted step (donation and policy casts intact) and the
shard_map in/out specs.  See ``docs/mesh_parallelism.md``.
"""

import numpy as np

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.communicators import mesh_utility
from chainermn_tpu.communicators.base import CommunicatorBase

#: canonical plan axis names (the SNIPPETS [2] ("batch", "model")
#: pattern under the repo's own vocabulary)
AXIS_DATA = 'data'
AXIS_MODEL = 'model'
AXIS_PIPE = 'pipe'
AXIS_EXPERT = 'expert'
#: the failure-domain axis ABOVE the mesh's data axis: devices inside
#: one slice share fast ICI, slices talk over DCN, and a slice is the
#: unit of both hierarchical gradient reduction (in-slice psum, then
#: cross-slice reduce) and supervisor shrink (a dead slice is removed
#: whole, never split) -- the TPU-native twin of the reference's
#: node-aware hierarchical communicators.
AXIS_SLICE = 'slice'
PLAN_AXES = (AXIS_DATA, AXIS_MODEL)
PLAN_AXES_3D = (AXIS_DATA, AXIS_MODEL, AXIS_PIPE)


class MeshPlan:
    """A named-axis mesh plus the spec handout for training on it.

    Attributes:
      mesh: the ``jax.sharding.Mesh`` -- 2-D ``(data, model)``, 3-D
        ``(data, model, pipe)`` when a pipeline width was requested,
        or ``(data, expert)`` for an expert-parallel plan.
      data_axes: axes batch sharding / gradient reduction / ZeRO span.
      model_axis: the tensor-parallel axis name (None on expert plans).
      pipe_axis: the pipeline-stage axis name, or None on 2-D plans.
      expert_axis: the expert-parallel axis name, or None.
      requested_tp / requested_pp / requested_ep: the widths the
        caller asked for (the effective widths are ``model_size`` /
        ``pipe_size`` / ``expert_size``; they differ only under
        graceful degradation).
    """

    def __init__(self, mesh, data_axes=(AXIS_DATA,),
                 model_axis=AXIS_MODEL, requested_tp=None,
                 pipe_axis=None, requested_pp=None,
                 expert_axis=None, requested_ep=None,
                 slice_axis=None, requested_slices=None):
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        if model_axis is not None and model_axis not in mesh.shape:
            model_axis = None
        self.model_axis = model_axis
        # a directly-constructed Mesh that binds the canonical pipe /
        # expert / slice names IS a 3-D / expert / multi-slice plan
        # (test meshes build this way); explicit kwargs override
        if pipe_axis is None and AXIS_PIPE in mesh.shape:
            pipe_axis = AXIS_PIPE
        if expert_axis is None and AXIS_EXPERT in mesh.shape:
            expert_axis = AXIS_EXPERT
        if slice_axis is None and AXIS_SLICE in mesh.shape:
            slice_axis = AXIS_SLICE
        self.pipe_axis = pipe_axis
        self.expert_axis = expert_axis
        self.slice_axis = slice_axis
        if (slice_axis is not None
                and slice_axis not in self.data_axes):
            # the slice level sits ABOVE data: batch sharding, ZeRO
            # and gradient reduction span (slice, data), slice major
            self.data_axes = (slice_axis,) + self.data_axes
        self.requested_tp = requested_tp
        self.requested_pp = requested_pp
        self.requested_ep = requested_ep
        self.requested_slices = requested_slices
        bound = self.data_axes + tuple(
            ax for ax in (self.model_axis, self.pipe_axis,
                          self.expert_axis) if ax is not None)
        for ax in bound:
            if ax not in mesh.shape:
                raise ValueError('mesh %r does not bind plan axis %r'
                                 % (dict(mesh.shape), ax))

    # -- construction --------------------------------------------------
    @classmethod
    def create(cls, tp=1, devices=None, axis_names=PLAN_AXES, pp=None,
               ep=None, slices=None):
        """Compose a plan over the global devices.

        ``tp`` is the requested model-axis width; it degrades to the
        largest divisor of the device count
        (:func:`mesh_utility.divisor_leq`), never errors on a small
        host.  Devices are ordered by the same slice-aware sort as
        the communicators (``mesh_utility.sorted_devices``), and the
        model axis stays more minor than ``data`` so tensor
        parallelism lands on tight ICI neighbors.

        ``pp`` (an int >= 1) adds the pipeline axis: the mesh becomes
        3-D ``(data, model, pipe)`` with ``pipe`` the MINOR
        (fastest-varying) axis, so the 1F1B stage-boundary
        ``ppermute`` rides neighbor links.  Degradation extends to
        3-D via :func:`mesh_utility.divisors_leq` -- tp clamps first,
        pp within what remains, the data axis absorbs the rest; the
        axis NAMES never change with the shape (1 device ->
        ``(1, 1, 1)``, ``tp * pp > n`` clamps both, primes degrade
        the later axis to 1).  ``pp=None`` (the default) keeps the
        2-D plan unchanged.

        ``ep`` (an int >= 1) builds the expert-parallel on-ramp
        instead: a ``(data, expert)`` mesh whose ``expert`` axis
        carries the :class:`chainermn_tpu.parallel.MoELayer`
        ``all_to_all`` (see :meth:`expert_param_specs`).  Composing
        ``ep`` with ``tp > 1`` or ``pp`` is not implemented yet.

        ``slices`` (an int >= 1) binds the failure-domain axis ABOVE
        the mesh: the slice-aware device sort already groups each ICI
        domain contiguously, so ``slices=N`` reshapes those groups
        into the MAJOR mesh axis -- one mesh row = one slice = one
        unit of loss.  Gradient reduction goes hierarchical over it
        (in-slice psum, then cross-slice reduce -- see
        :meth:`MeshPlanCommunicator._allreduce_impl`) and the
        supervisor shrinks by whole slices on ``slice_loss``.  The
        slice width has top clamping priority (a slice boundary is
        physical), then tp, then pp; ``slices=None`` (the default)
        keeps the plan sliceless.  Composing ``slices`` with ``ep``
        is not implemented yet.
        """
        if tp < 1:
            raise ValueError('tp must be >= 1, got %d' % tp)
        if slices is not None and slices < 1:
            raise ValueError('slices must be >= 1, got %d' % slices)
        devices = mesh_utility.sorted_devices(devices)
        n = len(devices)
        if ep is not None:
            if ep < 1:
                raise ValueError('ep must be >= 1, got %d' % ep)
            if tp > 1 or pp is not None or slices is not None:
                raise NotImplementedError(
                    'the expert axis composes with data parallelism '
                    'only for now: pass ep= without tp/pp/slices '
                    '(full mesh-placed MoE training is the follow-up)')
            eff = mesh_utility.divisor_leq(n, ep)
            arr = np.asarray(  # noqa: shardlint - eager driver-level
                devices, dtype=object).reshape(n // eff, eff)
            return cls(Mesh(arr, (AXIS_DATA, AXIS_EXPERT)),
                       data_axes=(AXIS_DATA,), model_axis=None,
                       expert_axis=AXIS_EXPERT, requested_ep=ep)
        if pp is None:
            if slices is None:
                eff = mesh_utility.divisor_leq(n, tp)
                arr = np.asarray(  # noqa: shardlint - eager driver
                    devices, dtype=object).reshape(n // eff, eff)
                data_name, model_name = axis_names
                return cls(Mesh(arr, (data_name, model_name)),
                           data_axes=(data_name,),
                           model_axis=model_name, requested_tp=tp)
            eff_s, eff_tp = mesh_utility.divisors_leq(n, (slices, tp))
            arr = np.asarray(  # noqa: shardlint - eager driver-level
                devices, dtype=object).reshape(
                    eff_s, n // (eff_s * eff_tp), eff_tp)
            data_name, model_name = axis_names
            return cls(Mesh(arr, (AXIS_SLICE, data_name, model_name)),
                       data_axes=(data_name,), model_axis=model_name,
                       requested_tp=tp, slice_axis=AXIS_SLICE,
                       requested_slices=slices)
        if pp < 1:
            raise ValueError('pp must be >= 1, got %d' % pp)
        if len(axis_names) == 2:
            axis_names = tuple(axis_names) + (AXIS_PIPE,)
        data_name, model_name, pipe_name = axis_names
        if slices is None:
            eff_tp, eff_pp = mesh_utility.divisors_leq(n, (tp, pp))
            arr = np.asarray(  # noqa: shardlint - eager driver-level
                devices, dtype=object).reshape(
                    n // (eff_tp * eff_pp), eff_tp, eff_pp)
            return cls(Mesh(arr, (data_name, model_name, pipe_name)),
                       data_axes=(data_name,), model_axis=model_name,
                       requested_tp=tp, pipe_axis=pipe_name,
                       requested_pp=pp)
        eff_s, eff_tp, eff_pp = mesh_utility.divisors_leq(
            n, (slices, tp, pp))
        arr = np.asarray(  # noqa: shardlint - eager driver-level
            devices, dtype=object).reshape(
                eff_s, n // (eff_s * eff_tp * eff_pp), eff_tp, eff_pp)
        return cls(Mesh(arr, (AXIS_SLICE, data_name, model_name,
                              pipe_name)),
                   data_axes=(data_name,), model_axis=model_name,
                   requested_tp=tp, pipe_axis=pipe_name,
                   requested_pp=pp, slice_axis=AXIS_SLICE,
                   requested_slices=slices)

    # -- topology ------------------------------------------------------
    @property
    def size(self):
        return self.mesh.size

    @property
    def data_size(self):
        out = 1
        for ax in self.data_axes:
            out *= self.mesh.shape[ax]
        return out

    @property
    def model_size(self):
        if self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def pipe_size(self):
        """Pipeline-stage count (1 when no pipe axis is bound -- the
        shape-only degradation contract: a size-1 pipeline is the
        unpipelined program)."""
        if self.pipe_axis is None:
            return 1
        return self.mesh.shape[self.pipe_axis]

    @property
    def expert_size(self):
        if self.expert_axis is None:
            return 1
        return self.mesh.shape[self.expert_axis]

    @property
    def slice_size(self):
        """Number of failure-domain slices (1 when no slice axis is
        bound -- the shape-only degradation contract: a one-slice
        plan is the flat plan)."""
        if self.slice_axis is None:
            return 1
        return self.mesh.shape[self.slice_axis]

    @property
    def axis_names(self):
        return tuple(self.mesh.axis_names)

    def describe(self):
        """Provenance dict for bench rows / checkpoint manifests."""
        out = {'axes': {k: int(v) for k, v in self.mesh.shape.items()},
               'data_axes': list(self.data_axes),
               'model_axis': self.model_axis,
               'requested_tp': self.requested_tp,
               'effective_tp': int(self.model_size)}
        if self.pipe_axis is not None:
            out['pipe_axis'] = self.pipe_axis
            out['requested_pp'] = self.requested_pp
            out['effective_pp'] = int(self.pipe_size)
        if self.expert_axis is not None:
            out['expert_axis'] = self.expert_axis
            out['requested_ep'] = self.requested_ep
            out['effective_ep'] = int(self.expert_size)
        if self.slice_axis is not None:
            out['slice_axis'] = self.slice_axis
            out['requested_slices'] = self.requested_slices
            out['effective_slices'] = int(self.slice_size)
        return out

    # -- spec handout --------------------------------------------------
    def batch_spec(self, axis=0):
        """Batch spec: the leading (or ``axis``-th) dim sharded over
        the DATA axes only -- every model rank of a data replica sees
        the same per-replica batch."""
        return P(*([None] * axis + [self.data_axes]))

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def sharding(self, spec):
        return NamedSharding(self.mesh, spec)

    def batch_sharding(self, axis=0):
        return self.sharding(self.batch_spec(axis))

    def param_shardings(self, specs):
        """``NamedSharding`` tree from a ``PartitionSpec`` tree (e.g.
        :func:`chainermn_tpu.models.tp_param_specs`)."""
        return jax.tree_util.tree_map(self.sharding, specs)

    def state_specs(self, param_specs, params, state):
        """Broadcast a param spec tree through an optax state.

        Optimizer states embed param-STRUCTURED subtrees (adam's
        mu/nu); every subtree whose structure matches ``params`` gets
        ``param_specs`` verbatim, every other leaf (step counters,
        loss-scale scalars) is replicated.  This is how the
        tensor-parallel sharding of a weight follows its optimizer
        moments without per-optimizer plumbing."""
        return broadcast_specs_to_state(param_specs, params, state)

    def local_shape(self, shape, spec):
        """The per-device shape of a global ``shape`` under ``spec``
        on this mesh (sharded dims divided by their axis sizes)."""
        shape = list(shape)
        for i, axes in enumerate(tuple(spec) + (None,) * (
                len(shape) - len(tuple(spec)))):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else tuple(axes)
            for ax in axes:
                k = self.mesh.shape[ax]
                if shape[i] % k:
                    raise ValueError(
                        'dim %d of shape %r does not divide over axis '
                        '%r (size %d)' % (i, tuple(shape), ax, k))
                shape[i] //= k
        return tuple(shape)

    def stage_specs(self, params_stacked, body_specs=None):
        """``PartitionSpec`` tree placing each pipeline stage's
        parameters on its ``pipe`` coordinate: every leaf of a
        stage-STACKED tree (leading dim = ``pipe_size``; see
        :func:`chainermn_tpu.parallel.pipeline.stack_stage_params`)
        gets ``P(pipe_axis)`` -- or, with ``body_specs`` (a leaf-exact
        spec tree over the UNSTACKED leaf dims, e.g. the Megatron tp
        specs of one stage body), ``P(pipe_axis, *body_spec)`` so
        tensor parallelism composes inside each stage."""
        if self.pipe_axis is None:
            raise ValueError('stage_specs needs a pipeline axis: '
                             'build the plan with MeshPlan.create('
                             'pp=...)')
        pipe = self.pipe_axis
        if body_specs is None:
            return jax.tree_util.tree_map(lambda _: P(pipe),
                                          params_stacked)
        from jax.sharding import PartitionSpec
        return jax.tree_util.tree_map(
            lambda _leaf, sp: P(pipe, *tuple(sp)),
            params_stacked, body_specs,
            is_leaf=lambda v: isinstance(v, PartitionSpec))

    def expert_param_specs(self, params):
        """``PartitionSpec`` tree for a
        :class:`chainermn_tpu.parallel.MoELayer` parameter tree
        (:meth:`MoELayer.init_params`): the expert-stacked
        ``w_in``/``w_out`` shard their leading experts dim over the
        ``expert`` axis, the ``router`` (and any other <3-D leaf)
        replicates."""
        if self.expert_axis is None:
            raise ValueError('expert_param_specs needs an expert '
                             'axis: build the plan with '
                             'MeshPlan.create(ep=...)')
        ax = self.expert_axis

        def one(leaf):
            if getattr(leaf, 'ndim', 0) >= 3:
                return P(ax)
            return P()
        return jax.tree_util.tree_map(one, params)

    # -- updater threading ---------------------------------------------
    def communicator(self, reduce_dtype=None):
        """The updater-facing communicator for this plan (gradient
        reduction / ZeRO over the data axes only)."""
        return MeshPlanCommunicator(self, reduce_dtype=reduce_dtype)


def broadcast_specs_to_state(param_specs, params, state):
    """See :meth:`MeshPlan.state_specs` (module-level so the updater
    can call it without holding a plan)."""
    pstruct = jax.tree_util.tree_structure(params)

    def matches(node):
        try:
            return jax.tree_util.tree_structure(node) == pstruct
        except Exception:
            return False

    def one(node):
        if matches(node):
            return param_specs
        return jax.tree_util.tree_map(lambda _: P(), node)

    return jax.tree_util.tree_map(one, state, is_leaf=matches)


class MeshPlanCommunicator(CommunicatorBase):
    """Communicator adapter over a :class:`MeshPlan`.

    The classic strategies span the whole ``(inter, intra)`` mesh;
    this one scopes the DATA-parallel contract to the plan's ``data``
    axes -- :meth:`allreduce_grad` pmeans over ``data`` only (a
    tensor-parallel leaf is SHARDED over ``model``: its per-shard
    gradients are already exact and must not be combined across the
    axis), :meth:`shard_batch`/:meth:`batch_spec` shard the batch over
    ``data`` only (model ranks of one replica see the same batch), the
    in-trace :meth:`broadcast_data` syncs replicas along ``data``
    while leaving model shards alone, and :attr:`size`/
    :meth:`axis_rank` count DATA replicas -- which is what the
    updater's batch-divisibility check and ZeRO-1 partitioning
    consume ("partition along data only").  Metric/statistic
    :meth:`allreduce` still spans the full mesh (post-psum losses are
    replicated over ``model``, so the full-mesh mean equals the data
    mean).  Eager helpers (``replicate``, object p2p, barriers)
    inherit unchanged.
    """

    def __init__(self, plan, reduce_dtype=None):
        self.plan = plan
        super().__init__(mesh=plan.mesh, reduce_dtype=reduce_dtype)
        # introspection hooks (shardlint SL001/SL010, updater ZeRO)
        self.reduction_axes = plan.data_axes
        self.data_axes = plan.data_axes

    # -- topology ------------------------------------------------------
    @property
    def size(self):
        """Number of DATA replicas (batch divisor, ZeRO partition
        count) -- NOT the device count; that is ``mesh.size``."""
        return self.plan.data_size

    @property
    def inter_size(self):
        return self.plan.data_size

    @property
    def intra_size(self):
        return self.plan.model_size

    def axis_rank(self):
        """This device's DATA-replica index (valid in-trace)."""
        rank = 0
        for ax in self.plan.data_axes:
            rank = rank * self.mesh.shape[ax] + lax.axis_index(ax)
        return rank

    def model_rank(self):
        if self.plan.model_axis is None:
            raise ValueError('this plan binds no model axis')
        return lax.axis_index(self.plan.model_axis)

    # -- collectives ---------------------------------------------------
    def _allreduce_impl(self, grads):
        plan = self.plan
        if plan.slice_axis is not None:
            # hierarchical two-stage reduction: psum inside each slice
            # first (ICI -- cheap, wide links), then psum the per-slice
            # partials across slices (DCN -- the expensive hop moves
            # each leaf once per slice, not once per device).  The
            # staged sum over disjoint axis sets equals the flat psum
            # over all data axes; dividing by data_size restores the
            # pmean contract bit-for-bit in f32.  shardlint knows this
            # chain is deliberate via the target's ``staged_axes``
            # declaration (SL011's staged-reduce exemption).
            inner = tuple(ax for ax in plan.data_axes
                          if ax != plan.slice_axis)
            k = plan.data_size

            def staged(g):
                if inner:
                    g = lax.psum(g, inner)
                g = lax.psum(g, (plan.slice_axis,))
                return g / k
            return jax.tree_util.tree_map(staged, grads)
        axes = plan.data_axes
        return jax.tree_util.tree_map(
            lambda g: lax.pmean(g, axes), grads)

    def allreduce(self, x, op='mean'):
        axes = tuple(self.mesh.axis_names)
        red = {'mean': lambda v: lax.pmean(v, axes),
               'sum': lambda v: lax.psum(v, axes),
               'max': lambda v: lax.pmax(v, axes),
               'min': lambda v: lax.pmin(v, axes)}[op]
        return jax.tree_util.tree_map(red, x)

    def broadcast_data(self, params, root=0):
        """Every DATA replica receives replica ``root``'s values;
        model shards stay untouched (a full-mesh broadcast would
        overwrite one model rank's shard with another's).  In-trace
        only: eager placement of a plan-sharded tree goes through
        ``plan.param_shardings`` + ``device_put`` instead."""
        from chainermn_tpu.communicators.base import _is_tracing
        import jax.numpy as jnp

        if not _is_tracing(params):
            raise NotImplementedError(
                'eager broadcast_data is undefined for a plan-sharded '
                'tree; place it with '
                'plan.param_shardings(specs) / multihost_device_put')
        if _telemetry.live() is not None:
            _telemetry.event(
                '%s:broadcast_data' % type(self).__name__,
                kind='collective_trace',
                axes=list(self.plan.data_axes))
        me = self.axis_rank()

        def bcast(x):
            sel = jnp.where(me == root, x, jnp.zeros_like(x))
            return lax.psum(sel, self.plan.data_axes).astype(x.dtype)

        return jax.tree_util.tree_map(bcast, params)

    # -- driver-level helpers ------------------------------------------
    def shard_batch(self, tree, axis=0):
        from chainermn_tpu.training.placement import multihost_device_put
        sharding = NamedSharding(self.mesh, self.batch_spec(axis))
        with _telemetry.span('shard_batch', kind='h2d',
                             axes=list(self.plan.data_axes)):
            return multihost_device_put(tree, sharding)

    def batch_spec(self, axis=0):
        return self.plan.batch_spec(axis)

    def __repr__(self):
        return 'MeshPlanCommunicator(%s)' % (
            ', '.join('%s=%d' % (k, v)
                      for k, v in self.mesh.shape.items()))
