"""Communicator base class.

TPU-native rebuild of ``chainermn/communicators/_base.py``.  The
reference communicator is an eager, per-process object doing MPI/NCCL
calls; ours is a *mesh-backed* object whose collective methods are pure
functions valid inside ``shard_map``/``pjit`` traces over ``self.mesh``
(XLA lowers them to ICI/DCN collectives), plus a few eager driver-level
helpers for host-side data placement.

Correspondence with the reference API (``_base.py:15-80``):

- ``rank`` / ``size``            -> global device rank / device count
- ``intra_rank`` etc.            -> mesh coordinates (``_base.py:83-111``)
- ``send`` / ``recv``            -> :meth:`send_recv` (collective permute);
                                    typed eager wire protocol is unnecessary
                                    because XLA shapes are static
- ``broadcast_data(model)``      -> :meth:`broadcast_data` (root-select psum)
- ``allreduce_grad(model)``      -> :meth:`allreduce_grad` (strategy-defined)
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.communicators import mesh_utility
from chainermn_tpu.communicators.mesh_utility import (
    AXIS_INTER, AXIS_INTRA, AXES)


def _kv_key_state(client, key, unknown_counts=None):
    """Tri-state probe of a coordination-store key: ``'present'``,
    ``'absent'`` (the store POSITIVELY reports NOT_FOUND, i.e. the
    receiver consumed-and-deleted it), or ``'unknown'`` (a transient
    store/transport error -- neither conclusion is safe).

    NOT_FOUND is recognized case-insensitively in the message ("not
    found" included) AND in any structured status-code attribute the
    client's exception carries -- a coordination-service message
    rewording must not silently downgrade every consumed key to
    'unknown', which would make the GC sweep retry its sent-record
    forever (ADVICE r3).  As a second line of defense,
    ``unknown_counts`` (a dict the caller owns) counts consecutive
    'unknown' verdicts per key and warns when a key stays
    unclassifiable across many sweeps, so a systematic drift is loud
    instead of an invisible leak.

    Clients without ``key_value_try_get`` (jaxlib <= 0.4.36 ships
    only the blocking getter) are probed via ``key_value_dir_get`` on
    the key's parent -- a non-blocking POSITIVE enumeration either
    way: the key is listed (present) or it is not (absent); only a
    transport error yields 'unknown'."""
    try_get = getattr(client, 'key_value_try_get', None)
    if try_get is None:
        try:
            listed = client.key_value_dir_get(key.rsplit('/', 1)[0])
            state = ('present' if any(k == key for k, _ in listed)
                     else 'absent')
            if unknown_counts is not None:
                unknown_counts.pop(key, None)
            return state
        except Exception as e:
            if unknown_counts is not None:
                n = unknown_counts[key] = unknown_counts.get(key, 0) + 1
                if n in (3, 10, 30):
                    import warnings
                    warnings.warn(
                        'chainermn_tpu p2p GC: key %r unclassifiable '
                        'after %d probes (latest: %s); its sent-record '
                        'is kept and retried every sweep' % (key, n, e),
                        RuntimeWarning, stacklevel=2)
            return 'unknown'
    try:
        try_get(key)
        if unknown_counts is not None:
            unknown_counts.pop(key, None)
        return 'present'
    except Exception as e:
        up = str(e).upper()
        code = ''
        for attr in ('status_code', 'code', 'status'):
            v = getattr(e, attr, None)
            if v is None:
                continue
            try:
                code = str(v() if callable(v) else v).upper()
            except Exception:
                continue
            break
        # positive identification only: the structured code, the gRPC
        # status token (underscore form -- not natural prose), or a
        # message that LEADS with the status.  A bare substring match
        # on 'not found' would classify transient errors like 'leader
        # not found during election' as consumed and leak the key.
        if ('NOT_FOUND' in code or 'NOT_FOUND' in up
                or up.lstrip().startswith('NOT FOUND')):
            if unknown_counts is not None:
                unknown_counts.pop(key, None)
            return 'absent'
        if unknown_counts is not None:
            n = unknown_counts[key] = unknown_counts.get(key, 0) + 1
            if n in (3, 10, 30):
                import warnings
                warnings.warn(
                    'chainermn_tpu p2p GC: key %r unclassifiable '
                    'after %d probes (latest: %s); its sent-record '
                    'is kept and retried every sweep' % (key, n, e),
                    RuntimeWarning, stacklevel=2)
        return 'unknown'


def _is_tracing(tree):
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(tree))


class CommunicatorBase:
    """Mesh-backed communicator.

    ``allreduce_grad`` must be called inside a ``shard_map`` over
    ``self.mesh`` (the canonical way is via
    :func:`chainermn_tpu.create_multi_node_optimizer`); subclasses
    implement the reduction strategy in :meth:`_allreduce_impl`.
    """

    #: Declared reduction topology -- the mesh axes a full gradient
    #: allreduce covers.  Introspection hook for the static analyzer
    #: (:mod:`chainermn_tpu.analysis`): the union of reduce axes
    #: observed in ``allreduce_grad``'s jaxpr must equal this set.
    #: Strategies reducing over a subset (``single_node``) or nothing
    #: (``dummy``) override it.
    reduction_axes = AXES

    #: Axes the data-parallel contract spans: batch sharding
    #: (:meth:`shard_batch`), ZeRO-1 partitioning and
    #: :meth:`axis_rank`.  The classic strategies span the whole
    #: mesh; a composed plan
    #: (:class:`chainermn_tpu.parallel.MeshPlanCommunicator`)
    #: narrows this to its ``data`` axes so tensor-parallel shards
    #: are never partitioned or reduced across the ``model`` axis.
    data_axes = AXES

    def __init__(self, mesh=None, mesh_shape=None, devices=None,
                 reduce_dtype=None):
        """``reduce_dtype`` (e.g. ``'bfloat16'``): run every
        :meth:`allreduce_grad` in this dtype -- gradients are cast
        before the strategy's reduction and restored to their original
        dtypes afterwards, halving the bytes every gradient collective
        moves over ICI/DCN (the strategy-level twin of the multi-node
        optimizer's ``allreduce_dtype``; a ``StandardUpdater`` policy
        with a ``reduce_dtype`` imposes it here).  Declared via
        :meth:`declared_reduce_dtypes`, the introspection hook
        shardlint SL004 reads, so the deliberate narrowing is not a
        lint error.  ``None`` reduces in the gradients' own dtype.
        :meth:`allreduce` (metrics, BatchNorm statistics) and
        :meth:`broadcast_data` are NOT affected -- metric averages and
        the initial weight sync stay full precision.
        """
        if mesh is None:
            mesh = mesh_utility.build_mesh(devices, mesh_shape)
        self.mesh = mesh
        self.reduce_dtype = (jnp.dtype(reduce_dtype)
                             if reduce_dtype is not None else None)
        # env-activated fault injection (no-op unless
        # CHAINERMN_TPU_CHAOS is set; see utils/chaos.py)
        from chainermn_tpu.utils import chaos
        chaos.maybe_install_from_env()
        # env-activated runtime telemetry (no-op unless
        # CHAINERMN_TPU_TELEMETRY is set; see telemetry/)
        _telemetry.maybe_enable_from_env()

    # ------------------------------------------------------------------
    # Topology (reference `_base.py:15-21, 83-111`)
    # ------------------------------------------------------------------
    @property
    def size(self):
        """Total number of devices in the mesh (= reference world size)."""
        return self.mesh.size

    @property
    def inter_size(self):
        return self.mesh.shape[AXIS_INTER]

    @property
    def intra_size(self):
        return self.mesh.shape[AXIS_INTRA]

    @property
    def rank(self):
        """Driver-level rank: this *process*'s index.

        Inside a trace, per-device rank is :meth:`axis_rank`.  The
        reference has one process per device so its ``rank``/``size``
        form a pair; here they do NOT: ``rank`` counts processes while
        ``size`` counts devices.  Pair ``rank`` with
        :attr:`process_count` (e.g. for dataset sharding -- or better,
        pass the communicator to ``scatter_dataset`` and let it do
        this), and :meth:`axis_rank` with ``size``.
        """
        return jax.process_index()

    @property
    def process_count(self):
        """Number of controller processes participating in the mesh."""
        return len({d.process_index for d in self.mesh.devices.flat})

    def process_rank_in_mesh(self):
        """This process's index among the mesh's participating
        processes; raises if this process owns none of the mesh's
        devices."""
        procs = sorted({d.process_index for d in self.mesh.devices.flat})
        me = jax.process_index()
        if me not in procs:
            raise ValueError(
                'process %d owns no devices of this mesh (processes: %r)'
                % (me, procs))
        return procs.index(me)

    # -- in-trace coordinates ------------------------------------------
    def intra_rank(self):
        return lax.axis_index(AXIS_INTRA)

    def inter_rank(self):
        return lax.axis_index(AXIS_INTER)

    def axis_rank(self):
        """Global device rank, valid inside shard_map over ``self.mesh``."""
        return self.inter_rank() * self.intra_size + self.intra_rank()

    # ------------------------------------------------------------------
    # Collectives (in-trace)
    # ------------------------------------------------------------------
    def allreduce_grad(self, grads):
        """Mean-allreduce a gradient pytree across the whole mesh.

        Parity: communicator ``allreduce_grad`` including the 1/size
        averaging that every reference communicator applies (e.g.
        ``naive_communicator.py:19-20``).

        With :attr:`reduce_dtype` set, floating leaves are cast to it
        before the strategy's reduction and restored to their original
        dtypes after -- ONE cast point shared by all strategies, so
        every ``_allreduce_impl`` sees already-narrowed leaves and the
        declared dtype stays in lockstep with the executed one.
        """
        if _telemetry.live() is not None:
            # trace-time collective-issue mark (fires once per
            # compilation, not per step): correlates WHICH strategy
            # issued a gradient reduction into the program with the
            # step spans around its executions.  `axes` names the
            # mesh axes the reduction spans, so the report can split
            # dp vs tp collective time
            _telemetry.event(
                '%s:allreduce_grad' % type(self).__name__,
                kind='collective_trace',
                axes=list(self.reduction_axes),
                leaves=len(jax.tree_util.tree_leaves(grads)))
        rd = self.reduce_dtype
        if rd is None:
            return self._allreduce_impl(grads)
        from chainermn_tpu.precision import cast_floating
        reduced = self._allreduce_impl(cast_floating(grads, rd))
        return jax.tree_util.tree_map(
            lambda r, g: r.astype(jnp.result_type(g)), reduced, grads)

    def allreduce_plan(self, grads):
        """What one :meth:`allreduce_grad` of ``grads`` puts into the
        program, from shapes alone (nothing is traced): ``leaves`` and
        their ``bytes`` as reduced (after the :attr:`reduce_dtype`
        cast), and where the strategy decides it per leaf
        (``xla``, ``bucketed``) the ``collectives`` it issues and the
        ``packed_leaves`` that share one.  The attributes of the
        multi-node optimizer's ``allreduce_grad`` trace event."""
        from chainermn_tpu.precision import cast_floating
        reduced = jax.eval_shape(
            lambda g: cast_floating(g, self.reduce_dtype), grads)
        return self._plan_summary(jax.tree_util.tree_leaves(reduced))

    def _plan_summary(self, leaves):
        return {'leaves': len(leaves),
                'bytes': sum(leaf.size * leaf.dtype.itemsize
                             for leaf in leaves)}

    def step_compiler_options(self):
        """XLA options the train step that holds this strategy's
        :meth:`allreduce_grad` should be compiled under (``jax.jit``'s
        ``compiler_options``; :class:`StandardUpdater` passes them).
        None by default: a strategy asks for what its own collectives
        need, where the step is compiled, and no user sets a flag."""
        return {}

    def declared_reduce_dtypes(self):
        """Dtype names this strategy declares its gradient reduction
        may narrow to (shardlint SL004 introspection hook; the dtype
        twin of :attr:`reduction_axes`)."""
        if self.reduce_dtype is None:
            return set()
        return {str(self.reduce_dtype)}

    def _allreduce_impl(self, grads):
        raise NotImplementedError

    def allreduce(self, x, op='mean'):
        """Allreduce a single array or pytree over the full mesh."""
        red = {'mean': lambda v: lax.pmean(v, AXES),
               'sum': lambda v: lax.psum(v, AXES),
               'max': lambda v: lax.pmax(v, AXES),
               'min': lambda v: lax.pmin(v, AXES)}[op]
        return jax.tree_util.tree_map(red, x)

    def broadcast_data(self, params, root=0):
        """Every device receives ``root``'s values.

        Parity: ``broadcast_data`` / ``broadcast_naive``
        (``_communication_utility.py:57-60``).  Lowered as a masked psum
        -- XLA rewrites ``psum(select(rank==root, x, 0))`` into an
        efficient broadcast over ICI.

        Works both inside a trace (uses axis indices) and eagerly (uses
        replicated ``device_put``; with one controller every process
        holds the same host values, so replication *is* the broadcast).
        """
        if not _is_tracing(params):
            with _telemetry.span('broadcast_data', kind='collective',
                                 strategy=type(self).__name__,
                                 axes=list(AXES),
                                 seq=self._next_eager_seq(
                                     'broadcast_data')):
                return self.replicate(params)
        if _telemetry.live() is not None:
            _telemetry.event(
                '%s:broadcast_data' % type(self).__name__,
                kind='collective_trace', axes=list(AXES))
        me = self.axis_rank()

        def bcast(x):
            sel = jnp.where(me == root, x, jnp.zeros_like(x))
            return lax.psum(sel, AXES).astype(x.dtype)

        return jax.tree_util.tree_map(bcast, params)

    def send_recv(self, x, perm, axis=AXES):
        """Point-to-point: collective permute along one mesh axis.

        Parity: ``CommunicatorBase.send``/``recv`` (``_base.py:23-74``).
        The reference ships (ndim, shape, payload) as three eager MPI
        messages because Chainer shapes are dynamic; under XLA shapes
        are static so a single ``ppermute`` suffices, and its transpose
        (reverse permutation) is exactly the reference's
        ``Send.backward = recv`` (``point_to_point_communication.py:23-33``)
        -- supplied automatically by JAX autodiff.

        With the default ``axis`` (both mesh axes), ``perm`` pairs are
        *global* device ranks (row-major over (inter, intra), i.e.
        :meth:`axis_rank` values); pass a single axis name for
        axis-local permutes.
        """
        return lax.ppermute(x, axis, perm)

    # ------------------------------------------------------------------
    # Driver-level (eager) helpers
    # ------------------------------------------------------------------
    def replicate(self, tree):
        """Place a host pytree on the mesh fully replicated.

        Multihost-safe: each process places its own addressable
        shards locally (``training.placement.multihost_device_put``)
        -- no per-leaf coordination-service collectives.  Every
        process must pass the same host values (the replicated-init
        contract the reference has too)."""
        from chainermn_tpu.training.placement import multihost_device_put
        sharding = NamedSharding(self.mesh, P())
        with _telemetry.span('replicate', kind='h2d'):
            return multihost_device_put(tree, sharding)

    def shard_batch(self, tree, axis=0):
        """Place a host batch sharded over all devices along ``axis``.

        The TPU-native analogue of per-rank minibatching: one global
        array, leading dim split over (inter x intra).  Multihost-safe
        like :meth:`replicate`: every process passes the same GLOBAL
        batch and keeps only its own shards.
        """
        from chainermn_tpu.training.placement import multihost_device_put
        spec = [None] * axis + [AXES]
        sharding = NamedSharding(self.mesh, P(*spec))
        with _telemetry.span('shard_batch', kind='h2d'):
            return multihost_device_put(tree, sharding)

    def batch_spec(self, axis=0):
        return P(*([None] * axis + [AXES]))

    def _next_eager_seq(self, name, tag=None):
        """Per-(name, tag) occurrence counter stamped as the ``seq``
        attribute on eager collective spans.  Eager collectives are
        bulk-synchronous in program order, so every participating
        process counts the same rendezvous identically -- which is
        what lets ``telemetry.diagnosis`` pair the spans ACROSS ranks
        by (name, tag, seq) and attribute arrival skew.  One dict
        get/set per eager rendezvous -- noise next to the
        cross-process wait it annotates."""
        seqs = self.__dict__.setdefault('_eager_coll_seq', {})
        key = (name, tag)
        n = seqs.get(key, 0)
        seqs[key] = n + 1
        return n

    # -- peer liveness (heartbeat-backed dead-peer detection) ----------
    def enable_peer_liveness(self, directory, interval=1.0,
                             stall_timeout=5.0):
        """Start this process's heartbeat under ``directory`` (shared
        by all peers -- a common filesystem path, one
        ``heartbeat-{process_index}.json`` each) and arm dead-peer
        detection: every bounded wait in the eager channel
        (:meth:`recv_obj`, :meth:`barrier`,
        :meth:`allreduce_obj(timeout=...)`) then distinguishes a slow
        peer (:class:`~chainermn_tpu.utils.failure.ChannelTimeout`)
        from a dead one
        (:class:`~chainermn_tpu.utils.failure.PeerDeadError`) by
        probing the peer's heartbeat age against ``stall_timeout``.

        Returns the started
        :class:`~chainermn_tpu.utils.failure.Heartbeat` (stop it at
        teardown).
        """
        import os as _os
        import time as _time
        from chainermn_tpu.utils import failure
        hb = failure.Heartbeat(
            _os.path.join(directory,
                          'heartbeat-%d.json' % jax.process_index()),
            interval=interval).start()
        self._liveness = {'dir': directory, 'timeout': stall_timeout,
                          'enabled_at': _time.monotonic()}
        self._heartbeat = hb
        # hand the liveness dir off to the telemetry session: the
        # post-mortem doctor pairs this capture's flight records with
        # these heartbeat files to name the dead/stalled peer
        rec = _telemetry.live()
        if rec is not None:
            rec.liveness_dir = _os.path.abspath(directory)
            _telemetry.event('liveness_enabled', kind='liveness',
                             dir=rec.liveness_dir, interval=interval,
                             stall_timeout=stall_timeout)
        return hb

    def peer_state(self, process_index):
        """``'alive'`` / ``'dead'`` / ``'unknown'`` for a peer, from
        its heartbeat file.  ``'unknown'`` when liveness was never
        enabled, or the peer's file has not appeared yet within the
        startup grace window (a peer that is slow to write its FIRST
        beat is not dead)."""
        import os as _os
        import time as _time
        from chainermn_tpu.utils import failure
        live = self.__dict__.get('_liveness')
        if live is None:
            return 'unknown'
        if process_index == jax.process_index():
            return 'alive'
        path = _os.path.join(live['dir'],
                             'heartbeat-%d.json' % process_index)
        if not _os.path.exists(path):
            grace_over = (_time.monotonic() - live['enabled_at']
                          > live['timeout'])
            return 'dead' if grace_over else 'unknown'
        return ('dead' if failure.detect_stall(path, live['timeout'])
                else 'alive')

    def _raise_if_peer_dead(self, process_index, doing):
        from chainermn_tpu.utils import failure
        if self.peer_state(process_index) == 'dead':
            raise failure.PeerDeadError(
                '%s: peer process %d is dead (heartbeat stalled past '
                '%.1fs)' % (doing, process_index,
                            self._liveness['timeout']),
                process_index=process_index)

    def barrier(self, timeout=60.0, tag='barrier'):
        """Bounded cross-process rendezvous -- the eager mirror of the
        native engine's ``CMN_TIMEOUT`` barrier: every process must
        arrive within ``timeout`` seconds or the wait fails TYPED
        (:class:`~chainermn_tpu.utils.failure.PeerDeadError` naming
        the stalled peer when liveness is enabled, else
        :class:`~chainermn_tpu.utils.failure.ChannelTimeout`), instead
        of hanging the survivors forever the way an MPI barrier with a
        dead rank does.

        Uses the coordination service's native barrier when available,
        else a KV-key rendezvous with deadline-sliced waits.
        """
        if jax.process_count() == 1:
            return
        epochs = self.__dict__.setdefault('_barrier_epochs', {})
        n = epochs[tag] = epochs.get(tag, 0) + 1
        with _telemetry.span('barrier', kind='collective', tag=tag,
                             seq=n,
                             axes=list(self.mesh.axis_names)):
            return self._barrier_impl(timeout, tag, n)

    def _barrier_impl(self, timeout, tag, n):
        from chainermn_tpu.utils import chaos, failure
        client = self._kv_client()
        bid = 'chainermn_tpu/barrier/%s/%s/%d' % (
            self._p2p_channel(), tag, n)
        deadline = failure.Deadline(timeout)
        if chaos._active is not None:
            chaos.before_kv_wait()
        wait = getattr(client, 'wait_at_barrier', None)
        if wait is not None:
            try:
                wait(bid, max(int(deadline.remaining() * 1000), 1))
                return
            except Exception as e:
                for p in range(jax.process_count()):
                    self._raise_if_peer_dead(
                        p, 'barrier %r epoch %d' % (tag, n))
                raise failure.ChannelTimeout(
                    'barrier %r epoch %d: peers did not all arrive '
                    'within %.1fs' % (tag, n, timeout)) from e
        # KV fallback: publish own arrival, poll for every peer's
        me = jax.process_index()
        client.key_value_set('%s/%d' % (bid, me), '1')
        backoff = failure.Backoff(initial=0.05, max_delay=1.0)
        for p in range(jax.process_count()):
            if p == me:
                continue
            while True:
                try:
                    client.blocking_key_value_get(
                        '%s/%d' % (bid, p),
                        max(int(deadline.slice(backoff.next())
                                * 1000), 1))
                    break
                except Exception as e:
                    self._raise_if_peer_dead(
                        p, 'barrier %r epoch %d' % (tag, n))
                    if deadline.expired():
                        raise failure.ChannelTimeout(
                            'barrier %r epoch %d: process %d did not '
                            'arrive within %.1fs'
                            % (tag, n, p, timeout)) from e

    def allreduce_obj(self, value, op='mean', timeout=None):
        """Eager scalar/pytree allreduce across *processes*.

        Parity: the evaluator's pickle-based ``mpi_comm.allreduce``
        (``multi_node_evaluator.py:31-38``).  With a single controller
        every process computes the same global metrics, so this is the
        identity unless multi-process; then it runs a tiny jitted psum.

        ``timeout`` (seconds) bounds the wait: a :meth:`barrier` with
        that budget runs first, so a dead or stalled peer surfaces as
        a typed ``PeerDeadError``/``ChannelTimeout`` instead of the
        allgather blocking forever (the unbounded-wait hazard VERDICT
        r5 ranks top).  ``None`` preserves the raw unbounded
        collective.
        """
        if jax.process_count() == 1:
            return value
        if timeout is not None:
            self.barrier(timeout=timeout, tag='allreduce_obj')
        from jax.experimental import multihost_utils
        with _telemetry.span('allreduce_obj', kind='collective',
                             op=op, axes=list(self.mesh.axis_names),
                             seq=self._next_eager_seq(
                                 'allreduce_obj')):
            vals = multihost_utils.process_allgather(value)
        from chainermn_tpu.utils import chaos
        if chaos._active is not None:
            for _ in range(chaos.extra_collectives()):
                # phantom collective: same span + seq discipline as a
                # real rendezvous, but NO peer participates -- this
                # rank's recorded protocol stream diverges while the
                # run proceeds (the protocol-divergence doctor bait;
                # never touches _barrier_epochs, so no real wait)
                with _telemetry.span(
                        'allreduce_obj', kind='collective', op=op,
                        axes=list(self.mesh.axis_names),
                        seq=self._next_eager_seq('allreduce_obj')):
                    pass

        def red(stack):
            if op == 'mean':
                return stack.mean(axis=0)
            if op == 'sum':
                return stack.sum(axis=0)
            raise ValueError(op)
        return jax.tree_util.tree_map(red, vals)

    # -- eager cross-process object channel ----------------------------
    def _kv_client(self):
        try:
            from jax._src import distributed
            client = distributed.global_state.client
        except ImportError:  # pragma: no cover - jax internals moved
            client = None
        if client is None:
            raise RuntimeError(
                'cross-process object p2p needs jax.distributed to be '
                'initialized (multi-controller); with one process use '
                'plain Python values')
        return client

    def _p2p_channel(self):
        """Stable per-mesh channel namespace so two communicators over
        different meshes cannot cross wires.  NOTE: a communicator
        REBUILT over the same mesh resumes the same channel at seq 0;
        do not rebuild mid-conversation with unconsumed messages (pass
        a distinct ``channel`` to send_obj/recv_obj to segregate)."""
        import hashlib
        fp = ','.join(str(d.id) for d in self.mesh.devices.flat)
        fp += '|' + str(dict(self.mesh.shape))
        return hashlib.sha1(fp.encode()).hexdigest()[:12]

    def send_obj(self, obj, dest, tag=0, channel=None, timeout=30.0):
        """Eagerly ship an arbitrary picklable object to process
        ``dest``.

        Parity: the reference's typed wire protocol / pickle p2p
        (``_base.py:23-74``, ``dataset.py:29-43``) -- its eager MPI
        channel for things that are not traced arrays (datasets,
        configs, metrics).  Implemented over the jax.distributed
        key-value store, so it works across hosts (DCN), not just
        same-host like the shm engine.  FIFO per (src, dest, tag,
        channel).

        The publish is BOUNDED and self-healing: transient store
        failures (including chaos-injected drops) are retried with
        exponential backoff until ``timeout`` seconds, then raise
        :class:`~chainermn_tpu.utils.failure.ChannelTimeout` with the
        send cursor NOT advanced (the call can simply be reissued).
        A retry that finds the key already present treats the earlier
        attempt as delivered -- at-least-once publish, exactly-once
        consume (the receiver deletes on read).
        """
        import atexit
        import base64
        import pickle
        import time
        from chainermn_tpu.utils import chaos, failure
        client = self._kv_client()
        channel = channel or self._p2p_channel()
        seqs = self.__dict__.setdefault('_send_seq', {})
        stream = (dest, tag, channel)
        seq = seqs.get(stream, 0)
        key = 'chainermn_tpu/p2p/%s/%d/%d/%d/%d' % (
            channel, jax.process_index(), dest, tag, seq)
        payload = base64.b64encode(pickle.dumps(obj)).decode('ascii')
        deadline = failure.Deadline(timeout)
        backoff = failure.Backoff(initial=0.05, max_delay=1.0)
        with _telemetry.span('send_obj', kind='p2p', dest=dest,
                             tag=tag, seq=seq):
            while True:
                try:
                    if chaos._active is not None:
                        chaos.before_send()
                    client.key_value_set(key, payload)
                    if (chaos._active is not None
                            and chaos.duplicate_send()):
                        try:  # at-least-once duplicate, same key
                            client.key_value_set(key, payload)
                        except Exception:
                            pass  # store may reject the overwrite
                    break
                except Exception as e:
                    # the failed attempt may have landed server-side
                    # (or a previous retry did): already-present ==
                    # delivered
                    if _kv_key_state(client, key) == 'present':
                        break
                    if deadline.expired():
                        raise failure.ChannelTimeout(
                            'send_obj to process %d (tag %d seq %d): '
                            'publish kept failing for %.1fs (last: %r)'
                            % (dest, tag, seq, timeout, e)) from e
                    backoff.sleep(deadline)
        seqs[stream] = seq + 1
        # Hygiene (VERDICT r2 item 10): remember every key this process
        # published so undelivered ones can be GC'd -- a dead receiver
        # must not leak the coordinator's store.  recv_obj deletes on
        # consume; p2p_gc() sweeps the rest at teardown.
        sent = self.__dict__.setdefault('_p2p_sent_keys', {})
        sent[key] = (stream, seq, time.monotonic())
        if not self.__dict__.get('_p2p_atexit_registered'):
            # registered once per communicator; sweep only keys that
            # have sat undelivered for a while, so a receiver that is
            # alive but slow does not lose an in-flight message
            atexit.register(self.p2p_gc, grace=60.0)
            self._p2p_atexit_registered = True
        # keep the record bounded for long-running trainers: entries
        # for messages the receiver consumed long ago (key gone from
        # the store) are dropped opportunistically.  Probes are
        # expensive (try_get returns the full payload), so at most a
        # couple per send, and a still-present key is not re-probed
        # for another minute (_p2p_probe_at tracks per-key cooldown).
        if len(sent) > 128:
            now = time.monotonic()
            probed = self.__dict__.setdefault('_p2p_probe_at', {})
            stale = sorted(
                (k for k, v in sent.items()
                 if now - v[2] > 60.0 and now - probed.get(k, 0) > 60.0),
                key=lambda k: sent[k][2])[:2]
            unknowns = self.__dict__.setdefault('_p2p_unknown_counts',
                                                {})
            for k in stale:
                state = _kv_key_state(client, k, unknowns)
                if state == 'absent':
                    del sent[k]  # consumed: nothing left to GC
                    probed.pop(k, None)
                else:
                    # present -> still undelivered; unknown (transient
                    # store error) -> KEEP the record: dropping it
                    # would permanently leak the key from the sweep
                    probed[k] = now

    def recv_obj(self, source, tag=0, timeout=120.0, channel=None):
        """Blocking receive of the next object from process
        ``source`` (mirror of :meth:`send_obj`).

        The wait is BOUNDED and typed: it polls the store in
        exponentially-growing slices (never past the ``timeout``
        deadline -- :class:`~chainermn_tpu.utils.failure.Deadline`
        arithmetic), and between slices consults the sender's
        heartbeat when :meth:`enable_peer_liveness` armed it -- a dead
        sender surfaces as
        :class:`~chainermn_tpu.utils.failure.PeerDeadError` as soon as
        its heartbeat stalls, typically long before the full deadline;
        a merely-missing message raises
        :class:`~chainermn_tpu.utils.failure.ChannelTimeout` at the
        deadline.  On either failure the sequence cursor is NOT
        advanced, so the call can simply be retried."""
        import base64
        import pickle
        from chainermn_tpu.utils import chaos, failure
        client = self._kv_client()
        channel = channel or self._p2p_channel()
        if chaos._active is not None:
            chaos.on_recv()
        seqs = self.__dict__.setdefault('_recv_seq', {})
        seq = seqs.get((source, tag, channel), 0)
        key = 'chainermn_tpu/p2p/%s/%d/%d/%d/%d' % (
            channel, source, jax.process_index(), tag, seq)
        deadline = failure.Deadline(timeout)
        backoff = failure.Backoff(initial=0.1, max_delay=2.0)
        with _telemetry.span('recv_obj', kind='p2p', source=source,
                             tag=tag, seq=seq):
            while True:
                if chaos._active is not None:
                    chaos.before_kv_wait()
                try:
                    payload = client.blocking_key_value_get(
                        key, max(int(deadline.slice(backoff.next())
                                     * 1000), 1))
                    break
                except Exception as e:
                    self._raise_if_peer_dead(
                        source, 'recv_obj(source=%d, tag=%d, seq=%d)'
                        % (source, tag, seq))
                    if deadline.expired():
                        raise failure.ChannelTimeout(
                            'recv_obj from process %d (tag %d seq '
                            '%d): nothing arrived within %.1fs'
                            % (source, tag, seq, timeout)) from e
        # delete BEFORE advancing the cursor: shrinks (does not close --
        # the store has no atomic get+delete) the window in which the
        # sender's p2p_gc could see a consumed key as still-undelivered
        # and rewind its cursor under us; see p2p_gc's docstring.
        client.key_value_delete(key)
        seqs[(source, tag, channel)] = seq + 1
        return pickle.loads(base64.b64decode(payload))

    def p2p_gc(self, grace=0.0, timeout=None):
        """Delete object-p2p keys this process published that have not
        (observably) been consumed, for streams whose outstanding keys
        are ALL older than ``grace`` seconds, then roll each swept
        stream's send cursor back so a re-send reuses the freed
        sequence slots (the receiver's cursor never advanced past
        them, so retry works end-to-end).  Streams with any younger
        outstanding key are skipped whole -- never partially swept.

        Registered once per communicator at interpreter exit with
        ``grace=60``: keys younger than that are likely in flight to a
        live-but-slow receiver and are left alone (they leak only if
        the receiver is truly gone); older undelivered keys are the
        dead-receiver garbage this sweep exists for.  ``grace=0``
        sweeps everything immediately -- use it ONLY at explicit
        teardown when no receiver can be mid-``recv_obj``: the store
        has no atomic get+delete, so a key fetched but not yet deleted
        by the receiver would be classified undelivered and its
        sequence slot incorrectly rewound (with grace=60 a consume
        outstanding for a full minute is the failure the sweep exists
        for anyway).  Deleting a key the receiver already consumed is
        a no-op.
        Parity anchor: the reference's eager channel tears down with
        the MPI communicator (``_base.py:23-74``); the KV store has no
        such lifetime, so we give it one.

        ``timeout`` (seconds) bounds the whole sweep: probes against a
        wedged store stop at the deadline and the unswept records are
        kept for a later pass (the sweep is already incremental, so a
        bounded partial sweep is safe).
        """
        import time
        from chainermn_tpu.utils import failure
        sent = self.__dict__.get('_p2p_sent_keys')
        if not sent:
            return
        deadline = failure.Deadline(timeout)
        now = time.monotonic()
        # sweep whole streams atomically: if ANY key of a stream is
        # younger than grace, leave the entire stream alone.  Sweeping
        # an age prefix while newer keys survive would rewind the
        # cursor underneath live messages (retries would collide with
        # or be shadowed by the stale survivors).
        young_streams = {v[0] for v in sent.values()
                         if now - v[2] < grace}
        old = {k: v for k, v in sent.items()
               if v[0] not in young_streams}
        if not old:
            return
        try:
            client = self._kv_client()
        except Exception:
            return  # runtime already gone; nothing to clean
        swept_min = {}
        for key in sorted(old):
            if deadline.expired():
                break  # bounded sweep: the rest waits for a later pass
            stream, seq, _ = old[key]
            try:
                # distinguish consumed (receiver deleted it: cursor
                # must NOT rewind) from undelivered (still present:
                # delete and free its sequence slot for a retry); a
                # transient store error is NEITHER -- keep the record
                # for a later sweep rather than mis-classifying
                state = _kv_key_state(
                    client, key,
                    self.__dict__.setdefault('_p2p_unknown_counts',
                                             {}))
                if state == 'unknown':
                    continue
                if state == 'present':
                    client.key_value_delete(key)
                    swept_min[stream] = min(
                        swept_min.get(stream, seq), seq)
                del sent[key]
            except Exception:
                continue  # best-effort: coordinator may be shutting down
        # rewind send cursors so "re-send after sweep" lands where the
        # receiver is still waiting
        seqs = self.__dict__.get('_send_seq', {})
        for stream, seq in swept_min.items():
            seqs[stream] = min(seqs.get(stream, seq), seq)

    # ------------------------------------------------------------------
    def __repr__(self):
        return '%s(inter=%d, intra=%d)' % (
            type(self).__name__, self.inter_size, self.intra_size)
