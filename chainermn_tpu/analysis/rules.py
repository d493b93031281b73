"""shardlint rules.

Each rule is a function ``rule(ctx) -> [Finding, ...]`` over a
:class:`RuleContext` holding the target's traced jaxpr and its
declared topology.  Rule IDs are stable (``SL0xx``); see
``docs/static_analysis.md`` for the catalogue.  The ChainerMN
reference proved these invariants dynamically by running the suite
under ``mpiexec -n {1,2,3}``; here the sharding decisions live in
traced code, so the same invariants are PROVEN per strategy from the
jaxpr on CPU.
"""

import numpy as np

from chainermn_tpu.analysis import walker
from chainermn_tpu.analysis.findings import (
    Finding, SEV_ERROR, SEV_WARNING)


class RuleContext:
    """Everything a rule may inspect for one lint target.

    Attributes:
      target_name: display name (``"strategy:xla:allreduce_grad"``).
      jaxpr: the target's ``ClosedJaxpr`` (None when tracing failed).
      mesh_axes: ``{axis_name: size}`` of the target's mesh.
      reduction_axes: declared reduce topology (tuple of axis names)
        for gradient-reduction targets, else None -- the
        communicator's ``reduction_axes`` introspection hook.
      declared_dtypes: dtype names the target DECLARES reductions may
        narrow to (the communicator's / updater's
        ``declared_reduce_dtypes`` introspection hook -- a
        mixed-precision policy's reduce/compute dtypes); None or
        empty means any narrowing is a finding.
      signatures: list of abstract signatures of two synthetic
        consecutive steps (None for single-shot targets).
      compute_dtype: the dtype name the target DECLARES its compute
        runs in (a mixed-precision policy's compute dtype, or a
        model's native compute dtype); SL008 audits f32
        materializations only in declared-narrow graphs.  None
        disables that rule.
      overlap_check: run the SL009 collective-overlap audit on this
        target.  True for train-step targets only: a standalone
        collective helper (a strategy's bare ``allreduce_grad``) has
        nothing to overlap with BY CONSTRUCTION and would always
        read as serialized.
      plan_axes: the composed-mesh axes the target DECLARES its
        computation spans (a :class:`chainermn_tpu.parallel.MeshPlan`
        target declares ``('data', 'model')``); enables the SL010
        multi-axis family.  None (single-axis targets) disables it.
      rank_addressed: op names the target DECLARES rank-asymmetric
        (a root-addressed broadcast, a deliberate per-rank p2p leg);
        SL013's stream comparison and SL015's control-flow audit
        exempt exactly these.  None/empty means every collective must
        be rank-uniform.
      rank_streams: ``{rank: [record, ...]}`` per-rank collective
        streams for SL013 (``commcheck.verify_streams`` record shape)
        -- the runner replicates the traced jaxpr's stream (one SPMD
        program serves every rank); ``commcheck.run_commcheck`` and
        the fixtures supply genuinely per-rank simulated streams.
      p2p_streams: ``{rank: [record, ...]}`` per-rank eager op streams
        for SL014's wait-for matcher (``commcheck.match_p2p``); None
        skips the dynamic half (the static ppermute-chain half always
        runs off the jaxpr).
      trace_error: exception raised while tracing, if any.
    """

    def __init__(self, target_name, jaxpr=None, mesh_axes=None,
                 reduction_axes=None, signatures=None,
                 trace_error=None, declared_dtypes=None,
                 compute_dtype=None, overlap_check=False,
                 plan_axes=None, rank_addressed=None,
                 rank_streams=None, p2p_streams=None,
                 staged_axes=None):
        self.target_name = target_name
        self.jaxpr = jaxpr
        self.mesh_axes = dict(mesh_axes or {})
        self.reduction_axes = reduction_axes
        self.declared_dtypes = declared_dtypes
        self.compute_dtype = compute_dtype
        self.overlap_check = overlap_check
        self.plan_axes = (tuple(plan_axes) if plan_axes is not None
                          else None)
        self.staged_axes = (frozenset(staged_axes)
                            if staged_axes is not None else frozenset())
        self.rank_addressed = (tuple(rank_addressed)
                               if rank_addressed else ())
        self.rank_streams = rank_streams
        self.p2p_streams = p2p_streams
        self.signatures = signatures
        self.trace_error = trace_error

    def finding(self, rule_id, severity, message, eqn=None):
        return Finding(rule_id, severity, message,
                       target=self.target_name,
                       where=walker.eqn_source(eqn)
                       if eqn is not None else None)


# ---------------------------------------------------------------------
# SL001: collective axis names exist in the mesh and, for gradient
# reductions, their union matches the strategy's declared topology.
def rule_axis_topology(ctx):
    out = []
    if ctx.trace_error is not None:
        # an unknown axis name cannot even trace: JAX aborts with
        # "unbound axis name".  Claim that failure as this rule's
        # finding; other trace failures stay SL000 (see runner).
        msg = str(ctx.trace_error)
        if 'unbound axis name' in msg:
            out.append(ctx.finding(
                'SL001', SEV_ERROR,
                'collective references an axis the mesh does not '
                'bind: %s' % msg.splitlines()[0]))
        return out
    if ctx.jaxpr is None:
        return out
    known = set(ctx.mesh_axes)
    reduce_axes_seen = set()
    for eqn, _path in walker.iter_eqns(ctx.jaxpr):
        name = eqn.primitive.name
        if name not in walker.COLLECTIVE_PRIMS:
            continue
        axes = walker.eqn_axes(eqn)
        for ax in axes:
            if ax not in known:
                out.append(ctx.finding(
                    'SL001', SEV_ERROR,
                    '%s over unknown mesh axis %r (mesh axes: %s)'
                    % (name, ax, sorted(known)), eqn))
        if name in walker.REDUCE_PRIMS:
            reduce_axes_seen.update(a for a in axes if a in known)
    if ctx.reduction_axes is not None:
        declared = set(ctx.reduction_axes)
        if reduce_axes_seen != declared:
            out.append(ctx.finding(
                'SL001', SEV_ERROR,
                'reduce collectives cover axes %s but the strategy '
                'declares reduction_axes=%s'
                % (sorted(reduce_axes_seen), sorted(declared))))
    return out


# ---------------------------------------------------------------------
# SL002: every ppermute permutation is a bijection on its axis.
def rule_ppermute_bijective(ctx):
    out = []
    if ctx.jaxpr is None:
        return out
    for eqn, _path in walker.iter_eqns(ctx.jaxpr):
        if eqn.primitive.name != 'ppermute':
            continue
        perm = [tuple(int(v) for v in pair)
                for pair in eqn.params.get('perm', ())]
        axes = walker.eqn_axes(eqn)
        size = int(np.prod([ctx.mesh_axes.get(a, 1) for a in axes])) \
            if axes else 0
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
            out.append(ctx.finding(
                'SL002', SEV_ERROR,
                'ppermute permutation is not a bijection (duplicate '
                'source or destination): %r' % (perm,), eqn))
            continue
        if size and any(not (0 <= v < size) for v in srcs + dsts):
            out.append(ctx.finding(
                'SL002', SEV_ERROR,
                'ppermute index out of range for axis size %d: %r'
                % (size, perm), eqn))
            continue
        if size and len(perm) not in (0, size):
            out.append(ctx.finding(
                'SL002', SEV_WARNING,
                'ppermute covers %d of %d ranks: uncovered '
                'destinations receive zeros' % (len(perm), size),
                eqn))
    return out


# ---------------------------------------------------------------------
# SL003: redundant collective chains (psum-of-psum over overlapping
# axes, all_gather-of-all_gather over the same axis).
def rule_redundant_collectives(ctx):
    out = []
    if ctx.jaxpr is None:
        return out
    reduce_set = set(walker.REDUCE_PRIMS) - {
        'reduce_scatter', 'psum_scatter'}
    for jx, _path in walker.iter_jaxprs(ctx.jaxpr):
        producers = walker.producer_map(jx)
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name not in walker.COLLECTIVE_PRIMS:
                continue
            axes = set(walker.eqn_axes(eqn))
            for invar in eqn.invars:
                prev = producers.get(invar)
                if prev is None:
                    continue
                pname = prev.primitive.name
                paxes = set(walker.eqn_axes(prev))
                if (name in reduce_set and pname in reduce_set
                        and axes & paxes):
                    out.append(ctx.finding(
                        'SL003', SEV_WARNING,
                        '%s over %s consumes the output of %s over '
                        '%s: the value is already reduced over the '
                        'shared axis (re-reducing multiplies by axis '
                        'size or wastes a collective)'
                        % (name, sorted(axes), pname, sorted(paxes)),
                        eqn))
                elif (name == 'all_gather' and pname == 'all_gather'
                        and axes == paxes):
                    out.append(ctx.finding(
                        'SL003', SEV_WARNING,
                        'all_gather of an all_gather over the same '
                        'axis %s: the operand is already replicated '
                        'along it' % sorted(axes), eqn))
    return out


# ---------------------------------------------------------------------
# SL004: a reduction must not execute in a narrower dtype than its
# input (e.g. bf16 psum of f32 gradients loses mantissa on the wire)
# -- UNLESS the narrowed dtype is one the target DECLARES (a
# mixed-precision policy's reduce/compute dtype, or a communicator
# constructed with reduce_dtype): then the narrowing is the policy
# working as specified, not an accidental precision loss.
def rule_reduction_dtype(ctx):
    out = []
    if ctx.jaxpr is None:
        return out
    allowed = set()
    # the declared COMPUTE dtype is allowed too: a bf16-native model
    # whose forward psums activations in bf16 (the tp transformer's
    # embedding reduction) is the declared design, not an accidental
    # gradient narrowing
    declared = tuple(ctx.declared_dtypes or ())
    if ctx.compute_dtype is not None:
        declared += (ctx.compute_dtype,)
    for d in declared:
        try:
            allowed.add(np.dtype(d).name)
        except TypeError:
            continue
    for jx, _path in walker.iter_jaxprs(ctx.jaxpr):
        producers = walker.producer_map(jx)
        for eqn in jx.eqns:
            if eqn.primitive.name not in walker.REDUCE_PRIMS:
                continue
            for invar in eqn.invars:
                prev = producers.get(invar)
                if (prev is None
                        or prev.primitive.name
                        != 'convert_element_type'):
                    continue
                src = prev.invars[0].aval
                dst = prev.outvars[0].aval
                try:
                    narrow = (np.dtype(src.dtype).itemsize
                              > np.dtype(dst.dtype).itemsize)
                except TypeError:
                    continue
                if narrow and np.dtype(dst.dtype).name in allowed:
                    continue
                if narrow:
                    out.append(ctx.finding(
                        'SL004', SEV_ERROR,
                        '%s executes in %s on a value narrowed from '
                        '%s immediately before the collective: the '
                        'reduction loses precision on the wire '
                        '(declare an intentional reduce dtype via the '
                        "strategy's reduce_dtype or the updater's "
                        'policy)'
                        % (eqn.primitive.name, dst.dtype, src.dtype),
                        eqn))
    return out


# ---------------------------------------------------------------------
# SL005: donated buffers are consumed and can alias an output.
def rule_donation(ctx):
    out = []
    if ctx.jaxpr is None:
        return out
    for eqn, _path in walker.iter_eqns(ctx.jaxpr):
        if eqn.primitive.name != 'jit':
            continue
        donated = eqn.params.get('donated_invars')
        if not donated or not any(donated):
            continue
        sub = walker.raw_jaxpr(eqn.params['jaxpr'])
        used = set()
        for inner, _p in walker.iter_eqns(sub):
            used.update(id(v) for v in inner.invars)
        used.update(id(v) for v in sub.outvars)
        out_avals = [v.aval for v in sub.outvars]
        free_outputs = [(tuple(a.shape), str(a.dtype))
                        for a in out_avals]
        for i, (var, don) in enumerate(zip(sub.invars, donated)):
            if not don:
                continue
            aval = var.aval
            if id(var) not in used:
                out.append(ctx.finding(
                    'SL005', SEV_ERROR,
                    'donated argument %d (%s%s) is never consumed by '
                    'the jitted computation: the donation frees '
                    'nothing and jit only warns at run time'
                    % (i, aval.dtype, list(aval.shape)), eqn))
                continue
            sig = (tuple(aval.shape), str(aval.dtype))
            if sig in free_outputs:
                # claim one matching output slot: two donated inputs
                # cannot alias the same output buffer
                free_outputs.remove(sig)
            else:
                out.append(ctx.finding(
                    'SL005', SEV_ERROR,
                    'donated argument %d (%s%s) matches no output '
                    'buffer shape/dtype: XLA cannot alias it, the '
                    'donation is wasted and HBM holds both copies'
                    % (i, aval.dtype, list(aval.shape)), eqn))
    return out


# ---------------------------------------------------------------------
# SL006: no host round-trips inside the step.
def rule_host_callbacks(ctx):
    out = []
    if ctx.jaxpr is None:
        return out
    for eqn, path in walker.iter_eqns(ctx.jaxpr):
        if eqn.primitive.name in walker.CALLBACK_PRIMS:
            out.append(ctx.finding(
                'SL006', SEV_ERROR,
                '%s inside the compiled step: every call stalls the '
                'device on a host round-trip (enclosing scope: %s)'
                % (eqn.primitive.name, '/'.join(path) or 'top level'),
                eqn))
    return out


# ---------------------------------------------------------------------
# SL007: abstract signature stable across consecutive synthetic steps
# (weak-type / python-scalar / shape drift recompiles every call).
def rule_recompilation(ctx):
    out = []
    sigs = ctx.signatures
    if not sigs or len(sigs) < 2:
        return out
    first = sigs[0]
    for step, sig in enumerate(sigs[1:], start=1):
        if sig == first:
            continue
        detail = 'argument count changed (%d vs %d)' % (len(first),
                                                        len(sig))
        for i, (a, b) in enumerate(zip(first, sig)):
            if a != b:
                detail = ('argument leaf %d changed: '
                          '%s/%s/weak=%s vs %s/%s/weak=%s'
                          % (i, a[0], a[1], a[2], b[0], b[1], b[2]))
                break
        out.append(ctx.finding(
            'SL007', SEV_ERROR,
            'abstract step signature differs between synthetic '
            'iterations 1 and %d -- jit recompiles every step '
            '(%s)' % (step + 1, detail)))
        break
    return out


# ---------------------------------------------------------------------
# SL008: no f32-materialized activation-sized intermediates inside a
# declared-narrow (bf16/f16) compute graph.  An upcast that widens an
# activation-sized tensor doubles its HBM footprint ON TOP of the
# narrow original -- exactly the materialized-intermediate traffic
# PERF.md's batch sweep diagnosed around the BN/relu/add interludes.
# The sanctioned kernel layer (chainermn_tpu/ops/, and anything under
# a custom-derivative scope) is exempt: its upcasts are VMEM-local on
# the TPU Pallas path.  WARNING severity: flax-oracle paths upcast by
# design (the finding is the chase list, not a gate failure); the
# fused-norm step is the clean state.
def rule_f32_materialization(ctx):
    from chainermn_tpu.analysis import memtraffic

    out = []
    if ctx.jaxpr is None or ctx.compute_dtype is None:
        return out
    if str(ctx.compute_dtype) not in memtraffic.NARROW_DTYPES:
        return out
    for eqn, nbytes in memtraffic.f32_materializations(ctx.jaxpr):
        src = eqn.invars[0].aval
        dst = eqn.outvars[0].aval
        out.append(ctx.finding(
            'SL008', SEV_WARNING,
            '%s%s upcast to %s materialized (%.2f MB) in a '
            'declared-%s compute graph: activation-sized f32 '
            'intermediates are the HBM-traffic excess the fused '
            'kernel path (fused_norm=True / ops.batch_norm_act) '
            'removes'
            % (src.dtype, list(dst.shape), dst.dtype, nbytes / 1e6,
               ctx.compute_dtype), eqn))
    return out


# ---------------------------------------------------------------------
# SL009: a gradient-sized reduce collective must be SCHEDULABLE before
# its last consumer -- i.e. the program level containing it must hold
# work that neither feeds the collective nor consumes its result, so
# XLA's latency-hiding scheduler has something to hide the collective
# behind.  A step whose whole reduction is one fused buffer (flat /
# one-bucket strategies) serializes as
#   full backward -> pack -> ONE collective -> unpack -> optimizer:
# every equation is an ancestor or a descendant of the collective and
# the communication time is fully EXPOSED.  The bucketed strategy with
# >= 2 buckets is the clean state: each bucket's collective overlaps
# the other buckets' packing/reduction and the optimizer math of
# already-reduced buckets.  Scope: step targets only
# (ctx.overlap_check; see RuleContext).  Severity WARNING by design --
# like SL008 this is the chase list for ROADMAP item 5, and the
# dynamic twin (the telemetry/trace overlap fraction) measures what
# this rule predicts.

#: data-movement / dtype plumbing that cannot hide a collective's
#: latency (pack/unpack around a fused reduce is exactly this)
_SL009_TRIVIAL = frozenset((
    'convert_element_type', 'reshape', 'broadcast_in_dim', 'squeeze',
    'expand_dims', 'transpose', 'copy', 'slice', 'dynamic_slice',
    'dynamic_update_slice', 'concatenate', 'bitcast_convert_type',
    'stop_gradient', 'select_n'))
#: audit only reductions moving at least this many bytes: scalar
#: metric/loss psums are latency-bound either way and would drown the
#: report in noise
_SL009_MIN_BYTES = 4096
#: the level must hold at least this much other substantial work for
#: "nothing is independent" to mean "serialized" rather than "tiny
#: helper jaxpr"
_SL009_MIN_LEVEL_WORK = 3


def _sl009_work_floor(nbytes):
    """Bytes an equation must touch to count as work that could hide
    a collective of ``nbytes``: non-negligible RELATIVE to the
    collective (1/64th), floored at 512 B.  Without the relative
    scaling, scalar bookkeeping (adam's bias-correction powers) would
    count as 'independent work' and mask a fully serialized multi-MB
    reduction."""
    return max(512, nbytes // 64)


def _aval_bytes(aval):
    try:
        size = 1
        for d in aval.shape:
            size *= int(d)
        return size * np.dtype(aval.dtype).itemsize
    except (TypeError, AttributeError):
        return 0


def rule_collective_overlap(ctx):
    out = []
    if ctx.jaxpr is None or not getattr(ctx, 'overlap_check', False):
        return out
    for jx, _path in walker.iter_jaxprs(ctx.jaxpr):
        eqns = walker.raw_jaxpr(jx).eqns
        n = len(eqns)
        if n < 2:
            continue
        producer = {}
        for i, eqn in enumerate(eqns):
            for var in eqn.outvars:
                producer[var] = i
        # ancestor bitsets in one forward pass (eqn order is a
        # topological order of the level's def-use graph); direct
        # consumers collected for the reverse (descendant) pass
        anc = [0] * n
        consumers = [[] for _ in range(n)]
        for i, eqn in enumerate(eqns):
            mask = 0
            for var in eqn.invars:
                if hasattr(var, 'val'):
                    continue  # Literal constant: no producer
                p = producer.get(var)
                if p is not None:
                    mask |= anc[p] | (1 << p)
                    consumers[p].append(i)
            anc[i] = mask
        desc = [0] * n
        for i in range(n - 1, -1, -1):
            mask = 0
            for j in consumers[i]:
                mask |= desc[j] | (1 << j)
            desc[i] = mask
        def eqn_bytes(eqn):
            vals = [_aval_bytes(v.aval) for v in
                    list(eqn.invars) + list(eqn.outvars)
                    if hasattr(v, 'aval')]
            return max(vals, default=0)

        axis_index_mask = 0
        nontrivial = []
        for i, eqn in enumerate(eqns):
            if eqn.primitive.name == 'axis_index':
                axis_index_mask |= 1 << i
            if eqn.primitive.name not in _SL009_TRIVIAL:
                nontrivial.append((i, eqn_bytes(eqn)))
        # the level's schedulable reduce collectives (>= 512 B so a
        # genuinely bucketed sibling counts even when small, but
        # scalar metric psums do not), excluding rank-addressed ones
        # (the root-select psum lowering broadcast_data is a sync
        # primitive, not a gradient-reduction schedule)
        reduces = [
            i for i, eqn in enumerate(eqns)
            if eqn.primitive.name in walker.REDUCE_PRIMS
            and walker.eqn_axes(eqn)
            and not (anc[i] & axis_index_mask)
            and eqn_bytes(eqn) >= 512]
        for i in reduces:
            eqn = eqns[i]
            nbytes = max((_aval_bytes(v.aval) for v in eqn.invars
                          if hasattr(v, 'aval')), default=0)
            if nbytes < _SL009_MIN_BYTES:
                continue
            related = anc[i] | desc[i]
            # a SIBLING reduce neither feeding nor consuming this one
            # is exactly what bucketed/per-leaf strategies create: the
            # collectives pipeline with one another and with the
            # pack/unpack + optimizer math of already-reduced buckets,
            # so each is schedulable before its last consumer
            if any(j != i and not (related >> j) & 1
                   for j in reduces):
                continue
            floor = _sl009_work_floor(nbytes)
            big_rest = [j for j, b in nontrivial
                        if j != i and b >= floor]
            if len(big_rest) < _SL009_MIN_LEVEL_WORK:
                continue  # tiny helper level, nothing to judge
            out.append(ctx.finding(
                'SL009', SEV_WARNING,
                '%s of %.1f KB is the ONLY schedulable reduce at its '
                'program level: every gradient must exist before the '
                'fused collective starts and its %d consumers-and-'
                'producers serialize around it, so its wire time is '
                'exposed in the step.  Split the reduction into '
                'buckets issued as gradients complete (the '
                "'bucketed' strategy with bucket_mb sized for >= 2 "
                'buckets) so each collective overlaps the remaining '
                'backward/optimizer work'
                % (eqn.primitive.name, nbytes / 1e3, len(big_rest)),
                eqn))
    return out


# ---------------------------------------------------------------------
# SL010 family: multi-axis (composed-mesh) rules.  Scoped to targets
# that DECLARE a MeshPlan topology (ctx.plan_axes, e.g.
# ('data', 'model')): the single-axis strategy sweep keeps SL001's
# contract; these rules audit what only exists once axes COMPOSE.

# SL010: plan-axis discipline.  (a) every collective must act over
# declared plan axes only -- a collective over a mesh axis outside
# the plan means some subsystem still thinks it owns the whole mesh
# (the exact bug class composing dp x tp creates: a classic
# full-mesh allreduce_grad would average tensor-parallel SHARDS
# across the model axis); (b) every declared axis of size > 1 must be
# touched by at least one collective -- devices hold shards along a
# dead axis but never combine along it, so the axis only divides the
# batch/weights without buying parallel work.
def rule_plan_axis_coverage(ctx):
    out = []
    if ctx.jaxpr is None or ctx.plan_axes is None:
        return out
    declared = set(ctx.plan_axes)
    seen = set()
    for eqn, _path in walker.iter_eqns(ctx.jaxpr):
        if eqn.primitive.name not in walker.COLLECTIVE_PRIMS:
            continue
        axes = [a for a in walker.eqn_axes(eqn)
                if a in ctx.mesh_axes]
        seen.update(axes)
        stray = [a for a in axes if a not in declared]
        if stray:
            out.append(ctx.finding(
                'SL010', SEV_ERROR,
                '%s over axis %s outside the declared plan axes %s: '
                'a collective crossing an undeclared axis combines '
                'values the plan lays out as distinct shards'
                % (eqn.primitive.name, sorted(stray),
                   sorted(declared)), eqn))
    for ax in sorted(declared):
        if ctx.mesh_axes.get(ax, 1) > 1 and ax not in seen:
            out.append(ctx.finding(
                'SL010', SEV_ERROR,
                'declared plan axis %r (size %d) is never touched by '
                'any collective: the axis shards data/weights but no '
                'computation ever combines along it (dead axis -- '
                'drop it from the plan or wire its collectives)'
                % (ax, ctx.mesh_axes[ax])))
    return out


# SL011: cross-axis redundant collective chain.  SL003 flags
# re-reducing over an OVERLAPPING axis; in a composed mesh the new
# waste shape is a reduce over one axis feeding DIRECTLY into a
# reduce over a DISJOINT axis with no compute between: a single
# reduction over the union moves the same bytes in one collective
# (XLA lowers a multi-axis psum as one all-reduce over the product
# group) instead of two serialized launches.  Scoped to plan targets:
# the hierarchical/two_dimensional strategies STAGE their reductions
# across axes on purpose (reduce-scatter within, allreduce across)
# and declare no plan.  A PLAN target that stages deliberately -- the
# multi-slice plan's in-slice psum feeding the cross-slice DCN psum --
# declares the staging axes (``staged_axes``, e.g. ``('slice',)``):
# a disjoint chain whose either stage reduces purely over declared
# staging axes is the intended ICI/DCN split, not waste (crossing the
# DCN once with pre-reduced partials IS the optimization a flat
# psum over the union would undo).
def rule_cross_axis_chain(ctx):
    out = []
    if ctx.jaxpr is None or ctx.plan_axes is None:
        return out
    reduce_set = set(walker.REDUCE_PRIMS) - {
        'reduce_scatter', 'psum_scatter'}
    for jx, _path in walker.iter_jaxprs(ctx.jaxpr):
        producers = walker.producer_map(jx)
        for eqn in jx.eqns:
            if eqn.primitive.name not in reduce_set:
                continue
            axes = set(walker.eqn_axes(eqn))
            if not axes:
                continue
            for invar in eqn.invars:
                prev = producers.get(invar)
                if prev is None or prev.primitive.name \
                        not in reduce_set:
                    continue
                paxes = set(walker.eqn_axes(prev))
                if not paxes or axes & paxes:
                    continue  # overlap is SL003's finding
                if ctx.staged_axes and (axes <= ctx.staged_axes
                                        or paxes <= ctx.staged_axes):
                    continue  # declared hierarchical staging
                out.append(ctx.finding(
                    'SL011', SEV_WARNING,
                    '%s over %s directly consumes %s over %s: '
                    'consecutive reductions over disjoint plan axes '
                    'serialize two collective launches where one '
                    '%s over %s moves the same bytes once'
                    % (eqn.primitive.name, sorted(axes),
                       prev.primitive.name, sorted(paxes),
                       eqn.primitive.name,
                       sorted(axes | paxes)), eqn))
    return out


# SL012: tp-aware donation.  SL005 pairs donated inputs with output
# slots by shape/dtype -- which is blind to SHARDING: under a
# composed plan a donated model-sharded parameter whose matching
# output leaves the shard_map with a DIFFERENT spec (gathered to
# replicated, or resharded to another axis) cannot alias -- XLA must
# materialize the resharded output next to the donated buffer and
# the donation frees nothing.  The shard_map equation carries the
# in/out ``PartitionSpec``s (``in_specs``/``out_specs``), so the
# mismatch is statically visible.
def _spec_axes(spec):
    """``{dim: (axis names)}`` of a ``PartitionSpec`` (sharded dims
    only): two specs place a buffer alike iff these are equal."""
    out = {}
    for dim, entry in enumerate(spec):
        if entry is not None:
            out[dim] = entry if isinstance(entry, tuple) else (entry,)
    return out


def rule_tp_donation(ctx):
    out = []
    if ctx.jaxpr is None or ctx.plan_axes is None:
        return out
    for eqn, _path in walker.iter_eqns(ctx.jaxpr):
        if eqn.primitive.name != 'jit':
            continue
        donated = eqn.params.get('donated_invars')
        if not donated or not any(donated):
            continue
        sub = walker.raw_jaxpr(eqn.params['jaxpr'])
        donated_vars = {id(var): i
                        for i, (var, don) in enumerate(
                            zip(sub.invars, donated)) if don}
        for inner, _p in walker.iter_eqns(sub):
            if inner.primitive.name != 'shard_map':
                continue
            in_names = [_spec_axes(s) for s in inner.params['in_specs']]
            out_names = [_spec_axes(s)
                         for s in inner.params['out_specs']]
            out_sig = []
            for var, names in zip(inner.outvars, out_names):
                aval = getattr(var, 'aval', None)
                if aval is not None:
                    out_sig.append((tuple(aval.shape),
                                    str(aval.dtype), names))
            for pos, (var, names) in enumerate(
                    zip(inner.invars, in_names)):
                arg_i = donated_vars.get(id(var))
                if arg_i is None or not names:
                    continue  # not donated, or replicated anyway
                aval = var.aval
                sig = (tuple(aval.shape), str(aval.dtype))
                matches = [o for o in out_sig if o[:2] == sig]
                if not matches:
                    continue  # SL005's finding, not ours
                if not any(o[2] == names for o in matches):
                    out.append(ctx.finding(
                        'SL012', SEV_WARNING,
                        'donated argument %d (%s%s, sharded %r into '
                        'the shard_map) matches outputs only under a '
                        'different sharding (%s): the resharded '
                        'output cannot alias the donated shard and '
                        'the donation frees nothing'
                        % (arg_i, aval.dtype, list(aval.shape), names,
                           [o[2] for o in matches]), inner))
    return out


# ---------------------------------------------------------------------
# SL013: rank-divergent collective sequence.  The streams come from
# three sources feeding ONE checker core (commcheck.verify_streams):
# the runner replicates a traced target's jaxpr stream per rank (one
# SPMD program serves every rank -- uniform by construction, so this
# half documents the invariant), commcheck.run_commcheck traces each
# strategy at simulated world sizes {2,3,4} and simulates the eager
# protocol per rank through the recording communicator (where a
# Python branch on rank genuinely diverges), and telemetry doctor
# replays RECORDED spans from a capture through the same core.
def rule_rank_divergence(ctx):
    streams = getattr(ctx, 'rank_streams', None)
    if not streams:
        return []
    from chainermn_tpu.analysis import commcheck
    div = commcheck.verify_streams(
        streams, rank_addressed=getattr(ctx, 'rank_addressed', ()))
    if div is None:
        return []
    return [ctx.finding(
        'SL013', SEV_ERROR,
        'rank-divergent collective sequence at %s -- every rank must '
        'issue the same collectives in the same order or the fleet '
        'wedges at the first unmatched rendezvous' % div['summary'])]


# ---------------------------------------------------------------------
# SL014: p2p/ppermute match + deadlock.  Dynamic half: the wait-for
# matcher over recorded eager send_obj/recv_obj/barrier streams
# (unmatched send/recv, key/tag collision, cycle of blocking ops).
# Static half: every scan-REPEATED ppermute's permutation table must
# compose into a chain that delivers to every rank of its axis --
# SL002's bijectivity check extended to multi-step schedules.
def rule_p2p_deadlock(ctx):
    from chainermn_tpu.analysis import commcheck
    out = []
    streams = getattr(ctx, 'p2p_streams', None)
    if streams:
        for item in commcheck.match_p2p(streams):
            out.append(ctx.finding('SL014', SEV_ERROR,
                                   item['message']))
    out.extend(commcheck.ppermute_chain_rule(ctx))
    return out


# ---------------------------------------------------------------------
# SL015: collective under rank-dependent control flow.  Taint every
# var derived from axis_index (the SL009-style per-level forward
# pass); a lax.cond / lax.switch whose predicate is tainted and whose
# branches contain a collective launches that collective on only SOME
# ranks -- unless the target declares the op rank-addressed.
# ppermute is auto-exempt (rank-addressed by definition).  The eager
# mirror -- Python code guarded by ``comm.rank`` -- cannot appear in
# a jaxpr; it is caught by SL013's recorded/simulated stream
# comparison instead.
def rule_rank_dependent_collective(ctx):
    out = []
    if ctx.jaxpr is None:
        return out
    exempt = set(getattr(ctx, 'rank_addressed', ()))
    for jx, _path in walker.iter_jaxprs(ctx.jaxpr):
        tainted = set()
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == 'axis_index':
                tainted.update(id(v) for v in eqn.outvars)
                continue
            if name == 'cond' and eqn.invars:
                pred = eqn.invars[0]
                if not hasattr(pred, 'val') and id(pred) in tainted:
                    colls = sorted({
                        inner.primitive.name
                        for br in eqn.params.get('branches', ())
                        for inner, _p in walker.iter_eqns(br)
                        if inner.primitive.name
                        in walker.COLLECTIVE_PRIMS
                        and inner.primitive.name != 'ppermute'
                        and inner.primitive.name not in exempt})
                    if colls:
                        out.append(ctx.finding(
                            'SL015', SEV_WARNING,
                            'collective(s) %s inside lax.cond/'
                            'lax.switch whose predicate derives from '
                            'axis_index: ranks take different '
                            'branches, so the collective launches on '
                            'only SOME ranks and the rest never '
                            'arrive at the rendezvous (declare the '
                            'op rank-addressed on the target if this '
                            'asymmetry is the design)'
                            % ', '.join(colls), eqn))
            if any(id(v) in tainted for v in eqn.invars
                   if not hasattr(v, 'val')):
                tainted.update(id(v) for v in eqn.outvars)
    return out


#: rule id -> (callable, one-line description)
RULES = {
    'SL001': (rule_axis_topology,
              'collective axis names exist in the mesh and reduce '
              'collectives match the declared reduction topology'),
    'SL002': (rule_ppermute_bijective,
              'ppermute permutations are bijections on their axis'),
    'SL003': (rule_redundant_collectives,
              'no redundant collective chains (psum-of-psum, '
              'gather-of-gather)'),
    'SL004': (rule_reduction_dtype,
              'reductions do not execute in a narrower dtype than '
              'their inputs'),
    'SL005': (rule_donation,
              'donated buffers are consumed and can alias an output'),
    'SL006': (rule_host_callbacks,
              'no host round-trips (callbacks) inside the step'),
    'SL007': (rule_recompilation,
              'abstract step signature is stable across iterations '
              '(no recompilation leak)'),
    'SL008': (rule_f32_materialization,
              'no f32-materialized activation-sized intermediates '
              'inside declared-bf16/f16 compute graphs (outside the '
              'kernel layer)'),
    'SL009': (rule_collective_overlap,
              'gradient-sized reduce collectives are schedulable '
              'before their last consumer (independent work exists '
              'to overlap them with; step targets only)'),
    'SL010': (rule_plan_axis_coverage,
              'composed-mesh targets: collectives act over declared '
              'plan axes only, and every declared axis of size > 1 '
              'is combined by at least one collective'),
    'SL011': (rule_cross_axis_chain,
              'no reduce-feeding-reduce chains over disjoint plan '
              'axes (one multi-axis collective moves the same bytes '
              'once)'),
    'SL012': (rule_tp_donation,
              'donated plan-sharded buffers alias an output of the '
              'SAME sharding (a gathered/resharded output cannot '
              'alias and wastes the donation)'),
    'SL013': (rule_rank_divergence,
              'per-rank collective signature streams are identical '
              'up to declared rank-addressed ops (simulated '
              '(world_size, rank) sweep; doctor replays captures '
              'through the same core)'),
    'SL014': (rule_p2p_deadlock,
              'eager send/recv/barrier streams match without tag '
              'collisions or blocking-op cycles, and scan-repeated '
              'ppermute chains compose to deliver to every rank'),
    'SL015': (rule_rank_dependent_collective,
              'no collective under lax.cond/lax.switch control flow '
              'whose predicate derives from axis_index, unless '
              'declared rank-addressed'),
}


def run_rules(ctx, only=None):
    """Run every rule (or the ``only`` subset) over one context."""
    findings = []
    for rule_id, (fn, _desc) in sorted(RULES.items()):
        if only is not None and rule_id not in only:
            continue
        findings.extend(fn(ctx))
    return findings
