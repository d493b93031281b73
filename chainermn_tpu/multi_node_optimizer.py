"""Multi-node optimizer wrapper.

Rebuild of ``chainermn/multi_node_optimizer.py``.  The reference proxies
a Chainer optimizer and rewrites ``update()``: the first call broadcasts
the model from rank 0 (initial weight sync, **no** optimizer step), each
later call allreduces gradients then steps (``:11-29``).

Here the wrapped object is an ``optax.GradientTransformation`` and the
same semantics are expressed functionally so the whole thing lives
inside one jitted ``shard_map`` train step:

- state carries a ``needs_broadcast`` flag (reference ``:8-9,23-26``);
- step 0: updates = (root's params - my params), inner state untouched;
- step k>0: updates = inner.update(allreduce_grad(grads)).

The averaging is fused into the reduction exactly as the reference fuses
``* 1/size`` into its collective (``_communication_utility.py:75-77``).
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from chainermn_tpu import telemetry as _telemetry


class MultiNodeOptimizerState(NamedTuple):
    needs_broadcast: jnp.ndarray  # bool scalar
    actual_state: Any


class DoubleBufferState(NamedTuple):
    inner: Any
    pending: Any          # previous step's reduced gradients
    have_pending: jnp.ndarray  # bool scalar


def create_multi_node_optimizer(actual_optimizer, communicator,
                                broadcast_first=True,
                                allreduce_dtype=None,
                                double_buffering=False):
    """Wrap an optax optimizer with mesh-wide gradient averaging.

    Parity with ``chainermn.create_multi_node_optimizer(opt, comm)``
    (reference ``multi_node_optimizer.py:48-49``).  The result is itself
    an ``optax.GradientTransformation``; its ``update`` must run inside
    ``shard_map`` over ``communicator.mesh`` (the standard updater does
    this for you).

    ``allreduce_dtype`` (e.g. ``'bfloat16'``): cast gradients to a
    narrower dtype for the reduction and back afterwards -- halves the
    bytes every collective moves over ICI/DCN at the cost of reduced
    summation precision (the mean is computed in the narrow dtype).
    The TPU-native form of ChainerMN's fp16-allreduce option; leave
    ``None`` (full precision) unless gradient traffic is the
    bottleneck.  Applies to the gradient allreduce only -- the
    first-call weight broadcast stays full-precision.

    ``double_buffering``: apply the PREVIOUS step's reduced gradients
    while this step's reduction is in flight (the TPU-native analogue
    of ChainerMN-family ``DoubleBufferingOptimizer``).  Inside the
    compiled step nothing downstream consumes this step's collective
    -- its result only feeds the carried state -- so XLA's
    latency-hiding scheduler is free to overlap the whole reduction
    with the optimizer apply and any compute scheduled after it,
    instead of stalling the step tail on the last gradient bucket.
    The win is largest when the reduction rides slow links (DCN
    between slices).  Cost: parameters are updated with
    one-step-STALE gradients (a standard staleness-1 trajectory; use
    a slightly lower LR if convergence wobbles), and the first
    post-broadcast step applies no update (it only fills the buffer).
    """
    if allreduce_dtype is not None:
        allreduce_dtype = jnp.dtype(allreduce_dtype)

    def init(params):
        inner = actual_optimizer.init(params)
        if double_buffering:
            inner = DoubleBufferState(
                inner=inner,
                pending=jax.tree_util.tree_map(jnp.zeros_like, params),
                have_pending=jnp.asarray(False))
        return MultiNodeOptimizerState(
            needs_broadcast=jnp.asarray(broadcast_first),
            actual_state=inner)

    def update(grads, state, params=None):
        if params is None and broadcast_first:
            raise ValueError(
                'the multi-node optimizer requires params in update() '
                '(the first call performs the initial weight broadcast, '
                'reference multi_node_optimizer.py:23-26); pass '
                'broadcast_first=False to opt out')

        def first_call(_):
            # Initial weight sync in place of a step (reference :23-26).
            # Unlike the reference, the program of this call holds the
            # gradient allreduce too (hoisted out of the cond, below):
            # it runs once, its result is dropped here, and what the
            # call returns is bit-for-bit the sync alone.
            if _telemetry.live() is not None:
                # trace-time mark: the L4 wrapper's broadcast is in
                # the program.  Fires once per COMPILATION -- the
                # broadcast-appears-exactly-once regression test pins
                # both the wrapper semantics and the no-recompile
                # contract on this event's count.
                _telemetry.event('multi_node_optimizer:broadcast_data',
                                 kind='collective_trace')
            synced = communicator.broadcast_data(params)
            updates = jax.tree_util.tree_map(
                lambda s, p: (s - p).astype(p.dtype), synced, params)
            return updates, state.actual_state

        # The gradient reduction, OUTSIDE the cond: a `conditional` is
        # one operation on the core's serial line, which starts when
        # ALL its operands exist (the last gradient of the backward)
        # and beside which nothing runs.  Out here every collective
        # depends on its own gradients only, so the compiler is free
        # to place it behind the backward step that produced them and
        # to run it under the rest of the backward.  The cond keeps
        # what differs between the two calls: the weight sync against
        # the inner optimizer's update.
        g = grads
        if allreduce_dtype is not None:
            g = jax.tree_util.tree_map(
                lambda x: x.astype(allreduce_dtype), g)
        if _telemetry.live() is not None:
            # trace-time mark, once per COMPILATION: what the strategy
            # puts into the program for this tree (`collectives`,
            # `leaves`, `packed_leaves`, `bytes`)
            _telemetry.event('multi_node_optimizer:allreduce_grad',
                             kind='collective_trace',
                             **communicator.allreduce_plan(g))
        with jax.named_scope('grad_allreduce'):
            reduced = communicator.allreduce_grad(g)
        if allreduce_dtype is not None:
            reduced = jax.tree_util.tree_map(
                lambda r, orig: r.astype(orig.dtype), reduced, grads)

        def later_call(_):
            if not double_buffering:
                with jax.named_scope('optimizer_update'):
                    return actual_optimizer.update(
                        reduced, state.actual_state, params)
            db = state.actual_state
            # apply the PREVIOUS reduction; this step's `reduced` goes
            # only into the carried state, so nothing in this step
            # waits on the collective
            zero_updates = jax.tree_util.tree_map(jnp.zeros_like,
                                                  grads)
            with jax.named_scope('optimizer_update'):
                updates, new_inner = lax.cond(
                    db.have_pending,
                    lambda _: actual_optimizer.update(
                        db.pending, db.inner, params),
                    lambda _: (zero_updates, db.inner), operand=None)
            return updates, DoubleBufferState(
                inner=new_inner, pending=reduced,
                have_pending=jnp.asarray(True))

        updates, new_inner = lax.cond(
            state.needs_broadcast, first_call, later_call, operand=None)
        return updates, MultiNodeOptimizerState(
            needs_broadcast=jnp.asarray(False), actual_state=new_inner)

    return optax.GradientTransformation(init, update)
