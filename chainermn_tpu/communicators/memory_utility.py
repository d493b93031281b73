"""Gradient tensor fusion.

TPU-native rebuild of ``chainermn/communicators/_memory_utility.py``.
The reference maintains raw CUDA buffers (``DeviceMemory``,
``HostPinnedMemory``) and loops over parameters every iteration to
pack/unpack them into one contiguous region (``:77-92``) so a single
collective covers the whole model.

Under XLA the packing itself is a traced op (one fused concatenate, no
per-iteration Python loop at run time) and buffer lifetime is owned by
the compiler, so there is no allocator class to manage.  What remains
is the *schema*: a deterministic flatten/unflatten of a pytree into one
1-D buffer per dtype, with the reference's sorted-parameter-order
determinism (``hierarchical_communicator.py:24``) provided by pytree
ordering.
"""

import jax
import jax.numpy as jnp


class PackSchema:
    """Shapes/dtypes/offsets for a fused flat buffer of a pytree."""

    def __init__(self, tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        self.treedef = treedef
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = []
        for sh in self.shapes:
            n = 1
            for d in sh:
                n *= int(d)
            self.sizes.append(n)
        self.total = sum(self.sizes)


def pack_params(tree, dtype=None):
    """Fuse a pytree into one flat buffer (+ schema to invert).

    Parity: ``pack_params`` (``_memory_utility.py:77-83``) -- but it is
    a pure function XLA fuses into the surrounding graph rather than a
    stream of device memcpys.
    """
    schema = PackSchema(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype or jnp.float32), schema
    buf_dtype = dtype or leaves[0].dtype
    flat = jnp.concatenate([l.ravel().astype(buf_dtype) for l in leaves])
    return flat, schema


def unpack_params(buf, schema):
    """Invert :func:`pack_params` (reference ``_memory_utility.py:86-92``)."""
    leaves = []
    offset = 0
    for shape, dt, n in zip(schema.shapes, schema.dtypes, schema.sizes):
        leaves.append(buf[offset:offset + n].reshape(shape).astype(dt))
        offset += n
    return jax.tree_util.tree_unflatten(schema.treedef, leaves)


def pad_to_multiple(buf, multiple):
    """Pad a flat buffer so collective-scatter shards divide evenly."""
    n = buf.shape[0]
    rem = (-n) % multiple
    if rem:
        buf = jnp.concatenate([buf, jnp.zeros((rem,), buf.dtype)])
    return buf, n


def plan_by_dtype(leaves):
    """Default fusion plan: one group per dtype (mixed-precision models
    must not be flattened into one buffer -- casting bf16/f32 together
    corrupts gradients)."""
    by_dtype = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    return [idxs for _, idxs in sorted(by_dtype.items(),
                                       key=lambda kv: kv[0].name)]


def fused_reduce(tree, reduce_buf):
    """Apply ``reduce_buf(flat_buffer) -> flat_buffer`` to a pytree,
    one fused buffer per dtype (:func:`plan_by_dtype`), so the
    collective count is O(#dtypes), not O(#params).

    Who still packs the whole tree: the strategies whose reduction
    NEEDS a flat buffer -- ``hierarchical``, ``two_dimensional`` and
    ``non_cuda_aware`` reduce-scatter over a padded 1-D buffer,
    ``single_node`` and ``dummy`` keep the reference's one-buffer shape
    (``flat`` packs by itself, across dtypes).  ``xla`` and
    ``bucketed`` do NOT come through here since PR 38: they reduce
    every large leaf in its own shape and pack only the small ones
    (``xla_communicator.py``), because one buffer over all gradients
    cannot start reducing before the last of them exists and costs two
    relayouting passes over every byte.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = [None] * len(leaves)
    for idxs in plan_by_dtype(leaves):
        buf, schema = pack_params([leaves[i] for i in idxs])
        buf = reduce_buf(buf)
        for i, leaf in zip(idxs, unpack_params(buf, schema)):
            out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)
