"""Fused single-collective allreduce (reference ``flat_communicator.py``).

The reference packs every gradient into ONE contiguous device buffer and
performs a single CUDA-aware MPI ``Allreduce`` over it
(``flat_communicator.py:19-39``).  Ours keeps that exact shape: all
leaves are promoted to one common dtype and fused into a single buffer
for a single ``pmean`` -- one collective total, maximal fusion, at the
cost of upcasting narrow dtypes in mixed-precision models.  (Contrast
``xla``, which reduces each large leaf alone in its own dtype and
packs the small ones per dtype: no upcast, no whole-tree buffer.)
Original dtypes are restored on unpack.
"""

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.communicators import memory_utility
from chainermn_tpu.communicators.base import CommunicatorBase
from chainermn_tpu.communicators.mesh_utility import AXES


class FlatCommunicator(CommunicatorBase):

    def _allreduce_impl(self, grads):
        leaves = jax.tree_util.tree_leaves(grads)
        if not leaves:
            return grads
        common = leaves[0].dtype
        for leaf in leaves[1:]:
            common = jnp.promote_types(common, leaf.dtype)
        buf, schema = memory_utility.pack_params(grads, dtype=common)
        buf = lax.pmean(buf, AXES)
        return memory_utility.unpack_params(buf, schema)
