"""ChainerMN-TPU: a TPU-native distributed deep-learning framework.

A from-scratch rebuild of the capability surface of ChainerMN (the
multi-node distributed-training extension for Chainer; reference public
API at ``chainermn/__init__.py:1-10``) designed for TPUs: SPMD over
``jax.sharding.Mesh``, XLA collectives over ICI/DCN, ``shard_map``/``pjit``
for parallelism, and Pallas kernels for hot ops.

Public API (parity with the reference's five entry points, plus the
TPU-native extras):

- :func:`create_communicator` -- mesh-backed communicator factory
- :func:`scatter_dataset` -- per-process dataset partitioning
- :class:`MultiNodeChainList` -- model-parallel stage container
- :func:`create_multi_node_evaluator` -- cross-replica metric averaging
- :func:`create_multi_node_optimizer` -- gradient-allreduce optimizer wrapper
"""

from chainermn_tpu.communicators import create_communicator  # noqa
from chainermn_tpu.communicators.base import CommunicatorBase  # noqa
from chainermn_tpu.dataset import scatter_dataset  # noqa
from chainermn_tpu.datasets import create_empty_dataset  # noqa
from chainermn_tpu.link import MultiNodeChainList  # noqa
from chainermn_tpu.multi_node_evaluator import create_multi_node_evaluator  # noqa
from chainermn_tpu.multi_node_optimizer import create_multi_node_optimizer  # noqa
from chainermn_tpu import precision  # noqa
from chainermn_tpu.precision import Policy  # noqa
from chainermn_tpu import telemetry  # noqa
from chainermn_tpu import utils  # noqa

__version__ = '0.1.0'
