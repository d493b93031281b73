"""Pallas TPU kernels for the hot ops.

The reference has no kernel layer -- its FLOPs live in Chainer/CuPy and
its only native code is the NCCL binding (``chainermn/nccl/nccl.pyx``).
On TPU the compute path is XLA, and the ops worth hand-scheduling are
the ones XLA fuses poorly: attention (materializes the (T, T) score
matrix), large-vocab softmax cross-entropy (materializes probabilities),
and whole-model elementwise optimizer sweeps (one HBM pass per param
tensor instead of one fused pass).

Every op has a pure-``jnp`` reference implementation used (a) as the
numerics oracle in tests and (b) as the fallback on non-TPU backends
where the Mosaic compiler is unavailable; there the Pallas path runs in
interpret mode only when explicitly requested
(``CHAINERMN_TPU_PALLAS_INTERPRET=1``).
"""

from chainermn_tpu.ops.flash_attention import (  # noqa
    chunk_attention_reference, decode_attention_paged_reference,
    decode_attention_reference, decode_paged_grid, flash_attention,
    flash_attention_chunk,
    flash_attention_decode, flash_attention_decode_paged, mha_reference,
    paged_kv_append)
from chainermn_tpu.ops.cross_entropy import (  # noqa
    softmax_cross_entropy, softmax_cross_entropy_reference)
from chainermn_tpu.ops.layer_norm import layer_norm, layer_norm_reference  # noqa
from chainermn_tpu.ops.batch_norm_act import (  # noqa
    batch_norm_act, batch_norm_act_inference, batch_norm_act_reference)
from chainermn_tpu.ops.optimizer import fused_momentum_sgd, momentum_sgd  # noqa
from chainermn_tpu.ops.int8_matmul import (  # noqa
    dequant, dequant_matmul, dequant_matmul_reference)
from chainermn_tpu.ops.grouped_matmul import (  # noqa
    dropless_experts, grouped_swiglu, grouped_swiglu_reference)
from chainermn_tpu.ops.gated_delta import (  # noqa
    causal_conv, causal_conv_step, conv_tail, gated_delta_reference,
    gated_delta_rule, gated_delta_step, pack_state, pack_tail,
    state_shape, tail_shape, unpack_state)
from chainermn_tpu.ops.hyper_connection import (  # noqa
    mhc_coefficients, mhc_coefficients_reference, mhc_rows)
from chainermn_tpu.ops.selective_scan import (  # noqa
    selective_scan, selective_scan_reference, selective_scan_step)
