"""What the ``phi4flash`` family's kernels MUST move or compute, from
shapes: the numerators of its roofline shares.  Only what the algorithm
needs is counted at NOMINAL sizes (a row's state read once and written
once in float32, the K and V of the positions a query may see, the
recurrence's own multiply-adds a real token, every weight once a tick),
so that no share can read over 100: lane padding, the zero halves of a
packed query, pad rows and pad positions are the kernel's cost, not its
work."""

import math

from chipbench.reference import phi4flash as reference
from chipbench.rooflines import BF16, share  # noqa: F401 (readers use it)

#: bytes of a float32 element
F32 = 4


def layer_kinds(cfg):
    """``(Mamba layers, window layers, readers of the shared K/V
    leaf)`` of a ``phi4flash`` configuration: 9, 8 and 8 at 32 layers
    (the K/V layer and the 7 cross layers read ONE leaf)."""
    kinds = reference.layer_kinds(cfg)
    return (kinds.count('mamba') + kinds.count('memory'),
            kinds.count('window'), 1 + kinds.count('cross'))


def state_row_bytes(cfg):
    """One sequence's SSM state in one Mamba layer: d_inner x d_state
    float32 (327,680 bytes at 5120 x 16)."""
    di, n, _, _ = reference.widths(cfg)
    return di * n * F32


def ssm_decode_bytes(cfg, state_rows):
    """Bytes one decode tick's state updates must move: every row's
    state read once and written once in every Mamba layer.  The token's
    x, delta, B, C (41 KB a row) are 6% of that and left out."""
    return state_rows * layer_kinds(cfg)[0] * 2 * state_row_bytes(cfg)


def kv_position_bytes(cfg):
    """K and V of one position in one layer that holds them: 5,120
    bytes at 20 heads of 64 in bfloat16."""
    head_dim = cfg['hidden_size'] // cfg['num_attention_heads']
    return 2 * cfg['num_key_value_heads'] * head_dim * BF16


def attn_decode_bytes(cfg, shared_kv_positions, kv_window_positions):
    """K and V bytes one decode tick's attention kernels must read:
    the shared leaf's live positions once a READER
    (``shared_kv_positions`` counts them so: positions x 8), and at
    most the window in each window layer (``kv_window_positions``:
    summed over rows, of one layer)."""
    return (shared_kv_positions
            + layer_kinds(cfg)[1] * kv_window_positions) \
        * kv_position_bytes(cfg)


def scan_prefill_flops(cfg, scan_tokens):
    """Floating-point operations the recurrence itself needs for
    ``scan_tokens`` real prompt tokens: three multiply-adds a state
    element a token (the decay on ``h``, ``B (delta x)`` into it, ``C``
    out of it) in every Mamba layer."""
    di, n, _, _ = reference.widths(cfg)
    return scan_tokens * 3 * 2 * di * n * layer_kinds(cfg)[0]


def scan_prefill_bytes(cfg, scan_tokens):
    """Bytes the recurrence must move for those tokens: x, delta, z, m
    (d_inner each) and B, C (d_state each) once each in bfloat16, in
    every Mamba layer (the state stays on the chip from token to
    token): 41,024 bytes a token a layer."""
    di, n, _, _ = reference.widths(cfg)
    return scan_tokens * (4 * di + 2 * n) * BF16 * layer_kinds(cfg)[0]


def scan_prefill_least_seconds(cfg, scan_tokens, flops_per_s,
                               bytes_per_s):
    """The larger of the recurrence's two least times."""
    return max(scan_prefill_flops(cfg, scan_tokens) / flops_per_s,
               scan_prefill_bytes(cfg, scan_tokens) / bytes_per_s)


def weight_bytes(cfg):
    """Bytes of the weights as they are served, every leaf once (the
    embedding once, as the head): bfloat16 but for the float32 leaves
    (7.70 GB at the published sizes)."""
    def leaves(spec, name=None):
        if isinstance(spec, dict):
            for key, sub in spec.items():
                yield from leaves(sub, key)
        else:
            yield math.prod(spec[0]) * (
                F32 if name in reference.F32_LEAVES else BF16)

    return sum(leaves(reference.param_spec(cfg)))


def tick_read_bytes(cfg, state_rows, shared_kv_positions,
                    kv_window_positions):
    """Bytes one decode tick must read: the weights, the states (read;
    the write is not a read), the rings and the shared K/V."""
    return (weight_bytes(cfg)
            + ssm_decode_bytes(cfg, state_rows) // 2
            + attn_decode_bytes(cfg, shared_kv_positions,
                                kv_window_positions))


def shared_kv_read_share(cfg, state_rows, shared_kv_positions,
                         kv_window_positions):
    """Percent of a tick's reads that are the cross-decoder (and the
    K/V layer) reading the ONE shared leaf."""
    return (100.0 * shared_kv_positions * kv_position_bytes(cfg)
            / tick_read_bytes(cfg, state_rows, shared_kv_positions,
                              kv_window_positions))
