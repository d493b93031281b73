"""What the ``olmo_hybrid`` family's kernels MUST move or compute, from
shapes: the numerators of its roofline shares.  Only what the algorithm
needs is counted (a row's state read once and written once at its
NOMINAL float32 size, the K/V of live positions, the recurrence's own
three products a real token), so that no share can read over 100: lane
padding, a gather before the update, the chunked form's extra products
and pad positions are the kernel's cost, not its work."""

from chipbench.rooflines import BF16, share  # noqa: F401 (readers use it)

#: bytes of a float32 element
F32 = 4


def layer_kinds(cfg):
    """``(linear layers, full layers)`` of an ``olmo_hybrid``
    configuration."""
    linear = sum(1 for kind in cfg['layer_types']
                 if kind == 'linear_attention')
    return linear, len(cfg['layer_types']) - linear


def state_row_bytes(cfg):
    """One sequence's recurrent state in one linear layer: heads x dk x
    dv float32 (2,211,840 bytes at 30 x 96 x 192)."""
    return (cfg['linear_num_value_heads'] * cfg['linear_key_head_dim']
            * cfg['linear_value_head_dim'] * F32)


def state_decode_bytes(cfg, state_rows):
    """Bytes one decode tick's state updates must move: every row's
    state read once and written once in every linear layer.  The
    token's q, k, v (46 KB a row) are 1% of that and left out."""
    return state_rows * layer_kinds(cfg)[0] * 2 * state_row_bytes(cfg)


def attn_decode_bytes(cfg, kv_positions):
    """K and V bytes one decode tick's attention kernels must read:
    every live position (``kv_positions``, summed over rows) in every
    full layer, 15,360 bytes a position a layer at 30 heads of 128."""
    head_dim = cfg['hidden_size'] // cfg['num_attention_heads']
    return (kv_positions * layer_kinds(cfg)[1]
            * 2 * cfg['num_key_value_heads'] * head_dim * BF16)


def scan_prefill_flops(cfg, scan_tokens):
    """Floating-point operations the recurrence itself needs for
    ``scan_tokens`` real prompt tokens: three products of 2 x dk x dv a
    head a token (``S^T k``, ``k u^T``, ``S^T q``) in every linear
    layer."""
    return (scan_tokens * 3 * 2 * cfg['linear_key_head_dim']
            * cfg['linear_value_head_dim']
            * cfg['linear_num_value_heads'] * layer_kinds(cfg)[0])


def scan_prefill_bytes(cfg, scan_tokens):
    """Bytes the recurrence must move for those tokens: q, k (heads x
    dk) and v, z, o (heads x dv) once each in bfloat16, in every linear
    layer (the state stays on the chip from token to token)."""
    heads = cfg['linear_num_value_heads']
    per_token = heads * (2 * cfg['linear_key_head_dim']
                         + 3 * cfg['linear_value_head_dim']) * BF16
    return scan_tokens * per_token * layer_kinds(cfg)[0]


def scan_prefill_least_seconds(cfg, scan_tokens, flops_per_s,
                               bytes_per_s):
    """The larger of the recurrence's two least times."""
    return max(scan_prefill_flops(cfg, scan_tokens) / flops_per_s,
               scan_prefill_bytes(cfg, scan_tokens) / bytes_per_s)
