"""What the ``solar_open2`` family's serving kernels MUST move or
compute, from shapes: the numerators of its roofline shares.  Only what
the algorithm needs is counted (a row's state read once and written
once at its NOMINAL float32 size, a touched held expert's three
matrices once, the K/V of live positions, the recurrence's own three
products a real token), so that no share can read over 100: a gather
before the update, the chunked form's pair matrices, its triangular
systems and its pad positions, an expert tile mostly empty, are the
kernels' cost, not their work."""

from chipbench.rooflines import BF16, share  # noqa: F401 (readers use it)

#: bytes of a float32 element
F32 = 4


def layer_kinds(cfg):
    """``(kda layers, gqa layers)`` of a ``solar_open2``
    configuration."""
    gqa = len(cfg['gqa_layers'])
    return cfg['num_hidden_layers'] - gqa, gqa


def _linear(cfg):
    lin = cfg['linear_attn_config']
    return lin['num_heads'], lin['head_dim']


def state_row_bytes(cfg):
    """One sequence's recurrent state in one ``kda`` layer: heads x dk
    x dv float32 (4,194,304 bytes at 64 x 128 x 128)."""
    heads, dim = _linear(cfg)
    return heads * dim * dim * F32


def state_decode_bytes(cfg, state_rows):
    """Bytes one decode tick's state updates must move: every row's
    state read once and written once in every ``kda`` layer.  The
    token's q, k, v, g (98 KB a row) are 1% of that and left out."""
    return state_rows * layer_kinds(cfg)[0] * 2 * state_row_bytes(cfg)


def attn_decode_bytes(cfg, kv_positions):
    """K and V bytes one decode tick's attention kernels must read:
    every live position (``kv_positions``, summed over rows) in every
    ``gqa`` layer, 4,096 bytes a position a layer at 8 heads of 128."""
    return (kv_positions * layer_kinds(cfg)[1]
            * 2 * cfg['num_key_value_heads'] * cfg['head_dim'] * BF16)


def expert_bytes(cfg):
    """One routed expert's three matrices (31,457,280 bytes at 4096 x
    1280)."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size'] * BF16


def moe_decode_bytes(cfg, experts_touched):
    """Bytes one decode tick's expert kernels must read: every layer
    streams each TOUCHED held expert once (``experts_touched``: the
    mean over the layers, every one of which is sparse).  The rows
    themselves (512 x 4096 in and out) are 0.4% of that and left
    out."""
    return experts_touched * cfg['num_hidden_layers'] * expert_bytes(cfg)


def scan_prefill_flops(cfg, scan_tokens):
    """Floating-point operations the recurrence itself needs for
    ``scan_tokens`` real prompt tokens: three products of 2 x dk x dv a
    head a token (``S^T k``, ``k u^T``, ``S^T q``) in every ``kda``
    layer."""
    heads, dim = _linear(cfg)
    return scan_tokens * 3 * 2 * dim * dim * heads * layer_kinds(cfg)[0]


def scan_prefill_bytes(cfg, scan_tokens):
    """Bytes the recurrence must move for those tokens: q, k, v, the
    per-channel decay g, the output gate and o, heads x 128 each, once
    in bfloat16, in every ``kda`` layer (the state stays on the chip
    from token to token)."""
    heads, dim = _linear(cfg)
    return scan_tokens * 6 * heads * dim * BF16 * layer_kinds(cfg)[0]


def scan_prefill_least_seconds(cfg, scan_tokens, flops_per_s,
                               bytes_per_s):
    """The larger of the recurrence's two least times."""
    return max(scan_prefill_flops(cfg, scan_tokens) / flops_per_s,
               scan_prefill_bytes(cfg, scan_tokens) / bytes_per_s)
