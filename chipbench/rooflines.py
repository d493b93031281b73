"""What a kernel MUST move or compute, from shapes: the numerators of
the roofline shares.  Only what the algorithm needs is counted (an
expert's weights once for every tick that touches it, the K/V a query
is allowed to see, the products of real prompt tokens), so that no
share can read over 100: padding rows, re-fetched tiles and masked
positions are the kernel's cost, not its work."""

#: bytes of a bfloat16 element: the serving precision of every cell
BF16 = 2


def layer_kinds(cfg):
    """``(window layers, full layers, expert layers)`` of an ``afmoe``
    configuration."""
    window = sum(1 for kind in cfg['layer_types']
                 if kind == 'sliding_attention')
    return (window, len(cfg['layer_types']) - window,
            cfg['num_hidden_layers'] - cfg['num_dense_layers'])


def expert_bytes(cfg):
    """One routed expert's three matrices (12,582,912 bytes at 2048 x
    1024)."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size'] * BF16


def moe_decode_bytes(cfg, experts_touched):
    """Bytes one decode tick's expert kernels must read: each expert
    layer streams every TOUCHED expert once (``experts_touched``: the
    mean over the expert layers).  The rows themselves (512 x 2048 in
    and out) are 0.03% of that and left out."""
    return experts_touched * layer_kinds(cfg)[2] * expert_bytes(cfg)


def attn_decode_bytes(cfg, kv_positions, kv_window_positions):
    """K and V bytes one decode tick's attention kernels must read:
    every live position in a full layer (``kv_positions``, summed over
    rows), at most the window in a window layer."""
    window, full, _ = layer_kinds(cfg)
    per_position = (2 * cfg['num_key_value_heads'] * cfg['head_dim']
                    * BF16)
    return (kv_positions * full + kv_window_positions * window) \
        * per_position


def moe_prefill_flops(cfg, prompt_tokens):
    """Floating-point operations the expert kernels of a prefill must
    do for ``prompt_tokens`` real tokens: k experts a token, three
    products of 2 * hidden * width each, in every expert layer."""
    return (prompt_tokens * cfg['num_experts_per_tok'] * 6
            * cfg['hidden_size'] * cfg['moe_intermediate_size']
            * layer_kinds(cfg)[2])


def share(needed, peak_per_s, seconds):
    """Percent of the peak: the least time over the time taken."""
    return 100.0 * needed / peak_per_s / seconds
