"""What the ``xing4`` family's kernels MUST move or compute, from
shapes: the numerators of its roofline shares.  Only what the algorithm
needs is counted (a latent row at its NOMINAL 576 values, the causal
half of a prompt's score square, an expert's weights once for every
tick that touches it, the streams read once), so that no share can
read over 100: lane padding (a row is stored in 640 lanes), the dead
half of a causal tile, pad rows and re-fetched tiles are the kernel's
cost, not its work."""

from chipbench.rooflines import BF16, share  # noqa: F401 (readers use it)

#: bytes of a float32 element
F32 = 4


def expert_layers(cfg):
    return cfg['num_hidden_layers'] - cfg['first_k_dense_replace']


def latent_row_bytes(cfg):
    """One cached position in one layer: ``kv_lora_rank`` +
    ``qk_rope_head_dim`` bfloat16 values (1,152 bytes at 512 + 64)."""
    return (cfg['kv_lora_rank'] + cfg['qk_rope_head_dim']) * BF16


def latent_position_flops(cfg):
    """Absorbed attention at one cached position in one layer: every
    head's score over the whole row and its value product over the
    latent part, 32 x 2 x (576 + 512) = 69,632."""
    rank, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    return cfg['num_attention_heads'] * 2 * ((rank + rope) + rank)


def mla_decode_least_seconds(cfg, latent_positions, flops_per_s,
                             bytes_per_s):
    """The larger of the latent decode kernel's two least times over
    ``latent_positions`` (positions read, summed over rows and layers):
    the kernel sits on the ridge (1.41 ns of bytes, 0.35 ns of products
    at the MXU's peak a position; a quarter-filled MXU makes them
    even)."""
    return max(latent_positions * latent_row_bytes(cfg) / bytes_per_s,
               latent_positions * latent_position_flops(cfg)
               / flops_per_s)


def causal_entries(tokens):
    """Live entries of a prompt's causal score square."""
    return tokens * (tokens + 1) / 2.0


def mla_prefill_flops(cfg, live_entries):
    """Expanded attention over ``live_entries`` causal (query, key)
    pairs of one prompt: 32 heads x 2 x (192 + 128) a pair, in every
    layer."""
    width = (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
             + cfg['v_head_dim'])
    return (live_entries * cfg['num_attention_heads'] * 2 * width
            * cfg['num_hidden_layers'])


def expert_bytes(cfg):
    """One routed expert's three matrices (22,020,096 bytes at 3584 x
    1024)."""
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size'] * BF16


def moe_decode_bytes(cfg, experts_touched):
    """Bytes one decode tick's expert kernels must read: each expert
    layer streams every TOUCHED expert once (``experts_touched``: the
    mean over the expert layers)."""
    return experts_touched * expert_layers(cfg) * expert_bytes(cfg)


def mhc_solves(cfg):
    """Solves of the residual path's coefficients a call: two a
    layer."""
    return 2 * cfg['num_hidden_layers']


def mhc_coeff_bytes(cfg, rows):
    """Bytes the coefficient kernels of one call over ``rows`` tokens
    must read: the four streams once (bfloat16) and ``phi`` once
    (float32), a solve."""
    n, d = cfg['hc_mult'], cfg['hidden_size']
    return mhc_solves(cfg) * (rows * n * d * BF16
                              + n * (n + 2) * n * d * F32)
