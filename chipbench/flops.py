"""Model FLOPs per sample, from shapes.  What the forward and backward
passes REQUIRE (no recomputation, causal attention counted as the half
it is); a multiply-add is two FLOPs; training is three forwards."""


def transformer_lm_forward_flops(cfg, seq_len):
    """One sequence of ``seq_len`` tokens through the decoder: the
    matmuls of every block and of the head, plus causal attention
    (QK^T and PV over the lower triangle)."""
    # QK^T and PV: 2 matmuls x 2 FLOPs x T^2 d, halved by causality
    attention = cfg['n_layer'] * 2 * seq_len * seq_len * cfg['n_embd']
    return 2 * transformer_lm_matmul_params(cfg) * seq_len + attention


def transformer_lm_matmul_params(cfg):
    """Weights that multiply every token: the blocks' four attention
    and two feed-forward matrices, and the vocabulary head."""
    d = cfg['n_embd']
    return (cfg['n_layer'] * (4 * d * d + 2 * d * cfg['n_inner'])
            + d * cfg['vocab_size'])


def resnet_forward_macs(cfg):
    """Multiply-adds of one image through the v1.5 bottleneck net
    (stride on the 3x3), convolutions and the classifier."""
    size = cfg['image_size']
    width = cfg['width']
    macs = 0

    def conv(hw_out, k, c_in, c_out):
        return hw_out * hw_out * k * k * c_in * c_out

    hw = size // 2
    macs += conv(hw, 7, 3, width)
    hw //= 2                                   # max pool
    c_in = width
    for i, blocks in enumerate(cfg['stage_sizes']):
        f = width * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            macs += conv(hw, 1, c_in, f)
            hw_out = hw // stride
            macs += conv(hw_out, 3, f, f)
            macs += conv(hw_out, 1, f, 4 * f)
            if j == 0:
                macs += conv(hw_out, 1, c_in, 4 * f)
            hw, c_in = hw_out, 4 * f
    return macs + c_in * cfg['num_classes']
