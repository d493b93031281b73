"""What the ``deepseek_v3`` family's TRAINING kernels must compute, from
shapes: the numerators of ``mla_train_mxu_share`` and
``moe_train_mxu_share``.  Only what the mathematics needs is counted,
so that no share can read over 100 whatever implements the work: the
dead half of a causal tile, the score tile a backward kernel makes
again, a layer's forward run twice under ``jax.checkpoint``, a visit
that computes its whole tile for a few rows and the ``gate`` / ``up``
the data-gradient kernel recomputes are the kernels' cost, not their
work."""

from chipbench.mla_rooflines import causal_entries, expert_layers
from chipbench.rooflines import share  # noqa: F401 (readers use it)

#: forward, the gradient by the data, the gradient by the weights (for
#: attention: by the queries, by the keys and values): a backward needs
#: twice the forward's products
PASSES = 3


def mla_train_flops(cfg, seq_len, sequences):
    """Forward and backward of expanded causal attention over
    ``sequences`` rows of ``seq_len`` positions in every layer: per
    live (query, key) pair and head the forward's two products (the
    score over 192, the value sum over 128), and in the backward the
    four the mathematics needs (dV and dP over 128, dQ and dK over
    192)."""
    width = (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
             + cfg['v_head_dim'])
    return (PASSES * sequences * causal_entries(seq_len)
            * cfg['num_attention_heads'] * 2 * width
            * cfg['num_hidden_layers'])


def moe_train_flops(cfg, held_assignments):
    """Forward and backward of the held experts over
    ``held_assignments`` (token, expert) rows, SUMMED over the expert
    layers as the step's counter has them: three matrices of 2 x hidden
    x width a row, three passes."""
    return (PASSES * held_assignments * 3 * 2 * cfg['hidden_size']
            * cfg['moe_intermediate_size'])


def held_assignments_expected(cfg, tokens):
    """What a uniform router puts on the held experts in one step, over
    the expert layers: ``tokens x k x held / router width`` a layer."""
    width = cfg.get('router_experts') or cfg['n_routed_experts']
    return (tokens * cfg['num_experts_per_tok'] * cfg['n_routed_experts']
            / width * expert_layers(cfg))
