"""The program's decoder-only LM, built from a configuration file."""

import jax.numpy as jnp

from chipbench.adapters import common


def model(cfg):
    from chainermn_tpu.models import TransformerLM
    return TransformerLM(
        vocab_size=cfg['vocab_size'], d_model=cfg['n_embd'],
        n_heads=cfg['n_head'], n_layers=cfg['n_layer'],
        d_ff=cfg['n_inner'], max_len=cfg['n_positions'])


def build_trainer(cfg, mix, examples, params, devices):
    from chainermn_tpu.models import lm_loss
    lm = model(cfg)
    loss = lm_loss(lambda p, t: lm.apply({'params': p}, t))
    return common.build_updater(cfg, mix, examples, params, loss,
                                devices, has_aux=True)


def build_engine(cfg, mix, params):
    """``GenerationEngine`` + ``GenerationQueue`` as the mix's
    ``engine`` block sizes them; every executable it will use is
    compiled (or read from the cache) by ``warmup()``."""
    from chainermn_tpu import serving
    from chainermn_tpu.precision import Policy

    e = mix['engine']
    engine = serving.GenerationEngine(
        model(cfg), params, n_slots=e['n_slots'],
        max_prompt_len=e['max_prompt_len'], max_len=e['max_len'],
        paged=e['paged'], page_size=e['page_size'], eos_id=None,
        policy=Policy.bf16())
    engine.warmup()
    queue = serving.GenerationQueue(
        max_prompt_len=engine.max_prompt_len,
        max_queue=4 * engine.n_slots,
        page_size=engine.page_size if engine.paged else None)
    return engine, queue


PARAM_DTYPE = {'train': jnp.float32, 'serve': jnp.bfloat16}
