"""The program's ``solar_open2`` decoder LM (``models.SolarOpen2LM``),
built from a configuration file.  Served only: the family has no
trainer here.  The model is imported with the module, so that a program
without the family fails the cell at once, before any weight is
made."""

import jax.numpy as jnp

from chainermn_tpu.models import SolarOpen2LM


def model(cfg):
    return SolarOpen2LM.from_config(cfg)


def build_engine(cfg, mix, params):
    """``GenerationEngine`` + ``GenerationQueue`` as the mix's
    ``engine`` block sizes them, every executable compiled (or read from
    the cache) by ``warmup()``.  The radix prefix index is off: a
    recurrent state has no shareable pages (the engine refuses the
    combination)."""
    from chainermn_tpu import serving
    from chainermn_tpu.precision import Policy

    e = mix['engine']
    engine = serving.GenerationEngine(
        model(cfg), params, n_slots=e['n_slots'],
        max_prompt_len=e['max_prompt_len'], max_len=e['max_len'],
        paged=e['paged'], page_size=e['page_size'], eos_id=None,
        prefix_sharing=False, policy=Policy.bf16())
    engine.warmup()
    queue = serving.GenerationQueue(
        max_prompt_len=engine.max_prompt_len,
        max_queue=4 * engine.n_slots, page_size=engine.page_size)
    return engine, queue


PARAM_DTYPE = {'serve': jnp.bfloat16}
