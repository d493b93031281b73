"""The program's ``deepseek_v3`` decoder LM (``models.DeepseekV3LM``),
built from a configuration file.  Trained only: the family has no
serving path here.  The model is imported with the module, so that a
program without the family fails the cell at once, before any weight is
made."""

import jax.numpy as jnp

from chainermn_tpu.models import DeepseekV3LM

from chipbench.adapters import common


def model(cfg):
    return DeepseekV3LM.from_config(cfg)


def build_trainer(cfg, mix, examples, params, devices):
    """``StandardUpdater`` under the configuration's ``train`` block;
    the loss is the model's own (mean next-token cross-entropy, the
    expert counters as its aux)."""
    return common.build_updater(cfg, mix, examples, params,
                                model(cfg).loss_fn(), devices,
                                has_aux=True)


PARAM_DTYPE = {'train': jnp.float32}
