"""The program's bottleneck ResNet, built from a configuration file."""

import jax.numpy as jnp

from chipbench.adapters import common
from chipbench.reference import resnet as reference


def model(cfg):
    from chainermn_tpu.models import ResNet
    return ResNet(stage_sizes=list(cfg['stage_sizes']),
                  num_classes=cfg['num_classes'], width=cfg['width'],
                  insize=cfg['image_size'])


def build_trainer(cfg, mix, examples, params, devices):
    from chainermn_tpu.models import StatefulClassifier
    return common.build_updater(
        cfg, mix, examples, params, StatefulClassifier(model(cfg)).loss,
        devices, model_state={
            'batch_stats': reference.init_batch_stats(cfg)})


PARAM_DTYPE = {'train': jnp.float32}
