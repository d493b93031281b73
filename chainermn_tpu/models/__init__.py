"""Model zoo.

The reference delegates models to Chainer plus an ImageNet zoo under
``examples/imagenet/models_v2/`` (alex, googlenet, googlenetbn, nin,
resnet50) and MLPs in the MNIST examples.  ChainerMN-TPU is standalone,
so the zoo lives in the package: flax.linen modules, NHWC layouts,
bfloat16-friendly, reported metrics matching the reference's
``chainer.report({'loss','accuracy'})`` convention via classifier
loss functions.
"""

from chainermn_tpu.models.mlp import MLP  # noqa
from chainermn_tpu.models.classifier import (  # noqa
    Classifier, StatefulClassifier, classifier_loss)
from chainermn_tpu.models.alex import Alex  # noqa
from chainermn_tpu.models.nin import NIN  # noqa
from chainermn_tpu.models.vgg import VGG, VGG16  # noqa
from chainermn_tpu.models.googlenet import GoogLeNet  # noqa
from chainermn_tpu.models.googlenetbn import GoogLeNetBN  # noqa
from chainermn_tpu.models.resnet50 import (  # noqa
    ResNet, ResNet50, ResNet101, ResNet152)
from chainermn_tpu.models.seq2seq import Seq2seq, seq2seq_loss  # noqa
from chainermn_tpu.models.afmoe import AfmoeLM  # noqa
from chainermn_tpu.models.olmo_hybrid import OlmoHybridLM  # noqa
from chainermn_tpu.models.xing4 import Xing4LM  # noqa
from chainermn_tpu.models.phi4flash import Phi4FlashLM  # noqa
from chainermn_tpu.models.deepseek_v3 import DeepseekV3LM  # noqa
from chainermn_tpu.models.solar_open2 import SolarOpen2LM  # noqa
from chainermn_tpu.models.transformer import (  # noqa
    TransformerLM, TransformerBlock, decode_step, decode_step_paged,
    init_kv_cache, init_paged_kv_cache, kv_cache_specs, lm_loss,
    lm_loss_sum, pipeline_parts, pipeline_stage_specs, prefill,
    prefill_paged, spec_verify, spec_verify_paged, tp_oracle,
    tp_param_specs)


def get_arch(name, **kwargs):
    """Architecture registry (parity with the reference's arch table at
    ``train_imagenet.py:103-109``)."""
    archs = {
        'alex': Alex,
        'googlenet': GoogLeNet,
        'googlenetbn': GoogLeNetBN,
        'nin': NIN,
        'resnet50': ResNet50,
        # MXU-friendly space-to-depth stem; exact weight-mapped
        # equivalent of resnet50 (models/resnet50.py)
        'resnet50_s2d': (lambda **kw: ResNet50(
            stem='space_to_depth', **kw)),
        'resnet101': ResNet101,
        'resnet152': ResNet152,
        'vgg16': VGG16,
    }
    if name not in archs:
        raise ValueError('unknown architecture %r (choose from %s)'
                         % (name, ', '.join(sorted(archs))))
    return archs[name](**kwargs)
