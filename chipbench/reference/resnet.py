"""Plain reference of the bottleneck residual network the program calls
``models.ResNet50``: He et al. 2015, Table 1, with the stride on the
3x3 convolution (the "v1.5" placement, listed under ``assumed``), NHWC,
batch normalisation over the whole batch in training mode (eps 1e-5,
biased variance), global average pool, a dense classifier; the loss is
the mean softmax cross-entropy.  float32 throughout, no kernels.
Nothing here imports the program."""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import flops
from chipbench.reference import common


def _bn_spec(c):
    return {'scale': ((c,), 1.0, 0.1), 'bias': ((c,), 0.0, 0.1)}


def _conv_spec(k, c_in, c_out):
    return {'kernel': ((k, k, c_in, c_out), 0.0,
                       math.sqrt(2.0 / (k * k * c_in)))}


def param_spec(cfg):
    """``(shape, mean, std)`` per leaf in the tree the program's module
    declares.  He-normal convolutions; every normalisation gets a
    scale near 1 (the program's own init zeroes each block's last
    scale, which would leave most gradients exactly zero and the
    comparison blind)."""
    width = cfg['width']
    spec = {'conv_init': _conv_spec(7, 3, width),
            'bn_init': _bn_spec(width)}
    c_in, n = width, 0
    for i, blocks in enumerate(cfg['stage_sizes']):
        f = width * 2 ** i
        for j in range(blocks):
            block = {'Conv_0': _conv_spec(1, c_in, f),
                     'BatchNorm_0': _bn_spec(f),
                     'Conv_1': _conv_spec(3, f, f),
                     'BatchNorm_1': _bn_spec(f),
                     'Conv_2': _conv_spec(1, f, 4 * f),
                     'BatchNorm_2': _bn_spec(4 * f)}
            if j == 0:
                block['proj'] = _conv_spec(1, c_in, 4 * f)
                block['proj_bn'] = _bn_spec(4 * f)
            spec['Bottleneck_%d' % n] = block
            c_in, n = 4 * f, n + 1
    spec['fc'] = {'kernel': ((c_in, cfg['num_classes']), 0.0, 0.01),
                  'bias': ((cfg['num_classes'],), 0.0, 0.01)}
    return spec


def init_params(cfg, seed, dtype=jnp.float32):
    spec = param_spec(cfg)
    return jax.jit(lambda key: common.init_from_spec(spec, key, dtype))(
        common.seed_key(seed))


def init_batch_stats(cfg):
    """Running statistics as the program's module declares them
    (mean 0, variance 1): training mode never reads them."""
    def stats(bn):
        c = bn['scale'][0][0]
        return {'mean': jnp.zeros((c,), jnp.float32),
                'var': jnp.ones((c,), jnp.float32)}

    def walk(node):
        out = {}
        for name, sub in node.items():
            if isinstance(sub, dict) and 'scale' in sub:
                out[name] = stats(sub)
            elif isinstance(sub, dict) and 'kernel' not in sub:
                out[name] = walk(sub)
        return out
    return walk(param_spec(cfg))


def _bn(x, p, eps=1e-5):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x), (0, 1, 2)) - jnp.square(mean)
    return (x - mean) * lax.rsqrt(var + eps) * p['scale'] + p['bias']


def _bottleneck(x, p, stride, prec):
    def conv(x, name, stride=1):
        return prec.store(prec.conv(x, p[name]['kernel'], stride, 'SAME'))

    y = prec.store(jax.nn.relu(_bn(conv(x, 'Conv_0'), p['BatchNorm_0'])))
    y = prec.store(jax.nn.relu(_bn(conv(y, 'Conv_1', stride),
                                   p['BatchNorm_1'])))
    y = conv(y, 'Conv_2')
    if 'proj' in p:
        x = prec.store(_bn(conv(x, 'proj', stride), p['proj_bn']))
    return prec.store(jax.nn.relu(_bn(y, p['BatchNorm_2']) + x))


def forward(params, images, cfg, prec):
    """images ``(B, S, S, 3)`` float32 -> logits ``(B, classes)``."""
    x = prec.store(prec.conv(prec.store(images),
                             params['conv_init']['kernel'], 2, 'SAME'))
    x = prec.store(jax.nn.relu(_bn(x, params['bn_init'])))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), 'SAME')
    n = 0
    for i, blocks in enumerate(cfg['stage_sizes']):
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            block = jax.checkpoint(
                lambda x, p, s=stride: _bottleneck(x, p, s, prec))
            x = block(x, params['Bottleneck_%d' % n])
            n += 1
    x = jnp.mean(x, (1, 2))
    return prec.einsum('bc,cn->bn', x, params['fc']['kernel']) \
        + params['fc']['bias']


def make_grad_fn(cfg, precision='float32'):
    """``grad_fn(params, (images, labels)) -> (mean loss, grads)``.
    Batch normalisation couples the rows, so the batch goes through
    whole, block by rematerialised block."""
    prec = common.Precision(precision)

    def loss(params, images, labels):
        logp = jax.nn.log_softmax(forward(params, images, cfg, prec), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    fn = jax.jit(jax.value_and_grad(loss))
    return lambda params, batch: fn(params, *(jnp.asarray(a)
                                              for a in batch))


def batch_of(examples):
    return tuple(np.stack([e[i] for e in examples]) for i in (0, 1))


def train_flops_per_sample(cfg, mix):
    del mix
    return 3 * 2 * flops.resnet_forward_macs(cfg)
