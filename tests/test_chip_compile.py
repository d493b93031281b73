"""Main-path Pallas kernels compiled for a DESCRIBED TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is
described (``v5e:2x2`` topology) and not attached, so what Mosaic
refuses -- a block shape off the (8, 128) tiling, a kernel over the
VMEM budget -- is found here on the CPU, at bench widths, before any
chip time is spent.  Nothing RUNS: numerics on the chip are
``tests/test_tpu_mosaic.py`` and ``chip_smoke.py``.

On the CPU ``pallas_mode()`` answers ``'fallback'``, so the fixture
steers the ``ops`` modules onto their Mosaic path itself; the
persistent compilation cache is off around these compiles (an entry
written for a described device cannot be read back without one).
"""

import os

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

import importlib

import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import ops

# by module path: ops/__init__ re-exports functions under the same
# names as three of these modules
KERNEL_MODULES = [importlib.import_module('chainermn_tpu.ops.' + name)
                  for name in ('flash_attention', 'layer_norm',
                               'cross_entropy', 'batch_norm_act',
                               'optimizer')]

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # no TPU compiler in this jaxlib
        pytest.skip('cannot describe a v5e topology here: %r' % (e,))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    for mod in KERNEL_MODULES:
        monkeypatch.setattr(mod, 'pallas_mode', lambda: 'native')
        monkeypatch.setattr(mod, 'interpret_flag', lambda: False)


def _sum_sq(fn):
    return lambda *a: jnp.sum(fn(*a).astype(F32) ** 2)


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, causal=True)


def _decode(q, k, v, lengths, k_scale=None, v_scale=None):
    return ops.flash_attention_decode(q, k, v, lengths, k_scale=k_scale,
                                      v_scale=v_scale)


def _decode_paged(q, k, v, tables, lengths, k_scale=None, v_scale=None):
    return ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, k_scale=k_scale, v_scale=v_scale)


def _bn_res(x, scale, bias, res):
    return ops.batch_norm_act(x, scale, bias, residual=res)[0]


def _sgd_leaf(g, v):
    return KERNEL_MODULES[-1]._leaf_update_pallas(g, v, 0.1, 0.9)


# bench widths: bench.py build_transformer (batch 8 x seq 1024, 8 heads
# x 64, d512, V32k), measure_generate (32 slots, cache 512, prompts
# <=128) and ResNet-50 batch 32 at 224 px
_QKV = [((8, 1024, 8, 64), BF16)] * 3
_Q1 = ((32, 8, 64), BF16)
_LEN = ((32,), I32)


def _slab(dtype):
    return [_Q1, ((32, 512, 8, 64), dtype), ((32, 512, 8, 64), dtype),
            _LEN]


def _pool(page, dtype):
    n_pages = 1 + 32 * (512 // page)
    return [_Q1, ((n_pages, page, 8, 64), dtype),
            ((n_pages, page, 8, 64), dtype),
            ((32, 512 // page), I32), _LEN]


CASES = {
    'flash_fwd_causal_t1024': (_flash, _QKV),
    'flash_fwd_bwd_causal_t1024': (
        jax.grad(_sum_sq(_flash), argnums=(0, 1, 2)), _QKV),
    'decode_slab_bf16': (_decode, _slab(BF16)),
    'decode_slab_int8': (
        _decode, _slab(I8) + [((32, 512, 8), F32)] * 2),
    'decode_paged_page16': (_decode_paged, _pool(16, BF16)),
    'decode_paged_page128': (_decode_paged, _pool(128, BF16)),
    'decode_paged_page16_int8': (
        _decode_paged, _pool(16, I8) + [((513, 16, 8), F32)] * 2),
    'decode_paged_page128_int8': (
        _decode_paged, _pool(128, I8) + [((129, 128, 8), F32)] * 2),
    'chunk_c128_ctx512': (
        ops.flash_attention_chunk,
        [((1, 128, 8, 64), BF16)] * 3 + [((1, 512, 8, 64), BF16)] * 2
        + [((1,), I32)]),
    'layer_norm_fwd_bwd': (
        jax.grad(_sum_sq(ops.layer_norm), argnums=(0, 1, 2)),
        [((8, 1024, 512), BF16), ((512,), F32), ((512,), F32)]),
    'cross_entropy_v32k_fwd_bwd': (
        jax.grad(lambda lg, y: jnp.sum(
            ops.softmax_cross_entropy(lg, y))),
        [((8192, 32000), F32), ((8192,), I32)]),
    'batch_norm_act_56x56x256_residual_fwd_bwd': (
        jax.grad(_sum_sq(_bn_res), argnums=(0, 1, 2, 3)),
        [((32, 56, 56, 256), BF16), ((256,), F32), ((256,), F32),
         ((32, 56, 56, 256), BF16)]),
    'fused_sgd_leaf': (_sgd_leaf, [((512, 2048), F32)] * 2),
}


@pytest.mark.parametrize('case', sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, mosaic):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'tpu_custom_call' in compiled.as_text(), (
        '%s compiled without its Mosaic kernel' % case)
