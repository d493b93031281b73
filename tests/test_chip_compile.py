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


@pytest.mark.parametrize('body', ['decode', 'prefill'])
def test_serving_executable_leaves_the_page_pool_in_place(
        body, one_chip, mosaic):
    """The serving executables at the widths of the benchmark's cell
    (gpt2-medium, 2,049 pages of 16, 32 rows; two layers of its 24),
    jitted as ``GenerationEngine._compile`` jits them and compiled for
    the described chip: besides the in-place write nothing makes a
    value of a pool leaf's shape, and the scratch is under one leaf.
    With the head dim left at 64 (the array then lies page-minor on
    the chip), or with one stacked array for all layers, this compile
    holds whole-pool ``copy`` and per-layer ``slice`` instructions
    (PERF.md, PR 26); the jaxpr pin in ``tests/test_transformer.py``
    cannot see either."""
    import os
    import sys

    from chainermn_tpu import models as M
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    model = M.TransformerLM(vocab_size=50257, d_model=1024, n_heads=16,
                            n_layers=2, d_ff=4096, max_len=1024)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, BF16, sharding=one_chip),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), I32))['params']))
    cache = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: M.init_paged_kv_cache(model, 2049, 16)))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    def decode(p, c, tokens, positions, tables):
        logits, c = M.decode_step_paged(model, p, c, tokens, positions,
                                        tables)
        return jnp.argmax(logits, axis=-1).astype(I32), c

    def prefill(p, c, tokens, length, pos0, table):
        logits, c = M.prefill_paged(model, p, c, tokens, length, table,
                                    pos0)
        return jnp.argmax(logits).astype(I32), c

    fn, operands = {
        'decode': (decode, (ints(32), ints(32), ints(32, 64))),
        'prefill': (prefill, (ints(1, 128), ints(), ints(), ints(64))),
    }[body]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *operands).compile()
    leaves = jax.tree_util.tree_leaves(cache)
    assert {leaf.shape for leaf in leaves} == {(2049, 16, 16, 128)}
    assert chip_smoke.pool_shaped(compiled.as_text(), leaves) == []
    leaf_bytes = 2049 * 16 * 16 * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes
    if body == 'decode':
        assert 'tpu_custom_call' in compiled.as_text()
