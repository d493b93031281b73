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
                               'optimizer', 'grouped_matmul',
                               'gated_delta', 'hyper_connection',
                               'selective_scan')]

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
    return KERNEL_MODULES[4]._leaf_update_pallas(g, v, 0.1, 0.9)


def _decode_ring(q, k, v, tables, lengths):
    return ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, group=8, window=2048, head_major=True)


def _decode_grouped(q, k, v, tables, lengths):
    return ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, group=8, head_major=True)


def _flash_window(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, window=2048)


def _append(k, v, k_new, v_new, pages, offsets):
    return ops.paged_kv_append(k, v, k_new, v_new, pages, offsets)[0]


# bench widths: bench.py build_transformer (batch 8 x seq 1024, 8 heads
# x 64, d512, V32k), measure_generate (32 slots, cache 512, prompts
# <=128) and ResNet-50 batch 32 at 224 px
_QKV = [((8, 1024, 8, 64), BF16)] * 3
# the gpt2m-train-1k cell's own call: 8 sequences x 16 heads
_QKV_CELL = [((8, 1024, 16, 64), BF16)] * 3
_Q1 = ((32, 8, 64), BF16)
_LEN = ((32,), I32)


def _layer_norm(dtype, *shape):
    """``x`` of ``shape`` in ``dtype`` under float32 ``gamma`` and
    ``beta``, as the transformer family keeps them."""
    return [(shape, dtype)] + [(shape[-1:], F32)] * 2


def _slab(dtype):
    return [_Q1, ((32, 512, 8, 64), dtype), ((32, 512, 8, 64), dtype),
            _LEN]


def _pool(page, dtype):
    n_pages = 1 + 32 * (512 // page)
    return [_Q1, ((n_pages, page, 8, 64), dtype),
            ((n_pages, page, 8, 64), dtype),
            ((32, 512 // page), I32), _LEN]


# the trinity-mini cell's widths: 32 query heads on 4 K/V heads of 128,
# 64 rows, pages of 64 (a ring of 33 for the 2,048 window), 128 experts
# of 2048 x 1024, 8 a token
_Q64 = ((64, 32, 128), BF16)


def _head_major(n_pages):
    return [((n_pages, 4, 64, 128), BF16)] * 2


_EXPERTS = [((128, 2048, 1024), BF16)] * 2 + [((128, 1024, 2048), BF16),
                                              ((128,), I32)]

# the gpt2m-serve-closed32 cell's paged decode call: 32 rows, a table
# 64 wide, the float pool head-major with two 64-wide heads a 128-lane
# row (8 packed heads of 16 positions, each query one of its row's
# group of 2); an int8 pool page-major, pages of 16 positions x 16
# heads x 128 lanes (the head dim 64 padded)
_Q32 = ((32, 16, 128), BF16)
_TABLE32 = [((32, 64), I32), ((32,), I32)]


def _page_major(dtype):
    return [((2049, 16, 16, 128), dtype)] * 2 + _TABLE32


def _packed():
    return [((2049, 8, 16, 128), BF16)] * 2


def _decode_packed(q, k, v, tables, lengths):
    return ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, scale=64 ** -0.5, group=2,
        head_major=True)


# the olmo-hybrid-serve-closed48 cell's widths: 30 query on 30 K/V heads
# of 128 (group 1, head-major pages of 32), 48 rows, and 30 heads of
# 96 x 192 float32 state, two heads side by side in the lanes
_Q48 = ((48, 30, 128), BF16)
_STATE = [((49, 15, 96, 384), F32), ((48,), I32)] \
    + [((48, 30, 96), BF16)] * 2 + [((48, 30, 192), BF16)] \
    + [((48, 30), F32)] * 2


def _decode_group1(q, k, v, tables, lengths):
    return ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, group=1, head_major=True)


def _delta_step(state, rows, q, k, v, g, beta):
    return ops.gated_delta_step(state, rows, q, k, v, g, beta)[0]


def _conv_step(tail, rows, x, w):
    return ops.causal_conv_step(tail, rows, x, w)


# the xing4-serve-closed48-long cell's widths: 32 query heads of 192
# (keys) / 128 (values) expanded in prefill; absorbed in decode, 48 rows
# reading ONE latent leaf of 640-lane rows in pages of 64, values its
# first 512 lanes; 64 experts of 3584 x 1024, 4 a token; four streams
# of 3584 under 24 coefficients a token
_LATENT = ((5761, 1, 64, 640), BF16)
_EXPERTS_3584 = [((64, 3584, 1024), BF16)] * 2 \
    + [((64, 1024, 3584), BF16), ((64,), I32)]
_MHC = [((24, 4 * 3584), F32), ((3,), F32), ((24,), F32)]


def _decode_latent(q, pool, tables, lengths):
    return ops.flash_attention_decode_paged(
        q, pool, None, tables, lengths, scale=0.1, group=32,
        head_major=True, value_lanes=512)


def _append_latent(pool, new, pages, offsets):
    return ops.paged_kv_append(pool, None, new, None, pages, offsets)[0]


def _mhc(x, phi, alpha, b):
    return jnp.concatenate([c.reshape(x.shape[0], -1) for c in
                            ops.mhc_coefficients(x, phi, alpha, b)], -1)


# the phi4flash-serve-closed96-think cell's widths: 96 rows; K/V heads
# packed by pair (10 rows of 128 lanes a position), 40 padded query
# heads in groups of 4; pages of 64: 7,681 in the ONE full leaf, 865 in
# a ring leaf (96 rings of 9 for a window of 512); d_inner 5120, 16
# state values, 4 taps; prompts up to 1,024
_FLASH_KV = lambda pages: [((pages, 10, 64, 128), BF16)] * 2  # noqa: E731
_Q96 = ((96, 40, 128), BF16)
_SCAN = lambda t: [((t, 5120), BF16), ((t, 5120), F32),       # noqa: E731
                   ((5120, 16), F32), ((t, 16), F32), ((t, 16), F32),
                   ((5120,), F32)]


def _decode_pairs(q, k, v, tables, lengths):
    return ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, scale=0.125, group=4, head_major=True)


def _decode_pairs_ring(q, k, v, tables, lengths):
    return ops.flash_attention_decode_paged(
        q, k, v, tables, lengths, scale=0.125, group=4, window=512,
        head_major=True)


def _flash_pairs_window(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, scale=0.125,
                               window=512)


def _scan_prompt(x, delta, a, b, c, d):
    return ops.selective_scan(x, delta, a, b, c, d, length=1000)[0]


def _scan_step(state, rows, x, delta, a, b, c, d):
    return ops.selective_scan_step(state, rows, x, delta, a, b, c, d)[0]


def _conv_step_bias(tail, rows, x, w, bias):
    return ops.causal_conv_step(tail, rows, x, w, bias)


# the kanana-train-8k-ep8share cell's widths: one 8,192-token sequence,
# 32 heads of 192 (keys) / 128 (values) with a backward; 16 held
# experts of 2048 x 768 under the rows of ALL 8,192 x 6 assignments
# (the held ones sorted first, about an eighth of them)
_EXPERTS_768 = [((16, 2048, 768), BF16)] * 2 + [((16, 768, 2048), BF16),
                                                ((16,), I32)]

CASES = {
    'flash_fwd_bwd_causal_192_128_t8192_train_cell': (
        jax.grad(_sum_sq(_flash), argnums=(0, 1, 2)),
        [((1, 8192, 32, 192), BF16)] * 2 + [((1, 8192, 32, 128), BF16)]),
    'grouped_swiglu_fwd_bwd_49152rows_2048x768_train_cell': (
        jax.grad(_sum_sq(ops.grouped_swiglu), argnums=(0, 1, 2, 3)),
        [((49152, 2048), BF16)] + _EXPERTS_768),
    'selective_scan_prompt_1024': (_scan_prompt, _SCAN(1024)),
    'selective_scan_prompt_64': (_scan_prompt, _SCAN(64)),
    'selective_scan_step_96rows': (
        _scan_step, [((97, 1, 16, 5120), F32), ((96,), I32)] + _SCAN(96)),
    'causal_conv_step_96rows_bias': (
        _conv_step_bias, [((97, 144, 128), BF16), ((96,), I32),
                          ((96, 5120), BF16), ((4, 5120), BF16),
                          ((5120,), BF16)]),
    'decode_paged_pairs_group4_page64': (
        _decode_pairs, [_Q96] + _FLASH_KV(7681)
        + [((96, 80), I32), ((96,), I32)]),
    'decode_paged_pairs_ring9_window512': (
        _decode_pairs_ring, [_Q96] + _FLASH_KV(865)
        + [((96, 9), I32), ((96,), I32)]),
    'flash_fwd_pairs_window512_group4_t1024': (
        _flash_pairs_window, [((1, 1024, 40, 128), BF16)]
        + [((1, 1024, 10, 128), BF16)] * 2),
    'paged_kv_append_96rows_10pairs': (
        _append, _FLASH_KV(7681) + [((96, 10, 128), BF16)] * 2
        + [((96,), I32)] * 2),
    'flash_fwd_causal_192_128_t6144': (
        _flash, [((1, 6144, 32, 192), BF16)] * 2
        + [((1, 6144, 32, 128), BF16)]),
    'decode_paged_latent_group32_page64': (
        _decode_latent, [((48, 32, 640), BF16), _LATENT,
                         ((48, 120), I32), ((48,), I32)]),
    'paged_kv_append_latent_48rows': (
        _append_latent, [_LATENT, ((48, 1, 640), BF16), ((48,), I32),
                         ((48,), I32)]),
    'grouped_swiglu_decode_192rows_3584x1024': (
        ops.grouped_swiglu, [((192, 3584), BF16)] + _EXPERTS_3584),
    'grouped_swiglu_prefill_24576rows_3584x1024': (
        ops.grouped_swiglu, [((24576, 3584), BF16)] + _EXPERTS_3584),
    'mhc_coefficients_48rows': (_mhc, [((48, 4 * 3584), BF16)] + _MHC),
    'mhc_coefficients_6144rows': (
        _mhc, [((6144, 4 * 3584), BF16)] + _MHC),
    'decode_paged_full_group1_30heads_page64': (
        _decode_group1, [_Q48] + [((3073, 30, 64, 128), BF16)] * 2
        + [((48, 64), I32), ((48,), I32)]),
    # ... and at the page size of the ``olmo-hybrid-7b`` cell: two
    # pages a grid step by the rule
    'decode_paged_full_group1_30heads_page32': (
        _decode_group1, [_Q48] + [((6145, 30, 32, 128), BF16)] * 2
        + [((48, 128), I32), ((48,), I32)]),
    'paged_kv_append_48rows_30heads': (
        _append, [((6145, 30, 32, 128), BF16)] * 2
        + [((48, 30, 128), BF16)] * 2 + [((48,), I32)] * 2),
    'gated_delta_step_48rows': (_delta_step, _STATE),
    'causal_conv_step_48rows': (
        _conv_step, [((49, 288, 128), BF16), ((48,), I32),
                     ((48, 11520), BF16), ((4, 11520), BF16)]),
    'decode_paged_gpt2m_cell': (
        _decode_packed, [_Q32] + _packed() + _TABLE32),
    'paged_kv_append_gpt2m_cell': (
        _append, _packed() + [((32, 8, 128), BF16)] * 2
        + [((32,), I32)] * 2),
    'decode_paged_gpt2m_cell_int8': (
        _decode_paged, [_Q32] + _page_major(I8)
        + [((2049, 16, 16), F32)] * 2),
    'decode_paged_ring_group8_page64': (
        _decode_ring, [_Q64] + _head_major(2113)
        + [((64, 33), I32), ((64,), I32)]),
    'decode_paged_full_group8_page64': (
        _decode_grouped, [_Q64] + _head_major(4097)
        + [((64, 64), I32), ((64,), I32)]),
    'flash_fwd_window2048_group8_t3072': (
        _flash_window, [((1, 3072, 32, 128), BF16)]
        + [((1, 3072, 4, 128), BF16)] * 2),
    'paged_kv_append_64rows': (
        _append, _head_major(2113) + [((64, 4, 128), BF16)] * 2
        + [((64,), I32)] * 2),
    'grouped_swiglu_decode_512rows': (
        ops.grouped_swiglu, [((512, 2048), BF16)] + _EXPERTS),
    'grouped_swiglu_prefill_8192rows': (
        ops.grouped_swiglu, [((8192, 2048), BF16)] + _EXPERTS),
    'flash_fwd_causal_t1024': (_flash, _QKV),
    'flash_fwd_bwd_causal_t1024': (
        jax.grad(_sum_sq(_flash), argnums=(0, 1, 2)), _QKV),
    'flash_fwd_bwd_causal_t1024_train_cell': (
        jax.grad(_sum_sq(_flash), argnums=(0, 1, 2)), _QKV_CELL),
    'flash_fwd_causal_prefill_bucket16': (
        _flash, [((1, 16, 16, 64), BF16)] * 3),
    # the largest step the tile rule emits: float32, d 128, 1,024 x 1,024
    'flash_fwd_bwd_causal_f32_d128_t2048': (
        jax.grad(_sum_sq(_flash), argnums=(0, 1, 2)),
        [((1, 2048, 2, 128), F32)] * 3),
    'flash_fwd_bwd_causal_t1000_padded': (
        jax.grad(_sum_sq(_flash), argnums=(0, 1, 2)),
        [((2, 1000, 4, 64), BF16)] * 3),
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
    'layer_norm_fwd_bwd_train_cell': (
        jax.grad(_sum_sq(ops.layer_norm), argnums=(0, 1, 2)),
        _layer_norm(BF16, 8, 1024, 1024)),
    # float32 rows: the widest tile the rule emits; 1,031 rows leave a
    # ragged last tile
    'layer_norm_fwd_bwd_f32_ragged': (
        jax.grad(_sum_sq(ops.layer_norm), argnums=(0, 1, 2)),
        _layer_norm(F32, 1031, 1024)),
    'layer_norm_fwd_decode_96rows_d2560': (
        ops.layer_norm, _layer_norm(BF16, 96, 2560)),
    'layer_norm_fwd_decode_32rows_d1024': (
        ops.layer_norm, _layer_norm(BF16, 32, 1024)),
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


#: the tail leaf is donated, as every executable of the engine donates
#: its cache: ``causal_conv_step`` says that its aliased output lies in
#: HBM, and this compiler aborts where the operand is its own copy of a
#: parameter that was not given up
DONATED = {'causal_conv_step_48rows': (0,),
           'causal_conv_step_96rows_bias': (0,)}


@pytest.mark.parametrize('case', sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, mosaic):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=DONATED.get(case, ())).lower(
        *args).compile()
    assert 'tpu_custom_call' in compiled.as_text(), (
        '%s compiled without its Mosaic kernel' % case)


@pytest.mark.parametrize('case', ['layer_norm_fwd_bwd_train_cell',
                                  'layer_norm_fwd_bwd_f32_ragged'])
def test_layer_norm_gradient_holds_the_forward_kernel_alone(
        case, one_chip, mosaic):
    """The compiled gradient holds one Pallas call, ``layer_norm_fwd``
    (the backward is XLA's on every platform), and where the rows end
    in a ragged tile no pad of them beside it."""
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.split('\n')
             if 'custom-call(' in line and 'tpu_custom_call' in line]
    assert len(calls) == 1 and 'layer_norm_fwd' in calls[0]
    assert ' pad(' not in text


@pytest.mark.parametrize('case', [
    'decode_paged_gpt2m_cell', 'decode_paged_gpt2m_cell_int8',
    'decode_paged_full_group8_page64', 'decode_paged_ring_group8_page64'])
def test_paged_decode_carries_several_pages_inside_its_vmem(
        case, one_chip, mosaic):
    """At both serving cells' exact shapes the rule gives a grid step
    several pages (16 of the ``gpt2m`` cell's lane-dense 32 KB pages, 8
    of an int8 pool's), and the VMEM Mosaic allocates for the kernel
    (``used_scoped_memory_configs`` of the compiled custom call) holds
    the two slots of that many pages inside the limit the call
    states."""
    import re
    fa = KERNEL_MODULES[0]
    fn, shapes = CASES[case]
    int8 = len(shapes) == 7
    pool, dtype = shapes[1]
    n_max = shapes[3][0][1]
    head_major = not int8
    pages = fa._paged_pages_per_step(pool[1:], dtype, n_max, int8,
                                     head_major)
    assert pages > 1
    if case == 'decode_paged_gpt2m_cell':
        assert pages == 16
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    call, = [line for line in
             jax.jit(fn).lower(*args).compile().as_text().split('\n')
             if 'custom-call(' in line
             and 'flash_attention_decode_paged' in line]
    limit, used = (
        int(re.search(r'"%s":\[\{[^\]]*"size":"(\d+)"' % key,
                      call).group(1))
        for key in ('scoped_memory_configs', 'used_scoped_memory_configs'))
    fetched, held = fa._paged_step_vmem(pages, pool[1:], dtype, int8,
                                        head_major)
    assert limit == fa._VMEM_LIMIT
    assert 2 * fetched <= used <= limit
    assert used <= held, 'the rule counts less than Mosaic allocates'


@pytest.mark.parametrize('body,int8_kv', [
    ('decode', False), ('prefill', False), ('prefill', True)])
def test_serving_executable_leaves_the_page_pool_in_place(
        body, int8_kv, one_chip, mosaic):
    """The serving executables at the widths of the benchmark's cell
    (gpt2-medium, 2,049 pages of 16, 32 rows; two layers of its 24),
    jitted as ``GenerationEngine._compile`` jits them and compiled for
    the described chip: besides the in-place write nothing makes a
    value of a pool leaf's shape, and the scratch is under one leaf.
    Both layouts: the float pool head-major and lane-dense (the
    cell's), an int8 pool page-major with its scale leaves, whose
    prefill passes too; its DECODE executable does not (ten
    ``slice-start`` of ``s8[2049,16,16,128]``: no cell serves int8,
    ROADMAP M1 has it).
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
        jax.eval_shape(lambda: M.init_paged_kv_cache(
            model, 2049, 16, int8_kv=int8_kv)))

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
    compiled = jax.jit(
        fn, donate_argnums=(1,),
        compiler_options=model.serve_compiler_options('tpu')).lower(
        params, cache, *operands).compile()
    # the family's options: a weight is prefetched into VMEM whole, not
    # in four slices (left to itself the compiler makes 32
    # ``slice-start`` / ``slice-done`` pairs in these two layers' decode
    # executable and joins them with ``ConcatBitcast`` calls)
    if not int8_kv:
        assert ' slice-start(' not in compiled.as_text()
        assert 'ConcatBitcast' not in compiled.as_text()
    leaves = cache['k'] + cache['v']
    page = (16, 16, 128) if int8_kv else (8, 16, 128)
    assert {leaf.shape for leaf in leaves} == {(2049,) + page}
    assert chip_smoke.pool_shaped(compiled.as_text(), leaves) == []
    leaf_bytes = 2049 * 16 * 16 * 128
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes
    if body == 'decode':
        assert 'tpu_custom_call' in compiled.as_text()


#: what the compile below checks of a family beside the common lines:
#: ``depth``, the cell's model cut for this compile (the widths stay);
#: ``leaves``, every shape a cache leaf has at the cell's engine sizes;
#: ``exact``: the cache is held at EXACTLY its nominal bytes (else
#: within 1%); ``scratch``, the leaf the executable's scratch stays
#: under; ``calls``, the fewest Pallas calls in (decode, prefill);
#: ``kernels``, names that must be in the text
SERVED = {
    # one window and one full expert layer of trinity-mini's five.  With
    # an XLA scatter as the decode write this compile holds two ``copy``
    # of every leaf (PERF.md, PR 27).  Attention, the append (decode)
    # and the expert kernel are all in
    'afmoe': dict(
        depth=dict(num_hidden_layers=2, num_dense_layers=0,
                   layer_types=('sliding_attention', 'full_attention')),
        leaves={(4097, 4, 64, 128), (2113, 4, 64, 128)}, exact=True,
        scratch=(2113, 4, 64, 128), calls=(3, 3)),
    # one period of olmo-hybrid-7b's two: three linear layers, which own
    # no page, and a full one.  The state's minor dim is 384 lanes, two
    # heads side by side (a ``(.., 96, 192)`` leaf would hold a third
    # more); the 3.6 MB tails too stay in place: ``causal_conv_step``
    # states that its aliased output lies in HBM, or the compiler stages
    # each leaf in VMEM whole around the kernel.  Decode: attention +
    # the append, and two steps a linear layer
    'olmo_hybrid': dict(
        depth=dict(num_hidden_layers=4, layer_types=None),
        leaves={(6145, 30, 32, 128), (49, 15, 96, 384), (49, 288, 128)},
        counts=(1, 3, 3), scratch=(6145, 30, 32, 128), calls=(8, 0)),
    # the dense layer and one of xing4-29b-a4b's five expert layers: a
    # 640-lane row is five whole tiles, 1,280 B a position a layer;
    # four solves of the residual path, each ONE kernel
    'xing4': dict(
        depth=dict(num_hidden_layers=2),
        leaves={(5761, 1, 64, 640)}, exact=True,
        scratch=(5761, 1, 64, 640), calls=(7, 7),
        kernels=('mhc_coefficients', 'grouped_swiglu')),
    # phi4-mini-flash WHOLE: ONE full K/V leaf pair, 8 rings, 9 state
    # and tail leaves; the 14 layers of the cross-decoder own no leaf.
    # Decode: 16 attentions, 9 appends, 9 convolution steps, 9 scan
    # steps; prefill: 8 window attentions and 9 scans (the
    # cross-decoder's one query row is plain XLA).  Weights and cache
    # together leave the chip room: 12.8 of 16 GB
    'phi4flash': dict(
        depth={},
        leaves={(7681, 10, 64, 128), (865, 10, 64, 128),
                (97, 1, 16, 5120), (97, 144, 128)},
        counts=(9, 9, 9), scratch=(7681, 10, 64, 128), calls=(43, 17),
        nominal=2 * (7681 + 8 * 865) * 10 * 64 * 128 * 2
        + 9 * 97 * (16 * 5120 * 4 + 144 * 128 * 2),
        arguments=12.9e9),
    # solar-open2-250b's share as the cell holds it, one period: the
    # gqa layer's K/V pools (8 heads of 128: the two minor dims a whole
    # tile), three kda layers' state leaves, a head a 128-lane tile
    # (4,194,304 B a row), and the 24,576 channels' tails; 6.62 GB of
    # weights beside 4.47 GB of cache.  Decode: an expert kernel a
    # layer, attention + the append, two steps a kda layer (the state's
    # with the decay a column over dk); prefill: the expert kernels and
    # the flash forward at group 8, the per-channel rule is XLA's
    'solar_open2': dict(
        depth={},
        leaves={(13825, 8, 64, 128), (65, 64, 128, 128), (65, 576, 128)},
        counts=(1, 3, 3), scratch=(13825, 8, 64, 128), calls=(12, 5),
        nominal=2 * 13825 * 8 * 64 * 128 * 2
        + 3 * 65 * (64 * 128 * 128 * 4 + 576 * 128 * 2),
        arguments=11.1e9, kernels=('grouped_swiglu',)),
}


@pytest.mark.parametrize('body', ['decode', 'prefill'])
@pytest.mark.parametrize('family', sorted(SERVED))
def test_family_serving_executable_leaves_its_cache_in_place(
        family, body, one_chip, mosaic):
    """A paged-only family's serving executables at the widths and the
    engine sizes of its benchmark cell (``chip_smoke.FAMILIES``: rows,
    pages, ring pages and state rows as the engine would size them),
    compiled for the described chip: nothing makes a value of a cache
    leaf's shape besides the write (``paged_kv_append`` and the state
    and tail steps in decode, the page scatter and the row update in
    prefill), the cache is held at its nominal bytes, the scratch is
    under one leaf and the family's kernels are in (``SERVED`` has what
    each family adds)."""
    import os
    import sys

    from chainermn_tpu import models as M
    from chainermn_tpu.serving.generate import GenerationEngine
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    row, case = chip_smoke.FAMILIES[family], SERVED[family]
    sizes = row['engine']
    rows, page = sizes['n_slots'], sizes['page_size']
    model = getattr(M, row['cls'])(**dict(row['cell'], **case['depth']))
    per_seq = -(-sizes['max_len'] // page)
    ring = model.window_ring(page)
    extra = {}
    if ring:
        extra['n_window_pages'] = 1 + rows * ring
    if model.has_state_row():
        extra['n_state_rows'] = 1 + rows
    width = per_seq + ring + model.has_state_row()

    def structs(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    params = structs(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), BF16)))
    cache = structs(jax.eval_shape(lambda: model.init_paged_kv_cache(
        1 + rows * per_seq, page, **extra)))
    if 'counts' in case:
        assert tuple(len(cache[name]) for name in (
            'k', 'state', 'tail')) == case['counts']

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    def decode(p, c, tokens, positions, tables):
        logits, c, counters = model.decode_step_paged(
            p, c, tokens, positions, tables)
        return GenerationEngine._sampled(logits, counters), c

    def prefill(p, c, tokens, length, pos0, table):
        logits, c, counters = model.prefill_paged(
            p, c, tokens, length, table, pos0)
        return GenerationEngine._sampled(logits, counters), c

    fn, operands = {
        'decode': (decode, (ints(rows), ints(rows), ints(rows, width))),
        'prefill': (prefill, (ints(1, sizes['prompt_bucket']), ints(),
                              ints(), ints(width))),
    }[body]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *operands).compile()
    leaves = jax.tree_util.tree_leaves(cache)
    assert {leaf.shape for leaf in leaves} == case['leaves']
    text = compiled.as_text()
    assert chip_smoke.pool_shaped(text, leaves) == []
    memory = compiled.memory_analysis()
    nominal = sum(leaf.dtype.itemsize * leaf.size for leaf in leaves)
    assert nominal == case.get('nominal', nominal)
    assert nominal <= memory.alias_size_in_bytes <= (
        nominal if case.get('exact') else 1.01 * nominal)
    assert memory.argument_size_in_bytes < case.get('arguments',
                                                    float('inf'))
    scratch = next(leaf for leaf in leaves
                   if leaf.shape == case['scratch'])
    assert memory.temp_size_in_bytes \
        < scratch.dtype.itemsize * scratch.size
    if 'kernels' in case:
        for kernel in case['kernels'] + (
                'flash_attention_decode_paged' if body == 'decode'
                else 'flash_attention_fwd',):
            assert kernel in text, kernel
    assert text.count('custom_call_target="tpu_custom_call"') \
        >= case['calls'][body == 'prefill']


@pytest.fixture
def four_chips(one_chip):
    """The four devices of the described ``v5e:2x2`` (after
    ``one_chip``: its skip and its cache handling hold here too)."""
    from jax.experimental import topologies
    return list(topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2').devices)


def test_dp4_step_reduces_leaf_by_leaf_outside_the_conditional(four_chips):
    """ISSUE 38, in the program the CHIP's compiler makes of a
    data-parallel step over four chips (the multi-node optimizer +
    `xla`, compiled as ``StandardUpdater`` compiles it): the gradient
    all-reduces sit in the entry computation and not in a branch of the
    optimizer's `conditional` (conditional code motion did not sink
    them), each large one has one operand in the weight's own shape,
    some of them are the compiler's asynchronous fused pairs, and
    nothing holds the whole tree in one 1-D buffer."""
    import re

    import chainermn_tpu
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    comm = chainermn_tpu.create_communicator('xla', devices=four_chips)
    assert comm.size == 4
    options = comm.step_compiler_options()
    assert options, 'no overlap options for a four-chip TPU mesh'
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-3), comm)
    n_layers, width = 6, 1024

    def loss(p, x):
        h = x
        for k in range(n_layers):
            h = jnp.tanh(h @ p['w%d' % k].astype(BF16) + p['b%d' % k])
        return jnp.mean(h.astype(F32) ** 2)

    def step(params, opt_state, x):
        grads = jax.grad(loss)(params, x)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    repl = NamedSharding(comm.mesh, P())

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=repl), tree)

    params = {}
    for k in range(n_layers):
        params['w%d' % k] = jax.ShapeDtypeStruct((width, width), F32)
        params['b%d' % k] = jax.ShapeDtypeStruct((width,), BF16)
    x = jax.ShapeDtypeStruct(
        (4 * 512, width), BF16,
        sharding=NamedSharding(comm.mesh, comm.batch_spec()))
    txt = jax.jit(
        jax.shard_map(step, mesh=comm.mesh,
                      in_specs=(P(), P(), comm.batch_spec()),
                      out_specs=(P(), P()), check_vma=False),
        donate_argnums=(0, 1), compiler_options=options,
    ).lower(sds(params), sds(jax.eval_shape(opt.init, params)),
            x).compile().as_text()

    # computations by name; the ENTRY one and the conditional's branches
    comps, entry, cur = {}, None, None
    for line in txt.split('\n'):
        m = re.match(r'^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$', line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith('}'):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    cond, = [line for line in comps[entry] if ' conditional(' in line]
    # branch 0 is the FALSE function of `lax.cond`: the later calls
    later = re.search(r'branch_computations=\{%?([\w.\-]+),',
                      cond).group(1)
    stepping = comps[later]
    assert any('divide' in line or 'sqrt' in line for line in stepping), (
        'Adam is not in the branch taken for it')
    assert not any('all-reduce' in line for line in stepping), (
        'an all-reduce sits in the branch that steps the optimizer')
    # in the entry computation: one collective a weight, in the
    # weight's own shape, synchronous or as the compiler's fused
    # asynchronous pair, and one packed bucket of the biases
    sync = [line for line in comps[entry]
            if re.search(r'= \S+ all-reduce\(', line)]
    started = [line for line in comps[entry]
               if re.search(r'%async-collective-start[.\d]* = ', line)]
    assert len(started) >= 1
    assert len(sync) + len(started) == n_layers + 1
    weights = [line for line in sync + started if re.search(
        r'= \(?(bf16\[\d+,\d+\]\S*, )?f32\[%d,%d\]' % (width, width), line)]
    bucket = [line for line in sync + started if re.search(
        r'= \(?bf16\[%d\]' % (n_layers * width), line)]
    assert (len(weights), len(bucket)) == (n_layers, 1)
    total = n_layers * (width * width + width)
    assert not re.search(r'f32\[%d\]' % total, txt)
    assert not re.search(r'f32\[%d\]' % (n_layers * width * width), txt)
