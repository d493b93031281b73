"""``chip_smoke.py`` rehearsed on the CPU.

The script itself needs a TPU and must FAIL without one (the driver
checks that first); its phase functions take their sizes as arguments,
so the same code runs here at tiny sizes with the Pallas kernels in
interpret mode and four of conftest's virtual devices as the
"four-chip host".  What only the chip can show -- Mosaic lowering,
full widths, real device placement -- is the script's own run there.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_LM = dict(d_model=16, n_heads=2, n_layers=1, d_ff=32, vocab=32)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    monkeypatch.delenv('CHAINERMN_TPU_PALLAS', raising=False)


def test_script_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    p = subprocess.run([sys.executable, 'chip_smoke.py'], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert 'FAILED in phase device' in p.stdout
    assert 'no tpu' in p.stdout


def test_script_refuses_kernels_switched_off():
    env = dict(os.environ, CHAINERMN_TPU_PALLAS='0')
    p = subprocess.run([sys.executable, 'chip_smoke.py'], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert 'CHAINERMN_TPU_PALLAS=0' in p.stdout


def test_summary_line_is_the_contract():
    dev = jax.devices()[0]
    line = chip_smoke.summary_line({'platform': dev.platform,
                                    'kind': dev.device_kind,
                                    'count': len(jax.devices())})
    assert '\n' not in line
    assert json.loads(line) == {'ok': True, 'device': {
        'platform': 'cpu', 'kind': dev.device_kind, 'count': 8}}


def test_check_device_knows_the_cpu_is_no_chip(interpret):
    with pytest.raises(chip_smoke.SmokeFailure, match='no tpu'):
        chip_smoke.check_device(1)
    # the right platform, but a device in no peaks table: an error
    with pytest.raises(KeyError, match='no spec table'):
        chip_smoke.check_device(1, platform='cpu', kernels='interpret')


def test_train_resnet_phase_tiny():
    from chainermn_tpu.models import ResNet
    out = chip_smoke.train_resnet(
        model=ResNet(stage_sizes=[1], num_classes=10, width=8),
        batch=8, insize=16, n_classes=10)
    assert len(out['losses']) == 3


def test_train_transformer_phase_tiny(interpret):
    out = chip_smoke.train_transformer(seq=16, batch=8,
                                       kernels='interpret', **TINY_LM)
    assert len(out['losses']) == 3
    assert out['check']['numerics_vs_oracle_ok']


def test_serve_phase_tiny(interpret):
    out = chip_smoke.serve(max_len=16, n_slots=2, max_prompt=4,
                           max_new=3, n_requests=2, page_sizes=(16,),
                           kernels='interpret', **TINY_LM)
    streams = out['streams']
    assert sorted(streams) == ['int8_paged16', 'int8_slab', 'paged16',
                               'slab']
    # page 16 == the slab key block here.  The int8 pool keeps the
    # kernel branch that shares the slab's arithmetic: the phase
    # REQUIRES equal tokens of it.  The float pool's branch rounds
    # ``p`` to bfloat16 and may part from the slab at a near-tie (the
    # phase requires that it be one); two requests of three tokens
    # hold none
    assert streams['int8_paged16'] == streams['int8_slab']
    assert streams['paged16'] == streams['slab']
    assert all(len(s) == 3 for s in streams['slab'])
    assert max(max(e) for e in out['errors'].values()) < 5e-2


def test_serve_streams_may_part_only_at_a_near_tie(monkeypatch):
    """The float paged stream against the slab's: nothing is asked of
    equal streams; a stream that leaves the slab for the float32
    forward's runner-up passes where the two logits lie within the
    bound and fails where they do not; one that leaves it for the
    worst token fails; what follows the first parting is not read."""
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import TransformerLM
    lm = dict(TINY_LM)
    model = TransformerLM(
        vocab_size=lm['vocab'], d_model=lm['d_model'],
        n_heads=lm['n_heads'], n_layers=lm['n_layers'], d_ff=lm['d_ff'],
        max_len=16)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))['params']
    prompts = [np.array([3, 1, 4], np.int32), np.array([1, 5], np.int32)]
    first, _ = chip_smoke._float32_logits(model, params, prompts, None)
    order = np.argsort(first, axis=-1)
    best, second, worst = order[:, -1], order[:, -2], order[:, 0]
    slab = [[int(best[0]), 7, 7], [int(best[1]), 2, 2]]
    check = chip_smoke._require_parting_at_ties
    check(model, params, prompts, slab, [list(s) for s in slab], 'same')
    # request 1 leaves at its FIRST token for the worst one
    got = [list(slab[0]), [int(worst[1]), 2, 2]]
    with pytest.raises(chip_smoke.SmokeFailure, match='token 0 of '
                       'request 1'):
        check(model, params, prompts, slab, got, 'worst')
    # the runner-up: a tie or not by the bound
    gap = float(first[0, best[0]] - first[0, second[0]])
    scale = abs(float(first[0].max())) + 1.0
    got = [[int(second[0]), int(worst[0]), 0], list(slab[1])]
    monkeypatch.setattr(chip_smoke, 'BF16_LOGITS_BOUND', 2 * gap / scale)
    check(model, params, prompts, slab, got, 'runner-up, a tie')
    monkeypatch.setattr(chip_smoke, 'BF16_LOGITS_BOUND', gap / scale / 2)
    with pytest.raises(chip_smoke.SmokeFailure, match='token 0 of '
                       'request 0'):
        check(model, params, prompts, slab, got, 'runner-up, decided')


#: each family's model at toy widths, under its own field names: what
#: ``serve_family`` lays over the table's ``small`` model and
#: ``serving_pool_check_family`` over its ``cell``
TINY = {
    'afmoe': dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, num_experts=8,
        num_experts_per_tok=2, sliding_window=8, page_size=4),
    'olmo_hybrid': dict(
        vocab_size=64, hidden_size=64, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=4,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=32, linear_value_head_dim=64, page_size=4),
    'xing4': dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
        page_size=8),
    'phi4flash': dict(
        vocab_size=64, hidden_size=64, intermediate_size=96,
        num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, sliding_window=8, page_size=4),
    'solar_open2': dict(
        vocab_size=64, hidden_size=64, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_attn_config=dict(head_dim=16, num_heads=4),
        n_routed_experts=4, router_experts=16, num_experts_per_tok=2,
        page_size=4),
}


@pytest.mark.parametrize('family', sorted(TINY))
def test_serve_family_phase_tiny(family, interpret):
    out = chip_smoke.serve_family(
        family, n_slots=3, max_prompt=24, max_len=48, max_new=12,
        n_requests=5, kernels='interpret', **TINY[family])
    # every slot (ring, state row) reused: 5 + 5 + 1 requests on 3
    reused = chip_smoke.FAMILIES[family]['reuse']
    assert len(out['streams']) == (11 if reused else 5)
    assert all(len(s) == 12 for s in out['streams'])
    assert out['gap_mean'] < 0.01


@pytest.mark.parametrize('family', sorted(TINY))
def test_serving_pool_family_phase_tiny(family):
    """As ``test_serving_pool_phase_tiny``: on the CPU the check must
    bite (a bfloat16 scatter goes through pool-shaped ``convert``
    instructions there, the jnp twins of the steps gather and scatter
    the state leaves through pool-shaped values); every kind of leaf
    the family has is named first."""
    depth = {'xing4': dict(num_hidden_layers=2)}.get(family, {})
    with pytest.raises(chip_smoke.SmokeFailure,
                       match='makes pool-shaped values'):
        chip_smoke.serving_pool_check_family(
            family, n_slots=2, max_prompt=8, max_len=32,
            prompt_bucket=8, **dict(TINY[family], **depth))


def test_the_phases_keep_their_names(monkeypatch, capsys):
    ran = []
    monkeypatch.setattr(
        chip_smoke, 'check_device',
        lambda chips: {'platform': 'tpu', 'kind': 'none', 'count': 1})
    monkeypatch.setattr(chip_smoke, 'serve_family',
                        lambda name: ran.append('serve ' + name))
    monkeypatch.setattr(chip_smoke, 'serving_pool_check_family',
                        lambda name: ran.append('pool ' + name))
    monkeypatch.delenv('CHAINERMN_TPU_PALLAS', raising=False)
    assert chip_smoke.main(['--phases', ','.join(
        kind + name for name in TINY
        for kind in ('serve_', 'serving_pool_'))]) == 0
    assert ran == [kind + name for name in chip_smoke.FAMILIES
                   for kind in ('serve ', 'pool ')]
    assert list(chip_smoke.FAMILIES) == [
        'afmoe', 'olmo_hybrid', 'xing4', 'phi4flash', 'solar_open2']


def test_phases_option_names_an_unknown_phase(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, 'check_device', lambda chips: {})
    monkeypatch.delenv('CHAINERMN_TPU_PALLAS', raising=False)
    assert chip_smoke.main(['--phases', 'serve_nothing']) == 1
    assert 'no phase' in capsys.readouterr().out


def test_multichip_phase_on_four_virtual_devices(interpret):
    out = chip_smoke.train_multichip(n_devices=4, seq=16,
                                     global_batch=4, tp=2,
                                     kernels='interpret', **TINY_LM)
    assert sorted(out) == ['dp2xtp2', 'dp4', 'one_device']
    for name in ('dp4', 'dp2xtp2'):
        assert abs(out[name][0] - out['one_device']) < 2e-2


_LEAF = 'bf16[9,8,2,8]{3,2,1,0:T(8,128)(2,1)}'
_CLEAN_HLO = '\n'.join([
    'HloModule jit_serve_decode, is_scheduled=true',
    '%fused_computation.3 (param_0.9: bf16[9,8,2,8], param_1: s32[2], '
    'param_2: bf16[2,2,8]) -> bf16[9,8,2,8] {',
    '  %param_0.9 = ' + _LEAF + ' parameter(0)',
    '  %param_2 = bf16[2,2,8]{2,1,0} parameter(2)',
    '  ROOT %scatter.0 = ' + _LEAF + ' scatter(%param_0.9, %param_1, '
    '%param_2), to_apply=%region_2.9',
    '}',
    'ENTRY %main.15 (p.1: bf16[16], c__k___0_.1: bf16[9,8,2,8]) -> '
    '(s32[2], bf16[9,8,2,8]) {',
    '  %c__k___0_.1 = ' + _LEAF + ' parameter(1)',
    '  %fusion.3 = ' + _LEAF + ' fusion(%c__k___0_.1, %i, %u), '
    'kind=kCustom, calls=%fused_computation.3, '
    'metadata={op_name="jit(dec)/scatter"}',
    '  %attn.2 = bf16[2,2,8]{2,1,0} custom-call(%t, %fusion.3), '
    'custom_call_target="tpu_custom_call"',
    '  ROOT %tuple.10 = (s32[2]{0}, ' + _LEAF + ') tuple(%tok, '
    '%fusion.3)',
    '}', ''])


@pytest.mark.parametrize('extra, found', [
    ('', []),
    # the relayout of a pool the runtime keeps page-minor
    ('  %copy.21 = bf16[9,8,2,8]{3,2,1,0:T(8,128)(2,1)} copy('
     '%c__k___0_.1), sharding={replicated}\n',
     [('copy', 'copy.21')]),
    # one layer cut out of a stacked pool for the custom call
    ('  %slice_bitcast_fusion.7 = bf16[9,8,2,8]{3,2,1,0} fusion('
     '%stacked), kind=kLoop, calls=%fused_computation.9\n',
     [('fusion', 'slice_bitcast_fusion.7')]),
    # an asynchronous copy hides the leaf in a tuple type
    ('  %slice-start.4 = ((bf16[9,8,2,8]{0,3,2,1}), bf16[9,2,2,8]'
     '{0,3,2,1:S(1)}, s32[]) slice-start(%c__k___0_.1)\n',
     [('slice-start', 'slice-start.4')]),
    # another array of the same size is no pool leaf
    ('  %copy.3 = f32[9,8,2,8]{3,2,1,0} copy(%x)\n', []),
])
def test_pool_shaped_finds_what_moves_a_leaf(extra, found):
    import jax.numpy as jnp
    leaf = jax.ShapeDtypeStruct((9, 8, 2, 8), jnp.bfloat16)
    text = _CLEAN_HLO.replace('  %attn.2 =', extra + '  %attn.2 =')
    assert chip_smoke.pool_shaped(text, [leaf]) == found


def test_serving_pool_phase_tiny():
    """The phase end to end at toy sizes.  XLA's CPU backend upcasts
    a bfloat16 scatter through pool-shaped ``convert`` instructions,
    so HERE the check must bite; that the chip's compiler leaves
    nothing of the kind is ``tests/test_chip_compile.py`` (described
    chip) and the script's own run (attached chip)."""
    with pytest.raises(chip_smoke.SmokeFailure,
                       match='makes pool-shaped values'):
        chip_smoke.serving_pool_check(
            max_len=32, n_slots=2, max_prompt=8, page_size=8,
            prompt_bucket=8, **dict(TINY_LM, n_layers=2))


def test_missing_kernel_fails_the_phase():
    with pytest.raises(chip_smoke.SmokeFailure, match='bypassed'):
        chip_smoke.require_kernels('HloModule plain', 'native', 'x')
    assert chip_smoke.require_kernels(
        'custom_call_target="tpu_custom_call"', 'native', 'x') == 1


def test_cache_helper_honours_the_environment(monkeypatch, tmp_path):
    from chainermn_tpu.utils import platform

    calls = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda *a: calls.append(a))
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    assert platform.enable_compilation_cache() == str(tmp_path)
    assert calls == []          # JAX reads the variable itself
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
    monkeypatch.setattr(platform, '_CHECKOUT', str(tmp_path / 'co'))
    monkeypatch.setattr(
        'jax.experimental.compilation_cache.compilation_cache.'
        'reset_cache', lambda: calls.append('reset'))
    want = str(tmp_path / 'co' / '.jax_compile_cache')
    assert platform.enable_compilation_cache() == want
    assert calls == [('jax_compilation_cache_dir', want), 'reset']


def test_cache_helper_default_is_in_the_checkout(monkeypatch):
    from chainermn_tpu.utils import platform
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    assert platform.enable_compilation_cache() == os.path.join(
        REPO, '.jax_compile_cache')
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, '.jax_compile_cache')
