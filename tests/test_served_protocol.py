"""One conformance test for the serving protocol (``models/_served.py``):
every family ``GenerationEngine`` serves gives every member the base
names, callable as the engine calls it, and the engine asks without
probing.
"""

import inspect
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import models, serving
from chainermn_tpu.models._served import ServedLM

PAGE = 8
#: every served family at a tiny shape (its own tests' widths)
TINY = {
    'TransformerLM': dict(vocab_size=32, d_model=32, n_heads=4, n_layers=1,
                          d_ff=32, max_len=64, dtype=jnp.float32),
    'AfmoeLM': dict(
        vocab_size=97, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_experts=8, num_experts_per_tok=2, sliding_window=8,
        layer_types=('sliding_attention',) * 4 + ('full_attention',)),
    'OlmoHybridLM': dict(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=32, linear_value_head_dim=64),
    'Xing4LM': dict(
        vocab_size=97, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2),
    'Phi4FlashLM': dict(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=8),
    'SolarOpen2LM': dict(
        vocab_size=97, hidden_size=64, moe_intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, linear_attn_config=dict(head_dim=16, num_heads=4),
        n_routed_experts=4, router_experts=16, first_expert=4,
        num_experts_per_tok=3),
}
PAGED_ONLY = sorted(set(TINY) - {'TransformerLM'})
N_SLOTS, MAX_PROMPT, MAX_LEN = 2, 8, 16


def _model(name):
    kw = dict(TINY[name])
    if name != 'TransformerLM':
        kw.update(max_position_embeddings=64, dtype=jnp.float32)
    return getattr(models, name)(**kw)


def _params(model):
    if isinstance(model, models.TransformerLM):
        return model.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))['params']
    return model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize('name', sorted(TINY))
def test_every_member_the_base_names_answers_the_engines_call(name):
    """What ``GenerationEngine.__init__``, its traced bodies and its
    tick call on a model, in the engine's own arguments; nothing
    compiles (the bodies are traced for their shapes)."""
    model = _model(name)
    assert isinstance(model, ServedLM)
    assert model.check_serving(
        paged=True, int8_kv=False, prefill_chunk=None,
        prefix_sharing=False, draft_model=False, plan=False) is None
    assert model.max_len == 64 and model.tp_axis is None
    assert model.vocab_size == TINY[name]['vocab_size']
    per_seq = -(-MAX_LEN // PAGE)
    ring, row = model.window_ring(PAGE), model.has_state_row()
    assert isinstance(ring, int) and row in (False, True)
    extra = {}
    if ring:
        extra['n_window_pages'] = 1 + N_SLOTS * ring
    if row:
        extra['n_state_rows'] = 1 + N_SLOTS
    cache = jax.eval_shape(lambda: model.init_paged_kv_cache(
        1 + N_SLOTS * per_seq, PAGE, int8_kv=False, **extra))
    assert jax.tree_util.tree_leaves(cache)
    assert isinstance(model.serve_counters, tuple)
    assert model.page_counter is None or isinstance(model.page_counter,
                                                    str)
    assert len(model.kv_lanes(cache)) in (0, 2)
    assert model.serve_compiler_options('cpu') == {}
    assert isinstance(model.serve_compiler_options('tpu'), dict)
    if row or model.page_counter:
        sizes = model.paged_cache_bytes(cache)
        assert len(sizes) in (2, 3) and sizes[0] > 0
        assert (sizes[1] > 0) == row
    read, steps = model.decode_paged_grid(
        cache, [3, 1], per_seq, ring, tp=1)
    assert 0 < read and 0 < steps
    params = jax.eval_shape(lambda: _params(model))
    width = per_seq + ring + row

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    for got, lead in (
            (jax.eval_shape(model.prefill_paged, params, cache,
                            ints(1, MAX_PROMPT), ints(), ints(width),
                            ints()), ()),
            (jax.eval_shape(model.decode_step_paged, params, cache,
                            ints(N_SLOTS), ints(N_SLOTS),
                            ints(N_SLOTS, width)), (N_SLOTS,))):
        logits, new, counters = got
        assert logits.shape == lead + (model.vocab_size,)
        assert logits.dtype == jnp.float32
        assert jax.tree_util.tree_map(
            lambda x: (x.shape, x.dtype), new) == jax.tree_util.tree_map(
            lambda x: (x.shape, x.dtype), cache)
        assert len(counters) == len(model.serve_counters)
        assert all(c.shape == () and c.dtype == jnp.float32
                   for c in counters)


@pytest.mark.parametrize('name', sorted(TINY))
def test_the_engine_is_built_without_probing_the_model(name):
    source = inspect.getsource(serving.GenerationEngine.__init__)
    assert not re.search(r'(getattr|hasattr)\(\s*(draft_)?model\b', source)
    model = _model(name)
    engine = serving.GenerationEngine(
        model, _params(model), n_slots=N_SLOTS, max_prompt_len=MAX_PROMPT,
        max_len=MAX_LEN, paged=True, page_size=PAGE, prefix_sharing=False)
    # the tick's span attributes are what each family's were: lanes only
    # from a family that gives them, cache bytes only with a state row
    # or a page counter
    lanes = dict(zip(('kv_live_lanes', 'kv_lanes'),
                     model.kv_lanes(engine._cache_struct)))
    assert engine._kv_lanes == lanes
    assert bool(lanes) == (name == 'TransformerLM')
    assert (engine._cache_bytes is not None) == bool(
        model.has_state_row() or model.page_counter)
    assert engine._page_counter == model.page_counter
    assert engine._compiler_options == {}           # not on a TPU here


@pytest.mark.parametrize('name', PAGED_ONLY)
def test_a_paged_only_family_refuses_everything_else_in_one_message(name):
    model = _model(name)
    with pytest.raises(ValueError) as refusal:
        serving.GenerationEngine(
            model, _params(model), n_slots=N_SLOTS,
            max_prompt_len=MAX_PROMPT, max_len=MAX_LEN, paged=False,
            prefix_sharing=True, prefill_chunk=4, int8_kv=True,
            draft_model=model, draft_params={}, plan=object())
    message = str(refusal.value)
    assert 'the %s family' % model.family in message
    # prefix sharing is the paged cache's: the engine asks for it only
    # with paged=True, and then the family refuses that too
    for asked in ('paged=False', 'prefill_chunk', 'int8_kv',
                  'draft_model', 'plan'):
        assert asked in message.split('asked for')[1], asked
    with pytest.raises(ValueError, match='%s.*asked for prefix_sharing$'
                       % model.family):
        model.check_serving(paged=True, prefix_sharing=True)
    for stub in ('init_kv_cache', 'prefill', 'decode_step', 'spec_verify',
                 'spec_verify_paged', 'kv_cache_specs'):
        with pytest.raises(NotImplementedError, match='%s.%s .*%s'
                           % (name, stub, re.escape(model.cache_name))):
            getattr(model, stub)()


def test_a_trained_family_refuses_to_be_served_by_name():
    model = models.DeepseekV3LM(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2, kv_lora_rank=16,
        num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, n_routed_experts=4, num_experts_per_tok=3)
    with pytest.raises(NotImplementedError,
                       match='DeepseekV3LM.check_serving: .*trained, not '
                             'served'):
        serving.GenerationEngine(model, {}, paged=True)
    for member in ('init_paged_kv_cache', 'prefill_paged',
                   'decode_step_paged', 'decode_paged_grid',
                   'paged_cache_bytes'):
        with pytest.raises(NotImplementedError,
                           match=member + ': .*trained, not served'):
            getattr(model, member)()


def test_a_family_that_forgets_a_member_is_told_which():
    class SixthLM(ServedLM):
        family = 'sixth'

    for member in ('init_paged_kv_cache', 'prefill_paged',
                   'decode_step_paged', 'decode_paged_grid',
                   'paged_cache_bytes'):
        with pytest.raises(NotImplementedError,
                           match='SixthLM.%s: not in this family yet'
                           % member):
            getattr(SixthLM(), member)()


def test_the_protocol_is_written_once():
    """``check_serving`` (but ``TransformerLM``'s, which refuses
    nothing), ``from_config`` (but ``deepseek_v3``'s, which extends the
    base's), ``_not_yet`` and the refusing stubs are the base's alone."""
    root = os.path.dirname(inspect.getsourcefile(models))
    defined = {}
    for fn in sorted(os.listdir(root)):
        if fn.endswith('.py'):
            with open(os.path.join(root, fn)) as f:
                for name in re.findall(r'^    def (\w+)\(', f.read(),
                                       re.M):
                    defined.setdefault(name, []).append(fn)
    assert defined['_not_yet'] == ['_served.py']
    assert defined['check_serving'] == ['_served.py', 'transformer.py']
    assert defined['from_config'] == ['_served.py', 'deepseek_v3.py']
    served = ['afmoe.py', 'olmo_hybrid.py', 'phi4flash.py',
              'solar_open2.py', 'xing4.py']
    for stub in ('init_kv_cache', 'prefill', 'decode_step', 'spec_verify',
                 'spec_verify_paged', 'kv_cache_specs'):
        assert defined[stub] == ['_served.py', 'transformer.py'], stub
    for member in ('init_paged_kv_cache', 'prefill_paged',
                   'decode_step_paged', 'decode_paged_grid'):
        assert defined[member] == sorted(
            ['_served.py', 'transformer.py'] + served), member
    assert defined['paged_cache_bytes'] == [
        '_served.py', 'olmo_hybrid.py', 'phi4flash.py', 'solar_open2.py',
        'xing4.py']
    assert np.all([issubclass(getattr(models, name), ServedLM)
                   for name in list(TINY) + ['DeepseekV3LM']])
