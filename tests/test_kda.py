"""The gated delta rule with a decay PER KEY CHANNEL (Kimi Delta
Attention; ``ops/gated_delta.py`` with ``g`` of shape ``(T, H, dk)``)
on the CPU at small sizes, float32: the chunked rule and the one-step
forms against the recurrence as written, and a ``g`` constant over
``dk`` against the scalar rule ``olmo_hybrid`` runs.

Tolerance: float32 throughout.  The chunked rule reorders the
recurrence's sums (a triangular solve a chunk of 64 in place of 64
rank-one updates, a pair's decay factored through a sub-block's first
position), which moves outputs of order 1 by a few 1e-6; 2e-5 holds
that with room.  A factoring that exponentiated a positive difference
would not come out wrong by 1e-5: at the decays of
``test_the_chunked_rule_is_stable_at_strong_decays`` it is ``inf``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu import ops
from chainermn_tpu.ops import gated_delta

ATOL = 2e-5
H, DK, DV = 3, 16, 32


@pytest.fixture(params=['fallback', 'interpret'])
def mode(request, monkeypatch):
    monkeypatch.delenv('CHAINERMN_TPU_PALLAS', raising=False)
    if request.param == 'interpret':
        monkeypatch.setenv('CHAINERMN_TPU_PALLAS_INTERPRET', '1')
    else:
        monkeypatch.delenv('CHAINERMN_TPU_PALLAS_INTERPRET',
                           raising=False)
    return request.param


def _operands(t, seed=0, low=0.3, heads=H, dk=DK, dv=DV):
    """Normalised ``q`` / ``k``, ``v`` of order one, per-channel decays
    uniform over ``(low, 1)``, ``beta`` over (0, 2)."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(t, heads, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(t, heads, dk)))
    v = rng.normal(size=(t, heads, dv))
    g = np.log(rng.uniform(low, 1.0, size=(t, heads, dk)))
    beta = rng.uniform(0.0, 2.0, size=(t, heads))
    return [x.astype(np.float32) for x in (q, k, v, g, beta)]


def _rule(*operands, **kw):
    return jax.jit(lambda *a: ops.gated_delta_rule(*a, **kw))(*operands)


@pytest.mark.parametrize('t', [1, 5, 16, 50, 64, 150, 256])
def test_the_chunked_rule_is_the_recurrence(t):
    """Under a sub-block, a sub-block, off the chunk, a chunk, T not a
    multiple of the chunk, whole chunks."""
    operands = _operands(t, seed=t)
    want_o, want_s = ops.gated_delta_reference(*operands)
    o, s = _rule(*operands)
    np.testing.assert_allclose(o, want_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=ATOL, rtol=0)


def test_the_chunked_rule_is_stable_at_strong_decays():
    """``exp(g)`` 1e-3 a step on a few channels: ``exp(-G)`` passes
    float32's largest after 13 steps, inside a sub-block of 16."""
    q, k, v, g, beta = _operands(150, seed=1)
    g[:, :, :3] = np.log(1e-3)
    g[40:90, 1, 5] = np.log(1e-3)
    want_o, want_s = ops.gated_delta_reference(q, k, v, g, beta)
    o, s = _rule(q, k, v, g, beta)
    assert np.all(np.isfinite(o)) and np.all(np.isfinite(s))
    np.testing.assert_allclose(o, want_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=ATOL, rtol=0)
    # the naive factoring, for the record: not finite
    with np.errstate(over='ignore'):
        assert not np.all(np.isfinite(
            np.exp(-np.cumsum(g[:64].astype(np.float32), axis=0))))


def test_beta_near_two_on_repeated_keys():
    """The triangular system at its worst: a power series in it would
    reach 1e30 before it cancels; with a decay near 1 the chunk's
    updates hardly fade."""
    q, k, v, g, beta = _operands(130, seed=2, low=0.97)
    k[20:60] = k[19]
    beta[20:60] = 1.98
    want_o, want_s = ops.gated_delta_reference(q, k, v, g, beta)
    o, s = _rule(q, k, v, g, beta)
    scale = max(1.0, float(np.abs(want_o).max()))
    np.testing.assert_allclose(o, want_o, atol=ATOL * scale, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=ATOL * scale, rtol=0)


@pytest.mark.parametrize('t, length', [(64, 21), (200, 130), (32, 32)])
def test_positions_past_the_length_change_nothing(t, length):
    operands = _operands(t, seed=3)
    want_o, want_s = ops.gated_delta_reference(
        *(x[:length] for x in operands))
    o, s = _rule(*operands, length=length)
    np.testing.assert_allclose(o[:length], want_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=ATOL, rtol=0)


@pytest.mark.parametrize('cut', [1, 64, 77])
def test_a_prompt_in_two_halves_through_state0_is_the_whole(cut):
    operands = _operands(150, seed=4)
    want_o, want_s = ops.gated_delta_reference(*operands)
    o1, s1 = _rule(*(x[:cut] for x in operands))
    o2, s2 = jax.jit(lambda s, *a: ops.gated_delta_rule(*a, state0=s))(
        s1, *(x[cut:] for x in operands))
    np.testing.assert_allclose(np.concatenate([o1, o2]), want_o,
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(s2, want_s, atol=ATOL, rtol=0)


def test_bfloat16_operands_are_taken_as_stored():
    """``q``, ``k``, ``v`` as the serving path hands them over."""
    q, k, v, g, beta = _operands(100, seed=5)
    low = [jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
    want_o, _ = ops.gated_delta_reference(
        *(np.asarray(x, np.float32) for x in low), g, beta)
    o, _ = _rule(*low, g, beta)
    assert o.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, atol=ATOL, rtol=0)


@pytest.mark.parametrize('heads, dk, dv', [(3, 16, 32), (4, 8, 128),
                                           (2, 16, 64)])
def test_the_step_is_one_step_of_the_recurrence(mode, heads, dk, dv):
    """Rows of the leaf updated where they lie, the others untouched:
    the gather / update / scatter and the interpreted Pallas kernel
    (one head a lane tile, and two heads side by side)."""
    q, k, v, g, beta = _operands(3, seed=6, heads=heads, dk=dk, dv=dv)
    _, start = ops.gated_delta_reference(
        *_operands(20, seed=7, heads=heads, dk=dk, dv=dv))
    leaf = jnp.zeros(ops.state_shape(6, heads, dk, dv), jnp.float32)
    leaf = leaf.at[jnp.asarray([2, 4, 5])].set(ops.pack_state(
        jnp.stack([start, 0.5 * start, jnp.zeros_like(start)])))
    rows = jnp.asarray([4, 2, 5], jnp.int32)
    o, new = jax.jit(ops.gated_delta_step)(leaf, rows, q, k, v, g, beta)
    for i, (row, s0) in enumerate(zip(
            (4, 2, 5), (0.5 * start, start, jnp.zeros_like(start)))):
        want_o, want_s = gated_delta._one_step(
            s0, q[i], k[i], v[i], g[i], beta[i])
        np.testing.assert_allclose(o[i], want_o, atol=1e-6, rtol=0)
        np.testing.assert_allclose(
            ops.unpack_state(new[row], heads), want_s, atol=1e-6, rtol=0)
    for row in (0, 1, 3):
        assert not np.any(np.asarray(new[row]))


def test_the_step_follows_the_chunked_rule(mode):
    """A prompt through the chunked rule, then tokens one at a time
    through the step: the whole through the recurrence."""
    operands = _operands(90, seed=8)
    want_o, want_s = ops.gated_delta_reference(*operands)
    _, state = _rule(*(x[:70] for x in operands))
    leaf = jnp.zeros(ops.state_shape(3, H, DK, DV), jnp.float32).at[
        1].set(ops.pack_state(state))
    step = jax.jit(ops.gated_delta_step)
    for t in range(70, 90):
        o, leaf = step(leaf, jnp.asarray([1], jnp.int32),
                       *(x[t:t + 1] for x in operands))
        np.testing.assert_allclose(o[0], want_o[t], atol=ATOL, rtol=0)
    np.testing.assert_allclose(ops.unpack_state(leaf[1], H), want_s,
                               atol=ATOL, rtol=0)


@pytest.mark.parametrize('t', [40, 100])
def test_a_decay_constant_over_dk_is_olmo_hybrids_rule(t, mode):
    """One scalar a head spread over the key channels: the three forms
    of the per-channel rule against the three of the scalar rule, which
    keeps its own bodies."""
    q, k, v, g, beta = _operands(t, seed=9)
    scalar = g[:, :, 0]
    spread = np.repeat(scalar[:, :, None], DK, axis=2)
    want_o, want_s = ops.gated_delta_reference(q, k, v, scalar, beta)
    o, s = ops.gated_delta_reference(q, k, v, spread, beta)
    np.testing.assert_allclose(o, want_o, atol=1e-6, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=1e-6, rtol=0)
    scalar_o, scalar_s = _rule(q, k, v, scalar, beta)
    o, s = _rule(q, k, v, spread, beta)
    np.testing.assert_allclose(o, scalar_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(s, scalar_s, atol=ATOL, rtol=0)
    leaf = jnp.zeros(ops.state_shape(2, H, DK, DV), jnp.float32).at[
        1].set(ops.pack_state(want_s))
    rows = jnp.asarray([1], jnp.int32)
    last = [x[-1:] for x in (q, k, v)]
    step = jax.jit(ops.gated_delta_step)
    scalar_o, scalar_leaf = step(leaf, rows, *last, scalar[-1:],
                                 beta[-1:])
    o, new = step(leaf, rows, *last, spread[-1:], beta[-1:])
    np.testing.assert_allclose(o, scalar_o, atol=1e-6, rtol=0)
    np.testing.assert_allclose(new, scalar_leaf, atol=1e-6, rtol=0)
