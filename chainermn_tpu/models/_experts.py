"""The dropless sparse feed-forward two families share: a sigmoid
router whose stored bias steers WHICH ``k`` experts a token takes and
never their weight (``afmoe``'s ``expert_bias``, DeepSeek-V3's
``noaux_tc`` with ``e_score_correction_bias``), gates the chosen scores
normalised and scaled, beside a shared expert every token takes."""

import jax
import jax.numpy as jnp
from jax import lax


def swiglu(x, p, dtype):
    gate = jnp.dot(x, p['w1'].astype(dtype))
    return jnp.dot(jax.nn.silu(gate) * jnp.dot(x, p['w3'].astype(dtype)),
                   p['w2'].astype(dtype))


def sigmoid_routed_experts(m, lp, k, route_norm, route_scale, dtype):
    """The sparse feed-forward on rows ``m`` (T, d) with the layer's
    ``router`` (d, E), ``expert_bias`` (E,), ``experts`` and ``shared``:
    returns it and the layer's two counters (experts with a row; the
    fullest expert's rows over the mean)."""
    from chainermn_tpu import ops

    e = lp['router'].shape[1]
    score = jax.nn.sigmoid(jnp.dot(
        m.astype(jnp.float32), lp['router'].astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    # the stored bias steers WHICH experts, never their weight
    _, chosen = lax.top_k(
        score + lp['expert_bias'].astype(jnp.float32), k)
    gate = jnp.take_along_axis(score, chosen, axis=1)
    if route_norm:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    gate = gate * route_scale
    experts = {name: w.astype(dtype)
               for name, w in lp['experts'].items()}
    routed, sizes = ops.dropless_experts(m, experts, chosen, gate)
    out = routed + swiglu(m, lp['shared'], dtype).astype(jnp.float32)
    counters = (jnp.sum(sizes > 0).astype(jnp.float32),
                jnp.max(sizes).astype(jnp.float32)
                * (e / (m.shape[0] * k)))
    return out.astype(dtype), counters
