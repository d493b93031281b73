"""The layer parts families share: an RMSNorm and a SwiGLU as functions
of their arguments, and the dropless sparse feed-forward of three
families: a sigmoid router whose stored bias steers WHICH ``k`` experts
a token takes and never their weight (``afmoe``'s ``expert_bias``,
DeepSeek-V3's ``noaux_tc`` with ``e_score_correction_bias``), gates the
chosen scores normalised and scaled, beside a shared expert every token takes.

A layer may be told which experts it HOLDS (expert parallelism's share:
``docs/mesh_parallelism.md``): it routes over all of them all the same
and computes its own experts' part of the result.  The exchange that
would bring the other chips' tokens here and send these results back
is not built: on one chip there is none."""

import jax
import jax.numpy as jnp
from jax import lax


def rms(x, weight, eps, dtype):
    """RMSNorm over the last dim in float32, out in ``dtype``."""
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                        + eps)
    return (xf * weight.astype(jnp.float32)).astype(dtype)


def swiglu(x, p, dtype):
    gate = jnp.dot(x, p['w1'].astype(dtype))
    return jnp.dot(jax.nn.silu(gate) * jnp.dot(x, p['w3'].astype(dtype)),
                   p['w2'].astype(dtype))


def sigmoid_routed_experts(m, lp, k, route_norm, route_scale, dtype,
                           first=0):
    """The sparse feed-forward on rows ``m`` (T, d) with the layer's
    ``router`` (d, E), ``expert_bias`` (E,), ``experts`` and ``shared``:
    returns it and the layer's three counters (held experts with a row;
    the fullest held expert's rows over the held mean; the assignments
    on held experts).

    ``lp['experts']`` holds the experts ``first .. first + held - 1``
    of the router's E: all of them, or a share.  The scores, the bias,
    the top-k and the gates' normalisation are over ALL E; the routed
    part is the held experts' (what absent ones would add is left
    out), the shared expert is whole.  Which body of
    ``ops.dropless_experts`` runs is read off the shapes: a layer that
    holds every expert pays for no mask."""
    from chainermn_tpu import ops

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
    whole = experts['w1'].shape[0] == lp['router'].shape[1]
    routed, sizes = ops.dropless_experts(
        m, experts, chosen, gate, first=None if whole else first)
    out = routed + swiglu(m, lp['shared'], dtype).astype(jnp.float32)
    held = jnp.sum(sizes).astype(jnp.float32)
    touched = jnp.sum(sizes > 0).astype(jnp.float32)
    fullest = jnp.max(sizes).astype(jnp.float32)
    return out.astype(dtype), (
        touched, fullest * sizes.shape[0] / jnp.maximum(held, 1.0), held)
