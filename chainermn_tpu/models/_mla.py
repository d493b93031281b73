"""Latent attention (DeepSeek-V2/V3's MLA) as the families that have it
share it: ``xing4`` (under its four-stream residual path, a query
latent, YaRN) and ``deepseek_v3`` (no query latent, plain rotary,
``rope_interleave``).  Keys and values come from ONE latent ``c`` of
``kv_lora_rank`` values a position (normed) beside ``qk_rope_head_dim``
rotary values ``k_r`` that every head shares.  What is here is the
EXPANDED form (every head's keys and values made from ``c``, then
``ops.flash_attention`` at 192 / 128): prefill's, the full forward's
and training's.  The absorbed form is decode's and lives with the
family that serves (``models/xing4.py``).

Plain functions of arrays and sizes: a family keeps its own parameter
names, norm and query path.  :func:`rope` is the one rotate-half rotary
of the package: ``afmoe``'s window layers turn their heads with it
too."""

import math

import jax.numpy as jnp


def inv_freq(dim, theta, scaling=None):
    """The ``dim / 2`` rotary frequencies ``theta^(-2i/dim)``; with a
    YaRN ``scaling`` dict, DeepSeek-V3's blend of the trained and the
    interpolated ones."""
    base = float(theta)
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = base ** -exponent
    scaling = dict(scaling or ())
    if not scaling:
        return extra
    orig = scaling['original_max_position_embeddings']

    def correction(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(scaling['beta_fast'])), 0)
    high = min(math.ceil(correction(scaling['beta_slow'])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return extra / scaling['factor'] * ramp + extra * (1.0 - ramp)


def rope(x, positions, freq, interleave=False):
    """Rotary positions over ``x``'s last dim; ``x`` (..., D) with
    ``positions`` broadcasting against its leading dims.  Rotate-half
    pairing: dim ``i`` turns with ``i + D/2`` by ``positions *
    freq[i]``.  ``interleave`` (HF's ``rope_interleave``): the pair
    turned by ``freq[i]`` is the ADJACENT dims ``(2i, 2i + 1)``; the
    dims are first put even-then-odd and then turned as halves, so the
    result is in that order: queries and keys alike, and every dot
    product of a query with a key is the adjacent-pair rotation's."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    if interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], -1).astype(x.dtype)


def split_kvb(wkv_b, rank, heads, nope):
    """``W_kvb`` as ``(W_k (C, H, nope), W_v (C, H, v))``."""
    w = wkv_b.reshape(rank, heads, -1)
    return w[..., :nope], w[..., nope:]


def expanded_attention(q_nope, q_rope, c, k_r, w_k, w_v, scale):
    """Causal attention over the rows themselves, every head's keys
    ``[c W_k | k_r]`` and values ``c W_v`` made from the latent.
    ``q_nope`` (..., T, H, nope), ``q_rope`` (..., T, H, rope), ``c``
    (..., T, C), ``k_r`` (..., T, rope), with or without a leading
    batch dim: (..., T, H * v)."""
    from chainermn_tpu import ops
    h = q_nope.shape[-2]
    k = jnp.concatenate([
        jnp.einsum('...tc,chn->...thn', c, w_k),
        jnp.broadcast_to(k_r[..., None, :],
                         k_r.shape[:-1] + (h, k_r.shape[-1]))], -1)
    v = jnp.einsum('...tc,chv->...thv', c, w_v)
    q = jnp.concatenate([q_nope, q_rope], -1)
    batched = q.ndim == 4
    if not batched:
        q, k, v = q[None], k[None], v[None]
    out = ops.flash_attention(q, k, v, causal=True, scale=scale)
    out = out.reshape(out.shape[:2] + (-1,))
    return out if batched else out[0]
