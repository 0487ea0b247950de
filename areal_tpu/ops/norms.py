"""Normalization + rotary embedding numerics.

Matches HF llama/qwen2 semantics exactly so converted checkpoints are
bit-compatible (reference equivalents: realhf/impl/model/modules/rms.py,
rotary.py).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 accumulation, cast back to x.dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def yarn_inv_freq(
    head_dim: int, theta: float, factor: float, original: int,
    beta_fast: float, beta_slow: float,
) -> np.ndarray:
    """YaRN's inverse frequencies [head_dim / 2] (HF `rope_type: yarn`,
    `truncate` true): dimension i keeps theta's own frequency where it
    makes more than `beta_fast` turns over the `original` positions the
    model was trained on, takes it divided by `factor` where it makes
    fewer than `beta_slow`, and a linear blend between — the ramp runs
    from low = floor(c(beta_fast)) to high = ceil(c(beta_slow)), c(r) =
    d ln(original / (2 pi r)) / (2 ln theta), both clamped to [0, d - 1].
    Trace-time numpy in float64: a constant of the program."""
    d = head_dim

    def turns_dim(r):
        return d * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), d - 1)
    ramp = np.clip(
        (np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3),
        0.0, 1.0,
    )
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    return (inv / factor * ramp + inv * (1.0 - ramp)).astype(np.float32)


def rope_cos_sin(
    positions: jax.Array, head_dim: int, theta: float, yarn=None
) -> tuple:
    """cos/sin tables for the given integer positions.

    positions: int32 [...]; returns cos, sin of shape [..., head_dim] using
    the HF convention: freqs repeated twice along the last dim
    ([f0..f{d/2-1}, f0..f{d/2-1}]).  `yarn`: None, plain rope; else
    (factor, original, beta_fast, beta_slow, attention_factor) — the
    inverse frequencies are `yarn_inv_freq`'s and cos and sin are both
    multiplied by `attention_factor`.
    """
    if yarn is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
        scale = None
    else:
        *blend, scale = yarn
        inv_freq = jnp.asarray(yarn_inv_freq(head_dim, theta, *blend))
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., d/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., d]
    if scale is None:
        return jnp.cos(emb), jnp.sin(emb)
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary(
    q: jax.Array, k: jax.Array, cos: jax.Array, sin: jax.Array
) -> tuple:
    """HF-style RoPE. q/k: [..., n_heads, head_dim]; cos/sin: [..., head_dim]
    (broadcast over the heads axis)."""
    cos = cos[..., None, :].astype(jnp.float32)
    sin = sin[..., None, :].astype(jnp.float32)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    q_out = qf * cos + _rotate_half(qf) * sin
    k_out = kf * cos + _rotate_half(kf) * sin
    return q_out.astype(q.dtype), k_out.astype(k.dtype)
