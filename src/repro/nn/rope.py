"""Rotary position embeddings (full-head, configurable theta), and the
YaRN frequencies and attention scale of DeepSeek-V2."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def yarn_correction_range(dim: int, theta: float, yarn) -> tuple[int, int]:
    """The rotary pair indices ``(low, high)`` between which YaRN blends
    interpolated into extrapolated frequencies: the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    def at(rotations):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(at(yarn.beta_fast)), 0)
    high = min(math.ceil(at(yarn.beta_slow)), dim - 1)
    return low, high


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies (dim/2,), float32: the plain ones below
    ``low``, divided by ``factor`` above ``high``, a linear ramp between
    (``DeepseekV2YarnRotaryEmbedding``)."""
    extra = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    low, high = yarn_correction_range(dim, theta, yarn)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (extra * mask + extra / yarn.factor * (1.0 - mask)).astype(
        np.float32)


def yarn_attention_scale(head_dim: int, yarn) -> float:
    """Softmax scale of attention under YaRN: ``head_dim**-0.5`` times
    the square of ``mscale_all_dim``'s magnitude correction."""
    scale = head_dim ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def yarn_cos_sin_scale(yarn) -> float:
    """The factor on YaRN's cos and sin (1 where both mscales agree)."""
    return (yarn_mscale(yarn.factor, yarn.mscale)
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               sin_fn=None, yarn=None) -> jax.Array:
    """x: (..., T, H, Dh); positions: broadcastable to (..., T).

    ``sin_fn`` overrides the sine (the rope-table LUT site, tabulated
    over one wrapped period [0, 2*pi)); the cosine reuses the same table
    a quarter period ahead.  ``None`` keeps the exact trig path verbatim.
    ``yarn`` (a :class:`~repro.configs.base.YarnScaling`) replaces the
    plain frequencies with YaRN's and scales cos and sin by its mscale
    ratio.
    """
    from .layers import FAST_STREAM

    d_head = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(d_head, theta)                # (Dh/2,)
    else:
        freqs = jnp.asarray(yarn_freqs(d_head, theta, yarn))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, Dh/2)
    if sin_fn is None:
        cos = jnp.cos(angles)[..., None, :]              # (..., T, 1, Dh/2)
        sin = jnp.sin(angles)[..., None, :]
    else:
        tau = 2.0 * jnp.float32(jnp.pi)
        sin = sin_fn(jnp.mod(angles, tau))[..., None, :]
        cos = sin_fn(jnp.mod(angles + 0.5 * jnp.float32(jnp.pi),
                             tau))[..., None, :]
    if yarn is not None and yarn_cos_sin_scale(yarn) != 1.0:
        cos = cos * yarn_cos_sin_scale(yarn)
        sin = sin * yarn_cos_sin_scale(yarn)
    if FAST_STREAM:
        # rotate in the stream dtype; trig stays f32 (tiny, position-only)
        cos = cos.astype(x.dtype)
        sin = sin.astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
