"""Rotary position embeddings.

Half-rotation (NeoX/Llama) layout: features are split into two halves that
rotate together — the layout HF Llama/Mistral/Gemma/Qwen checkpoints use, so
loaded weights need no permutation. Latent attention (``rope_interleave``)
rotates neighbouring pairs (2i, 2i+1) instead, under YaRN frequencies
(``yarn_freqs``, ``apply_rope_interleaved``).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def _llama3_scale(freqs: jnp.ndarray, scaling) -> jnp.ndarray:
    """Llama-3.1/3.2 frequency-dependent NTK scaling.

    Long-wavelength (low-frequency) components are stretched by ``factor``;
    short wavelengths are kept; the band between ``low_freq_factor`` and
    ``high_freq_factor`` (in units of original_max/wavelength) interpolates
    smoothly. Matches HF ``rope_type="llama3"``.
    """
    factor, low, high, original_max = scaling
    wavelen = 2.0 * jnp.pi / freqs
    ratio = original_max / wavelen
    smooth = jnp.clip((ratio - low) / (high - low), 0.0, 1.0)
    return jnp.where(
        ratio < low,
        freqs / factor,
        (1.0 - smooth) * freqs / factor + smooth * freqs,
    )


def rope_angles(
    positions: jnp.ndarray,
    head_dim: int,
    theta: float,
    scaling: tuple[float, float, float, float] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions.

    positions: [...]; returns cos/sin of shape [..., head_dim//2], f32.
    ``scaling``: optional llama-3 rope scaling as (factor, low_freq_factor,
    high_freq_factor, original_max_seq_len); None = unscaled.
    """
    half = head_dim // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    if scaling is not None:
        freqs = _llama3_scale(freqs, scaling)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate feature pairs (x1, x2) = (x[..:half], x[half:..]).

    x: [B, S, H, D]; cos/sin: [B, S, D//2] (broadcast over heads).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # [B, S, 1, D/2]
    s = sin[..., None, :]
    rot1 = x1 * c - x2 * s
    rot2 = x2 * c + x1 * s
    return jnp.concatenate([rot1, rot2], axis=-1).astype(x.dtype)


def yarn_freqs(dim: int, theta: float, yarn) -> jnp.ndarray:
    """YaRN inverse frequencies over ``dim`` rotated features (HF
    ``rope_type="yarn"``, ``truncate`` on): wavelengths that fit
    ``beta_fast`` rotations into the original context keep their
    frequency, those under ``beta_slow`` rotations are stretched by
    ``factor``, a linear ramp between. ``yarn``: models/config.YarnRope."""

    def correction_dim(rotations: float) -> float:
        return (
            dim * math.log(yarn.original_max / (rotations * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    half = dim // 2
    pos = theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    return (1.0 / (yarn.factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def yarn_attention_factor(yarn) -> float:
    """What cos and sin are multiplied by: mscale(factor, mscale) over
    mscale(factor, mscale_all_dim) where both are given (1 when equal)."""

    def mscale(m: float) -> float:
        return 1.0 if yarn.factor <= 1 else 0.1 * m * math.log(yarn.factor) + 1.0

    if yarn.mscale and yarn.mscale_all_dim:
        return mscale(yarn.mscale) / mscale(yarn.mscale_all_dim)
    return mscale(1.0)


def yarn_angles(
    positions: jnp.ndarray, dim: int, theta: float, yarn
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin [..., dim//2] at integer positions under YaRN."""
    ang = positions.astype(jnp.float32)[..., None] * yarn_freqs(dim, theta, yarn)
    f = yarn_attention_factor(yarn)
    return jnp.cos(ang) * f, jnp.sin(ang) * f


def query_position_scale(positions: jnp.ndarray, yarn) -> jnp.ndarray:
    """1 + beta * ln(1 + floor(position / original_max)): what queries
    are multiplied by (``llama_4_scaling_beta``); 1 under ``original_max``."""
    over = jnp.floor_divide(positions, yarn.original_max).astype(jnp.float32)
    return 1.0 + yarn.query_scaling_beta * jnp.log1p(over)


def apply_rope_interleaved(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate neighbouring pairs (x[2i], x[2i+1]) by angle i, in place.

    x: [B, S, H, D]; cos/sin: [B, S, D//2] (broadcast over heads).
    """
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
