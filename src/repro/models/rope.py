"""Rotary position embeddings (functional, half-rotation convention), with
YaRN scaling (DeepSeek-V2's ``rope_scaling`` of type ``yarn``) where a
``RopeScaling`` is given."""
import math

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor ``0.1·mscale·ln(factor) + 1`` (1 at factor ≤ 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(head_dim: int, theta: float, scaling=None):
    """(head_dim/2,) float32 rotation frequencies.  With YaRN ``scaling``
    the low frequencies are divided by ``factor`` and the high ones kept,
    with a linear ramp between the dims whose wavelength fits
    ``beta_fast`` and ``beta_slow`` turns into the original context."""
    half = head_dim // 2
    extra = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    if scaling is None:
        return extra

    def corr(rot):
        return (head_dim * math.log(scaling.original_max_position
                                    / (2 * math.pi * rot))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(scaling.beta_fast)), 0)
    high = min(math.ceil(corr(scaling.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def cos_sin_scale(scaling) -> float:
    """YaRN's factor on cos/sin: ``m(factor, mscale)/m(factor,
    mscale_all_dim)`` (1 without scaling)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def rope_cos_sin(positions, head_dim: int, theta: float, scaling=None):
    """positions: (...,) int32 → cos/sin of shape positions.shape + (head_dim/2,)."""
    freqs = inv_freq(head_dim, theta, scaling)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    m = cos_sin_scale(scaling)
    return (cos, sin) if m == 1.0 else (cos * m, sin * m)


def apply_rope(x, positions, theta: float, scaling=None):
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    cos, sin = rope_cos_sin(positions, d, theta, scaling)
    # broadcast to (B, S, 1, D/2)
    while cos.ndim < x.ndim - 1:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
