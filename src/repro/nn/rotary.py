"""Rotary position embeddings (RoPE), with YaRN frequency scaling.

YaRN (arXiv:2309.00071, as DeepSeek-V2 configures it): rotary pairs whose
wavelength is short against the original context keep their frequency,
pairs whose wavelength is long are divided by ``factor``, and a linear ramp
between the pair indices where the rotations over the original context
cross ``beta_fast`` and ``beta_slow`` blends the two.  Attention scales its
softmax by ``mscale(factor, mscale_all_dim)**2`` (``YaRN.softmax_factor``),
and cos/sin by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
Both apply at every position, not only past the original context.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YaRN:
    factor: float
    original_max_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def softmax_factor(self) -> float:
        return yarn_mscale(self.factor, self.mscale_all_dim) ** 2

    @property
    def cos_sin_factor(self) -> float:
        return (yarn_mscale(self.factor, self.mscale)
                / yarn_mscale(self.factor, self.mscale_all_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, d_head: int, theta: float,
                    max_positions: int) -> float:
    """The pair index whose wavelength turns ``rotations`` times over
    ``max_positions``."""
    return (d_head * math.log(max_positions / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_freqs(d_head: int, theta: float = 10000.0,
               yarn: Optional[YaRN] = None) -> jnp.ndarray:
    freqs = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))
    if yarn is None:
        return freqs
    lo = max(math.floor(_correction_dim(yarn.beta_fast, d_head, theta,
                                       yarn.original_max_positions)), 0)
    hi = min(math.ceil(_correction_dim(yarn.beta_slow, d_head, theta,
                                       yarn.original_max_positions)), d_head - 1)
    if hi == lo:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(d_head // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def rope_cos_sin(positions: jnp.ndarray, d_head: int, theta: float = 10000.0,
                 yarn: Optional[YaRN] = None):
    """positions: (..., L) int -> cos/sin (..., L, d_head//2) f32."""
    freqs = rope_freqs(d_head, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    scale = 1.0 if yarn is None else yarn.cos_sin_factor
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., L, H, D). cos/sin: (..., L, D//2) broadcast over heads."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)
