"""Rotary position embeddings (full, partial, dual-base local/global,
YaRN-scaled)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, theta: float,
                     fraction: float = 1.0) -> np.ndarray:
    """Inverse frequencies for the rotated sub-dimension.

    Returns (rot_dim // 2,) float32 as a *numpy* array (static metadata,
    safe to stack/convert at trace time).  ``fraction`` < 1 rotates only
    the leading ``fraction * head_dim`` dims (stablelm partial rotary)."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    k = np.arange(rot // 2, dtype=np.float32)
    return (1.0 / (theta ** (2.0 * k / rot))).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``
    (1 without scaling)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, rs) -> np.ndarray:
    """YaRN inverse frequencies for a ``head_dim``-wide rotation under
    :class:`~repro.configs.base.RopeScaling` ``rs``: dims that rotate
    more than ``beta_fast`` times over the original context keep their
    frequency, those under ``beta_slow`` are divided by ``factor``, with
    a linear ramp between (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    if rs.type != "yarn":
        raise ValueError(f"unknown rope scaling {rs.type!r}")
    if rs.mscale != rs.mscale_all_dim:
        # their ratio would scale cos and sin; DeepSeek-V2's configs set
        # both alike, so it is 1 and is not applied
        raise ValueError("YaRN with mscale != mscale_all_dim")
    half = head_dim // 2
    extra = rope_frequencies(head_dim, theta).astype(np.float64)
    inter = extra / rs.factor

    def dim_of(rotations: float) -> float:
        return (head_dim * math.log(rs.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rs.beta_fast)), 0)
    high = min(math.ceil(dim_of(rs.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp          # 1: original frequency, 0: interpolated
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array,
               inv_freq: jax.Array) -> jax.Array:
    """Rotate ``x`` (..., seq, heads, head_dim) by ``positions`` (..., seq).

    Only the leading 2*len(inv_freq) dims rotate; the rest pass through.
    """
    rot = 2 * inv_freq.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    ang = positions[..., :, None].astype(jnp.float32) * inv_freq  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., :, None, :]   # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    out = jnp.concatenate([out1, out2], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)
