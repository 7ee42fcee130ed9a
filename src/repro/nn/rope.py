"""Rotary position embeddings (shared by every attention family here)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0, rot_dims: Optional[int] = None
               ) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: (..., S).

    Rotate-half on the first ``rot_dims`` dims (all of ``D`` by default),
    with frequencies ``theta^(-2i/rot_dims)``; the remaining dims pass
    through unchanged (partial rotary, as StableLM uses)."""
    d = x.shape[-1]
    r = d if rot_dims is None else rot_dims
    freqs = rope_freqs(r, theta)                      # (r/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, r/2)
    if x.ndim == ang.ndim + 1:                        # head axis present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x if r == d else x[..., :r]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    out = out.astype(x.dtype)
    return out if r == d else jnp.concatenate([out, x[..., r:]], axis=-1)
