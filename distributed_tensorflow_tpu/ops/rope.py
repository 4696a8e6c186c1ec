"""Rotary position embeddings (RoPE; Su et al. 2021, RoFormer).

The reference predates rotary embeddings entirely (its only sequence axis is
a flattened 784-pixel image, ``/root/reference/demo1/train.py``); this module
completes the framework's modern-attention trio (GQA + sliding window + RoPE)
for the LM family. Positions enter attention as a per-position rotation of
the q/k head vectors instead of a learned additive table, which

  * removes the ``max_seq_len × d_model`` position table (the 64k/128k
    envelope model was spending a 131k-row embedding on it),
  * makes relative offsets the thing the q·k dot product sees (extrapolation
    and windowed attention behave sensibly), and
  * is pure fused elementwise work on TPU — the rotation rides the qkv
    projection's epilogue, no extra HBM pass, no kernel changes (the flash
    kernels consume already-rotated q/k).

Convention: the **split-half** (GPT-NeoX) layout — the head vector's first
half pairs with its second half, rotated by angles ``pos · θ^(-i/half)``.
TPU-friendly: pure slicing, no interleave gathers. Angles and the rotation
arithmetic run in f32 regardless of compute dtype (bf16 cos/sin of large
positions would quantize phases), cast back to the operand dtype at the end.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

__all__ = ["rope_cos_sin", "rope_tables", "apply_rope", "yarn_inv_freq",
           "yarn_mscale", "yarn_tables"]


def rope_cos_sin(positions, head_dim: int, theta: float = 10000.0):
    """cos/sin tables for :func:`apply_rope`.

    ``positions``: integer array, any shape (typically ``(S,)`` or
    ``(B, S)`` global token positions). Returns f32 ``cos, sin`` of shape
    ``positions.shape + (head_dim // 2,)``.
    """
    if head_dim % 2:
        raise ValueError(f"rope requires an even head_dim, got {head_dim}")
    half = head_dim // 2
    # θ^(-2i/d) for pair index i — written θ^(-i/half).
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def rope_tables(head_dim: int, seq_len: int, theta: float = 10000.0,
                positions=None, start=0):
    """The one table-building convention every attention path shares (plain
    sublayer, TpBlock): explicit global ``positions`` (B, S) → (B, S, half)
    tables; None → ``start + arange(seq_len)`` (``start`` is the KV cache's
    filled length during decode) → (1, S, half), broadcasting over batch."""
    if positions is None:
        pos = start + jnp.arange(seq_len, dtype=jnp.int32)
        cos, sin = rope_cos_sin(pos, head_dim, theta)
        return cos[None], sin[None]
    return rope_cos_sin(positions, head_dim, theta)


def apply_rope(x, cos, sin):
    """Rotate head vectors: ``x`` is (..., S, n_heads, head_dim) with
    ``cos``/``sin`` (..., S, head_dim//2) from :func:`rope_cos_sin` — the
    heads axis is broadcast (every head rotates by the same position
    angles, so GQA's unexpanded kv heads rotate identically to their query
    groups). Returns x's dtype; arithmetic in f32."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    c = cos[..., None, :]  # broadcast over the heads axis
    s = sin[..., None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention multiplier ``0.1 * mscale * ln(factor) + 1`` (1 at a
    factor of 1 or below)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's frequencies (Peng et al., arXiv:2309.00071, as DeepSeek-V2
    computes them), float64 (head_dim // 2,): the plain ``theta^(-2i/d)``
    (``extra``) for the fast pairs, the same divided by ``factor``
    (``inter``) for the slow ones, and a linear ramp between pair
    ``floor(dim(beta_fast))`` and ``ceil(dim(beta_slow))``, where
    ``dim(b) = d ln(original_len / (2 pi b)) / (2 ln theta)`` is the pair
    that turns ``b`` times over ``original_len`` positions."""
    half = head_dim // 2
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim)
    inter = extra / factor

    def dim(rotations):
        return (head_dim * math.log(original_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    extra_share = 1.0 - ramp
    return inter * (1.0 - extra_share) + extra * extra_share


def yarn_tables(positions, inv_freq, scale: float = 1.0):
    """cos/sin tables for :func:`apply_rope` from ``inv_freq`` (half,):
    ``positions`` any integer shape -> f32 ``positions.shape + (half,)``,
    each times ``scale`` (YaRN's ``mscale / mscale_all_dim``)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale
