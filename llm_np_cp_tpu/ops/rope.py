"""Rotary position embeddings.

Reference semantics (llama3.2_model.py:30-82): ``inv_freq = base^(-2i/d)``,
cos/sin built by duplicating the frequency block along the last axis
(``concat([freqs, freqs])``) and rotation applied with the half-split
``rotate_half`` convention: ``q*cos + rotate_half(q)*sin``.

Beyond the reference: llama-3 rope scaling (smooth low/high frequency
interpolation).  The reference reads ``rope_theta`` but ignores the
``rope_scaling`` config block entirely (SURVEY §2.2), which mis-positions
Llama-3.1/3.2 beyond the original 8k context; we implement it and switch it
off in reference-parity mode.

TPU note: cos/sin are computed once per forward from the position vector —
a [S, D] table, negligible next to the matmuls — so there is no precomputed
max-length table eating HBM, and positions can be traced values (cache
offsets) under jit.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from llm_np_cp_tpu.config import ModelConfig


def _inv_freq(config: ModelConfig, theta: float | None = None) -> jnp.ndarray:
    # the rotated columns: all of a head, or its leading ``rope_dim``
    dim = config.rope_dim or config.head_dim
    inv_freq = 1.0 / (
        # float(): a published theta of 1e11, read from JSON as an int,
        # is more than an int32 operand holds
        float(theta or config.rope_theta)
        ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    )
    if config.rope_scaling_type == "llama3":
        # Smoothly interpolate: high-frequency (short wavelength) components
        # unchanged, low-frequency components divided by `factor`, linear
        # ramp between the two corner wavelengths.
        factor = config.rope_scaling_factor
        low = config.rope_scaling_low_freq_factor
        high = config.rope_scaling_high_freq_factor
        orig = config.rope_scaling_original_max_position
        wavelen = 2.0 * math.pi / inv_freq
        low_wavelen = orig / low
        high_wavelen = orig / high
        smooth = (orig / wavelen - low) / (high - low)
        scaled = jnp.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
        interp = (1.0 - smooth) / factor * inv_freq + smooth * inv_freq
        is_medium = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
        inv_freq = jnp.where(is_medium, interp, scaled)
    return inv_freq


def rope_cos_sin(
    positions: jnp.ndarray, config: ModelConfig, dtype: jnp.dtype = jnp.float32,
    theta: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for ``positions`` (any leading shape) → [...,
    head_dim] — or ``[..., rope_dim]`` where the configuration rotates
    only a head's leading columns (``apply_rope`` reads the width off the
    table).  ``theta``: a layer kind's own base (``ModelConfig.attn_kind``;
    default: ``rope_theta``)."""
    inv_freq = _inv_freq(config, theta)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., dim/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., dim]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def deinterleave(x: jnp.ndarray) -> jnp.ndarray:
    """``[x0, x1, x2, ..] -> [x0, x2, .. | x1, x3, ..]`` on the last
    axis: a checkpoint that rotates the PAIRS ``(2i, 2i+1)``
    (``rope_interleave``, DeepSeek-V3) holds them side by side; moved
    apart, pair ``i`` is ``(i, i + d/2)`` and ``rotate_half`` rotates it.
    Queries and keys are both left in the moved order: a dot product
    does not see a permutation both sides share."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, *,
    interleave: bool = False,
) -> jnp.ndarray:
    """Rotate ``x``: [..., S, n_heads, head_dim] with cos/sin [..., S, head_dim].
    ``interleave``: ``x`` holds its pairs side by side (``deinterleave``).

    The head axis sits between the sequence axis and head_dim, so cos/sin
    broadcast with one unsqueeze (the reference's ``unsqueeze_dim=1`` on a
    [b, h, s, d] layout — llama3.2_model.py:77-82; we keep [b, s, h, d]
    because it writes into the KV cache without a transpose).
    """
    if interleave:
        x = deinterleave(x)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    rd = cos.shape[-1]
    if rd < x.shape[-1]:
        # partial rotation: the leading ``rd`` columns rotate (pairs
        # ``(i, i + rd/2)``), the rest pass as they are
        xr = x[..., :rd]
        return jnp.concatenate(
            [(xr * cos + rotate_half(xr) * sin).astype(x.dtype), x[..., rd:]],
            axis=-1)
    return (x * cos + rotate_half(x) * sin).astype(x.dtype)
