"""Normalization and positional-embedding ops.

Pure-JAX implementations: XLA fuses these elementwise chains into the
surrounding matmuls on TPU, so a hand-written kernel buys nothing
(unlike attention, where the O(T^2) intermediate forces the fused
Pallas kernel in attention.py)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             offset: float = 0.0) -> jax.Array:
    """RMSNorm in f32 accumulation, cast back to the input dtype.
    `offset` supports the Gemma convention of scaling by (1 + w)
    (the checkpoint stores w near zero)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    scale = weight.astype(jnp.float32)
    if offset:
        scale = scale + offset
    return (normed * scale).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    """LayerNorm (mean and variance over the last axis, weight and
    bias) in f32, cast back to the input dtype."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (
        normed * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    ).astype(x.dtype)


def rope_frequencies(
    head_dim: int, theta: float = 10000.0, scaling=None
) -> jax.Array:
    """Per-dimension RoPE inverse frequencies, optionally rescaled.

    `scaling` is None or a tuple
    `(kind, factor, low_freq_factor, high_freq_factor, original_max)`:

    - "linear": every frequency divided by `factor` (position
      interpolation).
    - "yarn": `(kind, factor, beta_slow, beta_fast, original_max)`,
      blended by pair index (below).
    - "llama3": Llama-3.1's piecewise scheme (public formula; HF
      modeling_rope_utils._compute_llama3_parameters): wavelengths
      shorter than original_max/high_freq_factor keep their frequency,
      longer than original_max/low_freq_factor divide by `factor`, and
      the band between interpolates smoothly.
    """
    half = head_dim // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    if scaling is None:
        return freqs
    kind, factor, low_ff, high_ff, orig_max = scaling
    if kind == "linear":
        return freqs / factor
    if kind == "llama3":
        low_wavelen = orig_max / low_ff
        high_wavelen = orig_max / high_ff
        wavelen = 2.0 * jnp.pi / freqs
        smooth = (orig_max / wavelen - low_ff) / (high_ff - low_ff)
        smoothed = (1.0 - smooth) * freqs / factor + smooth * freqs
        return jnp.where(
            wavelen > low_wavelen,
            freqs / factor,
            jnp.where(wavelen < high_wavelen, freqs, smoothed),
        )
    if kind == "yarn":
        # YaRN (arXiv:2309.00071; transformers'
        # `_compute_yarn_parameters`): `low_freq_factor` is
        # `beta_slow` and `high_freq_factor` `beta_fast`, rotations
        # over `original_max` positions. A pair that turns more than
        # `beta_fast` times keeps its frequency, one that turns less
        # than `beta_slow` times is interpolated by `factor`, and the
        # pairs between (by index, between the two correction
        # dimensions, the lower floored and the upper ceiled) blend
        # linearly.
        def correction_dim(rotations):
            return head_dim * math.log(
                orig_max / (rotations * 2.0 * math.pi)
            ) / (2.0 * math.log(theta))

        low = max(math.floor(correction_dim(high_ff)), 0)
        high = min(math.ceil(correction_dim(low_ff)), head_dim - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / max(high - low, 0.001),
            0.0, 1.0,
        )
        return freqs / factor * ramp + freqs * (1.0 - ramp)
    raise ValueError(f"unknown rope scaling kind {kind!r}")


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature `0.1 * mscale * ln(factor) + 1`
    (1 for a factor of at most 1): DeepSeek's latent attention scales
    its softmax by the square of it (`mscale_all_dim`)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_embedding(
    positions: jax.Array,
    head_dim: int,
    theta: float = 10000.0,
    scaling=None,
):
    """Rotary position embedding tables: returns (cos, sin) of shape
    [*positions.shape, head_dim // 2], f32."""
    freqs = rope_frequencies(head_dim, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(
    x: jax.Array, cos: jax.Array, sin: jax.Array
) -> jax.Array:
    """Apply RoPE to [batch, heads, seq, head_dim] given per-position
    (cos, sin) of shape [batch, seq, head_dim//2] (or broadcastable).
    Tables narrower than half the head turn its LEADING dims alone,
    twice their width, and the rest pass as they are (a partial rotary
    factor; the rope dims of a latent-attention head)."""
    turned = 2 * cos.shape[-1]
    if turned < x.shape[-1]:
        return jnp.concatenate(
            [apply_rotary(x[..., :turned], cos, sin), x[..., turned:]],
            axis=-1,
        )
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    # cos/sin: [b, t, half] -> [b, 1, t, half] to broadcast over heads.
    if cos.ndim == 3:
        cos = cos[:, None, :, :]
        sin = sin[:, None, :, :]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(dtype)


def swiglu(x: jax.Array, gate: jax.Array) -> jax.Array:
    """SwiGLU activation: silu(gate) * x."""
    return jax.nn.silu(gate) * x
