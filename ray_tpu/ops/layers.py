"""Core transformer ops: RMSNorm, RoPE, SwiGLU, cross-entropy.

Pure-jax implementations that XLA fuses into adjacent matmuls on TPU (these
are bandwidth-bound elementwise ops — the pallas_guide's advice is to let
XLA fuse them rather than hand-write kernels; attention is the exception and
lives in ``attention.py``).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(orig_dtype)


def layer_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Mean-subtracting norm over the last axis, in float32, no bias:
    ``(x - mean) * rsqrt(var + eps) * (1 + scale)`` (the weight is stored as
    an offset from one, as ``rms_norm``'s)."""
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(orig_dtype)


def rope_frequencies(head_dim: int, max_len: int,
                     theta: float = 500000.0) -> Tuple[jax.Array, jax.Array]:
    """Precompute cos/sin tables: [max_len, head_dim//2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def rope_rows(positions: jax.Array, head_dim: int,
              theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos, sin [N, head_dim / 2] of the given positions [N]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's rotary frequencies [head_dim / 2] (float64, a constant of the
    program): pair ``j`` turns at ``f_j = theta ** (-2j / head_dim)`` where it
    makes more than ``beta_fast`` turns over the ``original_len`` positions
    the model was trained on, at ``f_j / factor`` where it makes fewer than
    ``beta_slow``, and a linear blend of the two between. ``cd(n) = head_dim
    ln(original_len / (2 pi n)) / (2 ln theta)`` is the pair that makes ``n``
    turns; the ramp runs from ``floor(cd(beta_fast))`` to
    ``ceil(cd(beta_slow))``, both clipped to the pairs there are."""
    f = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def cd(turns):
        return (head_dim * math.log(original_len / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(cd(beta_fast)), 0)
    high = min(math.ceil(cd(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1`` (1 at a
    factor of 1 or under)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_rows(positions: jax.Array, head_dim: int, theta: float,
              factor: float, original_len: int, beta_fast: float,
              beta_slow: float, mscale: float, mscale_all_dim: float
              ) -> Tuple[jax.Array, jax.Array]:
    """``rope_rows`` at YaRN's frequencies: cos, sin [N, head_dim / 2] of the
    given positions, times ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)`` (1 where the two are equal, as DeepSeek-V3's are: the
    temperature then lies in the softmax scale alone)."""
    inv_freq = jnp.asarray(yarn_inv_freq(
        head_dim, theta, factor, original_len, beta_fast, beta_slow),
        jnp.float32)
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return jnp.cos(freqs) * m, jnp.sin(freqs) * m


def rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array
                     ) -> jax.Array:
    """Rotary embedding over interleaved pairs ``(2j, 2j + 1)`` (the GPT-J
    convention). x [N, d] or [N, H, d]; cos, sin [N, d / 2] (``rope_rows``)."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """Rotary embedding. x: [B, L, H, D]; cos/sin: [max_len, D//2]."""
    B, L, H, D = x.shape
    if positions is None:
        c = cos[:L][None, :, None, :]
        s = sin[:L][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.dot(x, w_gate)
    u = jnp.dot(x, w_up)
    return jnp.dot(jax.nn.silu(g) * u, w_down)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       ignore_index: int = -100,
                       z_loss: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """Token-level CE with optional z-loss; returns (loss, n_valid).

    logits: [..., V] float; labels: [...] int. fp32 log-softmax for
    stability regardless of activation dtype.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    clipped = jnp.clip(labels, 0, logits.shape[-1] - 1)
    true_logit = jnp.take_along_axis(
        logits, clipped[..., None], axis=-1)[..., 0]
    nll = lse - true_logit
    if z_loss > 0.0:
        nll = nll + z_loss * jnp.square(lse)
    valid = (labels != ignore_index).astype(jnp.float32)
    loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    return loss, jnp.sum(valid)
