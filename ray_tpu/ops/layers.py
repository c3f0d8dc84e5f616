"""Core transformer ops: RMSNorm, RoPE, SwiGLU, cross-entropy.

Pure-jax implementations that XLA fuses into adjacent matmuls on TPU (these
are bandwidth-bound elementwise ops — the pallas_guide's advice is to let
XLA fuse them rather than hand-write kernels; attention is the exception and
lives in ``attention.py``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


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
