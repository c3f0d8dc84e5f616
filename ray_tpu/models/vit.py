"""Vision Transformer family, functional pytree-parameter implementation.

Widens the model-family coverage beyond language (``llama.py``) and MoE
(``mixtral.py``) with the standard vision workhorse. Same design stance
as the rest of ``models/``: pure functions over a plain params pytree so
sharding rules, orbax checkpoints, and shard_map wrappers apply
unchanged, and every matmul is MXU-shaped (patchify is one big einsum,
bf16 by default, static shapes end to end).

TPU-first notes: patch embedding is a single [B, N, P*P*C] x [P*P*C, D]
matmul (not a conv — XLA lowers this straight onto the MXU); attention
reuses ``ops.attention`` (Pallas flash kernel on TPU, dense fallback
elsewhere); the classification head trains in f32 for loss stability.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import dense_attention
from ..ops.layers import rms_norm


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    num_classes: int = 1000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    dtype: Any = jnp.bfloat16

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def param_count(self) -> int:
        per_layer = (4 * self.d_model ** 2          # qkv + out
                     + 2 * self.d_model * self.d_ff  # mlp up/down
                     + 2 * self.d_model)             # norms
        return (self.patch_dim * self.d_model + self.d_model  # patch embed
                + (self.num_patches + 1) * self.d_model       # pos embed
                + self.d_model                                # cls token
                + self.n_layers * per_layer
                + self.d_model                                # final norm
                + self.d_model * self.num_classes + self.num_classes)


def _dense(key, shape, dtype, scale=None):
    scale = scale if scale is not None else (2.0 / shape[0]) ** 0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ViTConfig, key: jax.Array) -> Dict[str, Any]:
    ks = jax.random.split(key, 6 + cfg.n_layers)
    D = cfg.d_model
    params: Dict[str, Any] = {
        "patch_embed": {"w": _dense(ks[0], (cfg.patch_dim, D), cfg.dtype),
                        "b": jnp.zeros((D,), cfg.dtype)},
        "pos_embed": _dense(ks[1], (cfg.num_patches + 1, D), cfg.dtype,
                            scale=0.02),
        "cls_token": _dense(ks[2], (1, D), cfg.dtype, scale=0.02),
        "norm": jnp.zeros((D,), cfg.dtype),  # rms_norm is (1 + scale)
        "head": {"w": _dense(ks[3], (D, cfg.num_classes), jnp.float32,
                             scale=0.02),
                 "b": jnp.zeros((cfg.num_classes,), jnp.float32)},
        "layers": [],
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(ks[6 + i], 6)
        params["layers"].append({
            "attn_norm": jnp.zeros((D,), cfg.dtype),
            "wq": _dense(k[0], (D, D), cfg.dtype),
            "wk": _dense(k[1], (D, D), cfg.dtype),
            "wv": _dense(k[2], (D, D), cfg.dtype),
            "wo": _dense(k[3], (D, D), cfg.dtype),
            "mlp_norm": jnp.zeros((D,), cfg.dtype),
            "w_up": _dense(k[4], (D, cfg.d_ff), cfg.dtype),
            "w_down": _dense(k[5], (cfg.d_ff, D), cfg.dtype),
        })
    return params


def patchify(images: jax.Array, cfg: ViTConfig) -> jax.Array:
    """[B, H, W, C] -> [B, N, P*P*C] with one reshape/transpose chain."""
    B, H, W, C = images.shape
    P = cfg.patch_size
    x = images.reshape(B, H // P, P, W // P, P, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # B, h, w, P, P, C
    return x.reshape(B, (H // P) * (W // P), P * P * C)


def _attention(layer, x, cfg: ViTConfig, attn_impl):
    B, N, D = x.shape
    h = rms_norm(x, layer["attn_norm"])
    # ops.attention layout: [B, L, H, D] (llama.py:99 uses the same)
    q = (h @ layer["wq"]).reshape(B, N, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]).reshape(B, N, cfg.n_heads, cfg.head_dim)
    v = (h @ layer["wv"]).reshape(B, N, cfg.n_heads, cfg.head_dim)
    a = attn_impl(q, k, v, causal=False)  # bidirectional for vision
    a = a.reshape(B, N, D)
    return x + (a @ layer["wo"]).astype(x.dtype)


def _mlp(layer, x):
    h = rms_norm(x, layer["mlp_norm"])
    return x + (jax.nn.gelu(h @ layer["w_up"]) @ layer["w_down"]).astype(
        x.dtype)


def encode(params: Dict[str, Any], images: jax.Array,
           cfg: ViTConfig, attn_impl=None) -> jax.Array:
    """[B, H, W, C] images -> pooled CLS features [B, d_model] (f32).

    The encoder half of :func:`forward`, exposed so non-classification
    heads (the RL pixel policy/value module, ``rl/rl_module.py``) ride
    the same patch-embed + transformer path."""
    if attn_impl is None:
        # 1 + n_patches tokens never tile the TPU flash kernel's 128-row
        # blocks, and at these lengths dense attention is the right cost.
        attn_impl = dense_attention
    patches = patchify(images.astype(cfg.dtype), cfg)
    x = patches @ params["patch_embed"]["w"] + params["patch_embed"]["b"]
    B = x.shape[0]
    cls = jnp.broadcast_to(params["cls_token"], (B, 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"]
    for layer in params["layers"]:
        x = _attention(layer, x, cfg, attn_impl)
        x = _mlp(layer, x)
    x = rms_norm(x, params["norm"])
    return x[:, 0].astype(jnp.float32)  # CLS token


def forward(params: Dict[str, Any], images: jax.Array,
            cfg: ViTConfig, attn_impl=None) -> jax.Array:
    """[B, H, W, C] images -> [B, num_classes] logits (f32)."""
    pooled = encode(params, images, cfg, attn_impl)
    return pooled @ params["head"]["w"] + params["head"]["b"]


def loss_fn(params, batch, cfg: ViTConfig, attn_impl=None) -> jax.Array:
    """Softmax cross entropy over ``batch = {"images", "labels"}``."""
    logits = forward(params, batch["images"], cfg, attn_impl)
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(batch["labels"], cfg.num_classes)
    return -(onehot * logp).sum(-1).mean()


def flops_per_image(cfg: ViTConfig) -> float:
    """Approximate forward+backward FLOPs per image for MFU accounting."""
    N = cfg.num_patches + 1
    per_layer = (4 * 2 * N * cfg.d_model ** 2          # qkv + out proj
                 + 2 * 2 * N * N * cfg.d_model         # attention matmuls
                 + 2 * 2 * N * cfg.d_model * cfg.d_ff)  # mlp
    fwd = (2 * N * cfg.patch_dim * cfg.d_model
           + cfg.n_layers * per_layer
           + 2 * cfg.d_model * cfg.num_classes)
    return 3.0 * fwd  # fwd + ~2x bwd
