"""Llama-family transformer, functional pytree-parameter implementation.

The flagship model for the Train/bench path (north-star: Llama-3-8B data
parallel, BASELINE.json configs[1]). Pure functions over a params dict —
no module framework — so sharding rules (``parallel/sharding.py``), orbax
checkpointing, and shard_map wrappers see a plain pytree.

Parameter names align with ``parallel.sharding.LLAMA_RULES``:
``embedding``, per-layer ``wq wk wv wo w_gate w_up w_down attn_norm
mlp_norm``, final ``norm``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.quant import mm
from ..ops.attention import dense_attention, flash_attention
from ..ops.layers import apply_rope, cross_entropy_loss, rms_norm, rope_frequencies
from ..parallel.sharding import activation_sharding


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        h = self.head_dim
        per_layer = (d * self.n_heads * h + 2 * d * self.n_kv_heads * h
                     + self.n_heads * h * d + 3 * d * f + 2 * d)
        total = v * d + self.n_layers * per_layer + d
        if not self.tie_embeddings:
            total += d * v
        return total


# Model-card configs (sizes follow the published Llama-3 family shapes).
LLAMA3_8B = LlamaConfig()
LLAMA3_1B = LlamaConfig(d_model=2048, n_layers=16, n_heads=32, n_kv_heads=8,
                        d_ff=8192, vocab_size=128256)
LLAMA_DEBUG = LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=128, max_seq_len=256,
                          dtype=jnp.float32)


def _dense(key, shape, dtype, scale=None):
    if scale is None:
        # fan-in is the second-to-last dim (== dim 0 for 2-D weights,
        # correct for stacked [E, in, out] expert weights too)
        scale = 1.0 / math.sqrt(shape[-2] if len(shape) > 1 else shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 3)
    d, hd = cfg.d_model, cfg.head_dim
    params: Dict[str, Any] = {
        "embedding": _dense(keys[0], (cfg.vocab_size, d), cfg.dtype, 1.0),
        "norm": jnp.zeros((d,), cfg.dtype),
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(keys[1], (d, cfg.vocab_size), cfg.dtype)
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i + 3], 7)
        params["layers"].append({
            "wq": _dense(k[0], (d, cfg.n_heads * hd), cfg.dtype),
            "wk": _dense(k[1], (d, cfg.n_kv_heads * hd), cfg.dtype),
            "wv": _dense(k[2], (d, cfg.n_kv_heads * hd), cfg.dtype),
            "wo": _dense(k[3], (cfg.n_heads * hd, d), cfg.dtype),
            "w_gate": _dense(k[4], (d, cfg.d_ff), cfg.dtype),
            "w_up": _dense(k[5], (d, cfg.d_ff), cfg.dtype),
            "w_down": _dense(k[6], (cfg.d_ff, d), cfg.dtype),
            "attn_norm": jnp.zeros((d,), cfg.dtype),
            "mlp_norm": jnp.zeros((d,), cfg.dtype),
        })
    return params


def _attention_block(layer, x, cos, sin, cfg: LlamaConfig, attn_impl,
                     kv_cache=None, positions=None):
    B, L, _ = x.shape
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = mm(h, layer["wq"]).reshape(B, L, cfg.n_heads, cfg.head_dim)
    k = mm(h, layer["wk"]).reshape(B, L, cfg.n_kv_heads, cfg.head_dim)
    v = mm(h, layer["wv"]).reshape(B, L, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    new_cache = None
    if kv_cache is not None:
        k_all, v_all, cache_len = kv_cache
        with jax.named_scope("kv_write"):
            k_all = jax.lax.dynamic_update_slice(
                k_all, k.astype(k_all.dtype), (0, cache_len, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                v_all, v.astype(v_all.dtype), (0, cache_len, 0, 0))
        new_cache = (k_all, v_all, cache_len + L)
        mask_len = k_all.shape[1]
        pos = cache_len + jnp.arange(L)
        seg = (jnp.arange(mask_len)[None, :] <= pos[:, None]).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk",
                       q.astype(jnp.float32),
                       jnp.repeat(k_all, cfg.n_heads // cfg.n_kv_heads,
                                  axis=2).astype(jnp.float32))
        s = s * (cfg.head_dim ** -0.5)
        s = jnp.where(seg[None, None] > 0, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_all.dtype),
                       jnp.repeat(v_all, cfg.n_heads // cfg.n_kv_heads,
                                  axis=2))
    else:
        o = attn_impl(q, k, v, causal=True)
    o = o.reshape(B, L, cfg.n_heads * cfg.head_dim)
    return mm(o, layer["wo"]), new_cache


def _mlp_block(layer, x, cfg: LlamaConfig):
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    g = mm(h, layer["w_gate"])
    u = mm(h, layer["w_up"])
    return mm(jax.nn.silu(g) * u, layer["w_down"])


def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: LlamaConfig, attn_impl=None,
                   remat: bool = True) -> jax.Array:
    """Final-norm hidden states [B, L, D] (no lm_head projection).

    An ``attn_impl`` that is bound to a mesh
    (``ops.attention.make_flash_attention(mesh)``) says that the step is
    sharded, and the residual stream is then held to the batch layout
    (``parallel.sharding.activation_sharding``) after the embedding and after
    each residual sum: GSPMD gathers weights over ``fsdp`` and all-reduces the
    two row-parallel products of a layer over ``tp``, where unpinned it
    splits ``d_model`` over ``fsdp`` as the embedding is split. With any
    other ``attn_impl`` nothing is pinned."""
    mesh = getattr(attn_impl, "mesh", None)
    layout = None if mesh is None else activation_sharding(mesh)
    if attn_impl is None:
        attn_impl = flash_attention

    def pin(x):
        return (x if layout is None
                else jax.lax.with_sharding_constraint(x, layout))

    cos, sin = rope_frequencies(cfg.head_dim, tokens.shape[1], cfg.rope_theta)
    x = pin(params["embedding"][tokens].astype(cfg.dtype))

    def layer_fn(x, layer):
        a, _ = _attention_block(layer, x, cos, sin, cfg, attn_impl)
        x = pin(x + a)
        return pin(x + _mlp_block(layer, x, cfg))

    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    for layer in params["layers"]:
        x = layer_fn(x, layer)
    return rms_norm(x, params["norm"], cfg.norm_eps)


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            attn_impl=None, remat: bool = True) -> jax.Array:
    """Logits for a token batch. tokens: [B, L] int32 -> [B, L, V]."""
    x = forward_hidden(params, tokens, cfg, attn_impl=attn_impl,
                       remat=remat)
    head = (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"])
    return mm(x, head)


def next_token_targets(tokens: jax.Array) -> jax.Array:
    """Shifted targets with -100 (ignore) padding the final position."""
    return jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -100)], axis=1)


def loss_fn(params, batch, cfg: LlamaConfig, attn_impl=None,
            remat: bool = True, chunked_vocab: int = 0):
    """Next-token loss. batch: {"tokens": [B, L]} or {"tokens", "targets"}.

    ``chunked_vocab > 0`` streams the vocab softmax in chunks of that
    size (``ops/chunked_xent.py``): the full [B, L, V] fp32 logits are
    never materialized — the HBM win that enables larger batches on
    memory-bound chips.
    """
    tokens = batch["tokens"]
    targets = batch.get("targets")
    if targets is None:
        targets = next_token_targets(tokens)
    if chunked_vocab > 0:
        from ..ops.chunked_xent import chunked_cross_entropy

        x = forward_hidden(params, tokens, cfg, attn_impl=attn_impl,
                           remat=remat)
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        from ..ops.quant import Q8

        if isinstance(head, Q8):
            # chunked CE streams its own matmuls; feed it dense weights
            # (int8 training isn't a thing — this path is train-only)
            head = head.w.astype(x.dtype) * head.s
        B, L, D = x.shape
        return chunked_cross_entropy(
            x.reshape(B * L, D), head, targets.reshape(B * L),
            chunked_vocab)
    logits = forward(params, tokens, cfg, attn_impl=attn_impl, remat=remat)
    loss, n = cross_entropy_loss(logits, targets)
    return loss


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approximate training FLOPs/token (6*N + attention term) for MFU."""
    n_params = cfg.param_count()
    attn = 12 * cfg.n_layers * cfg.d_model * seq_len  # fwd+bwd attn matmuls
    return 6 * n_params + attn


def _decode_step(params, tokens, caches, start, cfg: LlamaConfig, cos,
                 sin, ffn=None):
    """One cached forward over ``tokens`` beginning at position ``start``.

    ``ffn(layer, x, cfg)`` swaps the feed-forward block — the hook the
    MoE family (mixtral) uses to share this loop; default is the dense
    SwiGLU MLP."""
    if ffn is None:
        ffn = _mlp_block
    x = params["embedding"][tokens].astype(cfg.dtype)
    positions = start + jnp.arange(tokens.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, tokens.shape)
    new_caches = []
    # named scopes: HLO metadata only, so a profile names each op's phase
    for layer, (kc, vc) in zip(params["layers"], caches):
        with jax.named_scope("attention"):   # holds the cache write
            a, nc = _attention_block(
                layer, x, cos, sin, cfg, None,
                kv_cache=(kc, vc, start), positions=positions)
            x = x + a
        with jax.named_scope("mlp"):
            x = x + ffn(layer, x, cfg)
        new_caches.append((nc[0], nc[1]))
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    head = (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"])
    return mm(x, head), new_caches


def _prefill(params, prompt, cfg: LlamaConfig, max_new: int, ffn=None):
    B, L = prompt.shape
    total = L + max_new
    caches = [
        (jnp.zeros((B, total, cfg.n_kv_heads, cfg.head_dim), cfg.dtype),
         jnp.zeros((B, total, cfg.n_kv_heads, cfg.head_dim), cfg.dtype))
        for _ in range(cfg.n_layers)
    ]
    cos, sin = rope_frequencies(cfg.head_dim, total, cfg.rope_theta)
    logits, caches = _decode_step(params, prompt, caches, 0, cfg, cos,
                                  sin, ffn=ffn)
    return logits, caches, L, cos, sin


def _generate(params, prompt, cfg: LlamaConfig, max_new: int, pick,
              ffn=None):
    """Shared scan-based decode loop; ``pick(logits, key) -> tokens``,
    ``ffn`` as in ``_decode_step`` (the MoE family passes its router)."""
    logits, caches, L, cos, sin = _prefill(params, prompt, cfg, max_new,
                                           ffn=ffn)
    key0 = jax.random.PRNGKey(0)
    key0, sub = jax.random.split(key0)
    next_tok = pick(logits[:, -1], sub)

    def scan_body(carry, _):
        caches, tok, pos, key = carry
        logits, caches = _decode_step(params, tok[:, None], caches, pos,
                                      cfg, cos, sin, ffn=ffn)
        key, sub = jax.random.split(key)
        nxt = pick(logits[:, -1], sub)
        return (caches, nxt, pos + 1, key), nxt

    (_, _, _, _), toks = jax.lax.scan(
        scan_body, (caches, next_tok, L, key0), None, length=max_new - 1)
    return jnp.concatenate([next_tok[:, None], toks.T], axis=1)


@partial(jax.jit, static_argnames=("cfg", "max_new"))
def generate_greedy(params, prompt: jax.Array, cfg: LlamaConfig,
                    max_new: int = 32):
    """KV-cached greedy decode. For sampling use ``generate_sample``."""
    return _generate(params, prompt, cfg, max_new,
                     lambda logits, key: jnp.argmax(logits, axis=-1))


@partial(jax.jit, static_argnames=("cfg", "max_new"))
def generate_sample(params, prompt: jax.Array, cfg: LlamaConfig,
                    key: jax.Array, max_new: int = 32,
                    temperature: float = 1.0):
    """KV-cached sampled decode with temperature."""
    logits, caches, L, cos, sin = _prefill(params, prompt, cfg, max_new)

    def pick(logits, k):
        return jax.random.categorical(
            k, logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6))

    key, sub = jax.random.split(key)
    next_tok = pick(logits[:, -1], sub)

    def scan_body(carry, _):
        caches, tok, pos, k = carry
        logits, caches = _decode_step(params, tok[:, None], caches, pos,
                                      cfg, cos, sin)
        k, sub = jax.random.split(k)
        nxt = pick(logits[:, -1], sub)
        return (caches, nxt, pos + 1, k), nxt

    (_, _, _, _), toks = jax.lax.scan(
        scan_body, (caches, next_tok, L, key), None, length=max_new - 1)
    return jnp.concatenate([next_tok[:, None], toks.T], axis=1)
