"""Plain reference of a dense decoder (Mistral-7B-v0.3 and its relatives).

Written from the published description — pre-norm decoder blocks, RMSNorm,
rotary position embedding on the first and second half of each head
("rotate-half", the layout of the Hugging Face weights), grouped-query
causal attention with no sliding window, a gated SiLU MLP, untied output
head — in ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``, with no kernel, no cache and no
batching tricks. It imports nothing from ``ray_tpu.models``.

Weights arrive as a dict in the PUBLISHED convention (a norm multiplies by
its weight; a projection is ``x @ W`` with ``W`` of shape [in, out]):

    embed [V, D]; head [D, V]; final_norm [D]; layers: list of
    {attn_norm, mlp_norm [D]; wq [D, H*hd]; wk, wv [D, Hkv*hd];
     wo [H*hd, D]; w_gate, w_up [D, F]; w_down [F, D]}

``from_program_tree`` maps this repo's parameter tree onto that: the only
departure is that the program stores a norm's weight as an offset from one
(it multiplies by ``1 + scale``), so the reference's weight is ``1 + scale``.

The layers run one at a time (``layer``), so float32 copies of one layer's
weights are all that has to fit beside a serving replica's bf16 model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rope(x, theta):
    """x: [L, H, hd] at positions 0..L-1."""
    L, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(L, dtype=F32)[:, None] * inv[None, :]      # [L, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "theta", "eps"))
def layer(x, w, *, n_heads, n_kv_heads, theta, eps):
    """One decoder block over one sequence. x: [L, D] float32."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        L, _ = x.shape
        h = _rms_norm(x, w["attn_norm"], eps)
        q = (h @ w["wq"]).reshape(L, n_heads, -1)
        k = (h @ w["wk"]).reshape(L, n_kv_heads, -1)
        v = (h @ w["wv"]).reshape(L, n_kv_heads, -1)
        hd = q.shape[-1]
        q, k = _rope(q, theta), _rope(k, theta)
        group = n_heads // n_kv_heads
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
        causal = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(L, n_heads * hd)
        x = x + o @ w["wo"]
        h = _rms_norm(x, w["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def logits(weights: dict, tokens, shape: dict):
    """[L] token ids -> [L, V] float32 logits, layer by layer."""
    kw = dict(n_heads=shape["num_attention_heads"],
              n_kv_heads=shape["num_key_value_heads"],
              theta=float(shape["rope_theta"]),
              eps=float(shape["rms_norm_eps"]))
    x = weights["embed"][jnp.asarray(tokens)].astype(F32)
    for w in weights["layers"]:
        x = layer(x, w, **kw)
    return _head(x, weights["final_norm"], weights["head"],
                 eps=kw["eps"])


def next_token_loss(weights: dict, tokens, shape: dict):
    """Mean cross-entropy of predicting token t+1 from tokens 0..t, over a
    [B, L] batch (every position but the last of each row)."""
    tokens = jnp.asarray(tokens)
    total, count = 0.0, 0
    for row in tokens:
        lg = logits(weights, row, shape)[:-1]
        lse = jax.nn.logsumexp(lg, axis=-1)
        true = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
        total = total + jnp.sum(lse - true)
        count += row.shape[0] - 1
    return total / count


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; ``layer`` upcasts one layer at a time)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731
    head = params["lm_head"] if "lm_head" in params else params["embedding"].T
    return {
        "embed": params["embedding"], "head": head,
        "final_norm": one(params["norm"]),
        "layers": [{**{k: lyr[k] for k in ("wq", "wk", "wv", "wo", "w_gate",
                                           "w_up", "w_down")},
                    "attn_norm": one(lyr["attn_norm"]),
                    "mlp_norm": one(lyr["mlp_norm"])}
                   for lyr in params["layers"]],
    }
