"""Plain reference of Command A+'s language model (``model_type:
cohere2_moe``): window and full attention mixed, a parallel block, sigmoid
top-k experts beside averaged shared experts, a tied head.

Written from the published configuration and the layer equations below, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``, with
no kernel, no cache, no ring and no chunks: every query sees all its keys in
one softmax under its layer's mask; the experts run in a loop one after
another. It imports nothing of ``ray_tpu.models``.

    x_0 = E[t] (no multiplier)
    h = LN_l(x_l);   x_{l+1} = x_l + Attn_l(h) + MoE_l(h)     (parallel block)
    logits = LN_f(x_L) E^T * logit_scale                       (tied head)

    LN   (x - mean x) * rsqrt(var x + eps) * g, eps layer_norm_eps, no bias
    Attn q = h W_q (H heads of d), k = h W_k, v = h W_v (kvh heads of d), no
         bias, no q/k norm; query head 16 g + r reads K/V head g; scores
         q . k / sqrt(d), softmax, out = [o_1 .. o_H] W_o.
         sliding_attention: rotary on q and k over interleaved pairs
         (2j, 2j + 1), theta rope_theta, no scaling; key j visible to query i
         iff 0 <= i - j < sliding_window.
         full_attention: NO position embedding; key j visible iff j <= i.
    MoE  s = sigmoid(h W_r) over all router_width outputs; the top k by s;
         w = s_sel / sum(s_sel); routed = sum_e w_e E_e(h), E(h) =
         W_down(silu(W_gate h) * W_up h); shared = (1 / n) sum_j S_j(h), n
         shared experts of the same shape; MoE(h) = routed + shared

Departures from the published model, each also under ``assumed`` in the
configuration's file:

- **The vision tower is absent**: the catalog row's ``config`` is the language
  model's.
- ``shared_expert_combination_strategy: average`` is read as the mean of the
  shared experts' outputs, added to the routed sum.
- No selection bias and no routed scaling: the config has neither key.
- The window's bound ``i - j < sliding_window`` (the query's own position and
  the ``sliding_window - 1`` before it); NoPE on the full layers
  (``described_as``: "global NoPE", the Cohere2 convention).
- Every weight is seeded, not trained.
- **One chip's share.** ``weights`` holds ``num_experts`` of the layer's
  routed experts, from ``expert_offset``; the router scores all
  ``router_width`` outputs, an expert that is not held adds nothing, here as
  in the program; the shared experts are whole. The vocabulary slice is the
  vocabulary. The layers held are the first ``num_hidden_layers`` of
  ``layer_types``.

Weights arrive in the published convention (a norm multiplies by its weight,
a projection is ``x @ W`` with ``W`` [in, out]); ``from_program_tree`` maps the
program's tree onto it (the program stores a norm's weight as an offset from
one). To fit beside a serving replica's model, the pieces run one at a time
under ``jit`` and upcast their own weights: attention one K/V head's group of
query heads at a time with ``QUERY_BLOCK`` queries a softmax (each still over
ALL its keys), the shared and the held experts one by one.

``forward(weights, tokens, shape, routing=None, rows=None)``: with ``routing``
given (int [layers, Lr, k]: the chosen experts of the first ``Lr`` positions)
those positions use THOSE experts, with this file's own scores for them;
later positions choose freely. It also returns, per layer, its own free
choice and how far each imposed choice lay under its own cut-off (as a share
of that cut-off), which is what a check of routing disagreements needs.
``rows`` names the positions whose logits are wanted (default: all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256


def _layer_norm(x, weight, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, theta):
    """x [L, ..., d] at positions 0 .. L - 1: pair ``(2j, 2j + 1)`` turned by
    the angle ``t * theta ** (-2j / d)``."""
    L, d = x.shape[0], x.shape[-1]
    angle = (jnp.arange(L, dtype=F32)[:, None]
             * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)[None, :])
    angle = angle.reshape((L,) + (1,) * (x.ndim - 2) + (d // 2,))
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(1j * angle)
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _layer_norm(x, weight.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("window", "theta"))
def _attention_group(h, wq, wk, wv, wo, *, window, theta):
    """One K/V head and its group of query heads over the whole sequence.
    h [L, D] normed; wq [D, G, d], wk and wv [D, d], wo [G, d, D]; ``window``
    0 for a full layer (no rotary, causal), else the window (rotary, both
    bounds) -> the group's part of the attention's output [L, D]."""
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        q = jnp.einsum("ld,dgk->lgk", h, wq.astype(F32))
        k, v = h @ wk.astype(F32), h @ wv.astype(F32)
        if window:
            q, k = _rotary(q, theta), _rotary(k, theta)
        scale = 1.0 / jnp.sqrt(F32(k.shape[1]))
        pad = -L % QUERY_BLOCK
        blocks = (L + pad) // QUERY_BLOCK
        j = jnp.arange(L)[None, :]

        def queries(args):
            qb, t0 = args
            i = (t0 + jnp.arange(QUERY_BLOCK))[:, None]
            ok = j <= i
            if window:
                ok = ok & (i - j < window)
            s = jnp.einsum("qgd,kd->gqk", qb, k) * scale
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, v)

        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        o = jax.lax.map(queries, (
            qp.reshape(blocks, QUERY_BLOCK, *qp.shape[1:]),
            jnp.arange(blocks) * QUERY_BLOCK))
        o = o.reshape(L + pad, *o.shape[2:])[:L]
        return jnp.einsum("lgd,gdo->lo", o, wo.astype(F32))


def attention(h, w, shape, kind):
    """h [L, D] float32, normed -> Attn(h), one K/V head's group at a time."""
    H, kvh = int(shape["num_attention_heads"]), int(shape["num_key_value_heads"])
    d, G = int(shape["head_dim"]), H // kvh
    window = int(shape["sliding_window"]) if kind == "sliding_attention" else 0
    wq = w["wq"].reshape(-1, kvh, G, d)
    wo = w["wo"].reshape(kvh, G, d, -1)
    out = jnp.zeros_like(h)
    for g in range(kvh):
        out = out + _attention_group(
            h, wq[:, g], w["wk"][:, g * d:(g + 1) * d],
            w["wv"][:, g * d:(g + 1) * d], wo[g], window=window,
            theta=float(shape["rope_theta"]))
    return out


@functools.partial(jax.jit, static_argnames=("top_k",))
def _route(h, w_router, imposed, n_imposed, *, top_k):
    """-> (weights [L, k], the experts used [L, k], this file's own choice,
    how far the worst imposed expert's score lies under this file's own
    ``top_k``-th, as a share of it: 0 where the sets agree or nothing is
    imposed)."""
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        s = jax.nn.sigmoid(h @ w_router.astype(F32))
        top, own = jax.lax.top_k(s, top_k)
        forced = (jnp.arange(L) < n_imposed)[:, None]
        chosen = jnp.where(forced, imposed, own)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        under = top[:, -1] - jnp.min(picked, axis=-1)
        gates = picked / jnp.sum(picked, axis=-1, keepdims=True)
        return gates, chosen, own, jnp.maximum(under, 0.0) / top[:, -1]


@jax.jit
def _expert(h, gate, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))
             ) @ w_down.astype(F32)
        return gate[:, None] * y


def experts(h, w, shape, imposed, n_imposed):
    """The expert layer on the normed h [L, D] -> (routed + shared [L, D],
    own choice [L, k], under [L])."""
    offset = int(shape.get("expert_offset") or 0)
    gates, chosen, own, under = _route(
        h, w["moe"]["w_router"], imposed, n_imposed,
        top_k=int(shape["num_experts_per_tok"]))
    n = w["shared"]["w_up"].shape[0]
    out = jnp.zeros_like(h)
    for j in range(n):                          # the shared experts' average
        out = out + _expert(h, jnp.full((h.shape[0],), 1.0 / n, F32),
                            w["shared"]["w_gate"][j], w["shared"]["w_up"][j],
                            w["shared"]["w_down"][j])
    for e in range(w["moe"]["w_up"].shape[0]):  # the experts held, in turn
        gate = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), -1)
        out = out + _expert(h, gate, w["moe"]["w_gate"][e],
                            w["moe"]["w_up"][e], w["moe"]["w_down"][e])
    return out, own, under


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _head(x, final_norm, embed, *, eps, scale):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(x, final_norm.astype(F32), eps) \
            @ embed.astype(F32).T * scale


def forward(weights: dict, tokens, shape: dict, routing=None, rows=None
            ) -> dict:
    """[L] token ids -> {"logits" [L or len(rows), V] float32, "own_routing"
    [layers, L, k], "under" [layers, L]}, layer by layer."""
    eps = float(shape["layer_norm_eps"])
    top_k = int(shape["num_experts_per_tok"])
    kinds = list(shape["layer_types"])[:int(shape["num_hidden_layers"])]
    L = len(tokens)
    x = weights["embed"][jnp.asarray(tokens)].astype(F32)
    own, under = [], []
    for li, (kind, w) in enumerate(zip(kinds, weights["layers"])):
        imposed = jnp.zeros((L, top_k), jnp.int32)
        n_imposed = 0
        if routing is not None:
            n_imposed = min(L, routing.shape[1])
            imposed = imposed.at[:n_imposed].set(
                jnp.asarray(routing[li][:n_imposed], jnp.int32))
        h = _norm(x, w["norm"], eps=eps)
        m, o, far = experts(h, w, shape, imposed, n_imposed)
        x = x + attention(h, w, shape, kind) + m
        own.append(o)
        under.append(far)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return {"logits": _head(x, weights["final_norm"], weights["embed"],
                            eps=eps, scale=float(shape.get("logit_scale", 1))),
            "own_routing": jnp.stack(own), "under": jnp.stack(under)}


def logits(weights: dict, tokens, shape: dict, routing=None):
    """[L] token ids -> [L, V] float32 logits."""
    return forward(weights, tokens, shape, routing)["logits"]


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; each piece upcasts its own)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731
    return {
        "embed": params["embedding"], "final_norm": one(params["norm"]),
        "layers": [{**lyr, "norm": one(lyr["norm"])}
                   for lyr in params["layers"]],
    }
