"""Plain reference of LFM2-24B-A2B's language model (``model_type:
lfm2_moe``): gated short convolutions three to one attention layer, two
leading dense layers, then sigmoid top-4 of 64 experts with a selection bias
and no shared expert, a tied head.

Written from the published configuration and the layer equations below, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``, with
no kernel, no cache, no conv tail, no pages and no chunks: a convolution sees
the whole sequence (zeros left of position 0), every query sees all its keys
in one softmax, the experts run in a loop one after another. It imports
nothing of ``ray_tpu.models``.

    x_0 = E[t]
    h = x_l + Op_l(RMS_op(x_l));   x_{l+1} = h + FFN_l(RMS_ffn(h))
    logits = RMS_f(x_L) E^T                                     (tied head)

    RMS   x * rsqrt(mean x^2 + eps) * g, eps norm_eps
    conv  [B, C, X] = split_3(u W_in) (each hidden_size wide); z = B * X;
          c_t = sum_{j=0..K-1} w[j] z_{t-K+1+j}, K = conv_L_cache, depthwise,
          zeros left of position 0, no bias, no activation; Op = (C * c) W_out
    attn  q = u W_q (H heads of d), k = u W_k, v = u W_v (kvh heads of d), no
          bias; q and k each through an RMS over the head's d with its own
          weight (q_layernorm, k_layernorm), THEN the rotary embedding over
          the whole head, half-split (dim i pairs with dim i + d/2), theta
          rope_theta; query head 4 g + r reads K/V head g; causal softmax of
          q . k / sqrt(d); out = [o_1 .. o_H] W_o
    FFN   l < num_dense_layers: W_2 (silu(W_1 u) * W_3 u) at intermediate_size
          else: s = sigmoid(u W_g) over all num_experts; the top k by s + b;
          w = s_sel / (sum s_sel + 1e-6) * routed_scaling_factor; FFN = sum_e
          w_e E_e(u), E(u) = W_down(silu(W_gate u) * W_up u) at
          moe_intermediate_size. No shared expert.

Departures from the published model, each also under ``assumed`` in the
configuration's file: ``head_dim`` is ``hidden_size / num_attention_heads``;
the head is tied to the embedding; the rotary convention; the ``1e-6``; the
order of ``B, C, X`` in ``W_in``'s output; every weight is seeded, and the
selection bias ``b`` is calibrated at initialisation, not trained. The layers
held are the first ``num_hidden_layers`` of ``layer_types``; ``weights`` holds
``num_experts`` of a layer's experts from ``expert_offset`` (all of them in the
benchmark's configuration), an expert that is not held adds nothing.

Weights arrive in the published convention (a norm multiplies by its weight,
a projection is ``x @ W`` with ``W`` [in, out]); ``from_program_tree`` maps the
program's tree onto it (the program stores a norm's weight as an offset from
one). To fit beside a serving replica's model, the pieces run one at a time
under ``jit`` and upcast their own weights: attention one K/V head's group of
query heads at a time with ``QUERY_BLOCK`` queries a softmax (each still over
ALL its keys), the experts one by one.

``forward(weights, tokens, shape, routing=None, rows=None)``: with ``routing``
given (int [expert layers, Lr, k]: the chosen experts of the first ``Lr``
positions) those positions use THOSE experts, with this file's own scores for
them; later positions choose freely. It also returns, per expert layer, its
own free choice and how far each imposed choice lay under its own cut-off (as
a share of that cut-off), which is what a check of routing disagreements
needs. ``rows`` names the positions whose logits are wanted (default: all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256
GATE_EPS = 1e-6


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, theta):
    """x [L, ..., d] at positions 0 .. L - 1: dim ``i`` and dim ``i + d/2``
    turned by the angle ``t * theta ** (-2i / d)``."""
    L, d = x.shape[0], x.shape[-1]
    angle = (jnp.arange(L, dtype=F32)[:, None]
             * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)[None, :])
    angle = angle.reshape((L,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms(x, weight.astype(F32), eps)


@jax.jit
def short_conv(u, w_in, conv_w, w_out):
    """The gated short convolution on the normed u [L, D] -> [L, D]."""
    with jax.default_matmul_precision("highest"):
        L, K = u.shape[0], conv_w.shape[0]
        b, c, x = jnp.split(u @ w_in.astype(F32), 3, axis=-1)
        z = jnp.pad(b * x, ((K - 1, 0), (0, 0)))
        y = sum(z[j:j + L] * conv_w[j].astype(F32)[None, :]
                for j in range(K))
        return (c * y) @ w_out.astype(F32)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _attention_group(h, wq, wk, wv, wo, q_norm, k_norm, *, theta, eps):
    """One K/V head and its group of query heads over the whole sequence.
    h [L, D] normed; wq [D, G, d], wk and wv [D, d], wo [G, d, D] -> the
    group's part of the attention's output [L, D]."""
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        q = jnp.einsum("ld,dgk->lgk", h, wq.astype(F32))
        k, v = h @ wk.astype(F32), h @ wv.astype(F32)
        q = _rotary(_rms(q, q_norm.astype(F32), eps), theta)
        k = _rotary(_rms(k, k_norm.astype(F32), eps), theta)
        scale = 1.0 / jnp.sqrt(F32(k.shape[1]))
        pad = -L % QUERY_BLOCK
        blocks = (L + pad) // QUERY_BLOCK
        j = jnp.arange(L)[None, :]

        def queries(args):
            qb, t0 = args
            i = (t0 + jnp.arange(QUERY_BLOCK))[:, None]
            s = jnp.einsum("qgd,kd->gqk", qb, k) * scale
            p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", p, v)

        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        o = jax.lax.map(queries, (
            qp.reshape(blocks, QUERY_BLOCK, *qp.shape[1:]),
            jnp.arange(blocks) * QUERY_BLOCK))
        o = o.reshape(L + pad, *o.shape[2:])[:L]
        return jnp.einsum("lgd,gdo->lo", o, wo.astype(F32))


def attention(h, w, shape):
    """h [L, D] float32, normed -> Attn(h), one K/V head's group at a time."""
    H = int(shape["num_attention_heads"])
    kvh = int(shape["num_key_value_heads"])
    d = int(shape.get("head_dim") or shape["hidden_size"] // H)
    G = H // kvh
    wq = w["wq"].reshape(-1, kvh, G, d)
    wo = w["wo"].reshape(kvh, G, d, -1)
    out = jnp.zeros_like(h)
    for g in range(kvh):
        out = out + _attention_group(
            h, wq[:, g], w["wk"][:, g * d:(g + 1) * d],
            w["wv"][:, g * d:(g + 1) * d], wo[g], w["q_norm"], w["k_norm"],
            theta=float(shape["rope_theta"]), eps=float(shape["norm_eps"]))
    return out


@jax.jit
def dense_mlp(u, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
                ) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "norm"))
def _route(h, w_router, bias, imposed, n_imposed, *, top_k, scale, norm):
    """-> (weights [L, k], the experts used [L, k], this file's own choice,
    how far the worst imposed expert's biased score lies under this file's
    own ``top_k``-th, as a share of it: 0 where the sets agree or nothing is
    imposed)."""
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        s = jax.nn.sigmoid(h @ w_router.astype(F32))
        biased = s + bias.astype(F32)
        top, own = jax.lax.top_k(biased, top_k)
        forced = (jnp.arange(L) < n_imposed)[:, None]
        chosen = jnp.where(forced, imposed, own)
        under = top[:, -1] - jnp.min(
            jnp.take_along_axis(biased, chosen, axis=-1), axis=-1)
        gates = jnp.take_along_axis(s, chosen, axis=-1)
        if norm:
            gates = gates / (jnp.sum(gates, -1, keepdims=True) + GATE_EPS)
        return gates * scale, chosen, own, \
            jnp.maximum(under, 0.0) / jnp.abs(top[:, -1])


@jax.jit
def _expert(h, gate, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))
             ) @ w_down.astype(F32)
        return gate[:, None] * y


def experts(h, moe, shape, imposed, n_imposed):
    """The expert layer on the normed h [L, D] -> (routed [L, D], own choice
    [L, k], under [L])."""
    offset = int(shape.get("expert_offset") or 0)
    gates, chosen, own, under = _route(
        h, moe["w_router"], moe["router_bias"], imposed, n_imposed,
        top_k=int(shape["num_experts_per_tok"]),
        scale=float(shape.get("routed_scaling_factor", 1.0)),
        norm=bool(shape.get("norm_topk_prob", True)))
    out = jnp.zeros_like(h)
    for e in range(moe["w_up"].shape[0]):       # the experts held, in turn
        gate = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), -1)
        out = out + _expert(h, gate, moe["w_gate"][e], moe["w_up"][e],
                            moe["w_down"][e])
    return out, own, under


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, final_norm.astype(F32), eps) @ embed.astype(F32).T


def forward(weights: dict, tokens, shape: dict, routing=None, rows=None
            ) -> dict:
    """[L] token ids -> {"logits" [L or len(rows), V] float32, "own_routing"
    [expert layers, L, k], "under" [expert layers, L]}, layer by layer."""
    eps = float(shape["norm_eps"])
    top_k = int(shape["num_experts_per_tok"])
    kinds = list(shape["layer_types"])[:int(shape["num_hidden_layers"])]
    L = len(tokens)
    x = weights["embed"][jnp.asarray(tokens)].astype(F32)
    own, under = [], []
    for kind, w in zip(kinds, weights["layers"]):
        u = _norm(x, w["op_norm"], eps=eps)
        if kind == "conv":
            x = x + short_conv(u, w["w_in"], w["conv_w"], w["w_out"])
        else:
            x = x + attention(u, w, shape)
        u = _norm(x, w["ffn_norm"], eps=eps)
        if "moe" not in w:
            x = x + dense_mlp(u, w["w_gate"], w["w_up"], w["w_down"])
            continue
        imposed = jnp.zeros((L, top_k), jnp.int32)
        n_imposed = 0
        if routing is not None:
            n_imposed = min(L, routing.shape[1])
            imposed = imposed.at[:n_imposed].set(
                jnp.asarray(routing[len(own)][:n_imposed], jnp.int32))
        m, o, far = experts(u, w["moe"], shape, imposed, n_imposed)
        x = x + m
        own.append(o)
        under.append(far)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return {"logits": _head(x, weights["final_norm"], weights["embed"],
                            eps=eps),
            "own_routing": (jnp.stack(own) if own
                            else jnp.zeros((0, L, top_k), jnp.int32)),
            "under": jnp.stack(under) if under else jnp.zeros((0, L), F32)}


def logits(weights: dict, tokens, shape: dict, routing=None):
    """[L] token ids -> [L, V] float32 logits."""
    return forward(weights, tokens, shape, routing)["logits"]


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; each piece upcasts its own)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731
    norms = ("op_norm", "ffn_norm", "q_norm", "k_norm")
    return {
        "embed": params["embedding"], "final_norm": one(params["norm"]),
        "layers": [{**lyr, **{n: one(lyr[n]) for n in norms if n in lyr}}
                   for lyr in params["layers"]],
    }
