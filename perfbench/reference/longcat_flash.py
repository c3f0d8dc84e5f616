"""Plain reference of LongCat-Flash-Omni's language model (``attention_method:
MLA``, ``zero_expert_type: identity``): shortcut-connected double layers.

Written from the published configuration and the layer equations below, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``, with
no kernel, no cache, no absorbed form and no chunks: every head's keys and
values are EXPANDED from the latent over the whole sequence and each query
sees all its keys in one softmax; the experts run in a loop one after
another. It imports nothing of ``ray_tpu.models``.

    h1 = h  + A_0(N(h));   u = N(h1);   m = MoE(u);   h2 = h1 + F_0(u)
    h3 = h2 + A_1(N(h2));  h4 = h3 + F_1(N(h3)) + m
    eps 1e-5; embedding unscaled; final RMSNorm; untied head.

    A  c_q = N(x W_qa); [q_nope | q_rope]_h = s_q (c_q W_qb)_h, s_q =
       sqrt(hidden / q_lora_rank); [c | k_r] = x W_kva; c <- s_kv N(c), s_kv =
       sqrt(hidden / kv_lora_rank); [k_nope | v]_h = (c W_kvb)_h; rotary on
       q_rope and on the one k_r all heads share; score = (q_nope . k_nope +
       q_rope . k_r) / sqrt(dn + dr), causal softmax; out = [o_1 .. o_H] W_o
    F  W_down(silu(W_gate u) * W_up u)
    MoE  s = softmax(u W_r) over all router_width outputs; the top k of s + b;
       g_j = scale * s_(e_j) (not normalised); sum_j g_j E_(e_j)(u), E_e the
       gated expert e for e < n_real and E_e(u) = u for e >= n_real

Departures from the published model, each also under ``assumed`` in the
configuration's file:

- **The audio and vision encoders and the codec decoder are absent**: the
  catalog row's ``config`` is the language model's.
- **Rotary convention**: the config does not say; the DeepSeek-V3 family's
  interleaved pairs ``(2j, 2j + 1)``, theta ``rope_theta``, over
  ``qk_rope_head_dim``, no scaling below ``max_position_embeddings``.
- **Where the two latent scales apply**: ``s_q`` on the up-projected query,
  ``s_kv`` on the normed latent (so on ``k_nope`` and ``v`` alike, not on
  ``k_r``).
- No ``norm_topk_prob``; every weight is seeded, not trained; the selection
  bias ``b`` is what the program's initialiser calibrated from them (all
  ``router_width`` outputs picked alike over a seeded sample), and arrives
  here with the weights.
- **One chip's share.** ``weights`` holds ``n_routed_experts`` of the layer's
  computing experts, from ``expert_offset``; the router scores all
  ``router_width`` outputs, a computing expert that is not held adds nothing,
  here as in the program, and the zero experts are added whole. The
  vocabulary slice is the vocabulary.

Weights arrive in the published convention (a norm multiplies by its weight,
a projection is ``x @ W`` with ``W`` [in, out]); ``from_program_tree`` maps the
program's tree onto it (the program stores a norm's weight as an offset from
one). To fit beside a serving replica's model, the pieces run one at a time
under ``jit`` and upcast their own weights: attention ``HEAD_GROUP`` heads at
a time with ``QUERY_BLOCK`` queries a softmax (each still over ALL its keys),
a dense MLP an eighth of its width at a time, the experts one by one.

``forward(weights, tokens, shape, routing=None, rows=None)``: with ``routing``
given (int [layers, Lr, k]: the chosen experts of the first ``Lr`` positions)
those positions use THOSE experts, with this file's own scores for them;
later positions choose freely. It also returns, per expert layer, its own
free choice and how far each imposed choice lay under its own cut-off (as a
share of that cut-off), which is what a check of routing disagreements needs.
``rows`` names the positions whose logits are wanted (default: all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_GROUP = 8
QUERY_BLOCK = 256
MLP_SLICES = 8


def _rms_norm(x, weight, eps):
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


@functools.partial(jax.jit, static_argnames=("rank", "theta", "eps"))
def _attention_inputs(x, norm, w_qa, q_norm, w_kva, kv_norm, *, rank, theta,
                      eps):
    """-> (normed query latent c_q [L, q_rank], the scaled key/value latent c
    [L, rank], the rotated shared key k_r [L, dr])."""
    with jax.default_matmul_precision("highest"):
        d = x.shape[1]
        h = _rms_norm(x, norm.astype(F32), eps)
        c_q = _rms_norm(h @ w_qa.astype(F32), q_norm.astype(F32), eps)
        kv = h @ w_kva.astype(F32)
        c = _rms_norm(kv[:, :rank], kv_norm.astype(F32), eps) \
            * jnp.sqrt(F32(d / rank))
        return c_q, c, _rotary(kv[:, rank:], theta)


@functools.partial(jax.jit, static_argnames=("dn", "dv", "theta", "d_model"))
def _attention_heads(c_q, c, k_r, w_qb, w_kvb, wo, *, dn, dv, theta, d_model):
    """A group of heads over the whole sequence. w_qb [q_rank, G, dn + dr],
    w_kvb [rank, G, dn + dv], wo [G, dv, D] -> the group's part of the
    attention's output [L, D]."""
    with jax.default_matmul_precision("highest"):
        L = c_q.shape[0]
        s_q = jnp.sqrt(F32(d_model / c_q.shape[1]))
        q = jnp.einsum("lr,rgd->lgd", c_q, w_qb.astype(F32)) * s_q
        q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], theta)
        kv = jnp.einsum("lc,cgd->lgd", c, w_kvb.astype(F32))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        scale = 1.0 / jnp.sqrt(F32(dn + k_r.shape[1]))
        pad = -L % QUERY_BLOCK
        blocks = (L + pad) // QUERY_BLOCK

        def queries(args):
            qn, qr, t0 = args
            s = (jnp.einsum("qgd,kgd->gqk", qn, k_nope)
                 + jnp.einsum("qgr,kr->gqk", qr, k_r)) * scale
            ok = jnp.arange(L)[None, :] <= (t0 + jnp.arange(QUERY_BLOCK))[:, None]
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kgd->qgd", p, v)

        qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0)))
        qr = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0)))
        o = jax.lax.map(queries, (
            qn.reshape(blocks, QUERY_BLOCK, *qn.shape[1:]),
            qr.reshape(blocks, QUERY_BLOCK, *qr.shape[1:]),
            jnp.arange(blocks) * QUERY_BLOCK))
        o = o.reshape(L + pad, *o.shape[2:])[:L]
        return jnp.einsum("lgd,gdo->lo", o, wo.astype(F32))


def attention(x, w, shape):
    """x [L, D] float32 -> A(N(x)), head group by head group."""
    H = int(shape["num_attention_heads"])
    rank, dn = int(shape["kv_lora_rank"]), int(shape["qk_nope_head_dim"])
    dv, theta = int(shape["v_head_dim"]), float(shape["rope_theta"])
    c_q, c, k_r = _attention_inputs(
        x, w["norm"], w["w_qa"], w["q_norm"], w["w_kva"], w["kv_norm"],
        rank=rank, theta=theta, eps=float(shape["rms_norm_eps"]))
    w_qb = w["w_qb"].reshape(w["w_qb"].shape[0], H, -1)
    w_kvb = w["w_kvb"].reshape(rank, H, dn + dv)
    wo = w["wo"].reshape(H, dv, -1)
    out = jnp.zeros_like(x)
    G = min(HEAD_GROUP, H)
    for g in range(0, H, G):
        out = out + _attention_heads(
            c_q, c, k_r, w_qb[:, g:g + G], w_kvb[:, g:g + G], wo[g:g + G],
            dn=dn, dv=dv, theta=theta, d_model=x.shape[1])
    return out


@jax.jit
def _mlp_slice(u, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
                ) @ w_down.astype(F32)


def mlp(u, w):
    """W_down(silu(W_gate u) * W_up u), a slice of the width at a time (the
    sum over the width is the second product's own)."""
    F = w["w_gate"].shape[1]
    step = -(-F // MLP_SLICES)
    out = jnp.zeros_like(u)
    for a in range(0, F, step):
        out = out + _mlp_slice(u, w["w_gate"][:, a:a + step],
                               w["w_up"][:, a:a + step],
                               w["w_down"][a:a + step])
    return out


@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def _route(u, w_router, bias, imposed, n_imposed, *, top_k, scale):
    """-> (gates [L, k], the experts used [L, k], this file's own choice,
    how far the worst imposed expert's biased score lies under this file's
    own ``top_k``-th, as a share of it: 0 where the sets agree or nothing is
    imposed)."""
    with jax.default_matmul_precision("highest"):
        L = u.shape[0]
        s = jax.nn.softmax(u @ w_router.astype(F32), axis=-1)
        biased = s + bias.astype(F32)[None, :]
        top, own = jax.lax.top_k(biased, top_k)
        forced = (jnp.arange(L) < n_imposed)[:, None]
        chosen = jnp.where(forced, imposed, own)
        under = top[:, -1] - jnp.min(
            jnp.take_along_axis(biased, chosen, axis=-1), axis=-1)
        gates = jnp.take_along_axis(s, chosen, axis=-1) * scale
        return gates, chosen, own, jnp.maximum(under, 0.0) / top[:, -1]


@jax.jit
def _expert(u, gate, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
             ) @ w_down.astype(F32)
        return gate[:, None] * y


def experts(u, w, shape, imposed, n_imposed):
    """The expert layer on its normed input u [L, D] -> (m [L, D], own
    choice [L, k], under [L])."""
    n_real = int(shape["router_width"]) - int(shape["zero_expert_num"])
    offset = int(shape.get("expert_offset") or 0)
    gates, chosen, own, under = _route(
        u, w["w_router"], w["router_bias"], imposed, n_imposed,
        top_k=int(shape["moe_topk"]),
        scale=float(shape["routed_scaling_factor"]))
    # the zero-compute experts: gate x input, whole
    out = jnp.sum(jnp.where(chosen >= n_real, gates, 0.0), -1)[:, None] * u
    for e in range(w["w_up"].shape[0]):         # the experts held, in turn
        gate = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), -1)
        out = out + _expert(u, gate, w["w_gate"][e], w["w_up"][e],
                            w["w_down"][e])
    return out, own, under


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def forward(weights: dict, tokens, shape: dict, routing=None, rows=None
            ) -> dict:
    """[L] token ids -> {"logits" [L or len(rows), V] float32, "own_routing"
    [layers, L, k], "under" [layers, L]}, sublayer by sublayer."""
    eps = float(shape["rms_norm_eps"])
    top_k = int(shape["moe_topk"])
    L = len(tokens)
    x = weights["embed"][jnp.asarray(tokens)].astype(F32)
    own, under = [], []
    for li, w in enumerate(weights["layers"]):
        imposed = jnp.zeros((L, top_k), jnp.int32)
        n_imposed = 0
        if routing is not None:
            n_imposed = min(L, routing.shape[1])
            imposed = imposed.at[:n_imposed].set(
                jnp.asarray(routing[li][:n_imposed], jnp.int32))
        x = x + attention(x, w["attn"][0], shape)               # h1
        u = _norm(x, w["mlp"][0]["norm"], eps=eps)
        m, o, far = experts(u, w["moe"], shape, imposed, n_imposed)
        x = x + mlp(u, w["mlp"][0])                             # h2
        x = x + attention(x, w["attn"][1], shape)               # h3
        x = x + mlp(_norm(x, w["mlp"][1]["norm"], eps=eps),
                    w["mlp"][1]) + m                            # h4
        own.append(o)
        under.append(far)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return {"logits": _head(x, weights["final_norm"], weights["head"],
                            eps=eps),
            "own_routing": jnp.stack(own), "under": jnp.stack(under)}


def logits(weights: dict, tokens, shape: dict, routing=None):
    """[L] token ids -> [L, V] float32 logits."""
    return forward(weights, tokens, shape, routing)["logits"]


_NORMS = ("norm", "q_norm", "kv_norm")


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; each piece upcasts its own)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731

    def sub(d):
        return {k: one(v) if k in _NORMS else v for k, v in d.items()}

    return {
        "embed": params["embedding"], "head": params["lm_head"],
        "final_norm": one(params["norm"]),
        "layers": [{"attn": [sub(a) for a in lyr["attn"]],
                    "mlp": [sub(m) for m in lyr["mlp"]],
                    "moe": lyr["moe"]} for lyr in params["layers"]],
    }
