"""Plain reference of Granite-4.0-H-Small's language model (``model_type:
granitemoehybrid``): Mamba-2 layers nine to one attention layer without
positions, a top-10-of-72 SwiGLU expert layer beside a shared expert behind
every mixer, four scalar multipliers, a tied head.

Written from the published configuration and the layer equations below, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``, with
no kernel, no cache, no chunks, no tails, no pages and no batching: the
state-space recurrence runs over time one position after another from a zero
state, the convolution sees the whole sequence (zeros left of position 0),
every query sees all its keys in one softmax, the experts run in a loop one
after another. It imports nothing of ``ray_tpu.models``.

    x_0 = E[t] * embedding_multiplier;   r = residual_multiplier
    h = x_l + r * Mixer_l(RMS_mixer(x_l))
    x_{l+1} = h + r * (Routed_l(u) + Shared_l(u)),   u = RMS_ffn(h)
    logits = RMS_f(x_L) E^T / logits_scaling                    (tied head)

    RMS    x * rsqrt(mean x^2 + eps) * g, eps rms_norm_eps
    mamba  [z | xBC | dt] = u W_in (d_inner, d_inner + 2 G N, H; no bias);
           xBC <- silu(conv1d(xBC)) (depthwise, causal, kernel mamba_d_conv,
           bias);  x [H x P], B, C [G x N] = split(xBC);
           D_t = softplus(dt + dt_bias);  A = -exp(A_log);
           H_t = exp(D_t A) H_(t-1) + D_t x_t (x) B_t;  y_t = H_t C_t + D x_t;
           y <- RMS_groups(y * silu(z)) (G groups; one here);  y W_out
    attn   q = u W_q (H heads of d), k = u W_k, v = u W_v (kvh heads of d),
           no bias, NO positional embedding; query head 4 g + r reads K/V
           head g; causal softmax of q . k * attention_multiplier; [o] W_o
    routed l = u W_r over all num_local_experts (float32, no bias); the k
           largest; g = softmax over those k logits; sum_e g_e E_e(u),
           E(u) = W_down(silu(W_gate u) * W_up u) at intermediate_size
    shared the same SwiGLU at shared_intermediate_size

Departures from the published model, each also under ``assumed`` in the
configuration's file: ``head_dim`` is ``hidden_size / num_attention_heads``;
the fused ``input_linear`` of an expert and of the shared expert is split as
gate then up; no ``time_step_limit`` clamps ``D_t``; every weight is seeded,
and the seeded routers are balanced in their weights at initialisation (they
arrive here with the weights; no bias exists). The layers held are the first
``num_hidden_layers`` of ``layer_types``. **One chip's share**: ``weights``
holds ``num_local_experts`` of a layer's experts from ``expert_offset``; the
router scores all ``router_width`` experts and an expert that is not held
adds nothing, here as in the program. The vocabulary slice is the vocabulary.

Weights arrive in the published convention (a norm multiplies by its weight,
a projection is ``x @ W`` with ``W`` [in, out]); ``from_program_tree`` maps the
program's tree onto it (the program stores a norm's weight as an offset from
one). To fit beside a serving replica's model, the pieces run one at a time
under ``jit`` and upcast their own weights: attention one K/V head's group of
query heads at a time with ``QUERY_BLOCK`` queries a softmax (each still over
ALL its keys), the experts one by one, the head ``VOCAB_BLOCK`` rows of the
table at a time.

``forward(weights, tokens, shape, routing=None, rows=None)``: with ``routing``
given (int [layers, Lr, k]: the chosen experts of the first ``Lr`` positions)
those positions use THOSE experts, with this file's own logits for them;
later positions choose freely. It also returns, per layer, its own free
choice and how far each imposed choice lay under its own cut-off (the tenth
logit), in units of the spread of that position's logits over the experts,
which is what a check of routing disagreements needs. ``rows`` names the
positions whose logits are wanted (default: all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256
VOCAB_BLOCK = 8192


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _f32(w):
    return jax.tree.map(lambda a: a.astype(F32), w)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms(x, weight.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "groups",
                                             "state", "eps"))
def mamba_mixer(u, w, *, heads, head_dim, groups, state, eps):
    """The Mamba-2 mixer on the normed u [L, D] -> [L, D], from a zero state,
    position by position."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        L = u.shape[0]
        di, gn = heads * head_dim, groups * state
        zxbcdt = u @ w["w_in"]
        z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + di + 2 * gn],
                      zxbcdt[:, di + di + 2 * gn:])
        K = w["conv_w"].shape[0]
        padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
        conv = w["conv_b"][None, :] + sum(
            padded[k:k + L] * w["conv_w"][k][None, :] for k in range(K))
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :di].reshape(L, heads, head_dim)
        B = jnp.repeat(xbc[:, di:di + gn].reshape(L, groups, state),
                       heads // groups, axis=1)             # [L, H, N]
        C = jnp.repeat(xbc[:, di + gn:].reshape(L, groups, state),
                       heads // groups, axis=1)
        step = jax.nn.softplus(dt + w["dt_bias"][None, :])  # [L, H]
        A = -jnp.exp(w["A_log"])

        def one(H, inp):
            xt, bt, ct, st = inp
            H = (jnp.exp(st * A)[:, None, None] * H
                 + (st[:, None] * xt)[:, :, None] * bt[:, None, :])
            return H, jnp.einsum("hpn,hn->hp", H, ct)

        _, y = jax.lax.scan(one, jnp.zeros((heads, head_dim, state), F32),
                            (xs, B, C, step))
        y = y + w["D"][None, :, None] * xs
        y = y.reshape(L, di) * jax.nn.silu(z)
        y = _rms(y.reshape(L, groups, di // groups), 1.0, eps)
        y = y.reshape(L, di) * w["gate_norm"]
        return y @ w["w_out"]


_MAMBA_KEYS = ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
               "gate_norm", "w_out")


@functools.partial(jax.jit, static_argnames=("scale",))
def _attention_group(h, wq, wk, wv, wo, *, scale):
    """One K/V head and its group of query heads over the whole sequence.
    h [L, D] normed; wq [D, G, d], wk and wv [D, d], wo [G, d, D] -> the
    group's part of the attention's output [L, D]."""
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        q = jnp.einsum("ld,dgk->lgk", h, wq.astype(F32))
        k, v = h @ wk.astype(F32), h @ wv.astype(F32)
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
            w["wv"][:, g * d:(g + 1) * d], wo[g],
            scale=float(shape["attention_multiplier"]))
    return out


@functools.partial(jax.jit, static_argnames=("top_k",))
def _route(u, w_router, imposed, n_imposed, *, top_k):
    """-> (gates [L, k], the experts used [L, k], this file's own choice, how
    far the worst imposed expert's logit lies under this file's own
    ``top_k``-th, in units of the position's spread of logits over the
    experts: 0 where the sets agree or nothing is imposed)."""
    with jax.default_matmul_precision("highest"):
        L = u.shape[0]
        logit = u @ w_router.astype(F32)                    # [L, E]
        top, own = jax.lax.top_k(logit, top_k)
        forced = (jnp.arange(L) < n_imposed)[:, None]
        chosen = jnp.where(forced, imposed, own)
        picked = jnp.take_along_axis(logit, chosen, axis=-1)
        under = top[:, -1] - jnp.min(picked, axis=-1)
        return (jax.nn.softmax(picked, axis=-1), chosen, own,
                jnp.maximum(under, 0.0) / jnp.std(logit, axis=-1))


@jax.jit
def _swiglu(u, gate, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
             ) @ w_down.astype(F32)
        return gate[:, None] * y


def experts(u, moe, shape, imposed, n_imposed):
    """The routed experts held on the normed u [L, D] -> (routed [L, D], own
    choice [L, k], under [L])."""
    offset = int(shape.get("expert_offset") or 0)
    gates, chosen, own, under = _route(
        u, moe["w_router"], imposed, n_imposed,
        top_k=int(shape["num_experts_per_tok"]))
    out = jnp.zeros_like(u)
    for e in range(moe["w_up"].shape[0]):       # the experts held, in turn
        gate = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), -1)
        out = out + _swiglu(u, gate, moe["w_gate"][e], moe["w_up"][e],
                            moe["w_down"][e])
    return out, own, under


@functools.partial(jax.jit, static_argnames=("scaling",))
def _head_block(x, rows, *, scaling):
    with jax.default_matmul_precision("highest"):
        return x @ rows.astype(F32).T / scaling


def _head(x, final_norm, embed, shape):
    x = _norm(x, final_norm, eps=float(shape["rms_norm_eps"]))
    return jnp.concatenate([
        _head_block(x, embed[v:v + VOCAB_BLOCK],
                    scaling=float(shape["logits_scaling"]))
        for v in range(0, embed.shape[0], VOCAB_BLOCK)], axis=-1)


def forward(weights: dict, tokens, shape: dict, routing=None, rows=None
            ) -> dict:
    """[L] token ids -> {"logits" [L or len(rows), V] float32, "own_routing"
    [layers, L, k], "under" [layers, L]}, layer by layer."""
    eps = float(shape["rms_norm_eps"])
    top_k = int(shape["num_experts_per_tok"])
    r = float(shape["residual_multiplier"])
    kinds = list(shape["layer_types"])[:int(shape["num_hidden_layers"])]
    L = len(tokens)
    x = weights["embed"][jnp.asarray(tokens)].astype(F32) \
        * float(shape["embedding_multiplier"])
    own, under = [], []
    for kind, w in zip(kinds, weights["layers"]):
        u = _norm(x, w["mixer_norm"], eps=eps)
        if kind == "mamba":
            x = x + r * mamba_mixer(
                u, {k: w[k] for k in _MAMBA_KEYS},
                heads=int(shape["mamba_n_heads"]),
                head_dim=int(shape["mamba_d_head"]),
                groups=int(shape["mamba_n_groups"]),
                state=int(shape["mamba_d_state"]), eps=eps)
        else:
            x = x + r * attention(u, w, shape)
        u = _norm(x, w["ffn_norm"], eps=eps)
        imposed = jnp.zeros((L, top_k), jnp.int32)
        n_imposed = 0
        if routing is not None:
            n_imposed = min(L, routing.shape[1])
            imposed = imposed.at[:n_imposed].set(
                jnp.asarray(routing[len(own)][:n_imposed], jnp.int32))
        m, o, far = experts(u, w["moe"], shape, imposed, n_imposed)
        sh = w["shared"]
        x = x + r * (m + _swiglu(u, jnp.ones((L,), F32), sh["w_gate"],
                                 sh["w_up"], sh["w_down"]))
        own.append(o)
        under.append(far)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return {"logits": _head(x, weights["final_norm"], weights["embed"],
                            shape),
            "own_routing": jnp.stack(own), "under": jnp.stack(under)}


def logits(weights: dict, tokens, shape: dict, routing=None):
    """[L] token ids -> [L, V] float32 logits."""
    return forward(weights, tokens, shape, routing)["logits"]


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; each piece upcasts its own)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731
    norms = ("mixer_norm", "ffn_norm", "gate_norm")
    return {
        "embed": params["embedding"], "final_norm": one(params["norm"]),
        "layers": [{**lyr, **{n: one(lyr[n]) for n in norms if n in lyr}}
                   for lyr in params["layers"]],
    }
