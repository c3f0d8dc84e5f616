"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type:
nemotron_h``): 52 blocks of three kinds, one mixer a block.

Written from the published configuration and description, in ``jax.numpy``
and float32 under ``default_matmul_precision("highest")``, with no kernel, no
cache, no chunks and no batching: the state-space recurrence runs over time
one position after another, the experts in a loop one after another. It
imports nothing of ``ray_tpu.models``.

    h <- h + mixer_i(RMSNorm_i(h)), eps 1e-5; final RMSNorm; untied head.

    M  [z | xBC | dt] = u W_in;  xBC <- silu(conv1d(xBC)) (depthwise, causal,
       kernel 4, bias);  x, B, C = split(xBC);  D_t = softplus(dt + dt_bias);
       H_t = exp(D_t A) H_(t-1) + D_t x_t (x) B_t, A = -exp(A_log);
       y_t = H_t C_t + D x_t;  y <- RMSNorm_groups(y * silu(z));  y W_out
    *  causal grouped-query attention, scale head_dim ** -0.5
    E  s = sigmoid(u W_r); top k of s + b; w = s[chosen] / (sum + 1e-20) * 2.5;
       sum_k w_k W_down,k relu(W_up,k u)^2 + W_down,s relu(W_up,s u)^2

Departures from the published model, each also under ``assumed`` in the
configuration's file:

- **No positional embedding in the attention layers.** The config carries
  ``rope_theta`` and ``partial_rotary_factor`` but the published
  ``nemotron_h`` attention applies no rotary embedding (position comes from
  the Mamba layers); there is no network here to check that, so it is an
  assumption, and the program and this file agree on it.
- ``A_log``, ``dt_bias``, ``D`` and every other weight are seeded, not
  trained; the selection bias ``b`` is what the program's initialiser
  calibrated from them (equal load over a seeded sample), and arrives here
  with the weights.
- The SSM state is float32 (this file is float32 throughout).
- **One chip's share.** ``weights`` holds ``experts_held`` of the layer's
  experts, from ``expert_offset``; the router scores all ``router_width``
  experts and an expert that is not held adds nothing, here as in the
  program. The vocabulary slice is the vocabulary.

Weights arrive in the published convention (a norm multiplies by its weight,
a projection is ``x @ W`` with ``W`` [in, out]); ``from_program_tree`` maps the
program's tree onto it (the program stores a norm's weight as an offset from
one). The blocks run one at a time under ``jit`` and upcast their own
weights, so one block's float32 copy (an ``E`` block: 0.72 GB) is all that
has to fit beside a serving replica's model.

``forward(weights, tokens, shape, routing=None)``: with ``routing`` given
(int [expert blocks, Lr, k]: the chosen experts of the first ``Lr``
positions) those positions use THOSE experts, with this file's own scores
for them; later positions choose freely. It also returns, per expert block,
its own free choice and how far each imposed choice lay under its own
cut-off, which is what a check of routing disagreements needs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _f32(w):
    return jax.tree.map(lambda a: a.astype(F32), w)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "groups",
                                             "state", "eps"))
def mamba_block(x, w, *, heads, head_dim, groups, state, eps):
    """x [L, D] float32 -> x + mixer(norm(x))."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        L = x.shape[0]
        di, gn = heads * head_dim, groups * state
        zxbcdt = _rms_norm(x, w["norm"], eps) @ w["w_in"]
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
        y = _rms_norm(y.reshape(L, groups, di // groups), 1.0, eps)
        y = y.reshape(L, di) * w["gate_norm"]
        return x + y @ w["w_out"]


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "eps"))
def attention_block(x, w, *, n_heads, n_kv_heads, eps):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        L = x.shape[0]
        h = _rms_norm(x, w["norm"], eps)
        q = (h @ w["wq"]).reshape(L, n_heads, -1)
        k = (h @ w["wk"]).reshape(L, n_kv_heads, -1)
        v = (h @ w["wv"]).reshape(L, n_kv_heads, -1)
        hd = q.shape[-1]
        k = jnp.repeat(k, n_heads // n_kv_heads, axis=1)
        v = jnp.repeat(v, n_heads // n_kv_heads, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        return x + o.reshape(L, n_heads * hd) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("top_k", "offset", "scale",
                                             "norm", "eps"))
def expert_block(x, w, imposed, n_imposed, *, top_k, offset, scale, norm,
                 eps):
    """-> (x + layer(norm(x)), this file's own choice [L, k], and per
    position how far the worst imposed expert's biased score lies under this
    file's own ``top_k``-th: 0 where the sets agree or nothing is imposed).
    ``imposed`` [L, k] holds the experts to use for positions below
    ``n_imposed`` (rows past it are ignored)."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        L = x.shape[0]
        u = _rms_norm(x, w["norm"], eps)
        s = jax.nn.sigmoid(u @ w["w_router"])               # [L, E]
        biased = s + w["router_bias"][None, :]
        top, own = jax.lax.top_k(biased, top_k)
        forced = (jnp.arange(L) < n_imposed)[:, None]
        chosen = jnp.where(forced, imposed, own)
        under = top[:, -1] - jnp.min(
            jnp.take_along_axis(biased, chosen, axis=-1), axis=-1)
        vals = jnp.take_along_axis(s, chosen, axis=-1)
        if norm:
            vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
        vals = vals * scale
        out = jnp.square(jax.nn.relu(u @ w["ws_up"])) @ w["ws_down"]
        for e in range(w["w_up"].shape[0]):     # the experts held, in turn
            gate = jnp.sum(jnp.where(chosen == offset + e, vals, 0.0), -1)
            out = out + gate[:, None] * (
                jnp.square(jax.nn.relu(u @ w["w_up"][e])) @ w["w_down"][e])
        return x + out, own, jnp.maximum(under, 0.0)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def forward(weights: dict, tokens, shape: dict, routing=None) -> dict:
    """[L] token ids -> {"logits" [L, V] float32, "own_routing"
    [expert blocks, L, k], "under" [expert blocks, L]}, block by block."""
    eps = float(shape["norm_eps"])
    pattern = shape["hybrid_override_pattern"]
    top_k = int(shape["num_experts_per_tok"])
    L = len(tokens)
    x = weights["embed"][jnp.asarray(tokens)].astype(F32)
    own, under, ei = [], [], 0
    for kind, w in zip(pattern, weights["layers"]):
        if kind == "M":
            x = mamba_block(x, w, heads=shape["mamba_num_heads"],
                            head_dim=shape["mamba_head_dim"],
                            groups=shape["n_groups"],
                            state=shape["ssm_state_size"], eps=eps)
        elif kind == "*":
            x = attention_block(x, w, n_heads=shape["num_attention_heads"],
                                n_kv_heads=shape["num_key_value_heads"],
                                eps=eps)
        else:
            imposed = jnp.zeros((L, top_k), jnp.int32)
            n_imposed = 0
            if routing is not None:
                n_imposed = min(L, routing.shape[1])
                imposed = imposed.at[:n_imposed].set(
                    jnp.asarray(routing[ei][:n_imposed], jnp.int32))
            x, o, u = expert_block(
                x, w, imposed, n_imposed, top_k=top_k,
                offset=int(shape.get("expert_offset") or 0),
                scale=float(shape["routed_scaling_factor"]),
                norm=bool(shape["norm_topk_prob"]), eps=eps)
            own.append(o)
            under.append(u)
            ei += 1
    return {"logits": _head(x, weights["final_norm"], weights["head"],
                            eps=eps),
            "own_routing": jnp.stack(own), "under": jnp.stack(under)}


def logits(weights: dict, tokens, shape: dict, routing=None):
    """[L] token ids -> [L, V] float32 logits."""
    return forward(weights, tokens, shape, routing)["logits"]


_NORMS = ("norm", "gate_norm")


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; each block upcasts its own)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731
    return {
        "embed": params["embedding"], "head": params["lm_head"],
        "final_norm": one(params["norm"]),
        "layers": [{k: one(v) if k in _NORMS else v for k, v in lyr.items()}
                   for lyr in params["layers"]],
    }
