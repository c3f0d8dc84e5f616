"""Plain reference of the DeepSeek-V3 family's language model (``model_type:
deepseek_v3``; GigaChat3.1-702B-A36B is the configuration): latent attention
under YaRN, a group-limited sigmoid router beside a shared expert, and the
multi-token-prediction (MTP) module.

Written from the published configuration and the layer equations below, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``, with
no kernel, no cache, no absorbed form, no chunks and no drafting: every head's
keys and values are EXPANDED from the latent over the whole sequence and each
query sees all its keys in one softmax; the experts run in a loop one after
another; the MTP module runs over the whole sequence at once. It imports
nothing of ``ray_tpu.models``.

    x = E[tokens];  x = x + A(N(x));  x = x + F(N(x));  logits = N(x) W_head
    eps ``rms_norm_eps``; embedding unscaled; untied head.

    A  c_q = N(h W_qa); [q_nope | q_rope]_h = (c_q W_qb)_h; [c | k_r] = h W_kva;
       c <- N(c); [k_nope | v]_h = (c W_kvb)_h (dn + dv a head); rotary on
       q_rope and on the one k_r all heads share, interleaved pairs, YaRN
       frequencies; score = (q_nope . k_nope + q_rope . k_r) * s, s =
       (dn + dr)^-0.5 * m(factor, mscale_all_dim)^2, m(f, a) = 0.1 a ln f + 1;
       causal softmax; out = [o_1 .. o_H] W_o
    YaRN  f_j = theta^(-2j/d); cd(n) = d ln(L0 / (2 pi n)) / (2 ln theta);
       low = floor(cd(beta_fast)), high = ceil(cd(beta_slow)), clipped to
       [0, d - 1]; ramp_j = clip((j - low) / (high - low), 0, 1); inv_freq_j =
       (f_j / factor) ramp_j + f_j (1 - ramp_j); cos and sin times
       m(factor, mscale) / m(factor, mscale_all_dim)
    F  of the first ``first_k_dense_replace`` layers W_down(silu(W_gate u) *
       W_up u); of the others shared(u) + routed(u): s = sigmoid(u W_r),
       s' = s + b; a group's score the sum of its two largest s'; the
       ``topk_group`` best of the ``n_group`` groups kept, the others' s' set
       to 0; the ``num_experts_per_tok`` largest s' chosen; gates the UNBIASED
       s of the chosen over their sum + 1e-20, times ``routed_scaling_factor``
    MTP  for position i: u_i = W_eh [N_e(E[t_(i+1)]) | N_h(g_i)], g_i the main
       model's output after its final norm; one decoder block of the expert
       kind over u_0 .. u_i, rotated at the positions 1 .. of the tokens it
       embeds; its own final norm; the main model's head: the logits of
       t_(i+2)

Departures from the published model, each also under ``assumed`` in the
configuration's file: the two MTP conventions above (the order of the two
halves, ``g`` after the final norm); the rotary pairing; every weight is
seeded, not trained; the selection bias ``b`` is what the program's
initialiser calibrated from them, and arrives here with the weights.
**One chip's share**: ``weights`` holds ``n_routed_experts`` of a layer's
``router_width`` experts, from ``expert_offset``; the router scores all its
outputs, an expert that is not held adds nothing, here as in the program; the
shared expert is added whole. The vocabulary slice is the vocabulary.

Weights arrive in the published convention (a norm multiplies by its weight,
a projection is ``x @ W`` with ``W`` [in, out]); ``from_program_tree`` maps the
program's tree onto it. To fit beside a serving replica's model, the pieces
run one at a time under ``jit`` and upcast their own weights.

``forward(weights, tokens, shape, routing=None, imposed=None, rows=None)``:
with ``routing`` (int [expert layers, L, k], the MTP block's last) and
``imposed`` (bool [expert layers, L]) a position whose flag is set uses THOSE
experts, with this file's own scores for them; every other position chooses
freely. It also returns, per expert layer, its own free choice and how far
each imposed choice lay under its own cut-offs (the kept groups' and the
chosen experts', as a share of each), which is what a check of routing
disagreements needs. ``rows`` names the positions whose logits are wanted
(default: all).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_GROUP = 8
QUERY_BLOCK = 256
MLP_SLICES = 8


def yarn_of(shape: dict):
    """(inverse frequencies [dr / 2] float64, what cos and sin are multiplied
    by, the softmax scale) at the published numbers."""
    sc = dict(zip(("factor", "original_max_position_embeddings", "beta_fast",
                   "beta_slow", "mscale", "mscale_all_dim"), shape["yarn"]))
    d, theta = int(shape["qk_rope_head_dim"]), float(shape["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def cd(n):
        return d * math.log(sc["original_max_position_embeddings"]
                            / (2 * math.pi * n)) / (2 * math.log(theta))

    def m(factor, a):
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(cd(sc["beta_fast"])), 0)
    high = min(math.ceil(cd(sc["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = f / sc["factor"] * ramp + f * (1 - ramp)
    scale = (int(shape["qk_nope_head_dim"]) + d) ** -0.5 \
        * m(sc["factor"], sc["mscale_all_dim"]) ** 2
    return (inv_freq, m(sc["factor"], sc["mscale"])
            / m(sc["factor"], sc["mscale_all_dim"]), scale)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, inv_freq, first, mult):
    """x [L, ..., d] at positions ``first .. first + L - 1``: pair ``(2j, 2j +
    1)`` turned by the angle ``t * inv_freq_j``, times ``mult``."""
    L, d = x.shape[0], x.shape[-1]
    angle = ((first + jnp.arange(L, dtype=F32))[:, None]
             * inv_freq.astype(F32)[None, :])
    angle = angle.reshape((L,) + (1,) * (x.ndim - 2) + (d // 2,))
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(1j * angle) \
        * mult
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("rank", "first", "mult", "eps"))
def _attention_inputs(x, norm, w_qa, q_norm, w_kva, kv_norm, inv_freq, *,
                      rank, first, mult, eps):
    """-> (normed query latent c_q [L, q_rank], the normed key/value latent c
    [L, rank], the rotated shared key k_r [L, dr])."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm.astype(F32), eps)
        c_q = _rms_norm(h @ w_qa.astype(F32), q_norm.astype(F32), eps)
        kv = h @ w_kva.astype(F32)
        c = _rms_norm(kv[:, :rank], kv_norm.astype(F32), eps)
        return c_q, c, _rotary(kv[:, rank:], inv_freq, first, mult)


@functools.partial(jax.jit, static_argnames=("dn", "first", "mult", "scale"))
def _attention_heads(c_q, c, k_r, w_qb, w_kvb, wo, inv_freq, *, dn, first,
                     mult, scale):
    """A group of heads over the whole sequence. w_qb [q_rank, G, dn + dr],
    w_kvb [rank, G, dn + dv], wo [G, dv, D] -> the group's part of the
    attention's output [L, D]."""
    with jax.default_matmul_precision("highest"):
        L = c_q.shape[0]
        q = jnp.einsum("lr,rgd->lgd", c_q, w_qb.astype(F32))
        q_nope = q[..., :dn]
        q_rope = _rotary(q[..., dn:], inv_freq, first, mult)
        kv = jnp.einsum("lc,cgd->lgd", c, w_kvb.astype(F32))
        k_nope, v = kv[..., :dn], kv[..., dn:]
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


def attention(x, w, shape, first: int = 0):
    """x [L, D] float32 at positions ``first ..`` -> A(N(x)), head group by
    head group."""
    H = int(shape["num_attention_heads"])
    rank, dn = int(shape["kv_lora_rank"]), int(shape["qk_nope_head_dim"])
    dv = int(shape["v_head_dim"])
    inv_freq, mult, scale = yarn_of(shape)
    inv_freq = jnp.asarray(inv_freq, F32)
    c_q, c, k_r = _attention_inputs(
        x, w["norm"], w["w_qa"], w["q_norm"], w["w_kva"], w["kv_norm"],
        inv_freq, rank=rank, first=first, mult=float(mult),
        eps=float(shape["rms_norm_eps"]))
    w_qb = w["w_qb"].reshape(w["w_qb"].shape[0], H, -1)
    w_kvb = w["w_kvb"].reshape(rank, H, dn + dv)
    wo = w["wo"].reshape(H, dv, -1)
    out = jnp.zeros_like(x)
    G = min(HEAD_GROUP, H)
    for g in range(0, H, G):
        out = jax.block_until_ready(out + _attention_heads(
            c_q, c, k_r, w_qb[:, g:g + G], w_kvb[:, g:g + G], wo[g:g + G],
            inv_freq, dn=dn, first=first, mult=float(mult),
            scale=float(scale)))
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
        out = jax.block_until_ready(out + _mlp_slice(
            u, w["w_gate"][:, a:a + step], w["w_up"][:, a:a + step],
            w["w_down"][a:a + step]))
    return out


#: a group whose score stands over this file's group cut-off by less than this
#: share of it is at a near tie: a program that rounds otherwise may keep the
#: next group in its place
GROUP_TIE = 0.05


@functools.partial(jax.jit, static_argnames=("n_group", "topk_group",
                                             "top_k", "scale"))
def _route(u, w_router, bias, chosen_for, imposed, *, n_group, topk_group,
           top_k, scale):
    """-> (gates [L, k], the experts used [L, k], this file's own choice, how
    far the imposed experts lie under this file's own cut-offs: the worse of
    (its weakest kept group's score less the weakest group an imposed expert
    stands in, as a share of the former) and (the ``top_k``-th s' less the
    weakest imposed expert's, as a share of the former, among the groups the
    imposed experts stand in and the groups that clear this file's group
    cut-off by more than ``GROUP_TIE``: a group at a near tie the program may
    have kept or not, and its experts are held against nobody); 0 where the
    sets agree or nothing is imposed)."""
    with jax.default_matmul_precision("highest"):
        L, E = u.shape[0], w_router.shape[1]
        size = E // n_group
        s = jax.nn.sigmoid(u @ w_router.astype(F32))
        biased = s + bias.astype(F32)[None, :]
        of_group = jax.lax.top_k(biased.reshape(L, n_group, size), 2)[0].sum(-1)
        best, kept = jax.lax.top_k(of_group, topk_group)
        groups = jnp.arange(n_group)

        def only(keep):     # s' with the groups not kept set to 0
            return jnp.where(jnp.repeat(keep, size, axis=1), biased, 0.0)

        keep = jnp.any(kept[:, :, None] == groups, axis=1)      # [L, G]
        _, own = jax.lax.top_k(only(keep), top_k)
        chosen = jnp.where(imposed[:, None], chosen_for, own)
        stands = chosen // size                                  # [L, k]
        g_under = jnp.max(best[:, -1:] - jnp.take_along_axis(
            of_group, stands, axis=-1), axis=-1) / best[:, -1]
        # the experts' cut-off among the groups the chosen experts stand in
        # and those any program kept (clear of the group cut-off)
        used = jnp.any(stands[:, :, None] == groups, axis=1)
        seen = only(used | (of_group > best[:, -1:] * (1.0 + GROUP_TIE)))
        top = jax.lax.top_k(seen, top_k)[0]
        e_under = (top[:, -1] - jnp.min(jnp.take_along_axis(
            seen, chosen, axis=-1), axis=-1)) / top[:, -1]
        gates = jnp.take_along_axis(s, chosen, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) * scale
        return gates, chosen, own, jnp.maximum(
            jnp.maximum(g_under, e_under), 0.0)


@jax.jit
def _expert(u, gate, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
             ) @ w_down.astype(F32)
        return gate[:, None] * y


def experts(u, moe, shared, shape, chosen_for, imposed):
    """The expert layer on its normed input u [L, D] -> (shared(u) +
    routed(u) [L, D], own choice [L, k], under [L])."""
    offset = int(shape.get("expert_offset") or 0)
    gates, chosen, own, under = _route(
        u, moe["w_router"], moe["router_bias"], chosen_for, imposed,
        n_group=int(shape["n_group"]), topk_group=int(shape["topk_group"]),
        top_k=int(shape["num_experts_per_tok"]),
        scale=float(shape["routed_scaling_factor"]))
    out = mlp(u, shared)
    for e in range(moe["w_up"].shape[0]):       # the experts held, in turn
        gate = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), -1)
        # ... and waited for: dispatched ahead of the device, every expert's
        # float32 [L, D] pieces stand in memory at once, beside an engine
        out = jax.block_until_ready(out + _expert(
            u, gate, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e]))
    return out, own, under


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight.astype(F32), eps)


@jax.jit
def _head(g, head):
    with jax.default_matmul_precision("highest"):
        return g @ head.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _mtp_input(e, g, enorm, hnorm, w_eh, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_rms_norm(e, enorm.astype(F32), eps),
             _rms_norm(g, hnorm.astype(F32), eps)], -1) @ w_eh.astype(F32)


def _layer(x, w, shape, eps, chosen_for, imposed, first=0):
    """One decoder layer -> (x, own choice or None, under or None)."""
    x = x + attention(x, w["attn"], shape, first)
    u = _norm(x, w["ffn_norm"], eps=eps)
    if "mlp" in w:
        return x + mlp(u, w["mlp"]), None, None
    y, own, under = experts(u, w["moe"], w["shared"], shape, chosen_for,
                            imposed)
    return x + y, own, under


def forward(weights: dict, tokens, shape: dict, routing=None, imposed=None,
            rows=None, mtp_rows=None) -> dict:
    """[L] token ids -> {"logits" [L or len(rows), V] float32, "mtp_logits"
    [L - 1 or len(mtp_rows), V] (row ``i`` from ``(g_i, t_(i+1))``: the
    logits of ``t_(i+2)``; absent without a module), "own_routing" and
    "under": one entry an expert layer, the MTP block's last, ([L, k] and [L];
    the MTP block's over its L - 1 rows)}."""
    eps = float(shape["rms_norm_eps"])
    top_k = int(shape["num_experts_per_tok"])
    tokens = jnp.asarray(tokens)
    L = tokens.shape[0]
    x = weights["embed"][tokens].astype(F32)
    own, under = [], []

    def given(e, n):
        if routing is None:
            return jnp.zeros((n, top_k), jnp.int32), jnp.zeros((n,), bool)
        return (jnp.asarray(routing[e][:n], jnp.int32),
                jnp.asarray(imposed[e][:n], bool))

    for w in weights["layers"]:
        x, o, far = _layer(x, w, shape, eps, *given(len(own), L))
        if o is not None:
            own.append(o)
            under.append(far)
    g = _norm(x, weights["final_norm"], eps=eps)
    out = {"logits": _head(g if rows is None else g[jnp.asarray(rows)],
                           weights["head"])}
    for m in weights["mtp"][:1]:
        u = _mtp_input(weights["embed"][tokens[1:]].astype(F32), g[:-1],
                       m["enorm"], m["hnorm"], m["eh_proj"], eps=eps)
        xm, o, far = _layer(u, m["layer"], shape, eps,
                            *given(len(own), L - 1), first=1)
        own.append(o)
        under.append(far)
        gm = _norm(xm, m["norm"], eps=eps)
        out["mtp_logits"] = _head(
            gm if mtp_rows is None else gm[jnp.asarray(mtp_rows)],
            weights["head"])
    out["own_routing"], out["under"] = own, under
    return out


def logits(weights: dict, tokens, shape: dict):
    """[L] token ids -> [L, V] float32 logits."""
    return forward(weights, tokens, shape)["logits"]


_NORMS = ("norm", "q_norm", "kv_norm", "ffn_norm", "enorm", "hnorm")


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; each piece upcasts its own)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731

    def sub(d):
        return {k: (one(v) if k in _NORMS else sub(v) if isinstance(v, dict)
                    else v) for k, v in d.items()}

    return {
        "embed": params["embedding"], "head": params["lm_head"],
        "final_norm": one(params["norm"]),
        "layers": [sub(lyr) for lyr in params["layers"]],
        "mtp": [sub(m) for m in params["mtp"]],
    }
