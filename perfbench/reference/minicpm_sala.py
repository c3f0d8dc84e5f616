"""Plain reference of MiniCPM-SALA (``model_type: minicpm_sala``): lightning
linear-attention layers with a block-sparse attention layer among every few.

Written from the published configuration and the family's descriptions, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``, with
no kernel, no cache, no chunks and no batching: the linear-attention
recurrence runs over time one position after another, and every query of a
sparse layer makes its own choice of blocks straight from the equations. It
imports nothing of ``ray_tpu.models``.

    h0 = scale_emb E[tok];   a = scale_depth / sqrt(published_depth)
    h <- h + a mixer_i(RMSNorm(h));   h <- h + a W_down(silu(W_gate u) * W_up u),
    u = RMSNorm(h);   logits = (RMSNorm(h) / (hidden_size / dim_model_base)) W_head

    lightning-attn   q = N_q(x W_q), k = N_k(x W_k), v = x W_v (N: RMSNorm per
       head); rotary on q, k over the whole head; q <- q / sqrt(d);
       S_t = l_h S_(t-1) + k_t^T v_t;  o_t = q_t S_t;  o <- RMSNorm(o) (all
       heads together) * sigmoid(x W_g);  o W_o
    minicpm4         q = N_q(x W_q), k = N_k(x W_k), v = x W_v, no rotary, 16
       query heads a K/V head, scale d^-0.5. Context n = t + 1 <= dense_len:
       causal softmax over every key. Else per K/V head: compressed keys
       c_j = mean(k_i, stride j <= i < stride j + kernel) for every j with
       stride j + kernel <= n; a_(h,j) = softmax_j(q_h . c_j / sqrt(d));
       A_j = sum of a_(h,j) over the group's heads; B_b = max(A_j over the
       windows j that overlap block b and exist); blocks b < init_blocks and
       b >= floor(t / block) - (window / block - 1) are forced; the topk
       highest B among b <= floor(t / block) are read; softmax over the keys
       i <= t of those blocks.  o <- o * sigmoid(x W_g);  o W_o

Departures from the published model and sizes the published ``config`` does
not carry, each also under ``assumed`` in the configuration's file:

- **The decay** is not in the config: Lightning Attention's convention,
  ``l_h = exp(-s_h)``, ``s_h = 2^(-8 (h+1) / H) (1 - i / (published_depth - 1)
  + 1e-5)`` for head ``h`` (from 0) of PUBLISHED layer ``i``.
- **The sparse sizes** (MiniCPM4's ``sparse_config``): kernel 32, stride 16,
  block 64, topk 64 counted with the forced blocks, 1 initial block, window
  2048, dense_len 8192, one-stage scoring as above. They arrive in ``shape``
  under ``sparse_*``.
- ``qk_norm`` on both mixers, the output norm over all 4096 channels.
- Every weight is seeded, not trained.
- **The held layers.** ``shape["mixer_types"]`` lists the layers held and
  ``shape["layer_offset"]`` the published index of the first: half the
  published depth with the embedding and the head, one stage of two.

Weights arrive in the published convention (a norm multiplies by its weight,
a projection is ``x @ W`` with ``W`` [in, out]); ``from_program_tree`` maps the
program's tree onto it. The layers run one at a time under ``jit`` and upcast
their own weights, and work in blocks of positions, so one layer's float32
copy (1.1 GB) and a few hundred megabytes of activations are all that has to
fit beside a serving replica's model.

``forward(weights, tokens, shape, selection=None, rows=None)``: with
``selection`` given (int [sparse layers, L, kvh, topk]: the blocks each
position past ``dense_len`` reads) a sparse layer attends over THOSE blocks;
it also returns its own free choice and how far under its own cut-off the
imposed choice's worst block lay, which is what a check of the selection
needs. ``rows`` limits the logits to those positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _f32(w):
    return jax.tree.map(lambda a: a.astype(F32), w)


def _mlp(x, w, a, eps, rows):
    """h <- h + a W_down(silu(W_gate u) * W_up u), ``rows`` positions at a
    time."""
    L = x.shape[0]
    pad = (-L) % rows

    def some(xb):
        u = _rms_norm(xb, w["mlp_norm"], eps)
        return xb + a * ((jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"]))
                         @ w["w_down"])

    out = jax.lax.map(some, jnp.pad(x, ((0, pad), (0, 0))).reshape(
        -1, rows, x.shape[1]))
    return out.reshape(-1, x.shape[1])[:L]


def _rotate(x, theta):
    """Rotary embedding over the whole head, halves convention. x [L, H, d]."""
    L, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = jnp.arange(L, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def decay_slopes(heads: int, layer: int, depth: int):
    """s_h of published layer ``layer`` of ``depth``, heads from 0."""
    h = jnp.arange(1, heads + 1, dtype=F32)
    return 2.0 ** (-8.0 * h / heads) * (1.0 - layer / (depth - 1) + 1e-5)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "theta", "a", "rows"))
def lightning_block(x, w, slope, *, heads, eps, theta, a, rows):
    """``slope`` [heads]: the layer's ``s_h`` (an operand, so that the
    layers share one compiled program)."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        L = x.shape[0]
        u = _rms_norm(x, w["norm"], eps)
        q = _rms_norm((u @ w["wq"]).reshape(L, heads, -1), w["q_norm"], eps)
        k = _rms_norm((u @ w["wk"]).reshape(L, heads, -1), w["k_norm"], eps)
        v = (u @ w["wv"]).reshape(L, heads, -1)
        d = q.shape[-1]
        q, k = _rotate(q, theta) / math.sqrt(d), _rotate(k, theta)
        decay = jnp.exp(-slope)[:, None, None]

        def step(S, qkv):
            qt, kt, vt = qkv
            S = decay * S + kt[:, :, None] * vt[:, None, :]
            return S, jnp.sum(qt[:, :, None] * S, axis=1)

        _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), F32), (q, k, v))
        o = _rms_norm(o.reshape(L, heads * d), w["o_norm"], eps)
        x = x + a * ((o * jax.nn.sigmoid(u @ w["wg"])) @ w["wo"])
        return _mlp(x, w, a, eps, rows)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "eps", "a", "kernel", "stride", "block", "topk",
    "init_blocks", "window", "dense_len", "rows", "impose"))
def sparse_block(x, w, imposed, *, n_heads, n_kv_heads, eps, a, kernel,
                 stride, block, topk, init_blocks, window, dense_len, rows,
                 impose):
    """-> (x, own choice [L, kvh, topk] (-1 for a position in the dense
    regime), under [L, kvh]: how far the imposed choice's worst block lay
    under this file's own cut-off, as a share of the cut-off; 1 where the
    imposed choice lacks a forced block)."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        L = x.shape[0]
        rep = n_heads // n_kv_heads
        u = _rms_norm(x, w["norm"], eps)
        q = _rms_norm((u @ w["wq"]).reshape(L, n_heads, -1), w["q_norm"], eps)
        k = _rms_norm((u @ w["wk"]).reshape(L, n_kv_heads, -1), w["k_norm"],
                      eps)
        v = (u @ w["wv"]).reshape(L, n_kv_heads, -1)
        d = q.shape[-1]
        J = max((L - kernel) // stride + 1, 1)
        win = jnp.minimum(jnp.arange(J)[:, None] * stride
                          + jnp.arange(kernel)[None, :], L - 1)
        c = k[win].mean(axis=1)                               # [J, kvh, d]
        nb = max(-(-L // block), topk)
        r = block // stride
        # the windows that overlap block b: r b - kernel/stride + 1 .. r b + r - 1
        jb = (jnp.arange(nb)[:, None] * r - kernel // stride + 1
              + jnp.arange(r + kernel // stride - 1)[None, :])  # [nb, 5]
        key_pos = jnp.arange(L)
        pad = (-L) % rows
        qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, rows, n_kv_heads, rep, d)
        imp = jnp.pad(imposed, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, rows, n_kv_heads, topk)

        def some(args):
            qs, t0, chosen_in = args
            t = t0 + jnp.arange(rows)
            n = t + 1
            exists = (jnp.arange(J)[None, :] * stride + kernel
                      <= n[:, None])                            # [rows, J]
            s = jnp.einsum("qgrd,jgd->qgrj", qs, c) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(exists[:, None, None, :], s,
                                         -jnp.inf), axis=-1)
            p = jnp.where(exists[:, None, None, :], p, 0.0)
            A = jnp.where(exists[:, None, :], p.sum(axis=2), -jnp.inf)
            Aj = jnp.where(((jb >= 0) & (jb < J))[None, None],
                           A[:, :, jnp.clip(jb, 0, J - 1)], -jnp.inf)
            B = Aj.max(axis=-1)                                 # [rows, kvh, nb]
            b = jnp.arange(nb)[None, :]
            cur = (t // block)[:, None]
            forced = ((b < init_blocks) | (b >= cur - (window // block - 1))
                      ) & (b <= cur)
            B = jnp.where(forced[:, None, :], jnp.inf, B)
            B = jnp.where((b <= cur)[:, None, :], B, -jnp.inf)
            vals, own = jax.lax.top_k(B, topk)
            chosen = chosen_in if impose else own
            picked = jnp.take_along_axis(B, chosen, axis=-1)
            picked = jnp.where(chosen <= cur[:, :, None], picked, jnp.inf)
            cut = vals[..., -1]
            under = jnp.maximum(cut - picked.min(axis=-1), 0.0) / cut
            has = (chosen[..., None] == jnp.arange(nb)).any(axis=2)
            lacks = (forced[:, None, :] & ~has).any(axis=-1)
            under = jnp.where(lacks, 1.0, under)
            sparse = (n > dense_len)[:, None]
            read = has | ~sparse[..., None]                     # [rows, kvh, nb]
            mask = read[:, :, key_pos // block] \
                & (key_pos[None, :] <= t[:, None])[:, None, :]
            sc = jnp.einsum("qgrd,kgd->qgrk", qs, k) / math.sqrt(d)
            pr = jax.nn.softmax(jnp.where(mask[:, :, None, :], sc, -jnp.inf),
                                axis=-1)
            o = jnp.einsum("qgrk,kgd->qgrd", pr, v)
            return (o.reshape(rows, n_heads * d),
                    jnp.where(sparse[..., None], own, -1),
                    jnp.where(sparse, under, 0.0))

        o, own, under = jax.lax.map(
            some, (qg, jnp.arange(qg.shape[0]) * rows, imp))
        o = o.reshape(-1, n_heads * d)[:L]
        x = x + a * ((o * jax.nn.sigmoid(u @ w["wg"])) @ w["wo"])
        return (_mlp(x, w, a, eps, rows),
                own.reshape(-1, n_kv_heads, topk)[:L],
                under.reshape(-1, n_kv_heads)[:L])


@functools.partial(jax.jit, static_argnames=("eps", "div"))
def _head(x, final_norm, head, *, eps, div):
    with jax.default_matmul_precision("highest"):
        return (_rms_norm(x, final_norm.astype(F32), eps) / div) \
            @ head.astype(F32)


def forward(weights: dict, tokens, shape: dict, selection=None, rows=None,
            block_rows: int = 128) -> dict:
    """[L] token ids -> {"logits" [L or len(rows), V] float32,
    "own_selection" [sparse layers, L, kvh, topk], "under"
    [sparse layers, L, kvh]}, layer by layer."""
    eps = float(shape["rms_norm_eps"])
    depth = int(shape["published_depth"])
    a = float(shape["scale_depth"]) / math.sqrt(depth)
    kvh, topk = int(shape["num_key_value_heads"]), int(shape["sparse_topk"])
    L = len(tokens)
    x = weights["embed"][jnp.asarray(tokens)].astype(F32) \
        * float(shape["scale_emb"])
    own, under, si = [], [], 0
    for i, (kind, w) in enumerate(zip(shape["mixer_types"],
                                      weights["layers"])):
        if kind == LIGHTNING:
            x = lightning_block(
                x, w, decay_slopes(int(shape["lightning_nh"]),
                                   int(shape["layer_offset"]) + i, depth),
                heads=int(shape["lightning_nh"]), eps=eps,
                theta=float(shape["rope_theta"]), a=a, rows=block_rows)
            continue
        imposed = (jnp.zeros((L, kvh, topk), jnp.int32) if selection is None
                   else jnp.asarray(selection[si], jnp.int32))
        x, o, u = sparse_block(
            x, w, imposed, n_heads=int(shape["num_attention_heads"]),
            n_kv_heads=kvh, eps=eps, a=a,
            kernel=int(shape["sparse_kernel_size"]),
            stride=int(shape["sparse_kernel_stride"]),
            block=int(shape["sparse_block_size"]), topk=topk,
            init_blocks=int(shape["sparse_init_blocks"]),
            window=int(shape["sparse_window_size"]),
            dense_len=int(shape["sparse_dense_len"]), rows=block_rows,
            impose=selection is not None)
        own.append(o)
        under.append(u)
        si += 1
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return {"logits": _head(x, weights["final_norm"], weights["head"], eps=eps,
                            div=float(shape["hidden_size"])
                            / float(shape["dim_model_base"])),
            "own_selection": jnp.stack(own), "under": jnp.stack(under)}


def logits(weights: dict, tokens, shape: dict, selection=None):
    """[L] token ids -> [L, V] float32 logits."""
    return forward(weights, tokens, shape, selection)["logits"]


_NORMS = ("norm", "mlp_norm", "q_norm", "k_norm", "o_norm")


def from_program_tree(params: dict) -> dict:
    """This repo's parameter tree -> the published convention. Nothing is
    copied but the norm vectors (weights stay in the dtype they are served
    in; each layer upcasts its own)."""
    one = lambda s: 1.0 + s.astype(F32)   # noqa: E731
    return {
        "embed": params["embedding"], "head": params["lm_head"],
        "final_norm": one(params["norm"]),
        "layers": [{k: one(v) if k in _NORMS else v for k, v in lyr.items()}
                   for lyr in params["layers"]],
    }
