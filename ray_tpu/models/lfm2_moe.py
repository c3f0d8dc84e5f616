"""LFM2-MoE family (``model_type: lfm2_moe``, LFM2-24B-A2B): gated short
convolutions three to one attention layer, leading dense layers, then sigmoid
top-k experts with a selection bias and no shared expert, a tied head.

    x_0 = E[t]
    h = x_l + Op_l(RMSNorm_op(x_l));   x_{l+1} = h + FFN_l(RMSNorm_ffn(h))
    logits = RMSNorm_f(x_L) E^T

- ``Op`` of a ``conv`` layer, the **gated short convolution**: ``[B, C, X] =
  split_3(W_in u)``, ``z = B * X``, ``c_t = sum_j w[j] z_{t-K+1+j}``
  (depthwise, causal, ``K = conv_kernel`` 3, no bias, no activation), ``Op =
  W_out (C * c)``. Per sequence it carries the last ``K - 1`` rows of ``z``
  (``ops/ssm.py``'s convolution helpers, shared with the Mamba-2 family).
- ``Op`` of a ``full_attention`` layer: grouped queries, no bias; ``q`` and
  ``k`` each through an RMSNorm over the head's width with its own weight
  BEFORE the rotary embedding (half-split convention, the whole head); causal.
- ``FFN`` of the first ``n_dense_layers`` layers: SwiGLU at ``d_ff``. Of every
  later layer: ``s = sigmoid(W_g u)`` in float32 over all ``n_experts``, the
  ``top_k`` chosen by ``s + b``, weights ``s / (sum s + 1e-6)`` times
  ``routed_scale``, gated experts at ``expert_d_ff``. The tree holds
  ``experts_held`` of them from ``expert_offset`` (``parallel/moe.py``).

Pure functions over a params dict. The device programs at the bottom are what
``models/paged.py``'s ``PagedEngine`` runs for this family: page pools for the
attention layers only, kept as ``paged_ops.lane_pool_shape`` (a head of 64 is
half a lane: a position's 8 heads side by side are four whole ones), and per
slot a convolution tail for every conv layer and nothing else. A prompt is
admitted ``prefill_chunk`` tokens at a time through ONE program that carries
the attention layers' K/V and the conv layers' tails, each chunk's left edge
the tail of the chunk before it.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm
from ..ops.layers import apply_rope, rms_norm, rope_rows
from ..ops.quant import mm
from ..parallel.moe import (balanced_bias,
                            expert_share,  # noqa: F401 (re-export)
                            moe_ffn_held, sigmoid_gates)
from .cohere2_moe import _prompt_attention
from .engine import _sample, prefill_in_chunks
from .paged_ops import paged_attention

F32 = jnp.float32
CONV, FULL = "conv", "full_attention"
#: the router's normalisation constant (the family's own: ``sigmoid_gates``'
#: default is another's)
GATE_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    d_model: int = 2048
    n_layers: int = 40                # layers held: the first of layer_types
    layer_types: Optional[Tuple[str, ...]] = None    # published, every layer
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3              # conv_L_cache
    d_ff: int = 11776                 # the leading dense layers' SwiGLU
    n_dense_layers: int = 2
    n_experts: int = 64               # the router's width: all experts
    experts_held: int = 64            # ... of which this tree holds these
    expert_offset: int = 0            # ... starting from this one
    top_k: int = 4
    expert_d_ff: int = 1536
    routed_scale: float = 1.0
    norm_topk: bool = True
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    # how the programs cut their work (no effect on the result)
    prefill_chunk: int = 2048         # tokens a dispatch
    key_block: int = 256              # keys a step of a prompt's softmax
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types = self.layer_types
        if types is None:   # the published pattern: attention at 2, 6, 10, ..
            types = tuple(FULL if i % 4 == 2 else CONV for i in range(40))
        object.__setattr__(self, "layer_types", tuple(types))
        if self.n_layers > len(self.layer_types):
            raise ValueError("layers held reach past layer_types")
        if set(self.layer_types) - {CONV, FULL}:
            raise ValueError(f"layer types are {CONV!r} and {FULL!r}")
        if self.expert_offset + self.experts_held > self.n_experts:
            raise ValueError("experts held reach past the router's width")
        if self.head_dim % 2 or self.prefill_chunk % self.key_block \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("rotary halves need an even width; key_block "
                             "divides prefill_chunk; K/V heads divide heads")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The type of each layer held."""
        return self.layer_types[:self.n_layers]

    @property
    def n_attn_layers(self) -> int:
        return self.kinds.count(FULL)

    @property
    def n_conv_layers(self) -> int:
        return self.kinds.count(CONV)

    @property
    def n_moe_layers(self) -> int:
        return max(self.n_layers - self.n_dense_layers, 0)

    def param_count(self, active: bool = False) -> int:
        """Parameters the tree holds; with ``active`` those one token reads
        (``top_k`` experts of a layer's)."""
        d = self.d_model
        conv = 4 * d * d + self.conv_kernel * d
        attn = (2 * d * self.n_heads * self.head_dim
                + 2 * d * self.n_kv_heads * self.head_dim + 2 * self.head_dim)
        experts = self.top_k if active else self.experts_held
        moe = (d * self.n_experts + self.n_experts
               + experts * 3 * d * self.expert_d_ff)
        dense = min(self.n_dense_layers, self.n_layers)
        return (self.n_conv_layers * conv + self.n_attn_layers * attn
                + dense * 3 * d * self.d_ff + self.n_moe_layers * moe
                + 2 * d * self.n_layers + d + self.vocab_size * d)


LFM2_MOE_DEBUG = Lfm2MoeConfig(
    vocab_size=96, d_model=64, n_layers=6, n_heads=8, n_kv_heads=2,
    head_dim=16, d_ff=96, n_dense_layers=2, n_experts=16, experts_held=16,
    top_k=3, expert_d_ff=48, prefill_chunk=16, key_block=8,
    dtype=jnp.float32)


# ------------------------------------------------------------------ weights
def _normal(key, shape, dtype, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_params(cfg: Lfm2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights: projections normal over the square root of their
    fan-in, the embedding too (the tied head reads it: logits of unit scale),
    the convolution's taps over the square root of their count, the norms
    small seeded numbers (``rms_norm`` multiplies by 1 + them) so that a test
    sees a misplaced norm, the router float32. The routers' selection bias is
    then calibrated (``calibrate_router_bias``): seeded weights without it
    send a batch to a few experts, and the step's time follows the seed."""
    key, sample = jax.random.split(key)
    return calibrate_router_bias(_seeded_params(cfg, key), cfg, sample)


def _seeded_params(cfg: Lfm2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    d, dt, hd = cfg.d_model, cfg.dtype, cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: Dict[str, Any] = {
        "embedding": _normal(keys[0], (cfg.vocab_size, d), dt,
                             1.0 / math.sqrt(d)),
        "norm": _normal(keys[1], (d,), dt, 0.05),
        "layers": [],
    }
    eh, f = cfg.experts_held, cfg.expert_d_ff
    for i, kind in enumerate(cfg.kinds):
        k = jax.random.split(keys[i + 2], 13)
        layer = {"op_norm": _normal(k[0], (d,), dt, 0.05),
                 "ffn_norm": _normal(k[1], (d,), dt, 0.05)}
        if kind == CONV:
            layer.update({
                "w_in": _normal(k[2], (d, 3 * d), dt),
                "conv_w": _normal(k[3], (cfg.conv_kernel, d), dt,
                                  1.0 / math.sqrt(cfg.conv_kernel)),
                "w_out": _normal(k[4], (d, d), dt)})
        else:
            layer.update({
                "wq": _normal(k[2], (d, cfg.n_heads * hd), dt),
                "wk": _normal(k[3], (d, cfg.n_kv_heads * hd), dt),
                "wv": _normal(k[4], (d, cfg.n_kv_heads * hd), dt),
                "wo": _normal(k[5], (cfg.n_heads * hd, d), dt),
                "q_norm": _normal(k[6], (hd,), dt, 0.05),
                "k_norm": _normal(k[7], (hd,), dt, 0.05)})
        if i < cfg.n_dense_layers:
            layer.update({"w_gate": _normal(k[8], (d, cfg.d_ff), dt),
                          "w_up": _normal(k[9], (d, cfg.d_ff), dt),
                          "w_down": _normal(k[10], (cfg.d_ff, d), dt)})
        else:
            e = jax.random.split(k[11], 3)
            layer["moe"] = {
                "w_router": _normal(k[12], (d, cfg.n_experts), F32),
                "router_bias": jnp.zeros((cfg.n_experts,), F32),
                "w_gate": _normal(e[0], (eh, d, f), dt),
                "w_up": _normal(e[1], (eh, d, f), dt),
                "w_down": _normal(e[2], (eh, f, d), dt)}
        params["layers"].append(layer)
    return params


def calibrate_router_bias(params, cfg: Lfm2MoeConfig, key: jax.Array,
                          n: int = 2048) -> Dict[str, Any]:
    """Set every expert layer's selection bias so that its experts are chosen
    about equally often (``nemotron_h.calibrate_router_bias``'s method,
    ``parallel.moe.balanced_bias``, on this family's layers): one pass of
    ``n`` seeded random tokens (one sequence, one chunk), each expert layer's
    bias set from its scores over them before the layer runs."""
    n = -(-n // cfg.key_block) * cfg.key_block
    tokens = jax.random.randint(key, (n,), 0, cfg.vocab_size)
    one = dataclasses.replace(cfg, prefill_chunk=n)
    biased = []

    def calibrated(moe, u):
        scores = jax.nn.sigmoid(jnp.dot(u.astype(F32), moe["w_router"]))
        moe = {**moe, "router_bias": balanced_bias(scores, cfg.top_k)}
        biased.append(moe)
        return moe

    _run_chunk(params, tokens, jnp.int32(0), jnp.int32(n),
               *_empty_carry(one, n), one, before_moe=calibrated)
    found = iter(biased)
    return {**params, "layers": [
        {**lyr, "moe": next(found)} if "moe" in lyr else lyr
        for lyr in params["layers"]]}


# ------------------------------------------------------------------- layers
def _split_in(layer, u):
    """``[B, C, X] = split_3(W_in u)`` and the convolution's input ``z = B *
    X``: u [.., D] -> (z, C), each [.., D]."""
    b, c, x = jnp.split(mm(u, layer["w_in"]), 3, axis=-1)
    return b * x, c


def _qkv(layer, h, positions, cfg: Lfm2MoeConfig):
    """h [N, D] at ``positions`` [N] -> q [N, H, d], k and v [N, kvh, d]; q
    and k normed over the head's width, then rotated."""
    N, hd = h.shape[0], cfg.head_dim
    q = mm(h, layer["wq"]).reshape(N, cfg.n_heads, hd)
    k = mm(h, layer["wk"]).reshape(N, cfg.n_kv_heads, hd)
    v = mm(h, layer["wv"]).reshape(N, cfg.n_kv_heads, hd)
    q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
    k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    cos, sin = rope_rows(positions, hd, cfg.rope_theta)
    return (apply_rope(q[None], cos, sin)[0],
            apply_rope(k[None], cos, sin)[0], v)


#: rows from which the held experts' product is grouped by expert
#: (``moe_ffn_grouped``: a prompt's chunk); under it every held expert
#: multiplies every row (``moe_ffn_share``: a decode step). At 64 gated
#: experts of 2048 x 1536, all held, top-4, on a TPU v5e (my chip run, PR 52;
#: PERF.md section 5): 64 rows 1.62 ms a layer against the grouped form's
#: 2.36 (1.47 ms is the layer's 1.21 GB at 819 GB/s), 256 rows 1.94 against
#: 3.88; 2048 rows grouped 4.95, where every expert on every row is 2.5 TFLOP
#: a layer (12.6 ms at the chip's peak). The two cross between 512 and 1024
#: rows; the engine runs 64 (a step) and ``prefill_chunk`` (a chunk). Since
#: PR 57 the grouped products are a Pallas kernel on the chip
#: (``parallel.moe.grouped_product_form``) and the same table reads (my chip
#: run, PR 57; share / grouped, kernel form): 64 rows 1.68 / 1.71, 128 rows
#: 1.68 / 1.75, 256 rows 2.01 / 1.84, 512 rows 3.57 / 1.94, 1024 rows 7.09 /
#: 2.24, 2048 rows grouped 2.79 (was 5.00): they now cross between 128 and
#: 256 rows, which no program of the engine runs; the value stands
GROUPED_FROM_ROWS = 512


def _ffn(layer, h, token_mask, cfg: Lfm2MoeConfig, before_moe=None):
    """h [T, D] normed -> (FFN(h) [T, D], the chosen experts [T, k] or None
    in a dense layer, int32[2]: held experts hit, most tokens of one)."""
    if "moe" not in layer:
        with jax.named_scope("dense_mlp"):
            out = mm(jax.nn.silu(mm(h, layer["w_gate"]))
                     * mm(h, layer["w_up"]), layer["w_down"])
        return out, None, jnp.zeros((2,), jnp.int32)
    moe = layer["moe"] if before_moe is None else before_moe(layer["moe"], h)
    with jax.named_scope("router"):
        vals, idx = sigmoid_gates(h, moe["w_router"], moe["router_bias"],
                                  cfg.top_k, cfg.routed_scale, cfg.norm_topk,
                                  GATE_EPS)
    held = {w: moe[w] for w in ("w_gate", "w_up", "w_down")}
    with jax.named_scope("experts"):
        # grouped with room for every pair: all experts are held
        out, hit, most = moe_ffn_held(
            h, vals, idx, held, cfg.expert_offset, token_mask, cfg.n_experts,
            GROUPED_FROM_ROWS)
    return out, idx, jnp.stack([hit, most]).astype(jnp.int32)


def _stacked(routing, rows: int, cfg: Lfm2MoeConfig):
    """The expert layers' chosen experts as one array [expert layers, rows,
    k]; of a tree whose layers held are all dense, none."""
    return (jnp.stack(routing) if routing
            else jnp.zeros((0, rows, cfg.top_k), jnp.int32))


def _head(params, x, cfg: Lfm2MoeConfig):
    return mm(rms_norm(x, params["norm"], cfg.norm_eps),
              params["embedding"].T)


def _run_chunk(params, tokens, start, n_valid, bufs, tails,
               cfg: Lfm2MoeConfig, before_moe=None):
    """One chunk of one sequence through every layer. tokens [N] at positions
    ``start ..``; bufs: per attention layer (K, V) [T, kvh * d] of the
    positions before; tails: per conv layer the last ``K - 1`` inputs of its
    convolution before ``start`` [K-1, D]. -> (hidden [N, D] before the final
    norm, bufs with the chunk's rows, tails AS OF ``min(n_valid, start + N)``,
    the chosen experts [expert layers, N, k])."""
    N = tokens.shape[0]
    x = params["embedding"][tokens].astype(cfg.dtype)
    t = start + jnp.arange(N)
    valid = t < n_valid
    kv, conv = iter(bufs), iter(tails)
    new_bufs, new_tails, routing = [], [], []
    for kind, layer in zip(cfg.kinds, params["layers"]):
        h = rms_norm(x, layer["op_norm"], cfg.norm_eps)
        if kind == CONV:
            with jax.named_scope("short_conv"):
                left = next(conv)
                z, c = _split_in(layer, h)
                new_tails.append(ssm.conv_tail(z, n_valid - start,
                                               cfg.conv_kernel, left))
                y = ssm.causal_conv(z, layer["conv_w"], None, left)
                out = mm((c.astype(F32) * y).astype(cfg.dtype),
                         layer["w_out"])
        else:
            buf_k, buf_v = next(kv)
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, t, cfg)
                buf_k = jax.lax.dynamic_update_slice_in_dim(
                    buf_k, k.reshape(N, -1).astype(buf_k.dtype), start, 0)
                buf_v = jax.lax.dynamic_update_slice_in_dim(
                    buf_v, v.reshape(N, -1).astype(buf_v.dtype), start, 0)
            new_bufs.append((buf_k, buf_v))
            with jax.named_scope("prompt_attn"):
                rows = (-1, cfg.n_kv_heads, cfg.head_dim)
                o = _prompt_attention(q, buf_k.reshape(rows),
                                      buf_v.reshape(rows), start, 0, cfg)
            with jax.named_scope("attention"):
                out = mm(o, layer["wo"])
        x = x + out
        f, idx, _ = _ffn(layer, rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                         valid, cfg, before_moe)
        x = x + f
        if idx is not None:
            routing.append(idx)
    return x, new_bufs, new_tails, _stacked(routing, N, cfg)


def _empty_carry(cfg: Lfm2MoeConfig, total: int):
    dt = cfg.dtype
    row = (total, cfg.n_kv_heads * cfg.head_dim)
    return ([(jnp.zeros(row, dt), jnp.zeros(row, dt))
             for _ in range(cfg.n_attn_layers)],
            [jnp.zeros((cfg.conv_kernel - 1, cfg.d_model), dt)
             for _ in range(cfg.n_conv_layers)])


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg: Lfm2MoeConfig):
    """tokens [L] -> logits [L, V]: the whole forward pass of one sequence
    as ONE chunk (tests hold it against the plain reference)."""
    L = tokens.shape[0]
    T = -(-L // cfg.key_block) * cfg.key_block
    one = dataclasses.replace(cfg, prefill_chunk=T)
    x = _run_chunk(params, jnp.pad(tokens, (0, T - L)), jnp.int32(0),
                   jnp.int32(L), *_empty_carry(one, T), one)[0]
    return _head(params, x[:L], cfg)


# ----------------------------------------------- programs of ``PagedEngine``
@functools.partial(jax.jit, static_argnames=("cfg", "total"))
def prefill_carry(cfg: Lfm2MoeConfig, total: int):
    """What a prefill carries from chunk to chunk, before the first: per
    attention layer the K and V rows [total, kvh * d] (as ``lane_pool_shape``
    lays a position out), per conv layer a zero tail [K-1, D]."""
    return _empty_carry(cfg, total)


def init_state(cfg: Lfm2MoeConfig, slots: int):
    """Per-slot state: every conv layer's tail [S, K-1, D]."""
    return [jnp.zeros((slots, cfg.conv_kernel - 1, cfg.d_model), cfg.dtype)
            for _ in range(cfg.n_conv_layers)]


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4, 5))
def _lfm2_prefill_chunk(params, tokens, start, n_valid, bufs, tails, cfg):
    """One chunk of one request's prefill; the carried rows and tails are
    donated. ``tokens`` [prefill_chunk] is padded past ``n_valid`` (a position
    of the whole prompt): the padded tail's K/V rows are stale and harmless
    (the decode steps overwrite them before a query can read them) and are
    not among a conv tail's inputs. -> (the logits at ``n_valid - 1`` if that
    row lies in this chunk, bufs, tails, the chosen experts [expert layers,
    chunk, k], which only a reference check reads)."""
    x, bufs, tails, routing = _run_chunk(params, tokens, start, n_valid, bufs,
                                         tails, cfg)
    row = jnp.clip(n_valid - 1 - start, 0, tokens.shape[0] - 1)
    return _head(params, x[row], cfg), bufs, tails, routing


def prefill(params, prompt, total: int, cfg: Lfm2MoeConfig,
            keep_routing: bool = False):
    """Prefill one request chunk by chunk (``engine.prefill_in_chunks`` over
    ``_lfm2_prefill_chunk``). -> (next-token logits, per attention layer the
    (K, V) rows [total, kvh * d] for the page scatter, per conv layer its
    tail at the prompt's end; with ``keep_routing`` also every prompt
    position's chosen experts [expert layers, len(prompt), k])."""
    first, (bufs, tails), routing = prefill_in_chunks(
        _lfm2_prefill_chunk, params, prompt, cfg.prefill_chunk,
        prefill_carry(cfg, total), cfg, keep_routing)
    out = (first, bufs, tails)
    return out + (routing,) if keep_routing else out


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_tails(conv_tails, new, slot):
    """One admission's conv tails into its slot of every conv layer, in place
    (the list is donated): one dispatch."""
    return [c.at[slot].set(n.astype(c.dtype))
            for c, n in zip(conv_tails, new)]


def _decode_logits(params, pools_k, pools_v, conv_tails, tables, toks,
                   lengths, cfg: Lfm2MoeConfig, page: int):
    """The decode step up to its logits [S, V]; the new pools and tails;
    int32[5]: held experts hit summed over the expert layers, most tokens of
    one expert, the active rows, the positions the active slots' queries
    attend in an attention layer (their context), and 1 (summed over the
    steps a call lands, they count them); the chosen experts [expert layers,
    S, k]."""
    S = toks.shape[0]
    x = params["embedding"][toks].astype(cfg.dtype)             # [S, D]
    active = lengths > 0
    page_idx = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]
    offs = lengths % page
    new_k, new_v, new_tails, routing = [], [], [], []
    hit, most = jnp.int32(0), jnp.int32(0)
    pools, conv = zip(pools_k, pools_v), iter(conv_tails)
    for kind, layer in zip(cfg.kinds, params["layers"]):
        h = rms_norm(x, layer["op_norm"], cfg.norm_eps)
        if kind == CONV:
            with jax.named_scope("short_conv"):
                z, c = _split_in(layer, h)
                y, tail = ssm.conv_step(next(conv), z, layer["conv_w"])
                new_tails.append(tail)
                out = mm((c.astype(F32) * y).astype(cfg.dtype),
                         layer["w_out"])
        else:
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, lengths, cfg)
            pool_k, pool_v = next(pools)
            o, pool_k, pool_v, _, _ = paged_attention(
                q[:, None], k[:, None], v[:, None], pool_k, pool_v, None,
                None, tables, lengths, page_idx, offs, False, cfg.dtype)
            new_k.append(pool_k)
            new_v.append(pool_v)
            with jax.named_scope("attention"):
                out = mm(o[:, 0], layer["wo"])
        x = x + out
        f, idx, counts = _ffn(
            layer, rms_norm(x, layer["ffn_norm"], cfg.norm_eps), active, cfg)
        x = x + f
        if idx is not None:
            routing.append(idx)
            hit, most = hit + counts[0], jnp.maximum(most, counts[1])
    counts = jnp.stack([
        hit, most, jnp.sum(active),
        jnp.sum(jnp.where(active, lengths + 1, 0)), 1]).astype(jnp.int32)
    return (_head(params, x, cfg), new_k, new_v, new_tails, counts,
            _stacked(routing, S, cfg))


@functools.partial(jax.jit, static_argnames=("cfg", "page"),
                   donate_argnums=(1, 2, 3))
def _lfm2_step(params, pools_k, pools_v, conv_tails, tables, toks, lengths,
               temps, top_ks, top_ps, keys, cfg, page):
    """One token for every slot: a conv layer advances the slot's tail by
    the token's gated input, an attention layer writes the slot's row at its
    (page, offset) of the pool and attends over the slot's pages block by
    block; the held experts' part of every expert layer. Pools and tails are
    donated. A slot of length 0 is inactive: it flows through (static
    shapes), its K/V row lands on page 0, it is routed to no expert, and an
    admission overwrites its whole tail before it is read.

    -> (int32[S + 5]: the tokens, then ``_decode_logits``' counts, so that
    one transfer fetches all; pools; tails; keys; the chosen experts [expert
    layers, S, k], which stay on the device unless a reference check asks for
    them; the tokens alone, int32[S], as the next step takes them: with the
    keys they let the engine dispatch that step before it has fetched this
    one's)."""
    logits, new_k, new_v, new_tails, counts, routing = _decode_logits(
        params, pools_k, pools_v, conv_tails, tables, toks, lengths, cfg,
        page)
    out, new_keys, picked = _sample(logits, temps, top_ks, top_ps, keys,
                                    lengths, counts)
    return out, new_k, new_v, new_tails, new_keys, routing, picked
