"""LongCat-Flash family (the language model of LongCat-Flash-Omni): latent
(MLA) attention, a shortcut-connected double layer, routed experts of which
a third compute nothing.

One decoder layer holds TWO attention sublayers, TWO dense gated MLPs and
ONE expert layer whose output skips the second half:

    h1 = h  + A_0(N(h));   u = N(h1);   m = MoE(u);   h2 = h1 + F_0(u)
    h3 = h2 + A_1(N(h2));  h4 = h3 + F_1(N(h3)) + m

so the experts' products depend on nothing of the second attention and the
second MLP: a scheduler may overlap them, and nothing here orders them.

- **Latent attention** ``A``: ``c_q = N(x W_qa)``, ``[q_nope | q_rope]_h =
  s_q (c_q W_qb)_h``; ``[c | k_r] = x W_kva``, ``c <- s_kv N(c)``; the cache
  of a position is ONE row ``(c, rope(k_r))`` shared by all heads
  (``kv_lora_rank + qk_rope_head_dim`` values); ``[k_nope | v]_h =
  (c W_kvb)_h``; scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(dn + dr)``.
  Rotary pairs are interleaved, ``(2j, 2j + 1)``. Prompts run the EXPANDED
  form (``k_nope`` and ``v`` from ``W_kvb``, key block by key block with an
  online softmax), decode steps the ABSORBED form over the page pool
  (``paged_ops.attend_latent``): the same mathematics.
- **Expert layer** (``parallel/moe.py``): softmax scores over
  ``router_width`` outputs, of which the last ``zero_expert_num`` are
  identity experts (``gate x input``); the top ``top_k`` by ``score + bias``,
  weights the unbiased scores times ``routed_scale``, not normalised; gated
  experts. The tree holds ``experts_held`` of the computing experts, from
  ``expert_offset``: one chip's share of an expert-parallel deployment. What
  the absent experts would add is left out; the zero experts are added whole
  for this chip's own tokens; on one chip the layer runs without its exchange.

Pure functions over a params dict. The device programs at the bottom are what
``models/paged.py``'s ``PagedEngine`` runs for this family: a prefill that
takes a prompt ``prefill_chunk`` tokens at a time, carrying the latent rows
of every sublayer (a later chunk RE-EXPANDS the earlier positions key block
by key block: ``2 C H (dn + dv)`` operations a position, a twelfth of what
attending them in the absorbed form would cost a 2048-query chunk); the
scatter of those rows into the slot's pages; and the decode step of all
slots.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.layers import (rms_norm, rope_interleaved as _rope,
                          rope_rows as _rope_rows)
from ..ops.quant import mm
from ..parallel.moe import (balanced_bias,
                            expert_share,  # noqa: F401 (re-export)
                            moe_ffn_zero, softmax_gates)
from .engine import _sample, prefill_in_chunks
from .paged_ops import (attend_latent, latent_pages, latent_positions_read,
                        write_latent)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    d_model: int = 6144
    n_layers: int = 28                # double layers held
    n_layers_published: int = 28
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 12288
    # the expert layer
    router_width: int = 768           # computing + zero-compute experts
    zero_expert_num: int = 256        # ... of which the last are identity
    experts_held: int = 512           # computing experts this tree holds
    expert_offset: int = 0            # ... starting from this one
    top_k: int = 12
    expert_d_ff: int = 2048
    routed_scale: float = 6.0
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    # how the prefill cuts its work (no effect on the result)
    prefill_chunk: int = 2048         # tokens a dispatch
    key_block: int = 256              # keys a step of the online softmax
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers > self.n_layers_published:
            raise ValueError("layers held reach past the published depth")
        if self.expert_offset + self.experts_held > self.n_real:
            raise ValueError("experts held reach past the computing experts")
        if self.qk_rope_head_dim % 2 or self.prefill_chunk % self.key_block:
            raise ValueError("rotary pairs need an even width; key_block "
                             "divides prefill_chunk")

    @property
    def n_real(self) -> int:
        """The router's computing experts (published, all chips')."""
        return self.router_width - self.zero_expert_num

    @property
    def n_sublayers(self) -> int:
        return 2 * self.n_layers

    @property
    def latent_width(self) -> int:
        """Values cached a position a sublayer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_scale(self) -> float:
        return math.sqrt(self.d_model / self.q_lora_rank)

    @property
    def kv_scale(self) -> float:
        return math.sqrt(self.d_model / self.kv_lora_rank)

    @property
    def attn_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def param_count(self) -> int:
        d, H = self.d_model, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        attn = (d * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * H * qk + d * self.latent_width
                + self.kv_lora_rank + self.kv_lora_rank * H
                * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * d + d)
        mlp = 3 * d * self.d_ff + d
        moe = (d * self.router_width + self.router_width
               + self.experts_held * 3 * d * self.expert_d_ff)
        return (self.n_layers * (2 * attn + 2 * mlp + moe) + d
                + 2 * self.vocab_size * d)


LONGCAT_FLASH_DEBUG = LongcatFlashConfig(
    vocab_size=96, d_model=64, n_layers=2, n_layers_published=4, n_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, d_ff=96, router_width=36, zero_expert_num=12,
    experts_held=24, top_k=3, expert_d_ff=48, prefill_chunk=16, key_block=8,
    dtype=jnp.float32)


# ------------------------------------------------------------------ weights
def _normal(key, shape, dtype, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_params(cfg: LongcatFlashConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights: projections normal over the square root of their
    fan-in, the two up-projections of the latents also over the scale their
    product is multiplied by (``W_qb`` over ``s_q``, ``W_kvb`` over ``s_kv``:
    queries, keys and values then have unit scale beside the rotary parts,
    as a trained model's have; without it the scores' deviation is
    ``s_q s_kv`` = 6.9 at the published widths, every softmax is one key's,
    and bfloat16 reads 0.28 sigma from float32: chip run, PR 34), the norms
    small seeded numbers (``rms_norm`` multiplies by 1 + them) so that a test
    sees them, the router float32. The routers'
    selection bias is then calibrated (``calibrate_router_bias``): seeded
    weights without it send a batch to a few of the 768."""
    key, sample = jax.random.split(key)
    return calibrate_router_bias(_seeded_params(cfg, key), cfg, sample)


def _seeded_params(cfg: LongcatFlashConfig, key: jax.Array) -> Dict[str, Any]:
    d, dt, H = cfg.d_model, cfg.dtype, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embedding": _normal(keys[0], (cfg.vocab_size, d), dt, 1.0),
        "lm_head": _normal(keys[1], (d, cfg.vocab_size), dt),
        "norm": _normal(keys[2], (d,), dt, 0.05),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i + 3], 3)
        attn, mlp = [], []
        for j in range(2):
            a = jax.random.split(jax.random.fold_in(k[0], j), 8)
            attn.append({
                "norm": _normal(a[0], (d,), dt, 0.05),
                "w_qa": _normal(a[1], (d, cfg.q_lora_rank), dt),
                "q_norm": _normal(a[2], (cfg.q_lora_rank,), dt, 0.05),
                "w_qb": _normal(a[3], (cfg.q_lora_rank, H * qk), dt,
                                1.0 / (cfg.q_scale
                                       * math.sqrt(cfg.q_lora_rank))),
                "w_kva": _normal(a[4], (d, cfg.latent_width), dt),
                "kv_norm": _normal(a[5], (cfg.kv_lora_rank,), dt, 0.05),
                "w_kvb": _normal(a[6], (cfg.kv_lora_rank, H * (
                    cfg.qk_nope_head_dim + cfg.v_head_dim)), dt,
                    1.0 / (cfg.kv_scale * math.sqrt(cfg.kv_lora_rank))),
                "wo": _normal(a[7], (H * cfg.v_head_dim, d), dt),
            })
            m = jax.random.split(jax.random.fold_in(k[1], j), 4)
            mlp.append({
                "norm": _normal(m[0], (d,), dt, 0.05),
                "w_gate": _normal(m[1], (d, cfg.d_ff), dt),
                "w_up": _normal(m[2], (d, cfg.d_ff), dt),
                "w_down": _normal(m[3], (cfg.d_ff, d), dt),
            })
        e = jax.random.split(k[2], 4)
        eh, f = cfg.experts_held, cfg.expert_d_ff
        params["layers"].append({"attn": attn, "mlp": mlp, "moe": {
            "w_router": _normal(e[0], (d, cfg.router_width), F32),
            "router_bias": jnp.zeros((cfg.router_width,), F32),
            "w_gate": _normal(e[1], (eh, d, f), dt),
            "w_up": _normal(e[2], (eh, d, f), dt),
            "w_down": _normal(e[3], (eh, f, d), dt),
        }})
    return params


def calibrate_router_bias(params, cfg: LongcatFlashConfig, key: jax.Array,
                          n: int = 2048) -> Dict[str, Any]:
    """Set every expert layer's selection bias so that all ``router_width``
    outputs, zero experts among them, are chosen about equally often
    (``nemotron_h.calibrate_router_bias``'s method on this family's layers
    and softmax scores): one pass of ``n`` seeded random tokens (one
    sequence), and at each expert layer the bias of output ``e`` becomes the
    offset that puts the (1 - top_k / router_width) quantile of its score
    over those tokens where every other's is. A token then picks
    ``top_k x n_real / router_width`` computing experts on average (the
    published 8 of 12). The bias only selects."""
    tokens = jax.random.randint(key, (n,), 0, cfg.vocab_size)
    one = dataclasses.replace(cfg, prefill_chunk=n, key_block=min(n, 512))
    lats = [jnp.zeros((n, cfg.latent_width), cfg.dtype)
            for _ in range(cfg.n_sublayers)]
    layers = []

    def calibrated(moe, u):
        scores = jax.nn.softmax(jnp.dot(u.astype(F32), moe["w_router"]), -1)
        moe = {**moe, "router_bias": balanced_bias(scores, cfg.top_k)}
        layers.append(moe)
        return moe

    _run_chunk(params, tokens, jnp.int32(0), jnp.int32(n), lats, one,
               before_moe=calibrated)
    return {**params, "layers": [{**lyr, "moe": moe} for lyr, moe
                                 in zip(params["layers"], layers)]}


# ---------------------------------------------------------------- sublayers
def _latent_qkv(att, h, cos, sin, cfg: LongcatFlashConfig):
    """h [N, D] at the positions of cos / sin -> (q_nope [N, H, dn], rotated
    q_rope [N, H, dr], the position's cache row [N, C + dr]: the normed,
    scaled latent, then the rotated key all heads share)."""
    N, C, dn = h.shape[0], cfg.kv_lora_rank, cfg.qk_nope_head_dim
    c_q = rms_norm(mm(h, att["w_qa"]), att["q_norm"], cfg.norm_eps)
    q = (mm(c_q, att["w_qb"]).astype(F32) * cfg.q_scale).astype(h.dtype)
    q = q.reshape(N, cfg.n_heads, -1)
    kv = mm(h, att["w_kva"])
    c = rms_norm(kv[:, :C].astype(F32), att["kv_norm"], cfg.norm_eps)
    row = jnp.concatenate([(c * cfg.kv_scale).astype(h.dtype),
                           _rope(kv[:, C:], cos, sin)], axis=-1)
    return q[..., :dn], _rope(q[..., dn:], cos, sin), row


def _kvb(att, cfg: LongcatFlashConfig):
    """``W_kvb`` as [C, H, dn + dv]: per head the latent's up-projection to
    keys (the first ``dn``) and values."""
    return att["w_kvb"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)


def _latent_prompt(q_nope, q_rope, buf, w_kvb, start, cfg: LongcatFlashConfig):
    """A chunk's queries over the positions so far in the EXPANDED form, key
    block by key block. q_nope [N, H, dn], q_rope [N, H, dr] at positions
    ``start ..``; buf [T, C + dr] holds every position's cache row up to the
    chunk's end; w_kvb [C, H, dn + dv]. Each block's keys and values are
    expanded from its latents, used and dropped: no ``L x L`` array, no
    expanded cache. -> o [N, H * dv]."""
    N, H, dn = q_nope.shape
    C, Kb, dv = cfg.kv_lora_rank, cfg.key_block, cfg.v_head_dim
    t = start + jnp.arange(N)

    def keys(kb, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(buf, kb * Kb, Kb)
        kv = jnp.einsum("kc,chd->khd", blk[:, :C], w_kvb)   # [Kb, H, dn + dv]
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn],
                        preferred_element_type=F32)
             + jnp.einsum("qhr,kr->hqk", q_rope, blk[:, C:],
                          preferred_element_type=F32)) * cfg.attn_scale
        ok = (kb * Kb + jnp.arange(Kb))[None, :] <= t[:, None]
        s = jnp.where(ok[None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(kv.dtype), kv[..., dn:],
            preferred_element_type=F32)
        return m_new, l * fix + p.sum(axis=-1), acc

    init = (jnp.full((H, N), -1e30, F32), jnp.zeros((H, N), F32),
            jnp.zeros((H, N, dv), F32))
    _, l, acc = jax.lax.fori_loop(0, (start + N + Kb - 1) // Kb, keys, init)
    o = (acc / l[..., None]).transpose(1, 0, 2)
    return o.reshape(N, H * dv).astype(q_nope.dtype)


def _ffn(mlp, u):
    return mm(jax.nn.silu(mm(u, mlp["w_gate"])) * mm(u, mlp["w_up"]),
              mlp["w_down"])


def _moe(moe, u, token_mask, cfg: LongcatFlashConfig):
    """u [T, D] -> (out [T, D], chosen experts [T, k], int32[3]: held
    experts hit, most tokens of one expert, pairs routed to zero experts)."""
    with jax.named_scope("router"):
        vals, idx = softmax_gates(u, moe["w_router"], moe["router_bias"],
                                  cfg.top_k, cfg.routed_scale)
    out, hit, most, zero = moe_ffn_zero(
        u, vals, idx, {w: moe[w] for w in ("w_gate", "w_up", "w_down")},
        cfg.expert_offset, cfg.n_real, token_mask)
    return out, idx, jnp.stack([hit, most, zero]).astype(jnp.int32)


def _double_layer(layer, x, attend, token_mask, cfg: LongcatFlashConfig,
                  before_moe=None):
    """One shortcut-connected decoder layer on x [T, D]. ``attend(j, att,
    h)`` is sublayer ``j``'s attention on the normed hidden state, as its
    caller caches it (a prompt's chunk or a step's pages). -> (x, the chosen
    experts [T, k], the expert layer's counts)."""
    for j in range(2):
        att, mlp = layer["attn"][j], layer["mlp"][j]
        with jax.named_scope("attention"):
            h = rms_norm(x, att["norm"], cfg.norm_eps)
        o = attend(j, att, h)
        with jax.named_scope("attention"):
            x = x + mm(o, att["wo"])
        u = rms_norm(x, mlp["norm"], cfg.norm_eps)
        if j == 0:      # joins the residual at the END of the second half
            moe = layer["moe"] if before_moe is None \
                else before_moe(layer["moe"], u)
            with jax.named_scope("moe"):
                routed, idx, counts = _moe(moe, u, token_mask, cfg)
        with jax.named_scope("mlp"):
            x = x + _ffn(mlp, u)
    return x + routed, idx, counts


def _head(params, x, cfg: LongcatFlashConfig):
    return mm(rms_norm(x, params["norm"], cfg.norm_eps), params["lm_head"])


def _run_chunk(params, tokens, start, n_valid, lats, cfg, before_moe=None):
    """One chunk of one sequence through every layer. tokens [N] at positions
    ``start ..``; lats: per sublayer the cache rows [T, C + dr] of the
    positions before. -> (hidden [N, D] before the final norm, lats with the
    chunk's rows, the chosen experts [layers, N, k])."""
    N = tokens.shape[0]
    x = params["embedding"][tokens].astype(cfg.dtype)
    cos, sin = _rope_rows(start + jnp.arange(N), cfg.qk_rope_head_dim,
                          cfg.rope_theta)
    valid = start + jnp.arange(N) < n_valid
    new, routing = [], []
    for i, layer in enumerate(params["layers"]):
        def attend(j, att, h, i=i):
            with jax.named_scope("attention"):
                q_nope, q_rope, row = _latent_qkv(att, h, cos, sin, cfg)
            with jax.named_scope("latent_write"):
                buf = jax.lax.dynamic_update_slice_in_dim(
                    lats[2 * i + j], row.astype(lats[2 * i + j].dtype),
                    start, axis=0)
            new.append(buf)
            with jax.named_scope("latent_attn"):
                return _latent_prompt(q_nope, q_rope, buf, _kvb(att, cfg),
                                      start, cfg)

        x, idx, _ = _double_layer(layer, x, attend, valid, cfg, before_moe)
        routing.append(idx)
    return x, new, jnp.stack(routing)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg: LongcatFlashConfig):
    """tokens [L] -> logits [L, V]: the whole forward pass of one sequence
    as ONE chunk (tests hold it against the plain reference)."""
    L = tokens.shape[0]
    T = -(-L // cfg.key_block) * cfg.key_block
    one = dataclasses.replace(cfg, prefill_chunk=T)
    x = _run_chunk(params, jnp.pad(tokens, (0, T - L)), jnp.int32(0),
                   jnp.int32(L), prefill_carry(one, T), one)[0]
    return _head(params, x[:L], cfg)


# ----------------------------------------------- programs of ``PagedEngine``
@functools.partial(jax.jit, static_argnames=("cfg", "total"))
def prefill_carry(cfg: LongcatFlashConfig, total: int):
    """What a prefill carries from chunk to chunk, before the first: per
    sublayer the cache rows [total, C + dr]. No state."""
    return [jnp.zeros((total, cfg.latent_width), cfg.dtype)
            for _ in range(cfg.n_sublayers)]


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4,))
def _longcat_prefill_chunk(params, tokens, start, n_valid, lats, cfg):
    """One chunk of one request's prefill; the carried rows are donated.
    ``tokens`` [prefill_chunk] is padded past ``n_valid`` (a position of the
    whole prompt): the padded tail's rows are stale, harmless as in
    ``engine._prefill_one`` (decode overwrites each before a query can read
    it). -> (the logits at ``n_valid - 1`` if that row lies in this chunk,
    lats, the chosen experts [layers, chunk, k], which only a reference check
    reads)."""
    x, lats, routing = _run_chunk(params, tokens, start, n_valid, lats, cfg)
    row = jnp.clip(n_valid - 1 - start, 0, tokens.shape[0] - 1)
    return _head(params, x[row], cfg), lats, routing


def prefill(params, prompt, total: int, cfg: LongcatFlashConfig,
            keep_routing: bool = False):
    """Prefill one request chunk by chunk (``engine.prefill_in_chunks`` over
    ``_longcat_prefill_chunk``). -> (next-token logits, per sublayer the
    cache rows [total, C + dr] for the page scatter; with ``keep_routing``
    also every prompt position's chosen experts [layers, len(prompt), k])."""
    first, (lats,), routing = prefill_in_chunks(
        _longcat_prefill_chunk, params, prompt, cfg.prefill_chunk,
        (prefill_carry(cfg, total),), cfg, keep_routing)
    return (first, lats, routing) if keep_routing else (first, lats)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_latent(pools, lats, page_ids):
    """One admission's cache rows into its pages of every sublayer's pool, in
    place: ``paged._scatter_pages``' one dispatch on ONE pool a sublayer.
    page_ids int32[P]; an id past the pool is dropped."""
    return [pool.at[page_ids].set(
        latent_pages(rows, 2 * pool.shape[1]).astype(pool.dtype), mode="drop")
        for pool, rows in zip(pools, lats)]


def _decode_logits(params, pools, tables, toks, lengths,
                   cfg: LongcatFlashConfig, page: int):
    """The decode step up to its logits [S, V]; the new pools; int32[7]: held
    experts hit summed over the expert layers, most tokens of one expert,
    pairs routed to zero experts, the cached positions the active slots hold
    (each read once a sublayer), the active rows, 1 (summed over the steps a
    call lands, they count them), and the positions a sublayer's read
    gathers (every slot's blocks, whole: ``attend_latent``'s own rule); the
    chosen experts [layers, S, k]."""
    x = params["embedding"][toks].astype(cfg.dtype)             # [S, D]
    active = lengths > 0
    page_idx = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]
    offs = lengths % page
    cos, sin = _rope_rows(lengths, cfg.qk_rope_head_dim, cfg.rope_theta)
    new, routing = [], []
    load = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        def attend(j, att, h, i=i):
            with jax.named_scope("attention"):
                q_nope, q_rope, row = _latent_qkv(att, h, cos, sin, cfg)
                w = _kvb(att, cfg)
            pool = write_latent(row, pools[2 * i + j], page_idx, offs)
            new.append(pool)
            return attend_latent(      # one query row a slot
                q_nope[:, None], q_rope[:, None],
                w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:],
                pool, tables, lengths, cfg.attn_scale)[:, 0]

        x, idx, counts = _double_layer(layer, x, attend, active, cfg)
        routing.append(idx)
        load = jnp.stack([load[0] + counts[0],
                          jnp.maximum(load[1], counts[1]),
                          load[2] + counts[2]])
    held = jnp.sum(jnp.where(active, lengths + 1, 0))
    counts = jnp.concatenate([load, jnp.stack([
        held, jnp.sum(active), 1,
        latent_positions_read(pools[0], tables, lengths, 1, cfg.n_heads)
    ]).astype(jnp.int32)])
    return _head(params, x, cfg), new, counts, jnp.stack(routing)


@functools.partial(jax.jit, static_argnames=("cfg", "page"),
                   donate_argnums=(1,))
def _longcat_step(params, pools, tables, toks, lengths, temps, top_ks,
                  top_ps, keys, cfg, page):
    """One token for every slot: on each of the ``2 x layers`` attention
    sublayers the new cache row written at the slot's (page, offset) and the
    absorbed form over the slot's pages of that sublayer's ONE pool; the
    held experts' part of each expert layer and its zero experts. Pools are
    donated. A slot of length 0 is inactive: it flows through (static
    shapes), its row lands on page 0, and it is routed to no expert.

    -> (int32[S + 7]: the tokens, then ``_decode_logits``' counts, so that
    one transfer fetches all; pools; keys; the chosen experts [layers, S, k],
    which stay on the device unless a reference check asks for them; the
    tokens alone, int32[S], as the next step takes them: with the keys they
    let the engine dispatch that step before it has fetched this one's)."""
    logits, new, counts, routing = _decode_logits(
        params, pools, tables, toks, lengths, cfg, page)
    out, new_keys, picked = _sample(logits, temps, top_ks, top_ps, keys,
                                    lengths, counts)
    return out, new, new_keys, routing, picked
