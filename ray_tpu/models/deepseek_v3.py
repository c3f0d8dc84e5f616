"""DeepSeek-V3 family (``model_type: deepseek_v3``; GigaChat3.1-702B-A36B is
the configuration served): latent (MLA) attention under YaRN, a sigmoid router
that keeps a few groups of experts before its top k, a shared expert beside
the routed sum, and a multi-token-prediction (MTP) module that drafts the
token after next.

    x = x + A(N(x));  x = x + F(N(x));  logits = N(x) W_head   (eps 1e-6)

- **Latent attention** ``A``: LongCat-Flash's sublayer, shared and not copied
  (``longcat_flash._latent_qkv``, ``_kvb``, ``_latent_prompt``,
  ``_scatter_latent``, ``prefill_carry`` read a config's fields; that family's
  two latent scales are 1 here). Values are ``v_head_dim`` wide beside keys of
  ``qk_nope_head_dim + qk_rope_head_dim``; the rotary 64 dims turn at YaRN's
  frequencies (``ops.layers.yarn_rows``) and the softmax scale carries YaRN's
  temperature squared (``attn_scale``).
- ``F`` of the first ``n_dense`` layers is a SwiGLU at ``d_ff``; of the
  others ``shared(u) + routed(u)``: ``parallel.moe.sigmoid_gates`` with
  ``n_group`` / ``topk_group`` (the top ``top_k`` of ``score + bias`` among
  the kept groups, weights the unbiased scores over their sum, times
  ``routed_scale``) over ``moe_ffn_held``: the tree holds ``experts_held`` of
  the router's ``n_experts`` from ``expert_offset``, one chip's share, and
  what the absent experts would add is left out.
- **The MTP module** (``n_nextn`` of them; the engine drafts with the first):
  for position ``i`` with ``g_i`` the main model's output after its final
  norm and ``t_(i+1)`` the token that follows, ``u_i = W_eh [N_e(E[t_(i+1)]) |
  N_h(g_i)]``, one decoder block of the expert kind over ``u_0 .. u_i`` with
  a latent cache of its own (row ``i`` of it, at the rotary position ``i + 1``
  of the token it embeds), its own final norm, the main model's head: the
  logits of ``t_(i+2)``.

The device programs at the bottom are what ``models/paged.py``'s
``PagedEngine`` runs for this family. An admission runs the MTP block over
each chunk one token on and ends with ``_deepseek_first_draft`` (the pair
``(g_(n-1), first token)``), so a slot enters its first step with a draft. A
step (``_deepseek_step``) takes every slot's last committed token ``t`` and
its draft ``d`` through the main model as TWO rows at positions ``[len, len +
1]``, accepts or refuses ``d`` (``verify_draft``: greedy equality, or the
speculative-sampling rule, which leaves the emitted distribution the main
model's own), commits one or two tokens a slot, runs the MTP block on the
committed pairs and draws the next draft. Positions, tokens, drafts and the
drafts' distributions pass from step to step on the device.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.layers import rms_norm, yarn_mscale, yarn_rows
from ..ops.quant import mm
from ..parallel.moe import balanced_bias, moe_ffn_held, sigmoid_gates
from .engine import _sample, prefill_in_chunks
from .longcat_flash import (_ffn, _kvb, _latent_prompt, _latent_qkv, _normal,
                            prefill_carry)
from .paged_ops import (attend_latent, latent_positions_read,
                        write_latent_rows)

F32 = jnp.float32

@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    d_model: int = 7168
    n_layers: int = 64                # decoder layers held
    n_layers_published: int = 64
    n_dense: int = 3                  # ... of which the first are dense
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 192
    d_ff: int = 18432
    # the expert layers
    n_experts: int = 256              # the router's width (all chips')
    experts_held: int = 256           # experts this tree holds
    expert_offset: int = 0            # ... starting from this one
    n_group: int = 8
    topk_group: int = 4
    top_k: int = 8
    expert_d_ff: int = 2048
    n_shared: int = 1
    routed_scale: float = 2.5
    n_nextn: int = 1                  # MTP modules held (0: no drafting)
    rope_theta: float = 1e5
    # YaRN: factor, original length, beta_fast, beta_slow, mscale,
    # mscale_all_dim (a factor of 1 is plain rotary)
    yarn: Tuple[float, ...] = (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    norm_eps: float = 1e-6
    # how the prefill cuts its work (no effect on the result)
    prefill_chunk: int = 2048         # tokens a dispatch
    key_block: int = 256              # keys a step of the online softmax
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "yarn", tuple(self.yarn))
        if self.n_layers > self.n_layers_published \
                or self.n_dense > self.n_layers:
            raise ValueError("layers held reach past the published depth, or "
                             "dense layers past the layers held")
        if self.expert_offset + self.experts_held > self.n_experts:
            raise ValueError("experts held reach past the router's width")
        if self.n_experts % self.n_group or self.topk_group > self.n_group:
            raise ValueError("the router's width is n_group equal groups, "
                             "of which topk_group are kept")
        if self.qk_rope_head_dim % 2 or self.prefill_chunk % self.key_block:
            raise ValueError("rotary pairs need an even width; key_block "
                             "divides prefill_chunk")
        if self.n_nextn not in (0, 1):
            raise ValueError("the engine drafts one token: n_nextn is 0 or 1")

    @property
    def n_sublayers(self) -> int:
        """Layers with a latent cache: the decoder's and the MTP block's."""
        return self.n_layers + self.n_nextn

    @property
    def latent_width(self) -> int:
        """Values cached a position a sublayer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    # LongCat-Flash's two latent scales, which this family does not have
    q_scale = 1.0
    kv_scale = 1.0

    @property
    def attn_scale(self) -> float:
        """``(dn + dr) ** -0.5`` times YaRN's temperature squared."""
        factor, mscale_all_dim = self.yarn[0], self.yarn[5]
        return ((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                * yarn_mscale(factor, mscale_all_dim) ** 2)

    def param_count(self) -> int:
        d, H = self.d_model, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        attn = (d * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * H * qk + d * self.latent_width
                + self.kv_lora_rank + self.kv_lora_rank * H
                * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * d + 2 * d)
        moe = (d * self.n_experts + self.n_experts
               + (self.experts_held + self.n_shared) * 3 * d
               * self.expert_d_ff)
        dense = 3 * d * self.d_ff
        mtp = self.n_nextn * (attn + moe + 2 * d * d + 3 * d)
        return (self.n_dense * (attn + dense)
                + (self.n_layers - self.n_dense) * (attn + moe) + mtp + d
                + 2 * self.vocab_size * d)


DEEPSEEK_V3_DEBUG = DeepseekV3Config(
    vocab_size=96, d_model=64, n_layers=3, n_layers_published=6, n_dense=1,
    n_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, d_ff=96, n_experts=16,
    experts_held=16, n_group=4, topk_group=2, top_k=3, expert_d_ff=48,
    yarn=(4.0, 16, 32.0, 1.0, 1.0, 1.0), prefill_chunk=16, key_block=8,
    dtype=jnp.float32)


def _rows(positions, cfg: DeepseekV3Config):
    """cos, sin of the given positions at the configuration's YaRN."""
    return yarn_rows(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                     *cfg.yarn)


# ------------------------------------------------------------------ weights
def init_params(cfg: DeepseekV3Config, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights as ``longcat_flash.init_params``' (projections normal
    over the square root of their fan-in, so that queries, keys and values
    have unit scale beside the rotary parts; norms small seeded numbers,
    ``rms_norm`` multiplies by 1 + them; routers float32), then every
    router's selection bias calibrated (``calibrate_router_bias``)."""
    key, sample = jax.random.split(key)
    return calibrate_router_bias(_seeded_params(cfg, key), cfg, sample)


def _seeded_layer(cfg: DeepseekV3Config, key: jax.Array, dense: bool):
    d, dt, H = cfg.d_model, cfg.dtype, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    a = jax.random.split(key, 17)
    layer = {"attn": {
        "norm": _normal(a[0], (d,), dt, 0.05),
        "w_qa": _normal(a[1], (d, cfg.q_lora_rank), dt),
        "q_norm": _normal(a[2], (cfg.q_lora_rank,), dt, 0.05),
        "w_qb": _normal(a[3], (cfg.q_lora_rank, H * qk), dt),
        "w_kva": _normal(a[4], (d, cfg.latent_width), dt),
        "kv_norm": _normal(a[5], (cfg.kv_lora_rank,), dt, 0.05),
        "w_kvb": _normal(a[6], (cfg.kv_lora_rank, H * (
            cfg.qk_nope_head_dim + cfg.v_head_dim)), dt),
        "wo": _normal(a[7], (H * cfg.v_head_dim, d), dt),
    }, "ffn_norm": _normal(a[8], (d,), dt, 0.05)}
    if dense:
        layer["mlp"] = {"w_gate": _normal(a[9], (d, cfg.d_ff), dt),
                        "w_up": _normal(a[10], (d, cfg.d_ff), dt),
                        "w_down": _normal(a[11], (cfg.d_ff, d), dt)}
        return layer
    eh, f, fs = cfg.experts_held, cfg.expert_d_ff, \
        cfg.expert_d_ff * cfg.n_shared
    layer["moe"] = {
        "w_router": _normal(a[9], (d, cfg.n_experts), F32),
        "router_bias": jnp.zeros((cfg.n_experts,), F32),
        "w_gate": _normal(a[10], (eh, d, f), dt),
        "w_up": _normal(a[11], (eh, d, f), dt),
        "w_down": _normal(a[12], (eh, f, d), dt)}
    layer["shared"] = {"w_gate": _normal(a[13], (d, fs), dt),
                       "w_up": _normal(a[14], (d, fs), dt),
                       "w_down": _normal(a[15], (fs, d), dt)}
    return layer


def _seeded_params(cfg: DeepseekV3Config, key: jax.Array) -> Dict[str, Any]:
    d, dt = cfg.d_model, cfg.dtype
    keys = jax.random.split(key, cfg.n_layers + cfg.n_nextn + 3)
    params: Dict[str, Any] = {
        "embedding": _normal(keys[0], (cfg.vocab_size, d), dt, 1.0),
        "lm_head": _normal(keys[1], (d, cfg.vocab_size), dt),
        "norm": _normal(keys[2], (d,), dt, 0.05),
        "layers": [_seeded_layer(cfg, keys[i + 3], i < cfg.n_dense)
                   for i in range(cfg.n_layers)],
        "mtp": [],
    }
    for j in range(cfg.n_nextn):
        k = jax.random.split(keys[cfg.n_layers + 3 + j], 5)
        params["mtp"].append({
            "enorm": _normal(k[0], (d,), dt, 0.05),
            "hnorm": _normal(k[1], (d,), dt, 0.05),
            "eh_proj": _normal(k[2], (2 * d, d), dt),
            "layer": _seeded_layer(cfg, k[3], False),
            "norm": _normal(k[4], (d,), dt, 0.05)})
    return params


def calibrate_router_bias(params, cfg: DeepseekV3Config, key: jax.Array,
                          n: int = 2048) -> Dict[str, Any]:
    """Set every expert layer's selection bias (the MTP block's too) so that
    all ``n_experts`` outputs are chosen about equally often, as load
    balancing leaves a trained ``e_score_correction_bias``:
    ``longcat_flash.calibrate_router_bias``'s one seeded pass of ``n`` tokens
    on this family's layers and sigmoid scores. The bias only selects."""
    tokens = jax.random.randint(key, (n + 1,), 0, cfg.vocab_size)
    one = dataclasses.replace(cfg, prefill_chunk=n, key_block=min(n, 512))
    lats = [jnp.zeros((n, cfg.latent_width), cfg.dtype)
            for _ in range(cfg.n_sublayers)]
    seen = []

    def calibrated(moe, u):
        scores = jax.nn.sigmoid(jnp.dot(u.astype(F32), moe["w_router"]))
        moe = {**moe, "router_bias": balanced_bias(scores, cfg.top_k)}
        seen.append(moe)
        return moe

    _run_chunk(params, tokens[:n], tokens[1:], jnp.int32(0), jnp.int32(n),
               lats, one, before_moe=calibrated)
    moes = iter(seen)

    def with_bias(layer):
        return {**layer, "moe": next(moes)} if "moe" in layer else layer

    layers = [with_bias(lyr) for lyr in params["layers"]]
    mtp = [{**m, "layer": with_bias(m["layer"])} for m in params["mtp"]]
    return {**params, "layers": layers, "mtp": mtp}


# ------------------------------------------------------------------- layers
#: rows from which the held experts' products are grouped by expert (a
#: prompt's chunk); under it (a step's 2 x slots rows) every held expert
#: multiplies every row, as ``granite_moe_hybrid`` measured for SwiGLU
#: experts of this width's order
GROUPED_FROM_ROWS = 1024


def _moe(layer, u, token_mask, cfg: DeepseekV3Config):
    """u [T, D] -> (shared(u) + routed(u), the chosen experts [T, k],
    int32[2]: held experts hit, most tokens of one expert)."""
    moe = layer["moe"]
    with jax.named_scope("router"):
        vals, idx = sigmoid_gates(
            u, moe["w_router"], moe["router_bias"], cfg.top_k,
            cfg.routed_scale, n_group=cfg.n_group, topk_group=cfg.topk_group)
    with jax.named_scope("moe"):
        routed, hit, most = moe_ffn_held(
            u, vals, idx, {w: moe[w] for w in ("w_gate", "w_up", "w_down")},
            cfg.expert_offset, token_mask, cfg.n_experts, GROUPED_FROM_ROWS)
    with jax.named_scope("shared_expert"):
        shared = _ffn(layer["shared"], u)
    return routed + shared, idx, jnp.stack([hit, most]).astype(jnp.int32)


def _block(layer, x, attend, token_mask, cfg: DeepseekV3Config,
           before_moe=None):
    """One decoder layer on x [T, D]. ``attend(att, h)`` is the attention on
    the normed hidden state, as its caller caches it. -> (x, the chosen
    experts [T, k] or None, the expert layer's counts or None)."""
    att = layer["attn"]
    with jax.named_scope("attention"):
        h = rms_norm(x, att["norm"], cfg.norm_eps)
    o = attend(att, h)
    with jax.named_scope("attention"):
        x = x + mm(o, att["wo"])
    u = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    if "mlp" in layer:
        with jax.named_scope("mlp"):
            return x + _ffn(layer["mlp"], u), None, None
    if before_moe is not None:
        layer = {**layer, "moe": before_moe(layer["moe"], u)}
    y, idx, counts = _moe(layer, u, token_mask, cfg)
    return x + y, idx, counts


def _mtp_input(mtp, params, g, next_tokens, cfg: DeepseekV3Config):
    """``W_eh [N_e(E[t_(i+1)]) | N_h(g_i)]``: g [T, D] the main model's
    outputs after its final norm, next_tokens [T] the tokens that follow."""
    e = rms_norm(params["embedding"][next_tokens].astype(cfg.dtype),
                 mtp["enorm"], cfg.norm_eps)
    h = rms_norm(g, mtp["hnorm"], cfg.norm_eps)
    return mm(jnp.concatenate([e, h], axis=-1), mtp["eh_proj"])


def _head(params, x, norm, cfg: DeepseekV3Config):
    return mm(rms_norm(x, norm, cfg.norm_eps), params["lm_head"])


def _add_load(load, counts):
    return load if counts is None else jnp.stack(
        [load[0] + counts[0], jnp.maximum(load[1], counts[1])])


def _run_chunk(params, tokens, next_tokens, start, n_valid, lats, cfg,
               before_moe=None):
    """One chunk of one sequence through every layer and the MTP block.
    tokens [N] at positions ``start ..``, next_tokens [N] the tokens one on
    (a position whose follower is not known yet holds anything: its MTP row
    is stale until ``_deepseek_first_draft`` or a step writes it); lats: per
    sublayer the cache rows [T, C + dr] of the positions before, the MTP
    block's last. -> (the main model's outputs after the final norm [N, D],
    the MTP block's outputs before its norm [N, D] or None, lats with the
    chunk's rows, the chosen experts [expert layers, N, k])."""
    N = tokens.shape[0]
    at = start + jnp.arange(N)
    new, routing = [], []

    def through(layer, x, buf_in, cos, sin, mask):
        def attend(att, h):
            with jax.named_scope("attention"):
                q_nope, q_rope, row = _latent_qkv(att, h, cos, sin, cfg)
            with jax.named_scope("latent_write"):
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf_in, row.astype(buf_in.dtype), start, axis=0)
            new.append(buf)
            with jax.named_scope("latent_attn"):
                return _latent_prompt(q_nope, q_rope, buf, _kvb(att, cfg),
                                      start, cfg)

        x, idx, _ = _block(layer, x, attend, mask, cfg, before_moe)
        if idx is not None:
            routing.append(idx)
        return x

    x = params["embedding"][tokens].astype(cfg.dtype)
    cos, sin = _rows(at, cfg)
    for i, layer in enumerate(params["layers"]):
        x = through(layer, x, lats[i], cos, sin, at < n_valid)
    g = rms_norm(x, params["norm"], cfg.norm_eps)
    xm = None
    for j, mtp in enumerate(params["mtp"]):
        cos, sin = _rows(at + 1, cfg)
        xm = through(mtp["layer"], _mtp_input(mtp, params, g, next_tokens,
                                              cfg),
                     lats[cfg.n_layers + j], cos, sin, at + 1 < n_valid)
    return g, xm, new, jnp.stack(routing)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg: DeepseekV3Config):
    """tokens [L] -> (logits [L, V], the MTP module's logits [L, V]: row
    ``i`` from ``(g_i, t_(i+1))`` predicts ``t_(i+2)``, so the last row, whose
    follower is not given, means nothing; None without a module). The whole
    forward pass of one sequence as ONE chunk (tests hold it against the
    plain reference)."""
    L = tokens.shape[0]
    T = -(-L // cfg.key_block) * cfg.key_block
    one = dataclasses.replace(cfg, prefill_chunk=T)
    padded = jnp.pad(tokens, (0, T - L + 1))
    g, xm, _, _ = _run_chunk(params, padded[:T], padded[1:], jnp.int32(0),
                             jnp.int32(L), prefill_carry(one, T), one)
    logits = mm(g[:L], params["lm_head"])
    if xm is None:
        return logits, None
    return logits, _head(params, xm[:L], params["mtp"][0]["norm"], cfg)


# ------------------------------------------------------------ the draft rule
def _distributions(logits, temps, top_ks, top_ps):
    """logits [N, V] -> each row's distribution after its temperature, top-k
    and nucleus top-p, float32: what ``engine._pick_token`` samples from. The
    vocabulary is sorted only if a row cuts it (one scalar for the batch, as
    ``engine._pick_tokens`` decides its own sort)."""
    scaled = logits.astype(F32) / jnp.maximum(temps, 1e-6)[:, None]

    def cut(scaled, logits, top_k, top_p):
        order = jnp.argsort(-logits)
        ranks = jnp.argsort(order)
        probs = jax.nn.softmax(scaled[order])
        k_mask = jnp.where(top_k > 0, ranks < top_k, True)
        p_mask = (jnp.cumsum(probs) - probs)[ranks] < top_p
        return jnp.where(k_mask & p_mask, scaled, -1e30)

    masked = jax.lax.cond(
        jnp.any((top_ks > 0) | (top_ps < 1.0)),
        lambda: jax.vmap(cut)(scaled, logits.astype(F32), top_ks, top_ps),
        lambda: scaled)
    return jax.nn.softmax(masked, axis=-1)


def _draw(probs, u):
    """One token a row by the inverse of the cumulative distribution: probs
    [N, V] (any positive total), u [N] uniform in [0, 1)."""
    cum = jnp.cumsum(probs, axis=-1)
    return jnp.minimum(jnp.sum(cum <= (u * cum[:, -1])[:, None], axis=-1),
                       probs.shape[-1] - 1).astype(jnp.int32)


def verify_draft(p0, p1, q, draft, u):
    """Speculative sampling (Leviathan et al. 2023; Chen et al. 2023) of one
    drafted token a row. p0 [N, V]: the target's distribution of the next
    token, p1 [N, V]: of the one after it given the draft, q [N, V]: the
    distribution the draft was drawn from, draft [N] (under 0: none was
    offered), u [N, 3] uniforms. The draft is accepted with probability
    ``min(1, p0(d) / q(d))``; refused, the token is drawn from ``norm(max(0,
    p0 - q))``; accepted, a second is drawn from ``p1``. What is emitted is
    distributed as the target's own sampling, whatever ``q``.
    -> (accepted bool[N], the first token, the second)."""
    has = draft >= 0
    d = jnp.maximum(draft, 0)[:, None]
    p_d = jnp.take_along_axis(p0, d, axis=-1)[:, 0]
    q_d = jnp.take_along_axis(q, d, axis=-1)[:, 0]
    accepted = has & (u[:, 0] * q_d < p_d)
    left = jnp.maximum(p0 - jnp.where(has[:, None], q, 0.0), 0.0)
    # nothing left (q covers p0 and the draft was still refused: rounding):
    # the target's own distribution
    left = jnp.where(left.sum(-1, keepdims=True) > 0.0, left, p0)
    first = jnp.where(accepted, d[:, 0], _draw(left, u[:, 1]))
    return accepted, first.astype(jnp.int32), _draw(p1, u[:, 2])


@jax.named_scope("sampling")
def _commit(l0, l1, draft, q, temps, top_ks, top_ps, keys, active):
    """A step's tokens from its two rows of logits [S, V]: -> (accepted
    bool[S], first int32[S], second int32[S], one uniform a slot for the next
    draft, the keys after). A greedy slot accepts when the main model's
    argmax IS the draft and commits argmaxes; the distributions are built
    only if an active slot samples."""
    splits = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
    u = jax.vmap(lambda k: jax.random.uniform(k, (4,)))(splits[:, 1])
    g0, g1 = (jnp.argmax(x, axis=-1).astype(jnp.int32) for x in (l0, l1))
    sampling = (temps > 0.0) & active

    def sampled():
        p = _distributions(jnp.concatenate([l0, l1]), jnp.tile(temps, 2),
                           jnp.tile(top_ks, 2), jnp.tile(top_ps, 2))
        S = l0.shape[0]
        acc, first, second = verify_draft(p[:S], p[S:], q, draft, u[:, :3])
        return (jnp.where(sampling, acc, g0 == draft),
                jnp.where(sampling, first, g0),
                jnp.where(sampling, second, g1))

    acc, first, second = jax.lax.cond(
        jnp.any(sampling), sampled, lambda: (g0 == draft, g0, g1))
    return acc & active, first, second, u[:, 3], splits[:, 0]


@jax.named_scope("sampling")
def _draft(logits, temps, top_ks, top_ps, u, active):
    """The next draft from the MTP block's logits [S, V]: -> (int32[S], the
    distribution it was drawn from, float32[S, V]: zeros where no active
    slot samples, a greedy slot's is never read)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampling = (temps > 0.0) & active

    def sampled():
        q = _distributions(logits, temps, top_ks, top_ps)
        return jnp.where(sampling, _draw(q, u), greedy), q

    return jax.lax.cond(
        jnp.any(sampling), sampled,
        lambda: (greedy, jnp.zeros(logits.shape, F32)))


# ----------------------------------------------- programs of ``PagedEngine``
@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4,))
def _deepseek_prefill_chunk(params, tokens, start, n_valid, lats, prompt,
                            cfg):
    """One chunk of one request's prefill, the MTP block run over it one
    token on; the carried rows are donated. ``tokens`` [prefill_chunk] is
    padded past ``n_valid`` (a position of the whole prompt); ``prompt`` is
    the whole padded prompt (one entry longer than the carried rows), from
    which the chunk takes each position's follower, and is handed back as it
    came. The padded tail's rows are stale, harmless as in
    ``longcat_flash._longcat_prefill_chunk``; so is the MTP row of position
    ``n_valid - 1``, whose follower is the token this admission samples.
    (The MTP block's cache rows come from its INPUT alone; its attention's
    output and its experts' feed nothing returned here, and the compiler
    drops them from this program: AOT, PR 60.)
    -> ((the logits at ``n_valid - 1``, the main model's output there after
    its final norm: they lie in the last chunk), lats, prompt, the chosen
    experts [expert layers, chunk, k], which only a reference check reads)."""
    N = tokens.shape[0]
    follows = jax.lax.dynamic_slice_in_dim(prompt, start + 1, N)
    g, _, lats, routing = _run_chunk(params, tokens, follows, start, n_valid,
                                     lats, cfg)
    row = jnp.clip(n_valid - 1 - start, 0, N - 1)
    return (mm(g[row], params["lm_head"]), g[row]), lats, prompt, routing


def prefill(params, prompt, total: int, cfg: DeepseekV3Config,
            keep_routing: bool = False):
    """Prefill one request chunk by chunk (``engine.prefill_in_chunks`` over
    ``_deepseek_prefill_chunk``). -> (next-token logits, the main model's
    output at the prompt's last position after its final norm, per sublayer
    the cache rows [total, C + dr] for the page scatter, the MTP block's
    last; with ``keep_routing`` also every prompt position's chosen experts
    [expert layers, len(prompt), k])."""
    whole = np.zeros(total + 1, np.int32)   # on the host: nothing compiles
    whole[:len(prompt)] = prompt            # per prompt length
    (first, g), (lats, _), routing = prefill_in_chunks(
        _deepseek_prefill_chunk, params, prompt, cfg.prefill_chunk,
        (prefill_carry(cfg, total), whole), cfg, keep_routing)
    return (first, g, lats, routing) if keep_routing else (first, g, lats)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1, 9,
                                                                      10))
def _deepseek_first_draft(params, pool, table, g, tok, n, temp, top_k, top_p,
                          drafts, draft_q, key, slot, cfg):
    """An admission's first draft: the MTP block on the pair ``(g_(n-1),
    first token)`` at row ``n - 1`` of the slot's MTP cache (rotary position
    ``n``), in the absorbed form over the pages the scatter has just written,
    which the row joins; the draft and its distribution written into the
    engine's per-slot arrays at ``slot`` (the pool and both arrays are
    donated). table [1, P]; g [D]; key: the slot's sampling key, from which
    the draft's uniform is folded where the step's own splits never reach.
    -> (pool, drafts, draft_q)."""
    u = jax.random.uniform(jax.random.fold_in(key, 1 << 20))
    mtp = params["mtp"][0]
    at = jnp.reshape(n - 1, (1,))
    cos, sin = _rows(at + 1, cfg)
    out = {}

    def attend(att, h):
        with jax.named_scope("attention"):
            q_nope, q_rope, row = _latent_qkv(att, h, cos, sin, cfg)
            w = _kvb(att, cfg)
        out["pool"] = write_latent_rows(row[:, None], pool, table, at)
        return attend_latent(
            q_nope[:, None], q_rope[:, None], w[..., :cfg.qk_nope_head_dim],
            w[..., cfg.qk_nope_head_dim:], out["pool"], table, at,
            cfg.attn_scale)[:, 0]

    x = _mtp_input(mtp, params, g[None], jnp.reshape(tok, (1,)), cfg)
    x, _, _ = _block(mtp["layer"], x, attend, jnp.ones((1,), bool), cfg)
    d, q = _draft(_head(params, x, mtp["norm"], cfg), temp[None],
                  top_k[None], top_p[None], u[None], jnp.ones((1,), bool))
    return (out["pool"], drafts.at[slot].set(d[0]),
            draft_q.at[slot].set(q[0]))


def _step_layer(layer, x, pool, tables, lengths, cos, sin, mask, load, cfg):
    """One decoder layer of a step on x [S R, D], ``R`` rows a slot at
    positions ``lengths .. lengths + R - 1``: the rows' cache rows written
    into the slot's pages, the absorbed form over them. -> (x, the pool, the
    chosen experts or None, ``load`` with the layer's counts)."""
    S = tables.shape[0]
    out = {}

    def attend(att, h):
        with jax.named_scope("attention"):
            q_nope, q_rope, row = _latent_qkv(att, h, cos, sin, cfg)
            w = _kvb(att, cfg)
        out["pool"] = write_latent_rows(row.reshape(S, -1, row.shape[-1]),
                                        pool, tables, lengths)
        o = attend_latent(
            q_nope.reshape(S, -1, *q_nope.shape[1:]),
            q_rope.reshape(S, -1, *q_rope.shape[1:]),
            w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:],
            out["pool"], tables, lengths, cfg.attn_scale)
        return o.reshape(x.shape[0], -1)

    x, idx, counts = _block(layer, x, attend, mask, cfg)
    return x, out["pool"], idx, _add_load(load, counts)


def _step_rows(params, pools, tables, rows, lengths, cfg: DeepseekV3Config):
    """rows [S, R] tokens at positions ``lengths .. lengths + R - 1`` of every
    slot through the decoder's layers. -> (the outputs after the final norm
    [S R, D], the logits [S, R, V], the decoder's pools, the chosen experts a
    layer, int32[2] the expert layers' load so far, the rows' positions)."""
    S, R = rows.shape
    at = (lengths[:, None] + jnp.arange(R)[None, :]).reshape(-1)
    mask = jnp.repeat(lengths > 0, R)
    x = params["embedding"][rows.reshape(-1)].astype(cfg.dtype)
    cos, sin = _rows(at, cfg)
    new, routing = [], []
    load = jnp.zeros((2,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        x, pool, idx, load = _step_layer(layer, x, pools[i], tables, lengths,
                                         cos, sin, mask, load, cfg)
        new.append(pool)
        if idx is not None:
            routing.append(idx)
    g = rms_norm(x, params["norm"], cfg.norm_eps)
    logits = mm(g, params["lm_head"]).reshape(S, R, -1)
    return g, logits, new, routing, load, at


def _step_counts(load, pool, tables, lengths, rows: int, drafted, accepted,
                 cfg):
    """A step's counts, as they ride behind its tokens: held experts hit
    summed over the expert layers, most tokens of one expert, the cached
    positions the active slots hold after the step's rows, drafts offered,
    drafts accepted, active slots, 1 (summed over the steps a call lands,
    they count them), and the positions one of the step's reads gathers
    (every slot's blocks, whole: ``attend_latent``'s own rule)."""
    active = lengths > 0
    return jnp.concatenate([load, jnp.stack([
        jnp.sum(jnp.where(active, lengths + rows, 0)), drafted, accepted,
        jnp.sum(active), 1,
        latent_positions_read(pool, tables, lengths, rows, cfg.n_heads)
    ]).astype(jnp.int32)])


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _deepseek_step_one(params, pools, tables, toks, lengths, temps, top_ks,
                       top_ps, keys, cfg):
    """One token for every slot of a tree without an MTP module (``n_nextn``
    0): the step every other family's engine row runs, on this family's
    layers. -> (int32[S + 8]: the tokens, then ``_step_counts``; pools; keys;
    (the chosen experts [expert layers, S, k], the logits [S, 1, V]); the
    tokens alone)."""
    _, logits, new, routing, load, _ = _step_rows(
        params, pools, tables, toks[:, None], lengths, cfg)
    out, new_keys, picked = _sample(
        logits[:, 0], temps, top_ks, top_ps, keys, lengths,
        _step_counts(load, pools[0], tables, lengths, 1, 0, 0, cfg))
    return out, new, new_keys, (jnp.stack(routing), logits), picked


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1, 9))
def _deepseek_step(params, pools, tables, toks, lengths, temps, top_ks,
                   top_ps, keys, draft_q, drafts, cfg):
    """One or two tokens for every slot. Rows ``[t, d]`` of every slot (its
    last committed token, not cached yet, and the draft of the one after) go
    through the main model at positions ``[len, len + 1]``: two latent rows
    written a layer, ``attend_latent`` with two query rows, logits ``l_0`` and
    ``l_1``. ``_commit`` accepts or refuses the draft: accepted, the step
    commits ``d`` and a token from ``l_1`` (the slot advances by two);
    refused, the one token, and the row written for ``d`` is stale and
    overwritten by the next step, as a padded prefill's tail is. The MTP
    block then runs on ``(g_0, first committed)`` and ``(g_1, second
    committed)``, its cache written for both, and the next draft is drawn
    from the last valid row's logits. A slot of length 0 is inactive: it
    flows through (static shapes), its rows land on page 0, and it is routed
    to no expert. Pools and the drafts' distributions are donated.

    -> (int32[3 S + 8]: every slot's two tokens, how many of them count,
    then ``_step_counts``, so that one transfer fetches all; pools; keys;
    what a reference check reads and the engine publishes as the step lands
    (the chosen experts [expert layers, 2 S, k], the two rows' logits [S, 2,
    V], the MTP block's logits of the next draft [S, V], which drafts were
    accepted); and for the next step, on the device: its tokens int32[S],
    its lengths, its drafts, the drafts' distributions)."""
    S = tables.shape[0]
    active = lengths > 0
    g, logits, new, routing, load, at = _step_rows(
        params, pools, tables,
        jnp.stack([toks, jnp.maximum(drafts, 0)], axis=1), lengths, cfg)
    accepted, first, second, u, new_keys = _commit(
        logits[:, 0], logits[:, 1], drafts, draft_q, temps, top_ks, top_ps,
        keys, active)
    mtp = params["mtp"][0]
    committed = jnp.stack([first, second], axis=1).reshape(-1)
    cos, sin = _rows(at + 1, cfg)   # a pair stands where its token does
    xm, pool, idx, load = _step_layer(
        mtp["layer"], _mtp_input(mtp, params, g, committed, cfg),
        pools[cfg.n_layers], tables, lengths, cos, sin, jnp.repeat(active, 2),
        load, cfg)
    xm = xm.reshape(S, 2, -1)
    q_logits = _head(params, jnp.where(accepted[:, None], xm[:, 1], xm[:, 0]),
                     mtp["norm"], cfg)
    new_drafts, new_q = _draft(q_logits, temps, top_ks, top_ps, u, active)
    n = jnp.where(active, 1 + accepted.astype(jnp.int32), 0)
    out = jnp.concatenate([committed, n, _step_counts(
        load, pool, tables, lengths, 2, jnp.sum(active & (drafts >= 0)),
        jnp.sum(accepted), cfg)])
    kept = (jnp.stack(routing + [idx]), logits, q_logits, accepted)
    return (out, new + [pool], new_keys, kept,
            jnp.where(accepted, second, first), lengths + n, new_drafts,
            new_q)
