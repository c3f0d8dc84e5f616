"""Nemotron-H family: a hybrid of Mamba-2, attention and routed-expert blocks.

One mixer a block, ``h <- h + mixer_i(RMSNorm_i(h))``, the kind of block
``i`` given by character ``i`` of ``cfg.pattern``:

- ``M``  Mamba-2 (``ops/ssm.py``): in-projection to ``[z | xBC | dt]``,
  depthwise causal convolution and SiLU on ``xBC``, the state-space scan,
  a gated group RMSNorm, out-projection. Per sequence it carries the SSM
  state (float32) and the last ``conv_kernel - 1`` inputs of the convolution.
- ``*``  causal grouped-query attention WITHOUT a positional embedding
  (position comes from the Mamba blocks). Per sequence it carries K/V.
- ``E``  routed experts (``parallel/moe.py``): sigmoid scores with a
  selection bias over ``n_experts``, the top ``top_k`` normalised and
  scaled, non-gated squared-ReLU experts, one shared expert. The tree holds
  ``experts_held`` of the experts, from ``expert_offset``: one chip's share
  of an expert-parallel deployment. What the absent experts would add is
  left out; on one chip the layer runs without its exchange.

Pure functions over a params dict, as ``models/llama.py``. The three device
programs at the bottom are what ``models/paged.py``'s ``PagedEngine`` runs
for this family: a prefill of one padded prompt that returns the recurrent
state AT ``n_valid``, the write of that state into a slot, and the decode
step of all slots over page pools and per-slot state, both donated.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops import ssm
from ..ops.layers import rms_norm
from ..ops.quant import mm
from ..parallel.moe import (balanced_bias, moe_ffn_share, relu2,
                            sigmoid_gates)
from .engine import _sample
from .paged_ops import paged_attention

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    d_model: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # routed experts
    n_experts: int = 128          # the router's width: all experts
    experts_held: int = 128       # how many of them this tree holds
    expert_offset: int = 0        # ... starting from this one
    top_k: int = 6
    expert_d_ff: int = 1856
    shared_d_ff: int = 3712
    routed_scale: float = 2.5
    norm_topk: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set("ME*"):
            raise ValueError(f"pattern {self.pattern!r}: one of M, E, * a "
                             "block")
        if self.expert_offset + self.experts_held > self.n_experts:
            raise ValueError("experts held reach past the router's width")
        if self.mamba_heads % self.n_groups or \
                self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide into their groups")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_attn_layers(self) -> int:
        return self.pattern.count("*")

    @property
    def n_mamba_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def n_moe_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    def param_count(self) -> int:
        d = self.d_model
        m = (d * (2 * self.d_inner + 2 * self.n_groups * self.ssm_state
                  + self.mamba_heads) + self.conv_dim * (self.conv_kernel + 1)
             + 3 * self.mamba_heads + self.d_inner + self.d_inner * d)
        a = 2 * d * self.n_heads * self.head_dim \
            + 2 * d * self.n_kv_heads * self.head_dim
        e = (d * self.n_experts + self.n_experts
             + self.experts_held * 2 * d * self.expert_d_ff
             + 2 * d * self.shared_d_ff)
        return (self.n_mamba_layers * m + self.n_attn_layers * a
                + self.n_moe_layers * e + self.n_layers * d + d
                + 2 * self.vocab_size * d)


NEMOTRON_H_DEBUG = NemotronHConfig(
    vocab_size=96, d_model=64, pattern="MEM*E", mamba_heads=8,
    mamba_head_dim=8, ssm_state=16, n_groups=2, chunk_size=8, n_heads=4,
    n_kv_heads=2, head_dim=16, n_experts=16, experts_held=16, top_k=3,
    expert_d_ff=48, shared_d_ff=96, dtype=jnp.float32)


# ------------------------------------------------------------------ weights
def _normal(key, shape, dtype, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_params(cfg: NemotronHConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights. ``A_log``, ``dt_bias`` and ``D`` follow the
    published initialisation's ranges (A in 1..16, dt log-uniform in
    1e-3..1e-1), the norms are small seeded numbers so that a test sees
    them; they and the router stay float32. The routers' selection bias
    is then calibrated (``calibrate_router_bias``): it is what balances
    the experts' load in a trained model, and seeded weights without it
    send a whole batch to a few experts."""
    key, sample = jax.random.split(key)
    return calibrate_router_bias(_seeded_params(cfg, key), cfg, sample)


def calibrate_router_bias(params, cfg: NemotronHConfig, key: jax.Array,
                          n: int = 512) -> Dict[str, Any]:
    """Set every expert layer's selection bias so that its experts are
    chosen about equally often: one pass of ``n`` seeded random tokens
    (one sequence) through the blocks, and at each expert layer the bias
    of expert ``e`` becomes the offset that puts the (1 - top_k /
    n_experts) quantile of its score over those tokens where every other
    expert's is. An expert is then over the common threshold for
    ``top_k / n_experts`` of the tokens, as load balancing leaves a
    trained router. The bias only selects; the weights of the chosen
    stay the scores without it."""
    tokens = jax.random.randint(key, (n,), 0, cfg.vocab_size)
    x = params["embedding"][tokens].astype(cfg.dtype)
    everyone = jnp.ones((n,), bool)
    layers = []
    for kind, layer in zip(cfg.pattern, params["layers"]):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == "M":
            out = _mamba_prompt(layer, h, n, cfg)[0]
        elif kind == "*":
            out = _attention_prompt(layer, h, cfg)[0]
        else:
            scores = jax.nn.sigmoid(jnp.dot(h.astype(F32),
                                            layer["w_router"]))
            layer = {**layer,
                     "router_bias": balanced_bias(scores, cfg.top_k)}
            out = _moe(layer, h, everyone, cfg)[0]
        layers.append(layer)
        x = x + out
    return {**params, "layers": layers}


def seeded_mamba(cfg, k) -> Dict[str, Any]:
    """One Mamba-2 mixer's seeded weights from the keys ``k[1] .. k[8]``:
    what every family with this mixer seeds (``cfg``: its ``d_model`` and
    Mamba-2 sizes)."""
    d, dt = cfg.d_model, cfg.dtype
    H, di = cfg.mamba_heads, cfg.d_inner
    step = jnp.exp(jax.random.uniform(k[3], (H,), F32)
                   * (math.log(0.1) - math.log(0.001))
                   + math.log(0.001))
    return {
        "w_in": _normal(k[1], (d, 2 * di + 2 * cfg.n_groups
                               * cfg.ssm_state + H), dt),
        "conv_w": _normal(k[2], (cfg.conv_kernel, cfg.conv_dim), dt,
                          1.0 / math.sqrt(cfg.conv_kernel)),
        "conv_b": _normal(k[8], (cfg.conv_dim,), dt, 0.05),
        # softplus(dt_bias) = step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(k[4], (H,), F32, 1.0, 16.0)),
        "D": 1.0 + 0.1 * jax.random.normal(k[5], (H,), F32),
        "gate_norm": _normal(k[6], (di,), dt, 0.05),
        "w_out": _normal(k[7], (di, d), dt),
    }


def _seeded_params(cfg: NemotronHConfig, key: jax.Array) -> Dict[str, Any]:
    d, dt = cfg.d_model, cfg.dtype
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: Dict[str, Any] = {
        "embedding": _normal(keys[0], (cfg.vocab_size, d), dt, 1.0),
        "lm_head": _normal(keys[1], (d, cfg.vocab_size), dt),
        "norm": jnp.zeros((d,), dt),
        "layers": [],
    }
    for i, kind in enumerate(cfg.pattern):
        k = jax.random.split(keys[i + 2], 9)
        layer = {"norm": _normal(k[0], (d,), dt, 0.05)}
        if kind == "M":
            layer.update(seeded_mamba(cfg, k))
        elif kind == "*":
            qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            layer.update({"wq": _normal(k[1], (d, qd), dt),
                          "wk": _normal(k[2], (d, kvd), dt),
                          "wv": _normal(k[3], (d, kvd), dt),
                          "wo": _normal(k[4], (qd, d), dt)})
        else:
            eh, f, fs = cfg.experts_held, cfg.expert_d_ff, cfg.shared_d_ff
            layer.update({
                "w_router": _normal(k[1], (d, cfg.n_experts), F32),
                "router_bias": jnp.zeros((cfg.n_experts,), F32),
                "w_up": _normal(k[3], (eh, d, f), dt),
                "w_down": _normal(k[4], (eh, f, d), dt),
                "ws_up": _normal(k[5], (d, fs), dt),
                "ws_down": _normal(k[6], (fs, d), dt),
            })
        params["layers"].append(layer)
    return params


def expert_share(params: Dict[str, Any], offset: int, held: int
                 ) -> Dict[str, Any]:
    """The tree of one chip of a deployment that divides each layer's
    experts: experts ``offset .. offset + held - 1`` of a tree that holds
    them all; everything else is on every chip alike."""
    layers = [{**lyr, "w_up": lyr["w_up"][offset:offset + held],
               "w_down": lyr["w_down"][offset:offset + held]}
              if "w_router" in lyr else lyr for lyr in params["layers"]]
    return {**params, "layers": layers}


# ------------------------------------------------------------------- mixers
def _split_in(zxbcdt, cfg):
    di = cfg.d_inner
    return (zxbcdt[..., :di], zxbcdt[..., di:di + cfg.conv_dim],
            zxbcdt[..., di + cfg.conv_dim:])


def _split_xbc(xbc, cfg):
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(*lead, cfg.mamba_heads, cfg.mamba_head_dim),
            xbc[..., di:di + gn].reshape(*lead, cfg.n_groups, cfg.ssm_state),
            xbc[..., di + gn:].reshape(*lead, cfg.n_groups, cfg.ssm_state))


def _gated_norm(y, z, scale, cfg):
    """RMSNorm of ``y * silu(z)`` over groups of ``d_inner / n_groups``."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    lead = g.shape[:-1]
    g = g.reshape(*lead, cfg.n_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + cfg.norm_eps)
    return (g.reshape(*lead, -1)
            * (1.0 + scale.astype(F32))).astype(cfg.dtype)


def _mamba_prompt(layer, u, n_valid, cfg, state=None, left=None):
    """u [L, D] -> (out [L, D], state [H, P, N] float32 and the convolution
    tail [K-1, C], both AS OF position ``n_valid``: a padded tail has
    ``dt = 0`` and is not among the tail's inputs). ``cfg``: any family's
    config with this mixer's sizes. Given ``state`` and ``left`` (what this
    call returned for the positions before ``u[0]``), ``u`` is one chunk of a
    prompt that goes on from them, and ``n_valid`` counts from ``u[0]``."""
    L = u.shape[0]
    z, xbc, dt = _split_in(mm(u, layer["w_in"]), cfg)
    tail = ssm.conv_tail(xbc, n_valid, cfg.conv_kernel, left)
    xbc = jax.nn.silu(ssm.causal_conv(xbc, layer["conv_w"], layer["conv_b"],
                                      left)).astype(cfg.dtype)
    x, B, C = _split_xbc(xbc, cfg)
    dt = jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])
    dt = jnp.where((jnp.arange(L) < n_valid)[:, None], dt, 0.0)
    y, state = ssm.ssd_chunked(x, dt, -jnp.exp(layer["A_log"]), B, C,
                               cfg.chunk_size, state)
    y = y + layer["D"][None, :, None] * x.astype(F32)
    y = _gated_norm(y.reshape(L, cfg.d_inner), z, layer["gate_norm"], cfg)
    return mm(y, layer["w_out"]), state, tail


def _mamba_token(layer, u, state, tail, active, cfg):
    """One token of every slot. u [S, D], state [S, H, P, N], tail
    [S, K-1, C]; an inactive lane's state stands still."""
    S = u.shape[0]
    z, xbc, dt = _split_in(mm(u, layer["w_in"]), cfg)
    conv, tail = ssm.conv_step(tail, xbc, layer["conv_w"], layer["conv_b"])
    x, B, C = _split_xbc(jax.nn.silu(conv).astype(cfg.dtype), cfg)
    dt = jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])
    dt = jnp.where(active[:, None], dt, 0.0)
    y, state = ssm.ssm_step(state, x, dt, -jnp.exp(layer["A_log"]), B, C)
    y = y + layer["D"][None, :, None] * x.astype(F32)
    y = _gated_norm(y.reshape(S, cfg.d_inner), z, layer["gate_norm"], cfg)
    return mm(y, layer["w_out"]), state, tail


def _moe(layer, u, token_mask, cfg: NemotronHConfig):
    """u [T, D] -> (out [T, D], chosen experts [T, k], held experts hit,
    most tokens of one expert)."""
    with jax.named_scope("router"):
        vals, idx = sigmoid_gates(u, layer["w_router"], layer["router_bias"],
                                  cfg.top_k, cfg.routed_scale, cfg.norm_topk)
    routed, hit, most = moe_ffn_share(
        u, vals, idx, {"w_up": layer["w_up"], "w_down": layer["w_down"]},
        cfg.expert_offset, token_mask)
    shared = mm(relu2(mm(u, layer["ws_up"])), layer["ws_down"])
    return routed + shared, idx, hit, most


def _qkv(layer, h, cfg: NemotronHConfig):
    lead = h.shape[:-1]
    return (mm(h, layer["wq"]).reshape(*lead, cfg.n_heads, cfg.head_dim),
            mm(h, layer["wk"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim),
            mm(h, layer["wv"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim))


def _attention_prompt(layer, h, cfg: NemotronHConfig):
    """Causal grouped-query attention over one sequence, no positional
    embedding. h [L, D] -> (out [L, D], k, v [L, kvh, d])."""
    L = h.shape[0]
    q, k, v = _qkv(layer, h, cfg)
    rep = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(L, cfg.n_kv_heads, rep, cfg.head_dim)
    s = jnp.einsum("qgrd,kgd->grqk", qg.astype(F32),
                   k.astype(F32)) * (cfg.head_dim ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", p.astype(v.dtype), v)
    return mm(o.reshape(L, cfg.n_heads * cfg.head_dim), layer["wo"]), k, v


def _run_prompt(params, tokens, n_valid, cfg: NemotronHConfig):
    """One sequence through every block. tokens [L] (padded past
    ``n_valid``) -> (hidden [L, D] before the final norm, per attention
    layer (k, v), per Mamba layer (state, tail), per expert layer the
    chosen experts [L, k])."""
    L = tokens.shape[0]
    x = params["embedding"][tokens].astype(cfg.dtype)
    valid = jnp.arange(L) < n_valid
    kv, states, routing = [], [], []
    for kind, layer in zip(cfg.pattern, params["layers"]):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == "M":
            with jax.named_scope("mamba"):
                out, state, tail = _mamba_prompt(layer, h, n_valid, cfg)
            states.append((state, tail))
        elif kind == "*":
            with jax.named_scope("attention"):
                out, k, v = _attention_prompt(layer, h, cfg)
            kv.append((k, v))
        else:
            with jax.named_scope("moe"):
                out, idx, _, _ = _moe(layer, h, valid, cfg)
            routing.append(idx)
        x = x + out
    return x, kv, states, routing


def _head(params, x, cfg: NemotronHConfig):
    return mm(rms_norm(x, params["norm"], cfg.norm_eps), params["lm_head"])


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg: NemotronHConfig):
    """tokens [L] -> logits [L, V]: the whole forward pass of one
    sequence, no cache (tests hold it against the plain reference)."""
    x, _, _, _ = _run_prompt(params, tokens, tokens.shape[0], cfg)
    return _head(params, x, cfg)


# ----------------------------------------------- programs of ``PagedEngine``
def init_state(cfg: NemotronHConfig, slots: int):
    """Per-slot recurrent state of every Mamba layer: (SSM states
    [S, H, P, N] float32, convolution tails [S, K-1, C])."""
    n = cfg.n_mamba_layers
    return ([jnp.zeros((slots, cfg.mamba_heads, cfg.mamba_head_dim,
                        cfg.ssm_state), F32) for _ in range(n)],
            [jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype)
             for _ in range(n)])


@functools.partial(jax.jit, static_argnames=("cfg", "total", "pad_len"))
def _hybrid_prefill(params, prompt_padded, n_valid, total, cfg, pad_len):
    """Prefill one request. Returns the next-token logits at position
    ``n_valid - 1``; per attention layer the dense (k, v) of
    [total, kvh, d] for the page scatter (a padded tail's rows are stale,
    harmless as in ``engine._prefill_one``); per Mamba layer the (state,
    tail) at ``n_valid``, NOT at the padded end; and the chosen experts
    [expert layers, pad_len, k], which only a reference check reads."""
    x, kv, states, routing = _run_prompt(params, prompt_padded, n_valid, cfg)
    first = _head(params, x[n_valid - 1], cfg)
    room = ((0, total - pad_len), (0, 0), (0, 0))
    caches = [(jnp.pad(k, room), jnp.pad(v, room)) for k, v in kv]
    return first, caches, states, jnp.stack(routing)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_state(ssm_states, conv_tails, new, slot):
    """One admission's recurrent state into its slot of every Mamba
    layer, in place (both lists are donated): one dispatch."""
    return ([s.at[slot].set(n[0]) for s, n in zip(ssm_states, new)],
            [c.at[slot].set(n[1].astype(c.dtype))
             for c, n in zip(conv_tails, new)])


def _decode_logits(params, pools_k, pools_v, scales_k, scales_v, ssm_states,
                   conv_tails, tables, toks, lengths, cfg, page, kv_int8):
    """The decode step up to its logits: [S, V], the new pools, scales and
    state, the expert layers' load (held experts hit summed over the
    layers, most tokens of one expert) and the chosen experts
    [expert layers, S, k], which only a reference check reads."""
    x = params["embedding"][toks].astype(cfg.dtype)             # [S, D]
    active = lengths > 0
    page_idx = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]
    offs = lengths % page
    new_k, new_v, new_ssm, new_conv, routing = [], [], [], [], []
    new_sk, new_sv = ([], []) if kv_int8 else (scales_k, scales_v)
    hit = jnp.int32(0)
    most = jnp.int32(0)
    ai = mi = 0
    for kind, layer in zip(cfg.pattern, params["layers"]):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == "M":
            with jax.named_scope("mamba"):
                out, state, tail = _mamba_token(
                    layer, h, ssm_states[mi], conv_tails[mi], active, cfg)
            new_ssm.append(state)
            new_conv.append(tail)
            mi += 1
        elif kind == "*":
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h[:, None, :], cfg)
            o, pool_k, pool_v, scale_k, scale_v = paged_attention(
                q, k, v, pools_k[ai], pools_v[ai],
                scales_k[ai] if kv_int8 else None,
                scales_v[ai] if kv_int8 else None, tables, lengths,
                page_idx, offs, kv_int8, cfg.dtype)
            new_k.append(pool_k)
            new_v.append(pool_v)
            if kv_int8:
                new_sk.append(scale_k)
                new_sv.append(scale_v)
            with jax.named_scope("attention"):
                out = mm(o[:, 0], layer["wo"])
            ai += 1
        else:
            with jax.named_scope("moe"):
                out, idx, n_hit, n_most = _moe(layer, h, active, cfg)
            routing.append(idx)
            hit = hit + n_hit.astype(jnp.int32)
            most = jnp.maximum(most, n_most.astype(jnp.int32))
        x = x + out
    return (_head(params, x, cfg), new_k, new_v, new_sk, new_sv, new_ssm,
            new_conv, jnp.stack([hit, most]), jnp.stack(routing))


@functools.partial(jax.jit, static_argnames=("cfg", "page", "kv_int8"),
                   donate_argnums=(1, 2, 5, 6))
def _hybrid_step(params, pools_k, pools_v, scales_k, scales_v, ssm_states,
                 conv_tails, tables, toks, lengths, temps, top_ks, top_ps,
                 keys, cfg, page, kv_int8):
    """One token for every slot: the recurrence on each slot's state, paged
    attention on the attention layers' pools, the held experts' part of the
    routed layers. Pools and state are donated. A slot of length 0 is
    inactive: it flows through (static shapes), its K/V row lands on page 0,
    its SSM state stands still, it is routed to no expert, and an admission
    overwrites its whole state before it is read.

    -> (int32[S + 2]: the tokens, then the held experts hit summed over the
    expert layers and the most tokens one expert got, so that one transfer
    fetches all; pools_k, pools_v, scales_k, scales_v, ssm_states,
    conv_tails, keys; the chosen experts [expert layers, S, k], which stay
    on the device unless a reference check asks for them; the tokens alone,
    int32[S], as the next step takes them: with them the engine dispatches
    that step before it has fetched this one's)."""
    (logits, new_k, new_v, new_sk, new_sv, new_ssm, new_conv,
     load, routing) = _decode_logits(params, pools_k, pools_v, scales_k, scales_v,
                            ssm_states, conv_tails, tables, toks, lengths,
                            cfg, page, kv_int8)
    out, new_keys, picked = _sample(logits, temps, top_ks, top_ps, keys,
                                    lengths, load)
    return (out, new_k, new_v, new_sk, new_sv, new_ssm, new_conv, new_keys,
            routing, picked)
