"""Granite-MoE-hybrid family (``model_type: granitemoehybrid``, Granite-4.0-H):
Mamba-2 layers nine to one attention layer without positions, a routed expert
layer beside a shared expert behind EVERY mixer, four scalar multipliers, a
tied head.

    x_0 = E[t] * embedding_multiplier;   r = residual_multiplier
    h = x_l + r * Mixer_l(RMSNorm_mixer(x_l))
    x_{l+1} = h + r * (Routed_l(u) + Shared_l(u)),   u = RMSNorm_ffn(h)
    logits = RMSNorm_f(x_L) E^T / logits_scaling

- ``Mixer`` of a ``mamba`` layer: Mamba-2 as ``ops/ssm.py`` states it, ONE
  implementation with the Nemotron-H family (``nemotron_h._mamba_prompt`` /
  ``_mamba_token``: in-projection to ``[z | xBC | dt]``, depthwise causal
  convolution with bias and SiLU on ``xBC``, the scan, the gated RMSNorm over
  ``n_groups`` groups, out-projection). Per sequence it carries the SSM state
  (float32) and the last ``conv_kernel - 1`` inputs of the convolution.
- ``Mixer`` of an ``attention`` layer: grouped queries, no bias, NO positional
  embedding; scores times ``attention_multiplier`` (not ``head_dim ** -0.5``);
  causal. Per sequence it carries K/V.
- ``Routed``: ``logits = u W_r`` in float32 over all ``n_experts``, the
  ``top_k`` largest, gates their softmax; SwiGLU experts at ``expert_d_ff``.
  The tree holds ``experts_held`` of them from ``expert_offset``: one chip's
  share of an expert-parallel deployment; what the absent experts would add is
  left out (``parallel/moe.py``). ``Shared``: the same SwiGLU at
  ``shared_d_ff``. No selection bias: the architecture has none.

Pure functions over a params dict. The device programs at the bottom are what
``models/paged.py``'s ``PagedEngine`` runs for this family: page pools for the
attention layers only, per slot the SSM state and the convolution tail of
every Mamba layer. A prompt is admitted ``prefill_chunk`` tokens at a time
through ONE program that carries the attention layers' K/V AND the recurrent
state: each Mamba layer's SSM state is the next chunk's ``h0``, its tail the
next chunk's left edge, both taken AT ``n_valid``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.layers import rms_norm
from ..ops.quant import mm
from ..parallel.moe import (expert_share,  # noqa: F401 (re-export)
                            moe_ffn_held, router_probs, top_k_gates)
from .cohere2_moe import _prompt_attention
from .engine import _sample, prefill_in_chunks
# the Mamba-2 mixer, its seeded weights and its per-slot state (allocation and
# the admission's write) are the Nemotron-H family's: one implementation
from .nemotron_h import (_mamba_prompt, _mamba_token, _normal,
                         _write_state, init_state,  # noqa: F401 (re-exports)
                         seeded_mamba)
from .paged_ops import paged_attention

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    d_model: int = 4096
    n_layers: int = 40                # layers held: the first of layer_types
    layer_types: Optional[Tuple[str, ...]] = None    # published, every layer
    # Mamba-2
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 256             # mamba_chunk_size: the SSD form's
    # attention
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    # the expert layer
    n_experts: int = 72               # the router's width: all experts
    experts_held: int = 72            # ... of which this tree holds these
    expert_offset: int = 0            # ... starting from this one
    top_k: int = 10
    expert_d_ff: int = 768
    shared_d_ff: int = 1536
    # the four multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    norm_eps: float = 1e-5
    # how the programs cut their work (no effect on the result)
    prefill_chunk: int = 2048         # tokens a dispatch
    key_block: int = 256              # keys a step of a prompt's softmax
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types = self.layer_types
        if types is None:   # the published pattern: attention at 5, 15, 25, 35
            types = tuple(ATTENTION if i % 10 == 5 else MAMBA
                          for i in range(max(self.n_layers, 40)))
        object.__setattr__(self, "layer_types", tuple(types))
        if self.n_layers > len(self.layer_types):
            raise ValueError("layers held reach past layer_types")
        if set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer types are {MAMBA!r} and {ATTENTION!r}")
        if self.expert_offset + self.experts_held > self.n_experts:
            raise ValueError("experts held reach past the router's width")
        if self.mamba_heads % self.n_groups or self.n_heads % self.n_kv_heads \
                or self.prefill_chunk % self.key_block:
            raise ValueError("heads must divide into their groups; key_block "
                             "divides prefill_chunk")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The type of each layer held."""
        return self.layer_types[:self.n_layers]

    @property
    def n_attn_layers(self) -> int:
        return self.kinds.count(ATTENTION)

    @property
    def n_mamba_layers(self) -> int:
        return self.kinds.count(MAMBA)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def slot_state_bytes(self) -> int:
        """One slot's recurrent state: the SSM state (float32) and the
        convolution tail (the model's dtype) of every Mamba layer held."""
        return self.n_mamba_layers * (
            4 * self.d_inner * self.ssm_state
            + jnp.dtype(self.dtype).itemsize * (self.conv_kernel - 1)
            * self.conv_dim)

    def param_count(self, active: bool = False) -> int:
        """Parameters the tree holds; with ``active`` those one token reads
        (``top_k`` experts of a layer's)."""
        d = self.d_model
        mamba = (d * (self.d_inner + self.conv_dim + self.mamba_heads)
                 + self.conv_dim * (self.conv_kernel + 1)
                 + 3 * self.mamba_heads + self.d_inner + self.d_inner * d)
        attn = 2 * d * self.n_heads * self.head_dim \
            + 2 * d * self.n_kv_heads * self.head_dim
        experts = self.top_k if active else self.experts_held
        ffn = (d * self.n_experts + 3 * d * self.shared_d_ff
               + experts * 3 * d * self.expert_d_ff)
        return (self.n_mamba_layers * mamba + self.n_attn_layers * attn
                + self.n_layers * (ffn + 2 * d) + d + self.vocab_size * d)


GRANITE_MOE_HYBRID_DEBUG = GraniteMoeHybridConfig(
    vocab_size=96, d_model=64, n_layers=10, mamba_heads=8, mamba_head_dim=8,
    ssm_state=16, chunk_size=8, n_heads=4, n_kv_heads=2, head_dim=16,
    n_experts=12, experts_held=12, top_k=3, expert_d_ff=32, shared_d_ff=48,
    prefill_chunk=16, key_block=8, dtype=jnp.float32)


# ------------------------------------------------------------------ weights
def init_params(cfg: GraniteMoeHybridConfig, key: jax.Array
                ) -> Dict[str, Any]:
    """Seeded weights: projections normal over the square root of their
    fan-in; ``A_log``, ``dt_bias``, ``D`` and the convolution as the
    Nemotron-H family seeds them (``nemotron_h.seeded_mamba``: the published
    initialisation's ranges); the norms small seeded numbers (``rms_norm``
    multiplies by 1 + them) so that a test sees a misplaced norm; the router
    float32. The embedding is normal over ``embedding_multiplier * sqrt(d)``:
    multiplied, it enters the stream at ``1 / sqrt(d)`` an entry, under the
    branches' sum, so that through the tied head a token's own row does not
    decide the next token. The routers are then balanced IN THEIR WEIGHTS
    (``balance_routers``): the architecture has no selection bias."""
    key, sample = jax.random.split(key)
    return balance_routers(_seeded_params(cfg, key), cfg, sample)


def _seeded_params(cfg: GraniteMoeHybridConfig, key: jax.Array
                   ) -> Dict[str, Any]:
    d, dt, hd = cfg.d_model, cfg.dtype, cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: Dict[str, Any] = {
        "embedding": _normal(keys[0], (cfg.vocab_size, d), dt,
                             1.0 / (cfg.embedding_multiplier * math.sqrt(d))),
        "norm": _normal(keys[1], (d,), dt, 0.05),
        "layers": [],
    }
    eh, f, fs = cfg.experts_held, cfg.expert_d_ff, cfg.shared_d_ff
    for i, kind in enumerate(cfg.kinds):
        k = jax.random.split(keys[i + 2], 18)
        layer = {"mixer_norm": _normal(k[0], (d,), dt, 0.05),
                 "ffn_norm": _normal(k[9], (d,), dt, 0.05)}
        if kind == MAMBA:
            layer.update(seeded_mamba(cfg, k))
        else:
            layer.update({
                "wq": _normal(k[1], (d, cfg.n_heads * hd), dt),
                "wk": _normal(k[2], (d, cfg.n_kv_heads * hd), dt),
                "wv": _normal(k[3], (d, cfg.n_kv_heads * hd), dt),
                "wo": _normal(k[4], (cfg.n_heads * hd, d), dt)})
        layer["moe"] = {
            "w_router": _normal(k[10], (d, cfg.n_experts), F32),
            "w_gate": _normal(k[11], (eh, d, f), dt),
            "w_up": _normal(k[12], (eh, d, f), dt),
            "w_down": _normal(k[13], (eh, f, d), dt)}
        layer["shared"] = {"w_gate": _normal(k[14], (d, fs), dt),
                           "w_up": _normal(k[15], (d, fs), dt),
                           "w_down": _normal(k[16], (fs, d), dt)}
        params["layers"].append(layer)
    return params


def balance_routers(params, cfg: GraniteMoeHybridConfig, key: jax.Array,
                    n: int = 512) -> Dict[str, Any]:
    """Take out of every column of every router its component along the mean
    normed input of a calibration pass: ``n`` seeded random tokens (one
    sequence, one chunk) through the layers, and at each expert layer, before
    it runs, ``W_r <- W_r - m (m . W_r) / (m . m)`` with ``m`` the mean over
    those tokens of the layer's normed input. A seeded stream has a common
    direction (every token's normed input shares it), and a seeded router
    column's product with it is a constant offset of that expert's logit at
    every token: a few experts take the batch. A trained router's load
    balancing leaves no such offset; without it the logits differ only by
    what differs between tokens. The equations stay the published ones: no
    bias is added, the weights are what changes."""
    n = -(-n // cfg.key_block) * cfg.key_block
    tokens = jax.random.randint(key, (n,), 0, cfg.vocab_size)
    one = dataclasses.replace(cfg, prefill_chunk=n)
    start, every = jnp.int32(0), jnp.ones((n,), bool)
    kv, rec = map(iter, _empty_carry(one, n))
    x = _embed(params, tokens, one)
    layers = []
    for kind, layer in zip(cfg.kinds, params["layers"]):
        x, _ = _mixer(kind, layer, x, start, jnp.int32(n),
                      next(rec if kind == MAMBA else kv), one)
        u = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        m = jnp.mean(u.astype(F32), axis=0)                     # [D]
        w = layer["moe"]["w_router"]
        layer = {**layer, "moe": {
            **layer["moe"],
            "w_router": w - jnp.outer(m, m @ w) / jnp.dot(m, m)}}
        x = _branch(x, _ffn(layer, u, every, one)[0], one)
        layers.append(layer)
    return {**params, "layers": layers}


# ------------------------------------------------------------------- layers
def _qkv(layer, h, cfg: GraniteMoeHybridConfig):
    """h [N, D] -> q [N, H, d] ALREADY times ``attention_multiplier *
    sqrt(d)``, k and v [N, kvh, d]. The reads this family shares
    (``cohere2_moe._prompt_attention``, ``paged_ops.paged_attention``)
    multiply their scores by ``d ** -0.5``: with the query scaled in float32
    before its one rounding, the scores are times ``attention_multiplier``
    and the shared reads stay as every other family compiles them."""
    N, hd = h.shape[0], cfg.head_dim
    q = jnp.dot(h, layer["wq"].astype(h.dtype), preferred_element_type=F32)
    q = (q * (cfg.attention_multiplier * math.sqrt(hd))).astype(cfg.dtype)
    return (q.reshape(N, cfg.n_heads, hd),
            mm(h, layer["wk"]).reshape(N, cfg.n_kv_heads, hd),
            mm(h, layer["wv"]).reshape(N, cfg.n_kv_heads, hd))


#: rows from which the held experts' product is grouped by expert
#: (``moe_ffn_grouped``: a prompt's chunk); under it every held expert
#: multiplies every row (``moe_ffn_share``: a decode step). At 36 held of 72
#: gated experts of 4096 x 768, top-10 (half the pairs held), alone on a TPU
#: v5e (my chip run, PR 56; PERF.md section 5): 64 rows 0.95 ms a layer
#: against the grouped form's 1.61 (0.83 ms is the layer's 0.68 GB at 819
#: GB/s: all 36 are hit), 256 rows 1.15 against 2.70, 512 rows 2.38 against
#: 3.24; 1024 rows 4.75 against 4.40, 2048 rows 10.67 against 7.48, where
#: every expert on every row is 1.39 TFLOP a layer. The two cross between 512
#: and 1024 rows; the engine runs 64 (a step) and ``prefill_chunk`` (a chunk).
#: Since PR 57 the grouped products are a Pallas kernel on the chip
#: (``parallel.moe.grouped_product_form``) and the same table reads (my chip
#: run, PR 57; share / grouped, kernel form): 64 rows 1.02 / 1.05, 128 rows
#: 1.04 / 1.10, 256 rows 1.24 / 1.22, 512 rows 2.24 / 1.50, 1024 rows 4.45 /
#: 1.99, 2048 rows grouped 4.03 (was 7.74): they now cross at ~256 rows, which
#: no program of the engine runs; the value stands
GROUPED_FROM_ROWS = 1024


def _ffn(layer, u, token_mask, cfg: GraniteMoeHybridConfig):
    """u [T, D] normed -> (Routed(u) + Shared(u) [T, D], the chosen experts
    [T, k], int32[2]: held experts hit, most tokens of one)."""
    moe = layer["moe"]
    with jax.named_scope("router"):
        # a softmax over all the logits renormalised over the chosen ten IS
        # the softmax over the ten chosen logits
        vals, idx = top_k_gates(router_probs(u, moe["w_router"]), cfg.top_k)
    held = {w: moe[w] for w in ("w_gate", "w_up", "w_down")}
    with jax.named_scope("moe"):
        # grouped with room for every pair (half the experts are held), so no
        # fallback is compiled beside it (room for 1.25 times the spread
        # router's pairs was 7.10 against 7.48 ms a layer at 2048 rows, PR 56)
        routed, hit, most = moe_ffn_held(
            u, vals, idx, held, cfg.expert_offset, token_mask, cfg.n_experts,
            GROUPED_FROM_ROWS)
    with jax.named_scope("shared_expert"):
        sh = layer["shared"]
        shared = mm(jax.nn.silu(mm(u, sh["w_gate"])) * mm(u, sh["w_up"]),
                    sh["w_down"])
    return routed + shared, idx, jnp.stack([hit, most]).astype(jnp.int32)


def _head(params, x, cfg: GraniteMoeHybridConfig):
    logits = mm(rms_norm(x, params["norm"], cfg.norm_eps),
                params["embedding"].T)
    return (logits.astype(F32) / cfg.logits_scaling).astype(logits.dtype)


def _embed(params, tokens, cfg: GraniteMoeHybridConfig):
    x = params["embedding"][tokens].astype(F32) * cfg.embedding_multiplier
    return x.astype(cfg.dtype)


def _branch(x, out, cfg: GraniteMoeHybridConfig):
    """``x + residual_multiplier * out`` in float32, rounded once."""
    return (x.astype(F32) + cfg.residual_multiplier * out.astype(F32)
            ).astype(x.dtype)


def _mixer(kind, layer, x, start, n_valid, carry,
           cfg: GraniteMoeHybridConfig):
    """A layer's mixer branch over one chunk: x [N, D] at positions ``start
    ..`` -> (``x + r * Mixer(RMSNorm(x))``, the layer's carry with the chunk
    in it). ``carry`` is an attention layer's (K, V) [T, kvh, d] of the
    positions before, a Mamba layer's (SSM state [H, P, N] float32, the
    convolution's last ``K - 1`` inputs [K-1, C]) as of ``start``; the
    latter comes back AS OF ``min(n_valid, start + N)``."""
    h = rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
    if kind == MAMBA:
        state, left = carry
        with jax.named_scope("mamba"):
            out, state, tail = _mamba_prompt(
                layer, h, n_valid - start, cfg, state, left)
        carry = (state, tail.astype(left.dtype))
    else:
        buf_k, buf_v = carry
        with jax.named_scope("attention"):
            q, k, v = _qkv(layer, h, cfg)
            buf_k = jax.lax.dynamic_update_slice_in_dim(
                buf_k, k.astype(buf_k.dtype), start, axis=0)
            buf_v = jax.lax.dynamic_update_slice_in_dim(
                buf_v, v.astype(buf_v.dtype), start, axis=0)
        carry = (buf_k, buf_v)
        with jax.named_scope("prompt_attn"):
            o = _prompt_attention(q, buf_k, buf_v, start, 0, cfg)
        with jax.named_scope("attention"):
            out = mm(o, layer["wo"])
    return _branch(x, out, cfg), carry


def _run_chunk(params, tokens, start, n_valid, bufs, states,
               cfg: GraniteMoeHybridConfig):
    """One chunk of one sequence through every layer. tokens [N] at positions
    ``start ..``; bufs and states: per attention layer and per Mamba layer
    what ``_mixer`` carries. -> (hidden [N, D] before the final norm, bufs
    with the chunk's rows, states AS OF ``min(n_valid, start + N)``, the
    chosen experts [layers, N, k])."""
    N = tokens.shape[0]
    x = _embed(params, tokens, cfg)
    valid = start + jnp.arange(N) < n_valid
    kv, rec = iter(bufs), iter(states)
    new_bufs, new_states, routing = [], [], []
    for kind, layer in zip(cfg.kinds, params["layers"]):
        mamba = kind == MAMBA
        x, carry = _mixer(kind, layer, x, start, n_valid,
                          next(rec if mamba else kv), cfg)
        (new_states if mamba else new_bufs).append(carry)
        f, idx, _ = _ffn(layer, rms_norm(x, layer["ffn_norm"], cfg.norm_eps),
                         valid, cfg)
        x = _branch(x, f, cfg)
        routing.append(idx)
    return x, new_bufs, new_states, jnp.stack(routing)


def _empty_carry(cfg: GraniteMoeHybridConfig, total: int):
    dt = cfg.dtype
    row = (total, cfg.n_kv_heads, cfg.head_dim)
    return ([(jnp.zeros(row, dt), jnp.zeros(row, dt))
             for _ in range(cfg.n_attn_layers)],
            [(jnp.zeros((cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
                        F32),
              jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim), dt))
             for _ in range(cfg.n_mamba_layers)])


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg: GraniteMoeHybridConfig):
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
def prefill_carry(cfg: GraniteMoeHybridConfig, total: int):
    """What a prefill carries from chunk to chunk, before the first: per
    attention layer the K and V rows [total, kvh, d], per Mamba layer a zero
    SSM state [H, P, N] float32 and a zero tail [K-1, C]."""
    return _empty_carry(cfg, total)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4, 5))
def _granite_prefill_chunk(params, tokens, start, n_valid, bufs, states, cfg):
    """One chunk of one request's prefill; the carried K/V rows and the
    recurrent state are donated. ``tokens`` [prefill_chunk] is padded past
    ``n_valid`` (a position of the whole prompt): the padded tail's K/V rows
    are stale and harmless (the decode steps overwrite them before a query
    can read them); its ``dt`` is 0, so the SSM state handed on is the state
    AT ``n_valid``, and it is not among a tail's inputs. -> (the logits at
    ``n_valid - 1`` if that row lies in this chunk, bufs, states, the chosen
    experts [layers, chunk, k], which only a reference check reads)."""
    x, bufs, states, routing = _run_chunk(params, tokens, start, n_valid,
                                          bufs, states, cfg)
    row = jnp.clip(n_valid - 1 - start, 0, tokens.shape[0] - 1)
    return _head(params, x[row], cfg), bufs, states, routing


def prefill(params, prompt, total: int, cfg: GraniteMoeHybridConfig,
            keep_routing: bool = False):
    """Prefill one request chunk by chunk (``engine.prefill_in_chunks`` over
    ``_granite_prefill_chunk``). -> (next-token logits, per attention layer
    the (K, V) rows [total, kvh, d] for the page scatter, per Mamba layer its
    (SSM state, tail) at the prompt's end; with ``keep_routing`` also every
    prompt position's chosen experts [layers, len(prompt), k])."""
    first, (bufs, states), routing = prefill_in_chunks(
        _granite_prefill_chunk, params, prompt, cfg.prefill_chunk,
        prefill_carry(cfg, total), cfg, keep_routing)
    out = (first, bufs, states)
    return out + (routing,) if keep_routing else out


def _decode_logits(params, pools_k, pools_v, ssm_states, conv_tails, tables,
                   toks, lengths, cfg: GraniteMoeHybridConfig, page: int):
    """The decode step up to its logits [S, V]; the new pools, SSM states and
    tails; int32[5]: held experts hit summed over the layers, most tokens of
    one expert, the active rows, the positions the active slots' queries
    attend in an attention layer (their context), and 1 (summed over the
    steps a call lands, they count them); the chosen experts [layers, S,
    k]."""
    x = _embed(params, toks, cfg)                               # [S, D]
    active = lengths > 0
    page_idx = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]
    offs = lengths % page
    new_k, new_v, new_ssm, new_conv, routing = [], [], [], [], []
    hit, most = jnp.int32(0), jnp.int32(0)
    pools, rec = zip(pools_k, pools_v), zip(ssm_states, conv_tails)
    for kind, layer in zip(cfg.kinds, params["layers"]):
        h = rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
        if kind == MAMBA:
            with jax.named_scope("mamba"):
                out, state, tail = _mamba_token(layer, h, *next(rec), active,
                                                cfg)
            new_ssm.append(state)
            new_conv.append(tail)
        else:
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, cfg)
            pool_k, pool_v = next(pools)
            o, pool_k, pool_v, _, _ = paged_attention(
                q[:, None], k[:, None], v[:, None], pool_k, pool_v, None,
                None, tables, lengths, page_idx, offs, False, cfg.dtype)
            new_k.append(pool_k)
            new_v.append(pool_v)
            with jax.named_scope("attention"):
                out = mm(o[:, 0], layer["wo"])
        x = _branch(x, out, cfg)
        f, idx, counts = _ffn(
            layer, rms_norm(x, layer["ffn_norm"], cfg.norm_eps), active, cfg)
        x = _branch(x, f, cfg)
        routing.append(idx)
        hit, most = hit + counts[0], jnp.maximum(most, counts[1])
    counts = jnp.stack([
        hit, most, jnp.sum(active),
        jnp.sum(jnp.where(active, lengths + 1, 0)), 1]).astype(jnp.int32)
    return (_head(params, x, cfg), new_k, new_v, new_ssm, new_conv, counts,
            jnp.stack(routing))


@functools.partial(jax.jit, static_argnames=("cfg", "page"),
                   donate_argnums=(1, 2, 3, 4))
def _granite_step(params, pools_k, pools_v, ssm_states, conv_tails, tables,
                  toks, lengths, temps, top_ks, top_ps, keys, cfg, page):
    """One token for every slot: a Mamba layer advances the slot's SSM state
    and tail, an attention layer writes the slot's row at its (page, offset)
    of the pool and attends over the slot's pages block by block; the held
    experts' part of every layer's routed experts beside its shared expert.
    Pools, states and tails are donated. A slot of length 0 is inactive: it
    flows through (static shapes), its K/V row lands on page 0, its SSM state
    stands still, it is routed to no expert, and an admission overwrites its
    whole state before it is read.

    -> (int32[S + 5]: the tokens, then ``_decode_logits``' counts, so that
    one transfer fetches all; pools; states; tails; keys; the chosen experts
    [layers, S, k], which stay on the device unless a reference check asks
    for them; the tokens alone, int32[S], as the next step takes them: with
    the keys they let the engine dispatch that step before it has fetched
    this one's)."""
    logits, new_k, new_v, new_ssm, new_conv, counts, routing = _decode_logits(
        params, pools_k, pools_v, ssm_states, conv_tails, tables, toks,
        lengths, cfg, page)
    out, new_keys, picked = _sample(logits, temps, top_ks, top_ps, keys,
                                    lengths, counts)
    return out, new_k, new_v, new_ssm, new_conv, new_keys, routing, picked
