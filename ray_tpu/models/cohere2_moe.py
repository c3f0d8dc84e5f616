"""Cohere2-MoE family (the language model of Command A+, ``model_type:
cohere2_moe``): window and full attention mixed, a parallel block, sigmoid
top-k experts beside averaged shared experts, a tied head.

    x_0 = E[t];   h = LN_l(x_l);   x_{l+1} = x_l + Attn_l(h) + MoE_l(h)
    logits = LN_f(x_L) E^T * logit_scale

- ``LN`` subtracts the mean, has a weight and no bias (``ops.layers.
  layer_norm``). One norm a layer feeds both branches.
- **Attention**: grouped queries (``n_heads / n_kv_heads`` query heads a K/V
  head), no bias, no q/k norm, scores over ``sqrt(head_dim)``. A
  ``sliding_attention`` layer rotates q and k over interleaved pairs
  (``ops.layers.rope_interleaved``) and key ``j`` is visible to query ``i``
  iff ``0 <= i - j < sliding_window``; a ``full_attention`` layer applies NO
  position at all and is causal.
- **Expert layer** (every layer has one): ``s = sigmoid(h W_r)`` in float32
  over all ``router_width`` outputs, the top ``top_k`` by ``s`` (no selection
  bias), weights ``s / sum(s)``, no scaling; gated experts; beside them
  ``n_shared`` shared experts of the same shape whose outputs are AVERAGED.
  The tree holds ``experts_held`` of the routed experts from
  ``expert_offset``: one chip's share of an expert-parallel deployment. What
  the absent experts would add is left out (``parallel/moe.py``).

Pure functions over a params dict. The device programs at the bottom are what
``models/paged.py``'s ``PagedEngine`` runs for this family. The two kinds of
layer keep two kinds of cache: a full layer's K/V in a **page pool** read in
blocks of table columns (``paged_ops.attend_pages_blocked``), a window
layer's K/V in a **per-slot ring** of the window's width
(``paged_ops.write_ring`` / ``attend_ring``), whose memory is fixed whatever
the slot's context. A prompt is admitted ``prefill_chunk`` tokens at a time
through ONE program that carries every layer's K/V; its attention
(``_prompt_attention``) is a flash kernel on the chip, in which a tile of rows
visits only the key tiles that hold a key visible to it, and a loop over key
blocks elsewhere, in which a window layer starts at the block of the first
key its first query sees.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import chunk_attention, chunk_attention_form
from ..ops.layers import layer_norm, rope_interleaved, rope_rows
from ..ops.quant import mm
from ..parallel.moe import (expert_share,  # noqa: F401 (re-export)
                            moe_ffn_held, sigmoid_gates)
from .engine import _sample, prefill_in_chunks
from .paged_ops import (attend_pages_blocked, attend_ring, block_pages_of,
                        ring_rows, write_kv, write_ring)

F32 = jnp.float32
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    d_model: int = 4096
    n_layers: int = 32                # layers held: the first of layer_types
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    layer_types: Optional[Tuple[str, ...]] = None    # published, every layer
    sliding_window: int = 4096
    # the expert layer
    router_width: int = 128           # routed experts of the layer, all chips'
    experts_held: int = 128           # ... of which this tree holds these
    expert_offset: int = 0            # ... starting from this one
    top_k: int = 8
    n_shared: int = 4
    expert_d_ff: int = 4096
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    # how the programs cut their work (no effect on the result)
    prefill_chunk: int = 2048         # tokens a dispatch
    key_block: int = 256              # keys a step of a prompt's online softmax
    page_block: int = 16              # not read since PR 44: the full read's
    #                                   block follows ``paged_ops.
    #                                   block_pages_of`` (these 16 columns at
    #                                   the published shape); the benchmark's
    #                                   configuration file names the field
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types = self.layer_types
        if types is None:   # the published pattern: three window, one full
            types = tuple(FULL if i % 4 == 3 else WINDOW
                          for i in range(max(self.n_layers, 32)))
        object.__setattr__(self, "layer_types", tuple(types))
        if self.n_layers > len(self.layer_types):
            raise ValueError("layers held reach past layer_types")
        if set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer types are {WINDOW!r} and {FULL!r}")
        if self.expert_offset + self.experts_held > self.router_width:
            raise ValueError("experts held reach past the router's width")
        if self.head_dim % 2 or self.prefill_chunk % self.key_block \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("rotary pairs need an even width; key_block "
                             "divides prefill_chunk; K/V heads divide heads")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The type of each layer held."""
        return self.layer_types[:self.n_layers]

    @property
    def n_full_layers(self) -> int:
        return self.kinds.count(FULL)

    @property
    def n_window_layers(self) -> int:
        return self.kinds.count(WINDOW)

    def param_count(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        expert = 3 * d * self.expert_d_ff
        layer = (attn + self.n_shared * expert + d * self.router_width + d
                 + self.experts_held * expert)
        return self.n_layers * layer + self.vocab_size * d + d


COHERE2_MOE_DEBUG = Cohere2MoeConfig(
    vocab_size=96, d_model=64, n_layers=4, n_heads=8, n_kv_heads=2,
    head_dim=16, sliding_window=16, router_width=16, experts_held=16,
    top_k=3, n_shared=2, expert_d_ff=48, prefill_chunk=16, key_block=8,
    dtype=jnp.float32)


# ------------------------------------------------------------------ weights
def _normal(key, shape, dtype, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_params(cfg: Cohere2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights: projections normal over the square root of their
    fan-in, the embedding too (the tied head reads it: logits then have unit
    scale, and a token's own embedding does not decide the next one), the
    norms small seeded numbers (``layer_norm`` multiplies by 1 + them) so
    that a test sees a misplaced norm, the router float32. The router has no
    selection bias, as the model has none."""
    d, dt, hd = cfg.d_model, cfg.dtype, cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: Dict[str, Any] = {
        "embedding": _normal(keys[0], (cfg.vocab_size, d), dt,
                             1.0 / math.sqrt(d)),
        "norm": _normal(keys[1], (d,), dt, 0.05),
        "layers": [],
    }
    eh, ns, f = cfg.experts_held, cfg.n_shared, cfg.expert_d_ff
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i + 2], 12)
        params["layers"].append({
            "norm": _normal(k[0], (d,), dt, 0.05),
            "wq": _normal(k[1], (d, cfg.n_heads * hd), dt),
            "wk": _normal(k[2], (d, cfg.n_kv_heads * hd), dt),
            "wv": _normal(k[3], (d, cfg.n_kv_heads * hd), dt),
            "wo": _normal(k[4], (cfg.n_heads * hd, d), dt),
            "shared": {"w_gate": _normal(k[5], (ns, d, f), dt),
                       "w_up": _normal(k[6], (ns, d, f), dt),
                       "w_down": _normal(k[7], (ns, f, d), dt)},
            "moe": {"w_router": _normal(k[8], (d, cfg.router_width), F32),
                    "w_gate": _normal(k[9], (eh, d, f), dt),
                    "w_up": _normal(k[10], (eh, d, f), dt),
                    "w_down": _normal(k[11], (eh, f, d), dt)},
        })
    return params


# ------------------------------------------------------------------- layers
def _qkv(layer, h, cos, sin, kind, cfg: Cohere2MoeConfig):
    """h [N, D] at the positions of cos / sin -> q [N, H, d], k and v [N,
    kvh, d]; q and k rotated in a window layer, as they are in a full one."""
    N = h.shape[0]
    q = mm(h, layer["wq"]).reshape(N, cfg.n_heads, cfg.head_dim)
    k = mm(h, layer["wk"]).reshape(N, cfg.n_kv_heads, cfg.head_dim)
    v = mm(h, layer["wv"]).reshape(N, cfg.n_kv_heads, cfg.head_dim)
    if kind == WINDOW:
        q, k = rope_interleaved(q, cos, sin), rope_interleaved(k, cos, sin)
    return q, k, v


def _prompt_attention(q, buf_k, buf_v, start, window, cfg: Cohere2MoeConfig):
    """A chunk's queries [N, H, d] at positions ``start ..`` over the
    positions so far, with an online softmax: no ``L x L`` array. buf_k,
    buf_v [T, kvh, d] hold every position's row up to the chunk's end. Key
    ``j`` is visible to query ``i`` iff ``j <= i`` and, with a ``window``,
    ``i - j < window``. -> o [N, H * d].

    Which form runs follows from what the call sees
    (``ops.attention.chunk_attention_form``): on a TPU, at heads of whole
    lanes and whole tiles of rows, the flash kernel
    ``ops.attention.chunk_attention`` (scores and accumulator in VMEM, a row
    tile visiting the key tiles it can see and no other); everywhere else
    (every CPU run, lfm2's heads of 64) the loop below over blocks of
    ``cfg.key_block`` keys, whose float32 maximum, sum and accumulator are
    carried through HBM and whose every row visits every block from the
    first one its first query sees to the chunk's end. One arithmetic: bf16
    operands, float32 scores and sums, the weights cast to V's dtype, one
    division at the end."""
    if chunk_attention_form(q.shape[0], buf_k.shape[0], q.shape[1],
                            cfg.n_kv_heads, cfg.head_dim,
                            q.dtype) == "kernel":
        return chunk_attention(q, buf_k, buf_v, start, window)
    N, kvh, d = q.shape[0], cfg.n_kv_heads, cfg.head_dim
    Kb = cfg.key_block
    qg = q.reshape(N, kvh, -1, d)
    rep = qg.shape[2]
    t = start + jnp.arange(N)

    def keys(kb, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(buf_k, kb * Kb, Kb)
        v = jax.lax.dynamic_slice_in_dim(buf_v, kb * Kb, Kb)
        s = jnp.einsum("qgrd,kgd->grqk", qg, k,
                       preferred_element_type=F32) * (d ** -0.5)
        j = (kb * Kb + jnp.arange(Kb))[None, :]
        ok = j <= t[:, None]
        if window:
            ok = ok & (t[:, None] - j < window)
        s = jnp.where(ok[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(ok[None, None], jnp.exp(s - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "grqk,kgd->grqd", p.astype(v.dtype), v,
            preferred_element_type=F32)
        return m_new, l * fix + p.sum(axis=-1), acc

    init = (jnp.full((kvh, rep, N), -1e30, F32), jnp.zeros((kvh, rep, N), F32),
            jnp.zeros((kvh, rep, N, d), F32))
    first = jnp.maximum(start - window + 1, 0) // Kb if window else 0
    _, l, acc = jax.lax.fori_loop(first, (start + N + Kb - 1) // Kb, keys,
                                  init)
    o = (acc / l[..., None]).transpose(2, 0, 1, 3)
    return o.reshape(N, -1).astype(q.dtype)


def _shared(sh, h, cfg: Cohere2MoeConfig):
    """The shared experts' AVERAGE: each ``W_down(silu(W_gate h) * W_up h)``,
    summed by the second product over (expert, width), over their count."""
    u = jax.nn.silu(jnp.einsum("td,ndf->ntf", h, sh["w_gate"])) \
        * jnp.einsum("td,ndf->ntf", h, sh["w_up"])
    y = jnp.einsum("ntf,nfd->td", u, sh["w_down"],
                   preferred_element_type=F32)
    return (y / cfg.n_shared).astype(h.dtype)


#: rows from which the held experts' product is grouped by expert
GROUPED_FROM_ROWS = 256


def _moe(layer, h, token_mask, cfg: Cohere2MoeConfig):
    """h [T, D] -> (routed + shared [T, D], chosen experts [T, k], int32[2]:
    held experts hit, most tokens of one expert). The held experts' product
    is grouped by expert where the rows are a prompt's chunk
    (``moe_ffn_grouped`` with room for twice the pairs a router that spreads
    its picks sends here: 8.0 against 20.1 ms a layer at 2048 rows, and 9.7
    with room for four times; 4.4 since PR 57, its products a Pallas kernel
    on the chip) and multiplies every row by every held expert where they
    are a decode step's few (``moe_ffn_share``: 2.9 against 3.6 ms at 32
    rows): 16 gated experts of 4096 x 4096 on a TPU v5e, PERF.md section
    5."""
    moe = layer["moe"]
    with jax.named_scope("router"):
        vals, idx = sigmoid_gates(
            h, moe["w_router"], jnp.zeros((cfg.router_width,), F32),
            cfg.top_k, 1.0)
    held = {w: moe[w] for w in ("w_gate", "w_up", "w_down")}
    routed, hit, most = moe_ffn_held(
        h, vals, idx, held, cfg.expert_offset, token_mask, cfg.router_width,
        GROUPED_FROM_ROWS)
    with jax.named_scope("shared_experts"):
        out = routed + _shared(layer["shared"], h, cfg)
    return out, idx, jnp.stack([hit, most]).astype(jnp.int32)


def _block(layer, x, attend, token_mask, cfg: Cohere2MoeConfig):
    """One parallel block on x [T, D]: ONE norm feeds the attention
    (``attend(h)``, as its caller caches it) and the expert layer, and both
    join the residual. -> (x, chosen experts [T, k], the expert counts)."""
    h = layer_norm(x, layer["norm"], cfg.norm_eps)
    o = attend(h)
    with jax.named_scope("attention"):
        a = mm(o, layer["wo"])
    with jax.named_scope("moe"):
        m, idx, counts = _moe(layer, h, token_mask, cfg)
    return x + a + m, idx, counts


def _head(params, x, cfg: Cohere2MoeConfig):
    h = layer_norm(x, params["norm"], cfg.norm_eps)
    return mm(h, params["embedding"].T) * cfg.logit_scale


def _run_chunk(params, tokens, start, n_valid, bufs, cfg: Cohere2MoeConfig):
    """One chunk of one sequence through every layer. tokens [N] at positions
    ``start ..``; bufs: per layer (K, V) [T, kvh, d] of the positions before.
    -> (hidden [N, D] before the final norm, bufs with the chunk's rows, the
    chosen experts [layers, N, k])."""
    N = tokens.shape[0]
    x = params["embedding"][tokens].astype(cfg.dtype)
    t = start + jnp.arange(N)
    cos, sin = rope_rows(t, cfg.head_dim, cfg.rope_theta)
    valid = t < n_valid
    new, routing = [], []
    for kind, layer, (buf_k, buf_v) in zip(cfg.kinds, params["layers"], bufs):
        def attend(h, kind=kind, layer=layer, buf_k=buf_k, buf_v=buf_v):
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, cos, sin, kind, cfg)
                buf_k = jax.lax.dynamic_update_slice_in_dim(
                    buf_k, k.astype(buf_k.dtype), start, axis=0)
                buf_v = jax.lax.dynamic_update_slice_in_dim(
                    buf_v, v.astype(buf_v.dtype), start, axis=0)
            new.append((buf_k, buf_v))
            with jax.named_scope("prompt_attn"):
                return _prompt_attention(
                    q, buf_k, buf_v, start,
                    cfg.sliding_window if kind == WINDOW else 0, cfg)

        x, idx, _ = _block(layer, x, attend, valid, cfg)
        routing.append(idx)
    return x, new, jnp.stack(routing)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg: Cohere2MoeConfig):
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
def prefill_carry(cfg: Cohere2MoeConfig, total: int):
    """What a prefill carries from chunk to chunk, before the first: per
    layer the K and V rows [total, kvh, d]."""
    shape = (total, cfg.n_kv_heads, cfg.head_dim)
    return [(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
            for _ in range(cfg.n_layers)]


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4,))
def _cohere_prefill_chunk(params, tokens, start, n_valid, bufs, cfg):
    """One chunk of one request's prefill; the carried rows are donated.
    ``tokens`` [prefill_chunk] is padded past ``n_valid`` (a position of the
    whole prompt): the padded tail's rows are stale and harmless (a full
    layer's are overwritten by the decode steps before a query can read
    them, a window layer's never reach its ring). -> (the logits at ``n_valid
    - 1`` if that row lies in this chunk, bufs, the chosen experts [layers,
    chunk, k], which only a reference check reads)."""
    x, bufs, routing = _run_chunk(params, tokens, start, n_valid, bufs, cfg)
    row = jnp.clip(n_valid - 1 - start, 0, tokens.shape[0] - 1)
    return _head(params, x[row], cfg), bufs, routing


def prefill(params, prompt, total: int, cfg: Cohere2MoeConfig,
            keep_routing: bool = False):
    """Prefill one request chunk by chunk (``engine.prefill_in_chunks`` over
    ``_cohere_prefill_chunk``). -> (next-token logits, per layer the (K, V)
    rows [total, kvh, d]: a full layer's for the page scatter, a window
    layer's for its ring; with ``keep_routing`` also every prompt position's
    chosen experts [layers, len(prompt), k])."""
    first, (bufs,), routing = prefill_in_chunks(
        _cohere_prefill_chunk, params, prompt, cfg.prefill_chunk,
        (prefill_carry(cfg, total),), cfg, keep_routing)
    return (first, bufs, routing) if keep_routing else (first, bufs)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_rings(rings_k, rings_v, rows, n, slot):
    """One admission's window-layer K/V into its slot of every ring, in place
    (both lists are donated): one dispatch. rows: per window layer the
    prefill's (K, V) [T, kvh, d]; of the prompt's ``n`` positions the last
    ``W`` (``paged_ops.ring_rows``)."""
    W = rings_k[0].shape[2]
    return ([ring.at[slot].set(ring_rows(k, n, W).astype(ring.dtype))
             for ring, (k, _) in zip(rings_k, rows)],
            [ring.at[slot].set(ring_rows(v, n, W).astype(ring.dtype))
             for ring, (_, v) in zip(rings_v, rows)])


def _decode_logits(params, pools_k, pools_v, rings_k, rings_v, tables, toks,
                   lengths, cfg: Cohere2MoeConfig, page: int):
    """The decode step up to its logits [S, V]; the new pools and rings;
    int32[6]: held experts hit summed over the layers, most tokens of one
    expert, the active rows, the positions the active slots' queries attend
    in a full layer (their context), the same in a window layer (each slot's
    capped at the window), and 1 (summed over the steps a call lands, they
    count them); the chosen experts [layers, S, k]."""
    x = params["embedding"][toks].astype(cfg.dtype)             # [S, D]
    active = lengths > 0
    page_idx = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]
    offs = lengths % page
    cos, sin = rope_rows(lengths, cfg.head_dim, cfg.rope_theta)
    new_k, new_v, new_rk, new_rv, routing = [], [], [], [], []
    hit, most = jnp.int32(0), jnp.int32(0)
    pools, rings = zip(pools_k, pools_v), zip(rings_k, rings_v)
    for kind, layer in zip(cfg.kinds, params["layers"]):
        def attend(h, kind=kind, layer=layer):
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, cos, sin, kind, cfg)
            if kind == WINDOW:
                ring_k, ring_v = write_ring(k, v, *next(rings), lengths)
                new_rk.append(ring_k)
                new_rv.append(ring_v)
                return attend_ring(q, ring_k, ring_v, lengths)
            pool_k, pool_v = next(pools)
            pool_k, pool_v, _, _ = write_kv(
                k[:, None], v[:, None], pool_k, pool_v, None, None, page_idx,
                offs, False)
            new_k.append(pool_k)
            new_v.append(pool_v)
            return attend_pages_blocked(
                q[:, None], pool_k, pool_v, tables, lengths,
                block_pages_of(*tables.shape, *pool_k.shape[1:], q.dtype)
            )[:, 0]

        x, idx, counts = _block(layer, x, attend, active, cfg)
        routing.append(idx)
        hit, most = hit + counts[0], jnp.maximum(most, counts[1])
    ctx = jnp.where(active, lengths + 1, 0)
    counts = jnp.stack([
        hit, most, jnp.sum(active), jnp.sum(ctx),
        jnp.sum(jnp.minimum(ctx, cfg.sliding_window)), 1]).astype(jnp.int32)
    return (_head(params, x, cfg), new_k, new_v, new_rk, new_rv, counts,
            jnp.stack(routing))


@functools.partial(jax.jit, static_argnames=("cfg", "page"),
                   donate_argnums=(1, 2, 3, 4))
def _cohere_step(params, pools_k, pools_v, rings_k, rings_v, tables, toks,
                 lengths, temps, top_ks, top_ps, keys, cfg, page):
    """One token for every slot: a window layer writes the slot's row into
    its ring and attends over the ring, a full layer writes it at the slot's
    (page, offset) of the pool and attends over the slot's pages block by
    block; the held experts' part of every expert layer beside the shared
    experts. Pools and rings are donated. A slot of length 0 is inactive: it
    flows through (static shapes), its rows land on page 0 and at index 0 of
    its own ring, and it is routed to no expert.

    -> (int32[S + 6]: the tokens, then ``_decode_logits``' counts, so that
    one transfer fetches all; pools; rings; keys; the chosen experts
    [layers, S, k], which stay on the device unless a reference check asks
    for them; the tokens alone, int32[S], as the next step takes them: with
    the keys they let the engine dispatch that step before it has fetched
    this one's)."""
    logits, new_k, new_v, new_rk, new_rv, counts, routing = _decode_logits(
        params, pools_k, pools_v, rings_k, rings_v, tables, toks, lengths,
        cfg, page)
    out, new_keys, picked = _sample(logits, temps, top_ks, top_ps, keys,
                                    lengths, counts)
    return out, new_k, new_v, new_rk, new_rv, new_keys, routing, picked
