"""The serving engine: continuous batching over a paged KV cache.

S slots share one jitted step; requests join and leave between steps, so
a long request never blocks a short one and the chip sees a full [S, 1]
decode batch (inactive slots flow through the math, outputs ignored:
static shapes, one compilation). ``submit`` enqueues; ``step`` returns
the (request_id, token) events it produced, a token of ``None`` marking
completion; ``run_to_completion`` drives the loop without streaming.
The vLLM memory model, TPU-shaped: K/V live in a shared page pool
(``[num_pages, page_size]`` per layer) and each sequence holds a page
table; pages are allocated as a sequence grows and freed when it ends,
so the pool admits far more sequences than a worst-case ``[S, max_len]``
cache of the same bytes. Reads gather a sequence's pages, writes are one
batched scatter at each slot's (page, offset), in place: every program
that writes the pools (each family's step, each family's scatter)
consumes the pools it is given and the engine holds what it returns. The
math is ``llama._decode_step``'s: tests hold it to
``llama.generate_greedy``.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import chunk_attention_form
from ..ops.layers import apply_rope, rms_norm, rope_frequencies
from ..ops.quant import mm
from ..parallel.moe import (grouped_pairs, grouped_product_form,
                            held_experts_form)
from ..util import events as plane_events
from .engine import _pick_one, _prefill_one, _sample
from .paged_ops import (_quant_kv, lane_pool_shape,  # noqa: F401
                        latent_pool_shape, paged_attention,
                        read_block_pages)  # (re-exports)
from .llama import LlamaConfig, _mlp_block
from . import cohere2_moe as cohere
from . import deepseek_v3 as deepseek
from . import granite_moe_hybrid as granite
from . import lfm2_moe as lfm2
from . import longcat_flash as longcat
from . import minicpm_sala as sala
from .cohere2_moe import Cohere2MoeConfig
from .deepseek_v3 import DeepseekV3Config
from .granite_moe_hybrid import GraniteMoeHybridConfig
from .lfm2_moe import Lfm2MoeConfig
from .longcat_flash import LongcatFlashConfig
from .minicpm_sala import MiniCPMSALAConfig
from .nemotron_h import (NemotronHConfig, _hybrid_prefill, _hybrid_step,
                         _write_state, init_state)


@functools.partial(jax.jit, static_argnames=("cfg", "page", "kv_int8"),
                   donate_argnums=(1, 2, 3, 4))
def _paged_step(params, pools_k, pools_v, scales_k, scales_v, tables,
                toks, lengths, temps, top_ks, top_ps, keys, cfg, cos,
                sin, page, kv_int8):
    """One token for every slot against the shared page pool.

    pools_*: per-layer [num_pages, page, kvh, d]. tables: [S, P] page
    ids per slot. Writes: one batched scatter per layer at each slot's
    (page_of(length), length % page), in place: the pools and the int8
    scales are donated, as every other family's step donates its own, so
    the caller keeps the returned ones and no handle on those it gave
    (un-donated, each of a step's pools was copied whole for its one
    row). Reads: each slot's live pages, block by block
    (``paged_ops.paged_attention``); no array as wide as the table.
    """
    S = tables.shape[0]
    x = params["embedding"][toks].astype(cfg.dtype)[:, None, :]  # [S,1,D]
    positions = lengths[:, None]
    page_idx = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]  # [S]
    offs = lengths % page
    new_pools_k, new_pools_v = [], []
    new_scales_k, new_scales_v = ([], []) if kv_int8 else (scales_k,
                                                           scales_v)
    # The named scopes change HLO metadata only: a profile then names
    # the phase of every device op; the op sequence is as before.
    for li, layer in enumerate(params["layers"]):
        with jax.named_scope("attention"):
            h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q = mm(h, layer["wq"]).reshape(S, 1, cfg.n_heads,
                                           cfg.head_dim)
            k = mm(h, layer["wk"]).reshape(S, 1, cfg.n_kv_heads,
                                           cfg.head_dim)
            v = mm(h, layer["wv"]).reshape(S, 1, cfg.n_kv_heads,
                                           cfg.head_dim)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        o, pool_k, pool_v, scale_k, scale_v = paged_attention(
            q, k, v, pools_k[li], pools_v[li],
            scales_k[li] if kv_int8 else None,
            scales_v[li] if kv_int8 else None, tables, lengths, page_idx,
            offs, kv_int8, cfg.dtype)
        new_pools_k.append(pool_k)
        new_pools_v.append(pool_v)
        if kv_int8:
            new_scales_k.append(scale_k)
            new_scales_v.append(scale_v)
        with jax.named_scope("attention"):
            x = x + mm(o, layer["wo"])
        with jax.named_scope("mlp"):
            x = x + _mlp_block(layer, x, cfg)
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    head = (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = mm(x[:, 0], head)                     # [S, V]
    out, new_keys, _ = _sample(logits, temps, top_ks, top_ps, keys, lengths)
    return (out, new_pools_k, new_pools_v, new_scales_k, new_scales_v,
            new_keys)


@functools.partial(jax.jit, static_argnames=("page", "kv_int8"),
                   donate_argnums=(0, 1, 2, 3))
def _scatter_pages(pools_k, pools_v, scales_k, scales_v, seq_caches,
                   page_ids, qmax, page, kv_int8):
    """One admission's K/V into its pages of every layer's pool, in
    place (the pools and scales are donated).

    seq_caches: per-layer dense (k, v) of [P*page, kvh, d], as the
    prefill programs return them; viewed as P rows of one page each.
    page_ids: int32[P], the pool page each row lands in; a row the
    request does not own (a shared prefix page, the padded tail) holds
    an id past the pool and is dropped. Nothing static depends on the
    prompt, so one program serves every admission. qmax: ``_quant_kv``'s
    127 as an operand, which keeps the int8 pages and scales bit for
    bit what the eager per-page loop before this program wrote.
    """
    new_k, new_v = [], []
    new_sk, new_sv = ([], []) if kv_int8 else (scales_k, scales_v)
    for li, (kc, vc) in enumerate(seq_caches):
        rows_k = kc.reshape((-1, page) + kc.shape[1:])
        rows_v = vc.reshape((-1, page) + vc.shape[1:])
        if kv_int8:
            rows_k, sk = _quant_kv(rows_k, qmax)
            rows_v, sv = _quant_kv(rows_v, qmax)
            new_sk.append(scales_k[li].at[page_ids].set(sk, mode="drop"))
            new_sv.append(scales_v[li].at[page_ids].set(sv, mode="drop"))
        new_k.append(pools_k[li].at[page_ids].set(
            rows_k.astype(pools_k[li].dtype), mode="drop"))
        new_v.append(pools_v[li].at[page_ids].set(
            rows_v.astype(pools_v[li].dtype), mode="drop"))
    return new_k, new_v, new_sk, new_sv


@functools.partial(jax.jit,
                   static_argnames=("cfg", "total", "pad_len"))
def _suffix_prefill(params, prefix_caches, suffix_padded, prefix_len,
                    n_valid_total, total, cfg, cos, sin, pad_len):
    """Prefill only the NON-cached suffix of a prompt: the dense
    single-sequence cache arrives pre-seeded with the shared prefix
    K/V (gathered from cached pages); suffix tokens run from position
    ``prefix_len``. Returns next-token logits at the prompt end plus
    the full dense cache (prefix + suffix) for page scatter."""
    from .llama import _decode_step

    b_caches = [(kc[None], vc[None]) for kc, vc in prefix_caches]
    logits, new = _decode_step(params, suffix_padded[None], b_caches,
                               prefix_len, cfg, cos, sin)
    first = logits[0, n_valid_total - prefix_len - 1]
    return first, [(kc[0], vc[0]) for kc, vc in new]


# ------------------------------------------------------------ the families
# What a family brings, by the type of its config: ``_Family``'s fields say
# what each callable is given and returns. Each takes the engine; a family's
# module knows nothing of it.
def _read_block(eng):
    """The positions a block of ``paged_attention``'s read holds in ``eng``'s
    step, by the rule the step itself takes its blocks from (the XLA read's,
    or the kernel's where the step reads lane pools on a TPU): what the step
    row's ``kv_positions_read`` counts in."""
    cfg = eng.cfg
    return eng.page * read_block_pages(eng.pools_k[0], eng.S, eng.P,
                                       cfg.head_dim, cfg.dtype)


def _scales(eng):
    """The int8 scales as ``_paged_step`` and ``_hybrid_step`` take them; in
    the model's dtype there are none, and the programs take placeholders
    (donated aliases of what they return)."""
    return ((eng.scales_k, eng.scales_v) if eng.kv_int8
            else (eng._no_scales, eng._no_scales))


def _scatter_kv(eng, caches, page_ids):
    """One dispatch of ``_scatter_pages``, which consumes the pools it is
    given, as the step does: both leave ``eng.pools_*`` the only handles."""
    (eng.pools_k, eng.pools_v, eng.scales_k,
     eng.scales_v) = _scatter_pages(
        eng.pools_k, eng.pools_v, eng.scales_k, eng.scales_v, caches,
        page_ids, np.float32(127.0), eng.page, eng.kv_int8)


def _write_slot_state(eng, state, slot, n):
    eng.ssm, eng.conv = _write_state(eng.ssm, eng.conv, state,
                                     np.int32(slot))


def _dense_state(eng):
    cfg = eng.cfg
    eng.cos, eng.sin = rope_frequencies(cfg.head_dim, eng.max_len,
                                        cfg.rope_theta)
    eng._read_block = _read_block(eng)  # ``_paged_step``: paged_attention


def _dense_prefill(eng, suffix, pad, n, shared):
    """The whole prompt, or (seeded with the shared prefix's K/V gathered
    from its cached pages) only the suffix, the compute the cache saves."""
    cfg = eng.cfg
    padded = jnp.asarray(suffix + [0] * (pad - len(suffix)), dtype=jnp.int32)
    if not shared:
        return _prefill_one(eng.params, padded, n, eng.max_len, cfg, eng.cos,
                            eng.sin, pad) + (None,)
    L0 = len(shared) * eng.page
    tbl = jnp.asarray(shared, dtype=jnp.int32)

    def prefix(pool, scale):
        rows = pool[tbl].reshape(L0, cfg.n_kv_heads, cfg.head_dim)
        if eng.kv_int8:     # dequantize borrowed pages
            rows = rows.astype(cfg.dtype) * scale[tbl].reshape(
                L0, cfg.n_kv_heads, 1).astype(cfg.dtype)
        return jnp.concatenate([rows, jnp.zeros(
            (eng.max_len - L0,) + rows.shape[1:], rows.dtype)])

    prefix_caches = [(prefix(pk, sk), prefix(pv, sv)) for pk, pv, sk, sv
                     in zip(eng.pools_k, eng.pools_v, eng.scales_k,
                            eng.scales_v)]
    return _suffix_prefill(
        eng.params, prefix_caches, padded, jnp.int32(L0), jnp.int32(n),
        eng.max_len, cfg, eng.cos, eng.sin, pad) + (None,)


def _dense_step(eng, uploads):
    toks, eng.pools_k, eng.pools_v, sk, sv, new_keys = _paged_step(
        eng.params, eng.pools_k, eng.pools_v, *_scales(eng), *uploads,
        eng.cfg, eng.cos, eng.sin, eng.page, eng.kv_int8)
    if eng.kv_int8:
        eng.scales_k, eng.scales_v = sk, sv
    return toks, new_keys, toks, None   # the tokens alone; nothing to publish


def _nemotron_state(eng):
    # per slot the SSM state (float32) and the convolution tail of every
    # Mamba layer: both rows with a Mamba-2 mixer keep them so
    eng.ssm, eng.conv = init_state(eng.cfg, eng.S)
    eng._read_block = _read_block(eng)  # the step reads: paged_attention
    # the last step's chosen experts [expert layers, S, k]: left on the
    # device, for a reference check to read
    eng.last_routing = None


def _nemotron_prefill(eng, suffix, pad, n, shared):
    padded = jnp.asarray(suffix + [0] * (pad - len(suffix)), dtype=jnp.int32)
    return _hybrid_prefill(eng.params, padded, n, eng.max_len, eng.cfg,
                           pad)[:3]


def _nemotron_step(eng, uploads):
    (toks, eng.pools_k, eng.pools_v, sk, sv, eng.ssm, eng.conv, new_keys,
     routing, next_tok) = _hybrid_step(
        eng.params, eng.pools_k, eng.pools_v, *_scales(eng), eng.ssm,
        eng.conv, *uploads, eng.cfg, eng.page, eng.kv_int8)
    if eng.kv_int8:
        eng.scales_k, eng.scales_v = sk, sv
    return toks, new_keys, next_tok, routing


def _nemotron_counts(eng, tail, sp):
    # the expert layers' load rode with the tokens; ONE step's figures (the
    # mean, where the call landed two: the row's reader averages rows)
    sp.set(experts_hit=int(tail[0]) // eng._landed,
           expert_tokens_max=int(tail[1]) // eng._landed)


def _sala_state(eng):
    cfg = eng.cfg
    if eng.page != cfg.block or cfg.topk > eng.P:
        raise ValueError(
            "this family's block is the page (page_size == cfg.block) and "
            "its table holds topk pages")
    eng.ssm, eng.conv = sala.init_state(cfg, eng.S), []
    # beside each sparse layer's K/V pool the indexer's cache: one
    # compressed key per ``stride`` positions, on the page of its first
    eng.pools_c = [jnp.zeros((eng.num_pages, eng.page // cfg.stride,
                              cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
                   for _ in range(eng.n_kv)]
    # the last step's chosen blocks [sparse layers, S, kvh, topk]: left on
    # the device, for a reference check to read
    eng.last_selection = None


def _sala_prefill(eng, suffix, pad, n, shared):
    first, caches, states = sala.prefill(
        eng.params, suffix, eng.max_len, eng.cfg)
    return first, caches, [(s,) for s in states]


def _sala_scatter(eng, caches, page_ids):
    kv, ckeys = caches
    eng.pools_k, eng.pools_v, eng.pools_c = sala._scatter_sala(
        eng.pools_k, eng.pools_v, eng.pools_c, kv, ckeys, page_ids)


def _sala_admit_fields(eng, n):
    # the compressed-key rows scattered
    return {"ckeys": eng.max_len // eng.cfg.stride * eng.n_kv}, {}


def _sala_step(eng, uploads):
    (toks, eng.pools_k, eng.pools_v, eng.pools_c, eng.ssm, new_keys,
     chosen, next_tok) = sala._sala_step(
        eng.params, eng.pools_k, eng.pools_v, eng.pools_c, eng.ssm,
        *uploads, eng.cfg, eng.page)
    return toks, new_keys, next_tok, chosen


def _sala_landed(eng, chosen):
    eng.last_selection = chosen


def _sala_counts(eng, tail, sp):
    sp.set(sparse_pages_read=int(tail[0]), sparse_pages_live=int(tail[1]),
           sparse_slots=int(tail[2]))


def _longcat_state(eng):
    # the last step's chosen experts [layers, S, k]: left on the device, for
    # a reference check to read
    eng.last_routing = None


def _longcat_prefill(eng, suffix, pad, n, shared):
    first, lats = longcat.prefill(eng.params, suffix, eng.max_len, eng.cfg)
    return first, lats, None


def _longcat_scatter(eng, lats, page_ids):
    eng.pools_k = longcat._scatter_latent(eng.pools_k, lats, page_ids)


def _longcat_admit_fields(eng, n):
    return {"latent_rows": eng.max_len * eng.n_kv}, {}


def _longcat_step(eng, uploads):
    toks, eng.pools_k, new_keys, routing, next_tok = longcat._longcat_step(
        eng.params, eng.pools_k, *uploads, eng.cfg, eng.page)
    return toks, new_keys, next_tok, routing


def _routing_landed(eng, routing):
    eng.last_routing = routing


def _longcat_counts(eng, tail, sp):
    # ``latent_positions_read``: what ``attend_latent`` gathered a sublayer,
    # every slot's blocks whole, against the live ``latent_positions``
    sp.set(experts_hit=int(tail[0]), expert_tokens_max=int(tail[1]),
           zero_picks=int(tail[2]), latent_positions=int(tail[3]),
           latent_positions_read=int(tail[6]),
           moe_rows=int(tail[4]), landed=int(tail[5]))


def _cohere_state(eng):
    cfg = eng.cfg
    # beside the full layers' pools, each window layer's K/V as a ring a
    # slot: position p at index p mod window, sized by the slots and the
    # model's window whatever a slot's context; no table, no allocator
    ring = (eng.S, cfg.n_kv_heads, cfg.sliding_window, cfg.head_dim)
    eng.rings_k = [jnp.zeros(ring, cfg.dtype)
                   for _ in range(cfg.n_window_layers)]
    eng.rings_v = [jnp.zeros(ring, cfg.dtype)
                   for _ in range(cfg.n_window_layers)]
    # the last step's chosen experts [layers, S, k]: left on the device, for
    # a reference check to read
    eng.last_routing = None


def _cohere_prefill(eng, suffix, pad, n, shared):
    first, bufs = cohere.prefill(eng.params, suffix, eng.max_len, eng.cfg)
    full, window = ([b for kind, b in zip(eng.cfg.kinds, bufs) if kind == k]
                    for k in (cohere.FULL, cohere.WINDOW))
    return first, full, window or None


def _cohere_write_state(eng, rows, slot, n):
    eng.rings_k, eng.rings_v = cohere._write_rings(
        eng.rings_k, eng.rings_v, rows, np.int32(n), np.int32(slot))


def _cohere_admit_fields(eng, n):
    return {}, {"ring_positions": min(n, eng.cfg.sliding_window)
                * eng.cfg.n_window_layers}


def _cohere_step(eng, uploads):
    (toks, eng.pools_k, eng.pools_v, eng.rings_k, eng.rings_v, new_keys,
     routing, next_tok) = cohere._cohere_step(
        eng.params, eng.pools_k, eng.pools_v, eng.rings_k, eng.rings_v,
        *uploads, eng.cfg, eng.page)
    return toks, new_keys, next_tok, routing


def _cohere_counts(eng, tail, sp):
    sp.set(moe_hit=int(tail[0]), moe_max=int(tail[1]), moe_rows=int(tail[2]),
           context_positions=int(tail[3]), window_positions=int(tail[4]),
           landed=int(tail[5]))


def _lfm2_state(eng):
    # per slot a convolution tail for every conv layer and nothing else (no
    # ``eng.ssm``): the last two gated inputs of each short convolution
    eng.conv = lfm2.init_state(eng.cfg, eng.S)
    eng._read_block = _read_block(eng)  # ``_lfm2_step``: paged_attention
    # the last step's chosen experts [expert layers, S, k]: left on the
    # device, for a reference check to read
    eng.last_routing = None


def _lfm2_prefill(eng, suffix, pad, n, shared):
    return lfm2.prefill(eng.params, suffix, eng.max_len, eng.cfg)


def _lfm2_write_state(eng, tails, slot, n):
    eng.conv = lfm2._write_tails(eng.conv, tails, np.int32(slot))


def _lfm2_admit_fields(eng, n):
    return {}, {"conv_rows": (eng.cfg.conv_kernel - 1)
                * eng.cfg.n_conv_layers}


def _lfm2_step(eng, uploads):
    (toks, eng.pools_k, eng.pools_v, eng.conv, new_keys, routing,
     next_tok) = lfm2._lfm2_step(
        eng.params, eng.pools_k, eng.pools_v, eng.conv, *uploads, eng.cfg,
        eng.page)
    return toks, new_keys, next_tok, routing


def _lfm2_counts(eng, tail, sp):
    sp.set(experts_hit=int(tail[0]), expert_tokens_max=int(tail[1]),
           moe_rows=int(tail[2]), context_positions=int(tail[3]),
           landed=int(tail[4]))


def _granite_prefill(eng, suffix, pad, n, shared):
    return granite.prefill(eng.params, suffix, eng.max_len, eng.cfg)


def _granite_admit_fields(eng, n):
    # rows of the slot's state the admission writes: a Mamba layer's SSM
    # state is heads x head_dim rows of ``ssm_state``, its tail K - 1 rows
    cfg = eng.cfg
    return {}, {"state_rows": cfg.n_mamba_layers
                * (cfg.d_inner + cfg.conv_kernel - 1)}


def _granite_step(eng, uploads):
    (toks, eng.pools_k, eng.pools_v, eng.ssm, eng.conv, new_keys, routing,
     next_tok) = granite._granite_step(
        eng.params, eng.pools_k, eng.pools_v, eng.ssm, eng.conv, *uploads,
        eng.cfg, eng.page)
    return toks, new_keys, next_tok, routing


def _granite_counts(eng, tail, sp):
    _lfm2_counts(eng, tail, sp)     # the same five ride with the tokens
    # the recurrences read and write each active row's whole state: from
    # shapes and the rows, on the host (4.8 GB a step is past an int32)
    sp.set(ssm_state_bytes=2 * int(tail[2]) * eng.cfg.slot_state_bytes)


def _deepseek_state(eng):
    # the last landed step's chosen experts, logits of its rows ([S, rows,
    # V]) and, where it drafts, the MTP block's logits of the next draft and
    # which drafts it accepted: left on the device, for a reference check
    eng.last_routing = eng.last_logits = None
    eng.last_draft_logits = eng.last_accepted = None
    if eng.cfg.n_nextn:
        # every slot's draft of the token after its last committed one (-1:
        # none) and the distribution it was drawn from, on the device: a step
        # hands them to the next as it hands on the pools
        eng.drafts = jnp.full((eng.S,), -1, jnp.int32)
        eng.draft_q = jnp.zeros((eng.S, eng.cfg.vocab_size), jnp.float32)


def _deepseek_prefill(eng, suffix, pad, n, shared):
    # the main model's output at the prompt's end is kept for the first draft
    first, eng._prompt_end, lats = deepseek.prefill(
        eng.params, suffix, eng.max_len, eng.cfg)
    return first, lats, None


def _deepseek_first_draft(eng, slot, tok, n):
    """The admission's first draft, from the pair (the prompt's last output,
    the first token): one dispatch, nothing fetched."""
    eng.pools_k[-1], eng.drafts, eng.draft_q = \
        deepseek._deepseek_first_draft(
            eng.params, eng.pools_k[-1], eng.tables[slot][None],
            eng._prompt_end, np.int32(tok), np.int32(n),
            np.float32(eng.temps[slot]), np.int32(eng.top_ks[slot]),
            np.float32(eng.top_ps[slot]), eng.drafts, eng.draft_q,
            eng.keys[slot].astype(np.uint32, copy=False), np.int32(slot),
            eng.cfg)


def _deepseek_step(eng, uploads):
    if not eng.cfg.n_nextn:     # no MTP module: one token a slot, as the rest
        toks, eng.pools_k, new_keys, kept, next_tok = \
            deepseek._deepseek_step_one(eng.params, eng.pools_k, *uploads,
                                        eng.cfg)
        return toks, new_keys, next_tok, kept
    (toks, eng.pools_k, new_keys, kept, next_tok, lengths, eng.drafts,
     eng.draft_q) = deepseek._deepseek_step(
        eng.params, eng.pools_k, *uploads, eng.draft_q, eng.drafts, eng.cfg)
    return toks, new_keys, next_tok, kept, lengths


def _deepseek_landed(eng, kept):
    eng.last_routing, eng.last_logits, *drafted = kept
    if drafted:
        eng.last_draft_logits, eng.last_accepted = drafted


def _deepseek_counts(eng, tail, sp):
    # ``latent_positions_read``: what ``attend_latent`` gathered a layer,
    # every slot's blocks whole, against the live ``latent_positions``
    sp.set(experts_hit=int(tail[0]), expert_tokens_max=int(tail[1]),
           latent_positions=int(tail[2]),
           latent_positions_read=int(tail[7]),
           drafted=int(tail[3]), accepted=int(tail[4]),
           moe_rows=int(tail[5]), landed=int(tail[6]))


@dataclass(frozen=True)
class _Family:
    n_kv: object            # cfg -> layers (or sublayers) with a pool
    state: object           # engine -> None: allocates what the family
    #                         keeps beside the pools, refuses what it cannot
    prefill: object         # (engine, the prompt less its shared prefix,
    #                         pad, prompt length, the prefix's pages) ->
    #                         (first logits, caches, per-layer state or None)
    step: object            # (engine, uploads) -> (the tokens and the step's
    #                         counts, the keys, the tokens alone as the next
    #                         step takes them: with them the engine runs
    #                         ahead of the device, ``_step``; what ``landed``
    #                         publishes or None; and, from a step that may
    #                         commit two tokens a slot, the lengths as the
    #                         next step takes them). It rebinds what the
    #                         program consumed
    counts: object = None   # (engine, what rode with the tokens, the step's
    #                         span): the family's fields of the step row
    scatter: object = _scatter_kv   # (engine, caches, page_ids): its own
    #                         where the prefill returns other than K/V
    admit_fields: object = None     # (engine, prompt length) -> its fields
    #                         of the admission's scatter and state spans
    chunked: bool = False   # its prompts are admitted in chunks of
    #                         ``cfg.prefill_chunk``, not padded to a bucket:
    #                         ``max_len`` is a whole number of chunks and
    #                         the prefill span says ``chunks``
    landed: object = None   # (engine, what the step kept on the device):
    #                         called when that step's tokens are fetched
    experts_form: object = None     # cfg -> the form a prompt chunk's
    #                         grouped expert products take (``ragged`` or
    #                         ``kernel``; None where its rows are not
    #                         grouped): asked once, when the engine is built,
    #                         and said by every prefill span
    prompt_attn: bool = False   # its chunk's attention is ``cohere2_moe.
    #                         _prompt_attention``: every prefill span says
    #                         the form it takes (``_prompt_attn_form``)
    pool_shape: object = None   # (cfg, num_pages, page_size) -> the shape
    #                         of a layer's pool where a position's row is
    #                         not laid out as (n_kv_heads, head_dim)
    one_pool: bool = False  # a position's row is not keys beside values of
    #                         the same shape: the layer has ONE pool (of
    #                         ``pool_shape``) and no V pool
    write_state: object = _write_slot_state     # (engine, the prefill's
    #                         state, slot, prompt length): its own where the
    #                         state is not ``eng.ssm`` / ``eng.conv``
    first_draft: object = None  # (engine, slot, the first token, prompt
    #                         length): the draft a slot enters its first
    #                         step with, of a family whose model has an MTP
    #                         module (``cfg.n_nextn``): its step then verifies
    #                         a draft and commits one or two tokens a slot
    no_prefix_cache: Optional[str] = (  # why ``enable_prefix_cache`` is
        #                     refused; None where the family has one
        "snapshots of recurrent state at page boundaries; a prefill of the "
        "suffix alone over latent pages")
    no_int8: Optional[str] = None   # why ``kv_dtype="int8"`` is refused;
    #                         None where the step reads int8 pages

    @property
    def buckets(self) -> tuple:
        """What a prompt is padded to under ``max_len``."""
        return () if self.chunked else (16, 64, 256)


def _held_form(family, width: str = "n_experts"):
    """``_Family.experts_form`` of a family whose routed layer is
    ``parallel.moe.moe_ffn_held``: that function's own answer
    (``held_experts_form``) at a chunk's rows, the configuration's sizes and
    the module's ``GROUPED_FROM_ROWS`` as it stands when the engine is
    built."""
    return lambda cfg: held_experts_form(
        cfg.prefill_chunk, cfg.top_k, cfg.experts_held, cfg.d_model,
        cfg.expert_d_ff, getattr(cfg, width), cfg.dtype,
        family.GROUPED_FROM_ROWS)[0]


def _prompt_attn_form(cfg, max_len: int) -> str:
    """The form ``cohere2_moe._prompt_attention`` takes (``kernel`` or
    ``loop``) at a chunk's queries over an engine's ``max_len`` buffered
    rows: the answer of the function it asks itself."""
    return chunk_attention_form(cfg.prefill_chunk, max_len, cfg.n_heads,
                                cfg.n_kv_heads, cfg.head_dim, cfg.dtype)


_FAMILIES = {
    # a K/V pool for every layer; the one family with a prefix cache
    LlamaConfig: _Family(
        lambda cfg: cfg.n_layers, _dense_state, _dense_prefill, _dense_step,
        no_prefix_cache=None),
    # pools for the attention layers only, per-slot recurrent state of the
    # Mamba layers beside them (``eng.ssm`` / ``eng.conv``): written whole at
    # admission, advanced by the step for all slots, donated to both
    NemotronHConfig: _Family(
        lambda cfg: cfg.n_attn_layers, _nemotron_state, _nemotron_prefill,
        _nemotron_step, _nemotron_counts, landed=_routing_landed),
    # pools for the sparse layers only, a compressed-key pool beside each
    # (``eng.pools_c``: the cache of the layer's block selection, on the same
    # pages) and per-slot lightning state (``eng.ssm``)
    MiniCPMSALAConfig: _Family(
        lambda cfg: cfg.n_sparse_layers, _sala_state, _sala_prefill,
        _sala_step, _sala_counts, _sala_scatter, _sala_admit_fields,
        chunked=True, landed=_sala_landed,
        no_int8="its step reads its pages, and the compressed keys beside "
                "them, in the model's dtype"),
    # ONE pool for each of the ``2 x layers`` latent-attention sublayers, a
    # position's row the compressed latent and the one rotary key all heads
    # share (no V pool, no per-slot state); admitted through the expanded
    # attention form, stepped in the absorbed form
    LongcatFlashConfig: _Family(
        lambda cfg: cfg.n_sublayers, _longcat_state, _longcat_prefill,
        _longcat_step, _longcat_counts, _longcat_scatter,
        _longcat_admit_fields, chunked=True, landed=_routing_landed,
        experts_form=lambda cfg: grouped_product_form(
            grouped_pairs(cfg.prefill_chunk, cfg.top_k), cfg.d_model,
            cfg.expert_d_ff, cfg.dtype),    # as ``moe_ffn_zero`` groups them
        pool_shape=lambda cfg, pages, page: latent_pool_shape(
            pages, page, cfg.latent_width), one_pool=True,
        no_int8="its latent pages are kept in the model's dtype"),
    # K/V pools for the full-attention layers only, read in blocks of table
    # columns, and each window layer's K/V as a per-slot ring of the window's
    # width (``eng.rings_k`` / ``eng.rings_v``), written and advanced as
    # per-slot state is: a slot's window memory is fixed whatever its context
    Cohere2MoeConfig: _Family(
        lambda cfg: cfg.n_full_layers, _cohere_state, _cohere_prefill,
        _cohere_step, _cohere_counts, admit_fields=_cohere_admit_fields,
        chunked=True, landed=_routing_landed,
        experts_form=_held_form(cohere, "router_width"), prompt_attn=True,
        write_state=_cohere_write_state,
        no_prefix_cache="window layers whose K/V is a per-slot ring: a ring "
                        "is not shareable by page",
        no_int8="its window layers' rings are kept in the model's dtype "
                "beside pages of the same: int8 would quantise one kind of "
                "layer and not the other"),
    # K/V pools for the attention layers only (one layer in four), a
    # position's 8 heads of 64 side by side as four whole lanes (a 4-D pool of
    # such heads is turned whole twice a step); per-slot state a convolution
    # tail for every conv layer and nothing else (``eng.conv``)
    Lfm2MoeConfig: _Family(
        lambda cfg: cfg.n_attn_layers, _lfm2_state, _lfm2_prefill,
        _lfm2_step, _lfm2_counts, admit_fields=_lfm2_admit_fields,
        chunked=True, landed=_routing_landed,
        experts_form=_held_form(lfm2), prompt_attn=True,
        pool_shape=lambda cfg, pages, page: lane_pool_shape(
            pages, page, cfg.n_kv_heads, cfg.head_dim),
        write_state=_lfm2_write_state,
        no_prefix_cache="snapshots of a conv layer's two-row tail at page "
                        "boundaries beside the shared pages",
        no_int8="its step reads its lane pools in the model's dtype"),
    # K/V pools for the attention layers only (one layer in ten, 4-D, a head
    # of 128), per-slot SSM state and convolution tails of the Mamba layers
    # beside them as the older Mamba-2 row keeps them; unlike that row's, its
    # prompts are admitted in chunks, the recurrent state carried from chunk
    # to chunk beside the attention layers' K/V
    GraniteMoeHybridConfig: _Family(
        lambda cfg: cfg.n_attn_layers, _nemotron_state, _granite_prefill,
        _granite_step, _granite_counts, admit_fields=_granite_admit_fields,
        chunked=True, landed=_routing_landed,
        experts_form=_held_form(granite), prompt_attn=True,
        no_prefix_cache="snapshots of every Mamba layer's SSM state and "
                        "tail at page boundaries beside the shared pages",
        no_int8="a float32 SSM state beside int8 pages: the step reads its "
                "pools in the model's dtype"),
    # ONE latent pool a layer that has attention, the MTP block's the last
    # (``longcat_flash``'s pool, scatter and chunked admission); where the
    # model holds an MTP module the step verifies a draft with two query rows
    # a slot and commits one or two tokens, and the drafts stay on the device
    DeepseekV3Config: _Family(
        lambda cfg: cfg.n_sublayers, _deepseek_state, _deepseek_prefill,
        _deepseek_step, _deepseek_counts, _longcat_scatter,
        _longcat_admit_fields, chunked=True, landed=_deepseek_landed,
        experts_form=_held_form(deepseek),
        pool_shape=lambda cfg, pages, page: latent_pool_shape(
            pages, page, cfg.latent_width), one_pool=True,
        first_draft=_deepseek_first_draft,
        no_int8="its latent pages are kept in the model's dtype"),
}


@dataclass
class _PagedSlot:
    request_id: str
    length: int
    max_new: int
    eos_id: Optional[int]
    prompt: List[int] = field(default_factory=list)   # original prompt
    pages: List[int] = field(default_factory=list)
    n_shared: int = 0        # leading pages borrowed from the prefix cache
    emitted: List[int] = field(default_factory=list)
    done: bool = False


#: Steps kept dispatched beyond the one whose tokens a call fetches, with
#: every slot held. One hides the host's part of a step; ten of 12-24 ms ride
#: out the ~0.1 s for which a shared host stops every process on it once or
#: twice a minute (the chip goes on with what it was given: PERF.md section
#: 6, PR 32). They cost an arrival nothing: with no slot free it waits for a
#: stream to end, and the steps in flight have landed by then.
_STEPS_AHEAD = 10
#: The same while a slot is free: a request that arrives then is admitted
#: once the steps in flight have landed, one a call, so only as many are
#: kept as hide the host's part of a call under the device's.
_STEPS_FREE_SLOT = 2


@dataclass
class _Flight:
    """A dispatched step whose tokens the host has not fetched yet."""
    toks: object        # device: the tokens (a recurrent step's counts after)
    keys: object        # device: the slots' sampling keys after the step
    active: List[int]   # the slots that decoded in it
    next_tok: object    # device int32[S]: the tokens alone, the next step's
    kept: object        # what the family publishes when it lands, or None
    lengths: object = None  # device int32[S]: the next step's lengths, where
    #                     a step may commit two tokens a slot (else the host
    #                     knows them)
    # what its ``serve.step.flight`` row says when it lands
    step: int = 0       # its number among the step programs dispatched
    depth: int = 0      # steps already in flight when it was dispatched
    admitted: int = 0   # admissions since the dispatch before it
    call: int = 0       # sid of the ``serve.engine.step`` that dispatched it
    t0_ns: int = 0      # start of its ``serve.step.dispatch`` (0: recorder off)

    def ended(self) -> bool:
        """Whether the device has finished the step (asks, never waits)."""
        return self.toks.is_ready()


class PagedEngine:
    """Slot-based continuous batching over a shared page pool.

    ``num_pages * page_size`` total cache positions are shared by ALL
    sequences; a request only ever holds ceil(current_len / page_size)
    pages, so short requests don't pay for long ones. Admission waits
    for pages, not for a worst-case slot.

    The pools (``self.pools_k`` / ``self.pools_v``, and the int8 scales)
    are rebound by every step and every admission's scatter, which consume
    the ones they are given: read them through the engine, between two
    calls, and keep no handle across one.

    Which device programs run follows from the type of ``cfg``: its row of
    ``_FAMILIES`` holds what the family keeps beside the pools and how it
    prefills, scatters and steps. Pages, tables, admission order, preemption
    by recompute and the spans are the same code for every row.

    Every family's step program hands its tokens on as a device array
    (``_Family.step``; ``_paged_step``'s tokens are that array), and the
    engine **runs ahead of the device** for every row: ``step()`` dispatches
    the next step on the tokens and keys the last one left on the device,
    keeps up to ``_STEPS_AHEAD`` dispatched while every slot is held
    (``_STEPS_FREE_SLOT`` while one is free) and fetches the oldest's
    tokens as it ends. The host's part of a step (uploads, dispatch,
    transfer, the pump between calls) then lies under the device's, and a
    host that stands still for a tenth of a second finds the device still
    at work. Events come some calls after their step's dispatch, at the
    instant they would have come; each dispatched step has a number of its
    own and, as it lands, a ``serve.step.flight`` row that joins its dispatch
    to its landing (``_land``). It runs ahead only while nothing but the
    count of tokens decides what the next step holds (``_runs_ahead``: no
    request waits beside a free slot, no stream has an ``eos_id`` or its
    last token dispatched, a page to spare for every held slot); otherwise
    the steps in flight land, one a call, and the call that lands the last
    is the synchronous one: it admits, so an arrival that finds a free slot
    waits for at most ``_STEPS_FREE_SLOT`` steps, and the prefix cache is
    read and written with nothing in flight.
    """

    def __init__(self, params, cfg, *, max_slots: int = 8,
                 num_pages: int = 64, page_size: int = 16,
                 max_len: int = 512, enable_prefix_cache: bool = False,
                 kv_dtype: str = "model"):
        self.params = params
        self.cfg = cfg
        self.S = max_slots
        self.page = page_size
        self.num_pages = num_pages
        self.P = max_len // page_size           # table width per slot
        self.max_len = self.P * page_size
        # a subclass of a row's config (a configuration file's own class
        # over ``LlamaConfig``) takes that row
        fam = self.family = next((_FAMILIES[t] for t in type(cfg).__mro__
                                  if t in _FAMILIES), None)
        if fam is None:
            raise TypeError(f"{type(cfg).__name__} has no row in _FAMILIES")
        if enable_prefix_cache and fam.no_prefix_cache:
            raise ValueError(
                "enable_prefix_cache needs what this engine does not keep "
                f"for this family ({fam.no_prefix_cache}): it runs without "
                "it")
        if kv_dtype not in ("model", "int8"):
            raise ValueError("kv_dtype must be 'model' or 'int8'")
        # kv_dtype="int8": pages store per-head-vector-quantized K/V
        # (half the bytes in bf16 deployments; the long-context memory
        # lever). Dequantize happens in the gather; outputs are CLOSE
        # to full precision, not bit-identical.
        self.kv_int8 = kv_dtype == "int8"
        if self.kv_int8 and fam.no_int8:
            raise ValueError(
                f"kv_dtype='int8' is refused for this family: {fam.no_int8}")
        if fam.chunked and self.max_len % cfg.prefill_chunk:
            raise ValueError(
                "this family's prefill fills max_len in whole chunks: "
                f"max_len {self.max_len} is not a multiple of "
                f"cfg.prefill_chunk {cfg.prefill_chunk}")
        form = fam.experts_form(cfg) if fam.experts_form else None
        self._prefill_fields = {"experts_form": form} if form else {}
        if fam.prompt_attn:
            self._prefill_fields["prompt_attn_form"] = _prompt_attn_form(
                cfg, self.max_len)
        self.n_kv = fam.n_kv(cfg)
        shape = (fam.pool_shape(cfg, num_pages, page_size) if fam.pool_shape
                 else (num_pages, page_size, cfg.n_kv_heads, cfg.head_dim))
        pool_dt = jnp.int8 if self.kv_int8 else cfg.dtype
        self.pools_k = [jnp.zeros(shape, pool_dt)
                        for _ in range(self.n_kv)]
        self.pools_v = [jnp.zeros(shape, pool_dt)
                        for _ in range(0 if fam.one_pool else self.n_kv)]
        sshape = shape[:-1]
        self.scales_k = [jnp.ones(sshape, jnp.float32)
                         for _ in range(self.n_kv)] \
            if self.kv_int8 else [None] * self.n_kv
        self.scales_v = [jnp.ones(sshape, jnp.float32)
                         for _ in range(self.n_kv)] \
            if self.kv_int8 else [None] * self.n_kv
        # what a step program takes in their place in the model's dtype
        self._no_scales = [0] * self.n_kv
        # Page 0 is a reserved scratch page: INACTIVE slots still flow
        # through the jitted step (static shapes) and their writes land
        # at tables[i,0]=0 / offset 0 — which must never be a page a
        # live sequence owns. Table padding also points at it; reads
        # beyond a sequence's length are position-masked regardless.
        self.free_pages = list(range(1, num_pages))
        self.tables = np.zeros((self.S, self.P), dtype=np.int32)
        self.slots: List[Optional[_PagedSlot]] = [None] * self.S
        self.last_tok = np.zeros(self.S, dtype=np.int32)
        self.temps = np.zeros(self.S, dtype=np.float32)
        self.top_ks = np.zeros(self.S, dtype=np.int32)
        self.top_ps = np.ones(self.S, dtype=np.float32)
        self.keys = np.stack([np.asarray(jax.random.PRNGKey(i))
                              for i in range(self.S)])
        self.pending: List[tuple] = []
        self._admit_events: List[tuple] = []
        self._prefill_buckets = fam.buckets
        # the positions a block of the step's K/V read holds, set by the
        # ``state`` of a family whose step reads through
        # ``paged_attention``; 0: no such read
        self._read_block = 0
        # the most tokens a step commits a slot: two where the model drafts
        # (its own MTP module and a family that steps with it), and then how
        # far a slot came is known on the device alone until a step lands:
        # ``slot.length`` is an upper bound while steps are in flight
        self._reach = 2 if fam.first_draft and cfg.n_nextn else 1
        fam.state(self)
        self._kv_positions = None   # (read, live) of the step dispatched
        # what this step() did, for its ``serve.engine.step`` row
        self._steps = self._admitted = self._preempted = 0
        self._step_counts = None    # what rode with a recurrent step's tokens
        self._landed = 0            # steps whose tokens this step() fetched
        self._flights: List[_Flight] = []   # dispatched, not fetched, in order
        # a dispatched step's identity (``k`` above numbers the CALL, which
        # under the run-ahead dispatches one step and lands another)
        self._dispatched = 0        # step programs dispatched so far
        self._undispatched_admits = 0   # admissions since the last dispatch
        self._call_sid = 0          # sid of the ``serve.engine.step`` under way
        # Prefix cache: full-prompt-page content hash -> (page id,
        # refcount). Pages with refcount 0 stay resident (reusable)
        # until pool pressure evicts them LRU (``_reclaim``).
        self.enable_prefix_cache = enable_prefix_cache
        self._prefix: Dict[tuple, list] = {}   # key -> [page, refs]
        self._prefix_lru: List[tuple] = []     # keys, oldest first
        self.prefix_hits = 0
        self.prefix_misses = 0

    # ---------------------------------------------------------- pages
    def _pages_needed(self, length: int) -> int:
        return -(-length // self.page)

    def _free(self, slot: _PagedSlot):
        for i, pg in enumerate(slot.pages):
            if i < slot.n_shared:
                self._decref(pg)
            else:
                self.free_pages.append(pg)
        slot.pages = []
        slot.n_shared = 0

    def _decref(self, page: int):
        for entry in self._prefix.values():
            if entry[0] == page:
                entry[1] -= 1
                return
        self.free_pages.append(page)  # cache entry was evicted

    def _reclaim(self, need: int) -> None:
        """Evict LRU unreferenced prefix pages until ``need`` are free."""
        while len(self.free_pages) < need and self._prefix_lru:
            for key in list(self._prefix_lru):
                entry = self._prefix.get(key)
                if entry is not None and entry[1] == 0:
                    self._prefix.pop(key)
                    self._prefix_lru.remove(key)
                    self.free_pages.append(entry[0])
                    break
            else:
                return  # everything referenced; nothing to evict

    def _available_pages(self) -> int:
        return len(self.free_pages) + sum(
            1 for k in self._prefix_lru
            if self._prefix.get(k, [0, 1])[1] == 0)

    def invalidate_prefix_cache(self) -> None:
        """Drop every cached prefix mapping — REQUIRED after a live
        weight swap, or future prompts hit K/V pages computed with the
        old checkpoint. Unreferenced pages return to the free pool
        immediately. Pages still shared by in-flight slots cannot be
        freed here (``_decref`` frees a page the moment its entry is
        gone, even with other holders) — their entries stay for the
        page-scan refcounting but move to unmatchable keys, so no new
        prompt can hit them; once the last holder drains, ``_reclaim``
        evicts them like any cold entry."""
        fresh: Dict[tuple, list] = {}
        lru: List[tuple] = []
        for i, key in enumerate(list(self._prefix_lru)):
            entry = self._prefix.get(key)
            if entry is None:
                continue
            if entry[1] == 0:
                self.free_pages.append(entry[0])
            else:
                stale_key = ("__stale__", i, entry[0])
                fresh[stale_key] = entry
                lru.append(stale_key)
        self._prefix = fresh
        self._prefix_lru = lru

    # ---------------------------------------------------------- admit
    def submit(self, request_id: str, prompt: List[int], *,
               max_new_tokens: int = 32, eos_id: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: Optional[int] = None) -> None:
        if len(prompt) + max_new_tokens + 1 > self.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) "
                f"exceeds per-sequence capacity {self.max_len}")
        if self._pages_needed(len(prompt) + max_new_tokens + 1) > \
                self.num_pages - 1:
            raise ValueError(
                "request needs more pages than the pool holds; grow "
                "num_pages or shrink the request")
        self.pending.append((request_id, list(prompt), max_new_tokens,
                             eos_id, float(temperature), int(top_k),
                             float(top_p), seed, None,
                             time.perf_counter_ns()))

    def _cached_prefix_pages(self, prompt: List[int]) -> List[int]:
        """Longest run of already-cached FULL prompt pages (never the
        whole prompt: at least one suffix token must run to produce the
        next-token logits)."""
        if not self.enable_prefix_cache:
            return []
        n = len(prompt)
        j_max = min(n // self.page, (n - 1) // self.page)
        pages: List[int] = []
        for j in range(1, j_max + 1):
            entry = self._prefix.get(tuple(prompt[:j * self.page]))
            if entry is None:
                break
            pages.append(entry[0])
        return pages

    def _register_prefix_pages(self, slot: _PagedSlot):
        """Put every full prompt page (borrowed or fresh) in the prefix
        cache and pin them via the slot's refcounts."""
        n = len(slot.prompt)
        j_max = min(n // self.page, (n - 1) // self.page)
        for j in range(1, j_max + 1):
            key = tuple(slot.prompt[:j * self.page])
            entry = self._prefix.get(key)
            if entry is None:
                self._prefix[key] = [slot.pages[j - 1], 1]
                self._prefix_lru.append(key)
            else:
                entry[1] += 1
                self._prefix_lru.remove(key)
                self._prefix_lru.append(key)  # LRU refresh
        slot.n_shared = j_max

    def _admit(self):
        while self.pending and any(s is None for s in self.slots):
            prompt = self.pending[0][1]
            shared = self._cached_prefix_pages(prompt)
            need = self._pages_needed(len(prompt) + 1) - len(shared)
            self._reclaim(need)
            if need > len(self.free_pages):
                return  # wait for pages, preserve FIFO order
            self._admit_one(self.pending.pop(0), shared, need)

    def _admit_one(self, request: tuple, shared: List[int], need: int):
        """Prefill one request, scatter its K/V into its pages, write its
        recurrent state (where the model has any) into its slot, sample
        its first token. One ``serve.engine.admit`` span, its phases
        inside it: they bracket what the host does, device time per phase
        comes from the trace by program name."""
        (rid, prompt, max_new, eos_id, temp, top_k, top_p,
         seed, key_state, submitted_ns) = request
        rid8 = str(rid)[:8]
        n = len(prompt)
        L0 = len(shared) * self.page       # cached prefix length
        suffix = prompt[L0:]
        pad = next((b for b in self._prefill_buckets
                    if b >= len(suffix)), self.max_len)
        with plane_events.span(
                "serve.engine.admit", "serve", rid=rid8, prompt_len=n,
                bucket=pad, shared_pages=len(shared),
                own_pages=need) as sp:
            if sp.sid:      # recorder on: submit() to this span's start
                sp.set(waited_ns=sp.t0_ns - submitted_ns)
            self._admitted += 1
            self._undispatched_admits += 1
            idx = self.slots.index(None)
            self.temps[idx] = temp
            self.top_ks[idx] = top_k
            self.top_ps[idx] = top_p
            if key_state is not None:   # resuming a preempted request
                self.keys[idx] = np.array(key_state)
            elif seed is not None:
                self.keys[idx] = np.array(jax.random.PRNGKey(seed))
            slot = _PagedSlot(rid, length=n, max_new=max_new,
                              eos_id=eos_id, prompt=list(prompt))
            own = [self.free_pages.pop() for _ in range(need)]
            slot.pages = list(shared) + own
            if shared:
                self.prefix_hits += 1
            elif self.enable_prefix_cache:
                self.prefix_misses += 1
            fam = self.family
            chunks = ({"chunks": -(-n // self.cfg.prefill_chunk),
                       **self._prefill_fields} if fam.chunked else {})
            if self._reach > 1:     # the MTP block ran one token on
                chunks["mtp_rows"] = n - 1
            scattered, written = (fam.admit_fields(self, n)
                                  if fam.admit_fields else ({}, {}))
            with plane_events.span("serve.admit.prefill", "serve",
                                   rid=rid8, **chunks):
                first_logits, seq_caches, state = fam.prefill(
                    self, suffix, pad, n, shared)
            self.tables[idx] = 0
            self.tables[idx, :len(slot.pages)] = slot.pages
            with plane_events.span("serve.admit.scatter", "serve",
                                   rid=rid8, pages=need, dispatches=1,
                                   **scattered):
                self._scatter(seq_caches, slot.pages, len(shared))
            if self.enable_prefix_cache:
                # off, no page is ever shared: the registry's keys (every
                # full-page prefix of the prompt as a tuple, quadratic in
                # its length: 0.3 s and 100 MB of a 40k-token admission)
                # would only be built, scanned at every free, and evicted
                self._register_prefix_pages(slot)
            if state is not None:
                with plane_events.span("serve.admit.state", "serve",
                                       rid=rid8, layers=len(state),
                                       dispatches=1, **written):
                    fam.write_state(self, state, idx, n)
            with plane_events.span("serve.admit.sample", "serve",
                                   rid=rid8):
                key = jnp.asarray(self.keys[idx], dtype=jnp.uint32)
                key, sub = jax.random.split(key)
                self.keys[idx] = np.array(key)
                tok = int(_pick_one(first_logits, jnp.float32(temp),
                                    jnp.int32(top_k), jnp.float32(top_p),
                                    sub))
            slot.emitted.append(tok)
            self.last_tok[idx] = tok
            self._admit_events.append((rid, tok))
            if (eos_id is not None and tok == eos_id) or \
                    len(slot.emitted) >= max_new:
                slot.done = True
            elif self._reach > 1:
                with plane_events.span("serve.admit.draft", "serve",
                                       rid=rid8, dispatches=1):
                    fam.first_draft(self, idx, tok, n)
            self.slots[idx] = slot

    def _scatter(self, seq_caches, pages: List[int], n_shared: int):
        """The computed K/V into the slot's OWN pages only (shared
        prefix pages already hold their content): one dispatch of the
        family's scatter."""
        page_ids = np.full(self.P, self.num_pages, dtype=np.int32)
        page_ids[n_shared:len(pages)] = pages[n_shared:]
        self.family.scatter(self, seq_caches, page_ids)

    # ----------------------------------------------------------- step
    def step(self) -> List[tuple]:
        self._admitted = self._preempted = 0
        with plane_events.span("serve.engine.step", "serve",
                               k=self._steps) as sp:
            self._steps += 1
            self._call_sid = sp.sid
            events, active, sampling = self._step()
            sp.set(active=active, sampling=sampling,
                   admitted=self._admitted,
                   tokens=sum(1 for _, tok in events if tok is not None),
                   pending=len(self.pending),
                   free_pages=len(self.free_pages),
                   preempted=self._preempted, flights=len(self._flights))
            if self._step_counts is not None:
                self.family.counts(self, self._step_counts, sp)
            if self._kv_positions:
                sp.set(kv_positions_read=self._kv_positions[0],
                       kv_positions_live=self._kv_positions[1])
        return events

    def _step(self):
        """-> (events, slots that decoded, those of them that sampled:
        0 means the step program took ``_pick_tokens``' argmax side).
        Four phases tile the time after ``_admit``: prepare (tables and
        uploads), dispatch (the step program's call until it returns),
        fetch (blocks on the device), emit (the per-slot loop). With steps
        in flight that this one can run ahead of, the fetch and the emit
        are the oldest's, after this one's dispatch; with steps in flight
        it cannot run ahead of, the call only lands the oldest, and the
        call that lands the last goes on as the synchronous one."""
        self._step_counts = self._kv_positions = None
        self._landed = 0
        events: List[tuple] = []
        flights = self._flights
        if flights and not self._runs_ahead():
            self._land(flights.pop(0), events)
            if flights:     # at the device's pace: one step a call
                return events, 0, 0
        if not flights:
            self._admit()
        with plane_events.span("serve.step.prepare", "serve"):
            events.extend(self._admit_events)
            self._admit_events = []
            for i, s in enumerate(self.slots):
                if s is not None and s.done:
                    events.append((s.request_id, None))
                    self._free(s)
                    self.slots[i] = None
                    self.tables[i] = 0  # inactive lane writes -> scratch
            active = [i for i, s in enumerate(self.slots)
                      if s is not None]
            if not active:
                return events, 0, 0
            active = self._grow_tables(active)
            if not active:
                return events, 0, 0
            lengths = np.array([self.slots[i].length if self.slots[i]
                                else 0 for i in range(self.S)],
                               dtype=np.int32)
            sampling = int(np.count_nonzero(self.temps[active] > 0.0))
            if self._read_block:
                # what ``paged_attention`` reads of a pool: the positions
                # before each slot's own (that row comes from the step's
                # arguments) in whole blocks, and how many of them are live
                held = lengths[active]
                self._kv_positions = (
                    int(np.sum(-(-held // self._read_block)
                               * self._read_block)), int(np.sum(held)))
            # one batched transfer: seven small uploads one by one were
            # 2.0 ms of every step on the chip (PERF.md section 6, PR 32)
            if not flights:
                uploads = jax.device_put((
                    self.tables, self.last_tok, lengths, self.temps,
                    self.top_ks, self.top_ps,
                    self.keys.astype(np.uint32, copy=False)))
            else:   # the last step dispatched hands on its tokens and keys
                last = flights[-1]
                tables, at, *rest = jax.device_put((
                    self.tables, lengths, self.temps, self.top_ks,
                    self.top_ps))
                if last.lengths is not None:
                    # ... and how far each slot came, which it alone knows
                    # where a step may commit two tokens a slot
                    at = last.lengths
                uploads = (tables, last.next_tok, at, *rest, last.keys)
        with plane_events.span("serve.step.dispatch", "serve",
                               step=self._dispatched,
                               depth=len(flights)) as sp:
            toks, new_keys, next_tok, kept, *lengths = self.family.step(
                self, uploads)
            for i in active:    # positions written or on their way, at most
                self.slots[i].length += self._reach
        now = _Flight(toks, new_keys, active, next_tok, kept, *lengths,
                      step=self._dispatched, depth=len(flights),
                      admitted=self._undispatched_admits,
                      call=self._call_sid, t0_ns=sp.t0_ns)
        self._dispatched += 1
        self._undispatched_admits = 0
        if all(self.slots[i].eos_id is None for i in active):
            # no token's value ends a stream: later calls may dispatch
            # before this step's tokens are fetched
            flights.append(now)
            # the oldest lands when it has ended (while the steps in
            # flight are still few, after an admission, its tokens are
            # not kept waiting) or when enough are in flight (the call
            # then waits for it, with the device at work on the others).
            # Enough: ``_STEPS_AHEAD`` with every slot held, when no
            # arrival could be admitted before a stream ends anyway; two
            # while a slot is free, which hide the host's part of a call
            # and are all that an arrival then waits for
            full = all(s is not None for s in self.slots)
            if len(flights) > (_STEPS_AHEAD if full else _STEPS_FREE_SLOT) \
                    or flights[0].ended():
                self._land(flights.pop(0), events)
        else:
            self._land(now, events)
        return events, len(active), sampling

    def _runs_ahead(self) -> bool:
        """Whether one more step can be dispatched before the tokens in
        flight are fetched: nothing waits for a free slot, no stream ends
        with a token in flight (the count says so, at the most tokens a
        flight may commit: a stream with an ``eos_id`` is never left in
        flight), and every slot can take one more page, so the step holds the
        slots the last one held and no admission, no release and no
        preemption comes between them."""
        held = self._flights[-1].active
        ahead = self._reach * len(self._flights)
        return (not (self.pending and any(s is None for s in self.slots))
                and len(self.free_pages) >= len(held)
                and all(len(self.slots[i].emitted) + ahead
                        < self.slots[i].max_new for i in held))

    def _land(self, flight: _Flight, events: List[tuple]) -> None:
        """Fetch a dispatched step's tokens and emit them. The step's
        ``serve.step.flight`` row is written as the tokens reach the host:
        from its dispatch span's start to here is its time in flight, and
        ``wait_ns`` of it the host stood blocked for the device."""
        with plane_events.span("serve.step.fetch", "serve",
                               step=flight.step) as sp:
            toks, keys = jax.device_get((flight.toks, flight.keys))
            # a slot's tokens of this step: one, or where the step may commit
            # two, its two and how many of them count
            S, reach = self.S, self._reach
            count = (np.ones(S, np.int32) if reach == 1
                     else toks[reach * S:(reach + 1) * S])
            if sp.sid and flight.t0_ns:     # recorder on, then and now
                plane_events.span_done(
                    "serve.step.flight", "serve", flight.t0_ns,
                    step=flight.step, depth=flight.depth,
                    active=len(flight.active), admitted=flight.admitted,
                    tokens=int(count[flight.active].sum()),
                    wait_ns=time.perf_counter_ns() - sp.t0_ns,
                    call=flight.call, landed_by=self._call_sid)
            self.keys = np.array(keys)
            self._landed += 1
            if self.family.counts:  # they rode with the tokens
                tail = toks[S if reach == 1 else (reach + 1) * S:]
                self._step_counts = (tail if self._step_counts is None
                                     else self._step_counts + tail)
            if flight.kept is not None:
                self.family.landed(self, flight.kept)
        with plane_events.span("serve.step.emit", "serve",
                               tokens=int(count[flight.active].sum()),
                               step=flight.step):
            for i in flight.active:
                s = self.slots[i]
                # the dispatch counted the most the step could commit
                s.length -= reach - int(count[i])
                for tok in toks[reach * i:reach * i + int(count[i])]:
                    tok = int(tok)
                    s.emitted.append(tok)
                    self.last_tok[i] = tok
                    events.append((s.request_id, tok))
                    if (s.eos_id is not None and tok == s.eos_id) or \
                            len(s.emitted) >= s.max_new:
                        # a second token past the end is dropped
                        s.done = True
                        events.append((s.request_id, None))
                        self._free(s)
                        self.slots[i] = None
                        self.tables[i] = 0
                        break

    def _grow_tables(self, active: List[int]) -> List[int]:
        """Grow page tables BEFORE the step for slots crossing a page
        boundary (the writes this step lands at positions ``length ..
        length + _reach - 1``, ``length`` the host's upper bound while steps
        are in flight); -> the slots still active."""
        for i in active:
            s = self.slots[i]
            if self._pages_needed(s.length + self._reach) > len(s.pages):
                if not self.free_pages:
                    self._reclaim(1)  # evict idle prefix pages first
                if not self.free_pages:
                    # Pool exhausted mid-flight: PREEMPT by recompute
                    # (vLLM's recompute policy) — free this sequence's
                    # pages and requeue it with prompt+emitted as the
                    # new prompt; re-prefill resumes it exactly where
                    # it paused once pages free up. Already-streamed
                    # tokens are not re-emitted: the resumed request's
                    # budget is what remains.
                    remaining = s.max_new - len(s.emitted)
                    self.pending.insert(0, (
                        s.request_id, s.prompt + s.emitted, remaining,
                        s.eos_id, float(self.temps[i]),
                        int(self.top_ks[i]), float(self.top_ps[i]),
                        None, np.array(self.keys[i]),
                        time.perf_counter_ns()))
                    self._preempted += 1
                    self._free(s)
                    self.slots[i] = None
                    self.tables[i] = 0
                    continue
                pg = self.free_pages.pop()
                s.pages.append(pg)
                self.tables[i, len(s.pages) - 1] = pg
        return [i for i, s in enumerate(self.slots) if s is not None]

    def has_work(self) -> bool:
        return bool(self.pending or self._flights) or any(
            s is not None for s in self.slots)

    def run_to_completion(self) -> Dict[str, List[int]]:
        results: Dict[str, List[int]] = {}
        acc: Dict[str, List[int]] = {}
        while self.has_work():
            for rid, tok in self.step():
                if tok is None:
                    results[rid] = acc.pop(rid, [])
                else:
                    acc.setdefault(rid, []).append(tok)
        return results
