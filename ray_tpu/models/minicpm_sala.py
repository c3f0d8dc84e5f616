"""MiniCPM-SALA family: lightning linear attention with a block-sparse
attention layer among every few, dense gated MLPs, MiniCPM's scalings.

``h0 = scale_emb E[tok]``; every layer ``h <- h + a mixer(RMSNorm(h))`` then
``h <- h + a MLP(RMSNorm(h))`` with ``a = scale_depth / sqrt(n_layers_published)``;
``logits = (RMSNorm(h) / (d_model / dim_model_base)) W_head``. The kind of
layer ``i`` is ``cfg.mixer_types[i]``:

- ``lightning-attn`` (``ops/linear_attn.py``): per-head RMSNorm on q and k,
  rotary over the whole head, ``S_t = l_h S_(t-1) + k_t^T v_t``, ``o_t = q_t
  S_t / sqrt(d)``, an RMSNorm over all heads, a sigmoid gate, out-projection.
  Per sequence it carries the float32 state ``S`` [H, d, d]. The decay of
  head ``h`` follows from the layer's PUBLISHED index, ``layer_offset + i``.
- ``minicpm4`` (InfLLM-v2): grouped-query attention without rotary, per-head
  RMSNorm on q and k, a sigmoid gate. A query whose context is at most
  ``dense_len`` attends to every key; a longer one scores the compressed
  keys (means of ``kernel`` keys every ``stride``), chooses ``topk`` blocks
  of ``block`` positions (``paged_ops.choose_blocks``) and attends to those.
  Per sequence it carries K/V and the compressed keys.

Pure functions over a params dict. The device programs at the bottom are
what ``models/paged.py``'s ``PagedEngine`` runs for this family, with
``page_size == block`` so that a block is a page: a prefill that takes a
prompt ``prefill_chunk`` tokens at a time, carrying the lightning states (AT
``n_valid``), the dense K/V and the compressed keys from chunk to chunk and
attending key block by key block under the selection's mask (the exact
result at dense cost, never an ``L x L`` array); the scatter of K/V and
compressed keys into the slot's pages; and the decode step of all slots.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import linear_attn
from ..ops.layers import apply_rope, rms_norm, rope_rows as _rope_rows
from ..ops.quant import mm
from .engine import _sample, prefill_in_chunks
from .llama import _mlp_block
from .paged_ops import (attend_chosen, attend_pages, choose_block_mask,
                        select_pages, write_ckeys, write_kv)

F32 = jnp.float32
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
_PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    d_model: int = 4096
    mixer_types: Tuple[str, ...] = _PUBLISHED_MIXERS
    layer_offset: int = 0             # published index of held layer 0
    n_layers_published: int = 32      # for the depth scaling and the decay
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    d_ff: int = 16384
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # the sparse layers' sizes (MiniCPM4's sparse_config)
    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64                    # counted with the forced blocks
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192
    # how the prefill cuts its work (no effect on the result)
    prefill_chunk: int = 2048         # tokens a dispatch
    la_block: int = 256               # lightning's block form
    query_block: int = 512            # queries a selection
    key_block: int = 512              # keys a step of the online softmax
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if not self.mixer_types or \
                set(self.mixer_types) - {LIGHTNING, SPARSE}:
            raise ValueError(f"mixer_types: each {LIGHTNING!r} or {SPARSE!r}")
        if self.layer_offset + self.n_layers > self.n_layers_published:
            raise ValueError("layers held reach past the published depth")
        if self.kernel != 2 * self.stride or self.block % self.stride \
                or self.window % self.block or self.n_heads % self.n_kv_heads:
            raise ValueError("kernel = 2 stride; stride divides block; "
                             "block divides window; K/V heads divide heads")
        if self.prefill_chunk % self.query_block \
                or self.prefill_chunk % self.key_block \
                or self.key_block % self.block or self.query_block % self.block:
            raise ValueError("block divides query_block and key_block, "
                             "which divide prefill_chunk")

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def n_sparse_layers(self) -> int:
        return self.mixer_types.count(SPARSE)

    @property
    def n_lightning_layers(self) -> int:
        return self.mixer_types.count(LIGHTNING)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.n_layers_published)

    def param_count(self) -> int:
        d = self.d_model
        ld = self.lightning_heads * self.lightning_head_dim
        qd, kvd = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        mlp = 3 * d * self.d_ff + d
        light = 4 * d * ld + ld * d + 2 * self.lightning_head_dim + ld + d
        sparse = 2 * d * qd + 2 * d * kvd + qd * d + 2 * self.head_dim + d
        return (self.n_lightning_layers * (light + mlp)
                + self.n_sparse_layers * (sparse + mlp)
                + d + 2 * self.vocab_size * d)


MINICPM_SALA_DEBUG = MiniCPMSALAConfig(
    vocab_size=96, d_model=64, mixer_types=(LIGHTNING, SPARSE, LIGHTNING,
                                            LIGHTNING),
    layer_offset=1, n_layers_published=8, n_heads=4, n_kv_heads=2,
    head_dim=16, lightning_heads=4, lightning_head_dim=16, d_ff=96,
    kernel=4, stride=2, block=8, topk=5, init_blocks=1, window=16,
    dense_len=32, prefill_chunk=16, la_block=8, query_block=8, key_block=16,
    dtype=jnp.float32)


# ------------------------------------------------------------------ weights
def _normal(key, shape, dtype, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_params(cfg: MiniCPMSALAConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights: projections normal over the square root of their
    fan-in, the embedding normal over ``scale_emb`` (so that ``h0`` has unit
    scale beside the mixers' outputs), the norms small seeded numbers
    (``rms_norm`` multiplies by 1 + them) so that a test sees them."""
    d, dt = cfg.d_model, cfg.dtype
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embedding": _normal(keys[0], (cfg.vocab_size, d), dt,
                             1.0 / cfg.scale_emb),
        "lm_head": _normal(keys[1], (d, cfg.vocab_size), dt),
        "norm": _normal(keys[2], (d,), dt, 0.05),
        "layers": [],
    }
    for i, kind in enumerate(cfg.mixer_types):
        k = jax.random.split(keys[i + 3], 13)
        if kind == LIGHTNING:
            hd = cfg.lightning_head_dim
            qd = kvd = cfg.lightning_heads * hd
        else:
            hd = cfg.head_dim
            qd, kvd = cfg.n_heads * hd, cfg.n_kv_heads * hd
        layer = {
            "norm": _normal(k[0], (d,), dt, 0.05),
            "wq": _normal(k[1], (d, qd), dt),
            "wk": _normal(k[2], (d, kvd), dt),
            "wv": _normal(k[3], (d, kvd), dt),
            "q_norm": _normal(k[4], (hd,), dt, 0.05),
            "k_norm": _normal(k[5], (hd,), dt, 0.05),
            "wg": _normal(k[6], (d, qd), dt),
            "wo": _normal(k[7], (qd, d), dt),
            "mlp_norm": _normal(k[8], (d,), dt, 0.05),
            "w_gate": _normal(k[9], (d, cfg.d_ff), dt),
            "w_up": _normal(k[10], (d, cfg.d_ff), dt),
            "w_down": _normal(k[11], (cfg.d_ff, d), dt),
        }
        if kind == LIGHTNING:
            layer["o_norm"] = _normal(k[12], (qd,), dt, 0.05)
        params["layers"].append(layer)
    return params


# ------------------------------------------------------------------- mixers
def _slopes(cfg: MiniCPMSALAConfig, i: int):
    return linear_attn.decay_slopes(cfg.lightning_heads, cfg.layer_offset + i,
                                    cfg.n_layers_published)


def _qkv(layer, h, heads: int, kv_heads: int, head_dim: int, eps: float):
    """h [..., D] -> q [..., heads, d], k and v [..., kv_heads, d], q and k
    through their per-head RMSNorm."""
    lead = h.shape[:-1]
    q = mm(h, layer["wq"]).reshape(*lead, heads, head_dim)
    k = mm(h, layer["wk"]).reshape(*lead, kv_heads, head_dim)
    v = mm(h, layer["wv"]).reshape(*lead, kv_heads, head_dim)
    return (rms_norm(q, layer["q_norm"], eps),
            rms_norm(k, layer["k_norm"], eps), v)


def _lightning_qkv(layer, h, cos, sin, cfg: MiniCPMSALAConfig):
    """h [N, D], cos/sin rows of the N positions -> rotated q, k and v
    [N, H, d]."""
    q, k, v = _qkv(layer, h, cfg.lightning_heads, cfg.lightning_heads,
                   cfg.lightning_head_dim, cfg.norm_eps)
    return (apply_rope(q[None], cos, sin)[0], apply_rope(k[None], cos, sin)[0],
            v)


def _lightning_out(layer, h, o, cfg: MiniCPMSALAConfig):
    """o [N, H, d] float32 (unscaled ``q S``) -> the mixer's output [N, D]:
    the scale, the norm over all heads, the gate, the out-projection."""
    o = (o * cfg.lightning_head_dim ** -0.5).reshape(o.shape[0], -1)
    o = rms_norm(o, layer["o_norm"], cfg.norm_eps).astype(cfg.dtype)
    return mm(o * jax.nn.sigmoid(mm(h, layer["wg"])), layer["wo"])


def _sparse_out(layer, h, o):
    return mm(o * jax.nn.sigmoid(mm(h, layer["wg"])), layer["wo"])


def _sparse_prompt(q, kbuf, vbuf, cbuf, start, cfg: MiniCPMSALAConfig):
    """A chunk's queries over the keys so far, key block by key block.
    q [C, H, d] at positions ``start ..``; kbuf, vbuf [T, kvh, d] hold every
    key up to the chunk's end; cbuf [T / stride, kvh, d] the compressed keys.
    A query past ``dense_len`` attends only inside its chosen blocks: the
    mask is applied at dense cost. -> (o [C, H*d], the chosen blocks as a
    mask [C, kvh, T / block])."""
    C, H, d = q.shape
    kvh = kbuf.shape[1]
    rep = H // kvh
    Qb, Kb, blk = cfg.query_block, cfg.key_block, cfg.block
    n_blocks = kbuf.shape[0] // blk
    scale = d ** -0.5

    def queries(args):
        qs, t0 = args                               # [Qb, kvh, rep, d]
        t = t0 + jnp.arange(Qb)
        with jax.named_scope("sparse_select"):
            logits = jnp.einsum("qgrd,jgd->qgrj", qs, cbuf,
                                preferred_element_type=F32) * scale
            chosen = choose_block_mask(logits, t + 1, cfg)
            allowed = chosen | (t + 1 <= cfg.dense_len)[:, None, None]

        def keys(kb, carry):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(kbuf, kb * Kb, Kb)
            v_blk = jax.lax.dynamic_slice_in_dim(vbuf, kb * Kb, Kb)
            s = jnp.einsum("qgrd,kgd->grqk", qs, k_blk,
                           preferred_element_type=F32) * scale
            ok = jax.lax.dynamic_slice_in_dim(allowed, kb * (Kb // blk),
                                              Kb // blk, axis=2)
            ok = jnp.repeat(ok, blk, axis=2)                  # [Qb, kvh, Kb]
            ok = ok & ((kb * Kb + jnp.arange(Kb))[None, :]
                       <= t[:, None])[:, None, :]
            s = jnp.where(ok.transpose(1, 0, 2)[:, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            fix = jnp.exp(m - m_new)
            acc = acc * fix[..., None] + jnp.einsum(
                "grqk,kgd->grqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=F32)
            return m_new, l * fix + p.sum(axis=-1), acc

        with jax.named_scope("sparse_attn"):
            init = (jnp.full((kvh, rep, Qb), -1e30, F32),
                    jnp.zeros((kvh, rep, Qb), F32),
                    jnp.zeros((kvh, rep, Qb, d), F32))
            _, l, acc = jax.lax.fori_loop(0, (t0 + Qb + Kb - 1) // Kb, keys,
                                          init)
            o = (acc / l[..., None]).transpose(2, 0, 1, 3)
        return o.reshape(Qb, H * d).astype(q.dtype), chosen

    qs = q.reshape(C // Qb, Qb, kvh, rep, d)
    o, chosen = jax.lax.map(queries, (qs, start + jnp.arange(C // Qb) * Qb))
    return o.reshape(C, H * d), chosen.reshape(C, kvh, n_blocks)


def _chunk_ckeys(cext, kbuf, start, C: int, cfg: MiniCPMSALAConfig):
    """The compressed keys whose windows end inside the chunk ``[start,
    start + C)``, from the dense keys: key ``j`` is the mean of rows
    ``[stride j, stride j + kernel)``, so the first of them begins ``stride``
    rows before the chunk. ``cext`` is the buffer with one row in front (row
    ``j + 1`` holds key ``j``), so that the first chunk's window before
    position 0 has somewhere to land."""
    st = cfg.stride
    before = jax.lax.dynamic_slice_in_dim(kbuf, jnp.maximum(start - st, 0), st)
    rows = jnp.concatenate(
        [before, jax.lax.dynamic_slice_in_dim(kbuf, start, C)])
    half = rows.astype(F32).reshape(C // st + 1, st, *rows.shape[1:]).sum(1)
    ck = ((half[:-1] + half[1:]) / cfg.kernel).astype(cext.dtype)
    return jax.lax.dynamic_update_slice_in_dim(cext, ck, start // st, axis=0)


def _head(params, x, cfg: MiniCPMSALAConfig):
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    return mm(x / (cfg.d_model / cfg.dim_model_base), params["lm_head"])


def _embed(params, tokens, cfg: MiniCPMSALAConfig):
    return (params["embedding"][tokens].astype(F32)
            * cfg.scale_emb).astype(cfg.dtype)


def _run_chunk(params, tokens, start, n_valid, states, kv, ckeys, cfg):
    """One chunk of one sequence through every layer. tokens [C] at
    positions ``start ..``; states: per lightning layer [H, d, d]; kv: per
    sparse layer (k, v) [T, kvh, d]; ckeys: per sparse layer
    [T / stride + 1, kvh, d]. -> (hidden [C, D] before the final norm, the
    three carried on, the chosen blocks as masks
    [sparse layers, C, kvh, T / block])."""
    C = tokens.shape[0]
    a = cfg.residual_scale
    x = _embed(params, tokens, cfg)
    cos, sin = _rope_rows(start + jnp.arange(C), cfg.lightning_head_dim,
                          cfg.rope_theta)
    new_states, new_kv, new_ck, chosen = [], [], [], []
    li = ai = 0
    for i, (kind, layer) in enumerate(zip(cfg.mixer_types, params["layers"])):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == LIGHTNING:
            with jax.named_scope("linear_attn"):
                q, k, v = _lightning_qkv(layer, h, cos, sin, cfg)
                o, state = linear_attn.chunkwise(
                    q, k, v, _slopes(cfg, i), states[li], n_valid - start,
                    cfg.la_block)
                out = _lightning_out(layer, h, o, cfg)
            new_states.append(state)
            li += 1
        else:
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, cfg.norm_eps)
            with jax.named_scope("kv_write"):
                kbuf = jax.lax.dynamic_update_slice_in_dim(
                    kv[ai][0], k.astype(kv[ai][0].dtype), start, axis=0)
                vbuf = jax.lax.dynamic_update_slice_in_dim(
                    kv[ai][1], v.astype(kv[ai][1].dtype), start, axis=0)
            with jax.named_scope("ckey_write"):
                cext = _chunk_ckeys(ckeys[ai], kbuf, start, C, cfg)
            o, mask = _sparse_prompt(q, kbuf, vbuf, cext[1:], start, cfg)
            with jax.named_scope("attention"):
                out = _sparse_out(layer, h, o)
            new_kv.append((kbuf, vbuf))
            new_ck.append(cext)
            chosen.append(mask)
            ai += 1
        x = x + out * a
        with jax.named_scope("mlp"):
            x = x + _mlp_block(layer, x, cfg) * a
    return x, new_states, new_kv, new_ck, jnp.stack(chosen)


@functools.partial(jax.jit, static_argnames=("cfg",))
def forward(params, tokens, cfg: MiniCPMSALAConfig):
    """tokens [L] -> logits [L, V]: the whole forward pass of one sequence
    as ONE chunk (tests hold it against the plain reference)."""
    L = tokens.shape[0]
    big = math.lcm(cfg.query_block, cfg.key_block, cfg.la_block)
    T = -(-max(L, cfg.topk * cfg.block) // big) * big   # topk blocks exist
    one = dataclasses.replace(cfg, prefill_chunk=T)
    states, kv, ckeys = prefill_carry(one, T)
    x = _run_chunk(params, jnp.pad(tokens, (0, T - L)), jnp.int32(0),
                   jnp.int32(L), states, kv, ckeys, one)[0]
    return _head(params, x[:L], cfg)


# ----------------------------------------------- programs of ``PagedEngine``
def init_state(cfg: MiniCPMSALAConfig, slots: int):
    """Per-slot state of every lightning layer: [S, H, d, d] float32."""
    return [jnp.zeros((slots, cfg.lightning_heads, cfg.lightning_head_dim,
                       cfg.lightning_head_dim), F32)
            for _ in range(cfg.n_lightning_layers)]


@functools.partial(jax.jit, static_argnames=("cfg", "total"))
def prefill_carry(cfg: MiniCPMSALAConfig, total: int):
    """What a prefill carries from chunk to chunk, before the first: the
    lightning states, per sparse layer the dense (k, v) [total, kvh, d] and
    the compressed keys with their row in front."""
    kv = (total, cfg.n_kv_heads, cfg.head_dim)
    ck = (total // cfg.stride + 1, cfg.n_kv_heads, cfg.head_dim)
    return ([s[0] for s in init_state(cfg, 1)],
            [(jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype))
             for _ in range(cfg.n_sparse_layers)],
            [jnp.zeros(ck, cfg.dtype) for _ in range(cfg.n_sparse_layers)])


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnums=(4, 5, 6))
def _sala_prefill_chunk(params, tokens, start, n_valid, states, kv, ckeys,
                        cfg):
    """One chunk of one request's prefill; the carried state, K/V and
    compressed keys are donated. ``tokens`` [prefill_chunk] is padded past
    ``n_valid`` (a position of the whole prompt): the padded tail's K/V rows
    and compressed keys are stale, harmless as in ``engine._prefill_one``
    (decode overwrites each before a query can read it), and the lightning
    state stands still over it. -> (the logits at ``n_valid - 1`` if that
    row lies in this chunk, states, kv, ckeys, the chosen blocks as masks
    [sparse layers, chunk, kvh, total / block], which only a reference check
    reads)."""
    x, states, kv, ckeys, chosen = _run_chunk(
        params, tokens, start, n_valid, states, kv, ckeys, cfg)
    row = jnp.clip(n_valid - 1 - start, 0, tokens.shape[0] - 1)
    return _head(params, x[row], cfg), states, kv, ckeys, chosen


def prefill(params, prompt, total: int, cfg: MiniCPMSALAConfig,
            keep_chosen: bool = False):
    """Prefill one request chunk by chunk (``engine.prefill_in_chunks`` over
    ``_sala_prefill_chunk``). -> (next-token logits, per sparse layer the
    dense (k, v) and the compressed keys [total / stride + 1, ...] for the
    page scatter, per lightning layer the state at ``len(prompt)``; with
    ``keep_chosen`` also every position's chosen blocks
    [sparse layers, len(prompt), kvh, topk])."""
    first, (states, kv, ckeys), masks = prefill_in_chunks(
        _sala_prefill_chunk, params, prompt, cfg.prefill_chunk,
        prefill_carry(cfg, total), cfg, keep_chosen)
    out = (first, (kv, ckeys), states)
    if keep_chosen:     # a mask holds topk blocks: their indices, ascending
        out += (np.argsort(~masks, axis=-1, kind="stable")[..., :cfg.topk],)
    return out


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _scatter_sala(pools_k, pools_v, pools_c, kv, ckeys, page_ids):
    """One admission's K/V and compressed keys into its pages of every
    sparse layer's pools, in place: ``paged._scatter_pages``' one dispatch
    with the compressed keys beside (row ``j`` of a sequence on the page
    that holds position ``stride j``). page_ids int32[P]; an id past the
    pool is dropped."""
    new_k, new_v, new_c = [], [], []
    for pk, pv, pc, (kc, vc), ck in zip(pools_k, pools_v, pools_c, kv, ckeys):
        page = pk.shape[1]
        new_k.append(pk.at[page_ids].set(
            kc.reshape((-1, page) + kc.shape[1:]).astype(pk.dtype),
            mode="drop"))
        new_v.append(pv.at[page_ids].set(
            vc.reshape((-1, page) + vc.shape[1:]).astype(pv.dtype),
            mode="drop"))
        new_c.append(pc.at[page_ids].set(
            ck[1:].reshape((-1, pc.shape[1]) + ck.shape[1:]).astype(pc.dtype),
            mode="drop"))
    return new_k, new_v, new_c


def _decode_logits(params, pools_k, pools_v, pools_c, states, tables, toks,
                   lengths, cfg: MiniCPMSALAConfig, page: int):
    """The decode step up to its logits [S, V]; the new pools and states;
    int32[3]: pages the sparse layers read, pages the same slots hold (both
    per K/V head and layer) and the slots past ``dense_len``; the chosen
    blocks [sparse layers, S, kvh, topk]."""
    a = cfg.residual_scale
    x = _embed(params, toks, cfg)                                   # [S, D]
    active = lengths > 0
    page_idx = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]
    offs = lengths % page
    dense = active & (lengths + 1 <= cfg.dense_len)
    sparse = active & ~dense
    dense_pages = min(tables.shape[1], -(-cfg.dense_len // page))
    cos, sin = _rope_rows(lengths, cfg.lightning_head_dim, cfg.rope_theta)
    new_k, new_v, new_c, new_states, chosen = [], [], [], [], []
    li = ai = 0
    for i, (kind, layer) in enumerate(zip(cfg.mixer_types, params["layers"])):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == LIGHTNING:
            with jax.named_scope("linear_attn"):
                q, k, v = _lightning_qkv(layer, h, cos, sin, cfg)
                o, state = linear_attn.recurrent_step(
                    states[li], q, k, v, _slopes(cfg, i), active)
                out = _lightning_out(layer, h, o, cfg)
            new_states.append(state)
            li += 1
        else:
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h[:, None, :], cfg.n_heads,
                               cfg.n_kv_heads, cfg.head_dim, cfg.norm_eps)
            pool_k, pool_v, _, _ = write_kv(
                k, v, pools_k[ai], pools_v[ai], None, None, page_idx, offs,
                False)
            pool_c = write_ckeys(pools_c[ai], pool_k, tables, lengths, cfg)
            idx = select_pages(q, pool_c, tables, lengths, cfg)
            o = attend_chosen(q, pool_k, pool_v, tables, idx, lengths)
            o = jax.lax.cond(
                jnp.any(dense),
                lambda o, q, pk, pv: jnp.where(
                    dense[:, None, None],
                    attend_pages(q, pk, pv, None, None,
                                 tables[:, :dense_pages], lengths, False,
                                 cfg.dtype), o),
                lambda o, q, pk, pv: o, o, q, pool_k, pool_v)
            with jax.named_scope("attention"):
                out = _sparse_out(layer, h, o[:, 0])
            new_k.append(pool_k)
            new_v.append(pool_v)
            new_c.append(pool_c)
            chosen.append(idx)
            ai += 1
        x = x + out * a
        with jax.named_scope("mlp"):
            x = x + _mlp_block(layer, x, cfg) * a
    live = jnp.where(sparse, lengths // page + 1, 0)
    per = cfg.n_sparse_layers * cfg.n_kv_heads
    counts = jnp.stack([jnp.sum(jnp.minimum(live, cfg.topk)) * per,
                        jnp.sum(live) * per,
                        jnp.sum(sparse)]).astype(jnp.int32)
    return (_head(params, x, cfg), new_k, new_v, new_c, new_states, counts,
            jnp.stack(chosen))


@functools.partial(jax.jit, static_argnames=("cfg", "page"),
                   donate_argnums=(1, 2, 3, 4))
def _sala_step(params, pools_k, pools_v, pools_c, states, tables, toks,
               lengths, temps, top_ks, top_ps, keys, cfg, page):
    """One token for every slot: the recurrence on each slot's lightning
    states; on the sparse layers the K/V write, a compressed key where a
    window completes, the choice of pages and attention over the chosen
    ones, or over the whole (short) table for a slot of at most ``dense_len``
    positions, a branch the program takes only when such a slot is active.
    Pools, compressed-key pools and states are donated. A slot of length 0
    is inactive: it flows through (static shapes), its K/V row lands on
    page 0, its compressed-key write is dropped (``n = 1`` completes no
    window) and its state stands still.

    -> (int32[S + 3]: the tokens, then the pages the sparse layers read, the
    pages the same slots hold and the slots past ``dense_len``, so that one
    transfer fetches all; pools_k, pools_v, pools_c, states, keys; the
    chosen blocks [sparse layers, S, kvh, topk], which stay on the device
    unless a reference check asks for them; the tokens alone, int32[S], as
    the next step takes them: with the keys they let the engine dispatch
    that step before it has fetched this one's)."""
    (logits, new_k, new_v, new_c, new_states, counts,
     chosen) = _decode_logits(params, pools_k, pools_v, pools_c, states,
                              tables, toks, lengths, cfg, page)
    out, new_keys, picked = _sample(logits, temps, top_ks, top_ps, keys,
                                    lengths, counts)
    return out, new_k, new_v, new_c, new_states, new_keys, chosen, picked
