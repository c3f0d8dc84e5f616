"""Attention kernels: Pallas flash attention for TPU + reference jax path.

The compute-tier replacement for the reference's delegated GPU attention
(the reference has no attention kernels of its own; RLlib/Train lean on
torch). Layout convention throughout: [B, L, H, D].

Two implementations:
  * ``flash_attention`` — Pallas TPU kernel, blockwise online softmax, MXU
    matmuls, causal-block skipping. Falls back transparently off-TPU.
  * ``dense_attention`` — pure-jax reference (XLA already fuses this well on
    short sequences; also the correctness oracle in tests).

And, for serving, ``chunk_attention`` (end of file): a prompt chunk's queries
at a traced offset over a buffer of every position so far, causal with an
optional window, [N, H, D] over [T, Hk, D]: the one kernel here that takes an
offset and a window.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Reference attention. q,k,v: [B, L, H, D] (k/v may have fewer heads
    for GQA — repeated to match)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    Hq, Hk = q.shape[2], k.shape[2]
    if Hk != Hq:
        rep = Hq // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        mask = jnp.arange(Lq)[:, None] + (Lk - Lq) >= jnp.arange(Lk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        s = jnp.where(seg_mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


# ---------------------------------------------------------------- pallas

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block_k, causal,
                  seq_len):
    """One (batch*head, q-block) program: stream K/V blocks, online softmax.

    Grid: (BH, num_q_blocks). Refs are blocked:
      q_ref: [block_q, D], k_ref/v_ref: [L, D] (full K/V for this head),
      o_ref: [block_q, D].
    """
    from jax.experimental import pallas as pl

    block_q, d = q_ref.shape
    q_idx = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale

    q_offset = q_idx * block_q
    num_k_blocks = seq_len // block_k
    if causal:
        # Skip fully-masked K blocks: only iterate to the block containing
        # the last query row.
        hi = (q_offset + block_q + block_k - 1) // block_k
        hi = min(hi, num_k_blocks) if isinstance(hi, int) else hi
    else:
        hi = num_k_blocks

    def body(i, carry):
        o_acc, m_acc, l_acc = carry
        k_blk = k_ref[pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=-1)
        o_new = o_acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, hi, body, (o0, m0, l0))
    o_ref[...] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def _flash_attention_bhld(q, k, v, causal, scale, block_q, block_k,
                          interpret):
    """q,k,v: [BH, L, D] — flattened batch*heads."""
    from jax.experimental import pallas as pl

    BH, L, D = q.shape
    if L % block_q or L % block_k:
        raise ValueError(
            f"sequence length {L} must be divisible by block_q={block_q} "
            f"and block_k={block_k}")
    grid = (BH, L // block_q)
    kernel = functools.partial(_flash_kernel, scale=scale, block_k=block_k,
                               causal=causal, seq_len=L)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, L, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, L, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        interpret=interpret,
        name="ray_tpu_flash_fwd",    # the kernel's name in a profile
    )(q, k, v)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Flash attention, [B, L, H, D] layout, GQA-aware, differentiable.

    The platform alone picks the path. On TPU this is the Mosaic flash
    kernel (fwd + bwd, so it is safe under ``jax.grad``), and a shape the
    kernel does not take raises: a caller that wants dense attention there
    calls ``dense_attention``. Everywhere else it is ``dense_attention``.
    """
    B, L, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    if not _on_tpu():
        return dense_attention(q, k, v, causal=causal, scale=scale,
                               segment_ids=segment_ids)
    if segment_ids is not None or L % 128 or D < 64:
        raise ValueError(
            f"flash_attention on TPU takes L % 128 == 0, head_dim >= 64 "
            f"and no segment_ids; got q{tuple(q.shape)} k{tuple(k.shape)} "
            f"segment_ids={'set' if segment_ids is not None else None} "
            f"(use dense_attention for this shape)")
    return _tpu_flash(q, k, v, causal, scale)


def make_flash_attention(mesh):
    """``flash_attention`` for arrays sharded over ``mesh``.

    XLA cannot partition a Mosaic kernel ("wrap the call in a shard_map"),
    so a jitted step whose q/k/v are sharded refuses to lower on TPU with
    the bare function. This wraps it: each device runs the kernel on its
    own batch rows (split as ``parallel.mesh.batch_sharding`` splits a
    batch) and heads (over ``tp``; GQA groups stay aligned because q and
    kv heads split the same way). The sequence stays whole — shard that
    with ``parallel.ring_attention``. Pass the result as a model's
    ``attn_impl``.

    The result says which mesh it is bound to (``.mesh``), and that is how
    a model learns that its step is sharded: ``llama.forward_hidden`` then
    holds its residual stream to the layout this wrapper already assumes of
    q/k/v (batch over the data axes), and GSPMD gathers weights over
    ``fsdp`` and all-reduces the stream over ``tp`` where, unpinned, it
    split ``d_model`` over ``fsdp`` and paid ~12 all-to-alls a layer around
    the norms and this call (``parallel/sharding.py`` has the counts)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(("dp", "fsdp", "ep"), None, "tp", None)

    def attn(q, k, v, causal: bool = False, scale: Optional[float] = None):
        fn = functools.partial(flash_attention, causal=causal, scale=scale)
        return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    attn.mesh = mesh
    return attn


#: Blocks of the three Mosaic kernels behind ``_tpu_flash`` (forward, ``dkv``,
#: ``dq``). Mosaic's own defaults are 128 everywhere, and until PR 50 the
#: seven backward fields stood at that 128 because only the forward kernel had
#: ever been timed: at L 4096 a ``dkv`` call then walks a 32 x 32 grid of tiny
#: products and runs at a tenth of the MXU's peak (6.8 ms an execution inside
#: the train cell's step, where the blocks below take 1.1). PR 50 timed all
#: three kernels alone on one TPU v5e (each call closed by
#: ``block_until_ready``, median of 24), every block in 128..2048 that the
#: kernel's own checks and the chip's compiler take, at ``[1, 16, 4096, 128]``
#: (one chip's share of the train cell) and again at L 2048 and 8192 and at a
#: head of 64 (``[2, 32, 2048, 64]``, ``[1, 16, 4096, 64]``): PERF.md section
#: 6 has the tables. What they say: the forward kernel wants 1024 in every
#: field (best or within 2 % of the best at all five shapes; a 2048-wide
#: field loses 5-10 % or overruns the 16 MiB of scoped VMEM). The ``dkv``
#: kernel wants major blocks of 1024 (2048 wins 4-7 % at L 8192 and loses
#: 2-8 % at the four others); its minor blocks hardly matter from 256 up (2 %
#: between them, inside the timing's noise), so they are 512: minors of 1024
#: are refused for 0.9 MB of VMEM at a head of 256 and L 16384, and 512
#: leaves a factor of two. The ``dq`` kernel wants 1024 rows of queries
#: against K/V blocks of 512 (a 1024-wide ``block_k_major_dq`` costs 8-30 %:
#: the library broadcasts ``di`` to that many lanes in HBM). The head's width
#: moved no winner (64 against 128), so the caps depend on nothing.
_FWD_CAP = 1024          # block_q, block_k_major, block_k
_BWD_MAJOR_CAP = 1024    # block_q_major_dkv, block_k_major_dkv, block_q_dq
_BWD_MINOR_CAP = 512     # block_q_dkv, block_k_dkv, block_k_major_dq,
#                          block_k_dq

#: Forward blocks (block_q, block_k_major, block_k) per sequence length from
#: an on-chip record, where one exists: records/flash_autotune.json, which
#: benchmarks/tpu_kernels.py writes (forward kernel only) and no run has yet.
#: Loaded once; the backward fields come from the caps above either way.
_AUTOTUNE_CACHE: Optional[dict] = None
import os as _os
_AUTOTUNE_PATH = _os.path.join(_os.path.dirname(_os.path.dirname(
    _os.path.dirname(_os.path.abspath(__file__)))),
    "records", "flash_autotune.json")


def _autotune_table() -> dict:
    global _AUTOTUNE_CACHE
    if _AUTOTUNE_CACHE is None:
        import json
        table = {}
        try:
            with open(_AUTOTUNE_PATH) as f:
                rec = json.load(f)
                # Tuned blocks are only valid at the head_dim they were
                # swept at (default 128, the sweep geometry).
                table["head_dim"] = int(rec.get("head_dim", 128))
                for row in rec.get("best", []):
                    table[int(row["seq"])] = (int(row["block_q"]),
                                              int(row["block_k_major"]),
                                              int(row["block_k"]))
        except FileNotFoundError:
            pass  # no on-chip sweep recorded: the caps apply
        _AUTOTUNE_CACHE = table
    return _AUTOTUNE_CACHE


def _block(cap: int, seq_len: int) -> int:
    """The largest of 128, 256, 512, ... up to ``cap`` that divides
    ``seq_len``: the blocks the sweep timed, and never one that does not
    tile L (L 640 gets 128 and L 1536 gets 512; the kernel refuses a block
    that leaves a remainder, at the caller's jit). A length that no multiple
    of 128 divides is one block."""
    b = cap
    while b >= 128:
        if seq_len % b == 0:
            return b
        b //= 2
    return seq_len


def flash_block_sizes(seq_len: int, head_dim: int = 128):
    """BlockSizes for the three Mosaic kernels: every field is the largest
    power-of-two multiple of 128 under its kernel's cap that divides
    ``seq_len`` (the caps and the on-chip sweep behind them are above
    ``_FWD_CAP``). Where an on-chip record holds forward blocks for this
    (L, head_dim) and they tile L, the forward fields are the record's; the
    backward fields are the rule's in both branches."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    table = _autotune_table()
    tuned = table.get(seq_len) if table.get("head_dim") == head_dim else None
    if tuned is not None and all(seq_len % b == 0 for b in tuned):
        bq, bkm, bk = tuned
    else:
        bq = bkm = bk = _block(_FWD_CAP, seq_len)
    major = _block(_BWD_MAJOR_CAP, seq_len)
    minor = _block(_BWD_MINOR_CAP, seq_len)
    return BlockSizes(
        block_q=bq, block_k_major=bkm, block_k=bk, block_b=1,
        block_q_major_dkv=major, block_k_major_dkv=major,
        block_k_dkv=minor, block_q_dkv=minor,
        block_k_major_dq=minor, block_k_dq=minor, block_q_dq=major,
    )


def _tpu_flash(q, k, v, causal: bool, scale: float) -> jax.Array:
    """Mosaic TPU flash attention ([B, H, L, D] layout internally)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as mosaic_flash,
    )

    B, L, H, D = q.shape
    Hk = k.shape[2]
    if Hk != H:
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot = mosaic_flash(qt, kt, vt, causal=causal, sm_scale=scale,
                      block_sizes=flash_block_sizes(L, D))
    return ot.transpose(0, 2, 1, 3)


def _flash_stats_kernel(q_ref, k_ref, v_ref, vis_ref, o_ref, m_ref, l_ref,
                        *, scale, block_k, seq_len_k):
    """Flash block with ONLINE-SOFTMAX STATS OUT — the composable unit of
    ring attention (ring steps merge (o, m, l) across devices; a
    normalizing kernel cannot compose). Per program: q [block_q, D],
    full K/V [Lk, D] for this head, vis [block_q, 1] = per-row count of
    visible key columns (global causal masking precomputed by the
    caller — keeps traced ring offsets out of kernel scalars).
    Outputs: o UNnormalized [block_q, D], m/l stats [block_q, 1].

    Masked entries use the finite NEG_INF: a fully-masked row yields
    m = NEG_INF and junk o/l, which the ring merge then multiplies by
    beta = exp(NEG_INF - m_new) = 0 — same contract as the dense
    ring _block_attn (parallel/ring_attention.py)."""
    from jax.experimental import pallas as pl

    block_q, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    vis = vis_ref[...]  # [block_q, 1] int32

    def body(i, carry):
        o_acc, m_acc, l_acc = carry
        k_blk = k_ref[pl.dslice(i * block_k, block_k), :].astype(
            jnp.float32)
        v_blk = v_ref[pl.dslice(i * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cols = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols < vis, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=-1)
        o_new = o_acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, seq_len_k // block_k, body,
                                (o0, m0, l0))
    o_ref[...] = o
    m_ref[...] = m[:, None]
    l_ref[...] = l[:, None]


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def _flash_stats_bhld(q, k, v, visible, scale, block_q, block_k,
                      interpret):
    """q,k,v: [BH, L, D]; visible: [BH, Lq, 1] int32 per-row visible-col
    counts. Returns (o [BH,Lq,D] unnormalized f32, m [BH,Lq] f32,
    l [BH,Lq] f32)."""
    from jax.experimental import pallas as pl

    BH, Lq, D = q.shape
    Lk = k.shape[1]
    if Lq % block_q or Lk % block_k:
        raise ValueError(f"L ({Lq},{Lk}) must tile ({block_q},{block_k})")
    grid = (BH, Lq // block_q)
    kernel = functools.partial(_flash_stats_kernel, scale=scale,
                               block_k=block_k, seq_len_k=Lk)
    o, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="ray_tpu_flash_stats",
    )(q, k, v, visible)
    return o, m[..., 0], l[..., 0]


def flash_attention_stats(q, k, v, visible, scale: Optional[float] = None,
                          block_q: int = 512, block_k: int = 512,
                          interpret: Optional[bool] = None):
    """Ring-composable flash block: [B, L, H, D] in, unnormalized
    ``(o [B,Lq,H,D] f32, m [B,H,Lq] f32, l [B,H,Lq] f32)`` out.

    The stats kernel itself defines no VJP; gradients through the ring
    flash path come from the RING-level custom VJP in
    ``parallel/ring_attention.py`` (standard ring backward from the
    final merged stats), which is what makes ``block_impl="flash"``
    trainable. VMEM residency: each program holds this head's full K/V
    ([Lk, D] f32 each) plus block-sized tiles, which bounds practical
    shard lengths to Lk*D*8B within the per-core VMEM budget (e.g.
    Lk=16k at D=128 is ~16 MiB); gridding K/V into block_k_major tiles
    (as Mosaic's kernel does) is the lift that removes the bound.

    ``visible``: [B, H, Lq] int32 — per-row count of visible key columns
    (Lk for unmasked rows, 0 for fully-masked rows; ring callers derive
    it from global q/k offsets, which keeps traced offsets out of the
    kernel). K/V may carry fewer heads (GQA) — repeated here.
    """
    B, Lq, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    Hk = k.shape[2]
    if Hk != H:
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
    Lk = k.shape[1]
    if interpret is None:
        interpret = not _on_tpu()

    def pick(limit, L):
        # Largest 128-multiple block <= limit that DIVIDES L (so any
        # L % 128 == 0 tiles — 768 would reject a blind min(512, L)).
        for b in (limit, 512, 384, 256, 128):
            if b <= limit and L % b == 0:
                return b
        return min(limit, L)

    bq = pick(min(block_q, Lq), Lq)
    bk = pick(min(block_k, Lk), Lk)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    visf = visible.reshape(B * H, Lq, 1).astype(jnp.int32)
    o, m, l = _flash_stats_bhld(qf, kf, vf, visf, scale, bq, bk, interpret)
    o = o.reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
    return o, m.reshape(B, H, Lq), l.reshape(B, H, Lq)


def pallas_flash_reference(q, k, v, causal: bool = False,
                           scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """This repo's own Pallas kernel (fwd only), runnable in interpret mode
    on CPU — kept as the in-tree kernel exemplar and correctness test
    subject; production paths use ``flash_attention``."""
    B, L, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    Hk = k.shape[2]
    if Hk != H:
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    of = _flash_attention_bhld(qf, kf, vf, causal, scale,
                               min(block_q, L), min(block_k, L), interpret)
    return of.reshape(B, H, L, D).transpose(0, 2, 1, 3)


# ------------------------------------------- a prompt chunk over its prefix
def _chunk_tile_range(row0, tq: int, tk: int, window: int, tiles: int):
    """(first, last) key tile that holds a key visible to one of the ``tq``
    queries at positions ``row0 ..``."""
    first = jnp.maximum(row0 - window + 1, 0) // tk if window else 0
    return first, jnp.minimum((row0 + tq - 1) // tk, tiles - 1)


def _chunk_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, qs_ref, m_ref, l_ref,
                  acc_ref, *, scale, window, tiles):
    """One (K/V head, query tile, key step) of ``chunk_attention``. q_ref
    [tq, rep * d]: the tile's rows of the ``rep`` heads that share the K/V
    head, side by side; k_ref, v_ref [tk, d]: the key tile the index map
    picked (the tile range's ``first + step``, held at its last once past
    it); o_ref as q_ref. The heads are stacked ``heads`` to a product: qs_ref
    [rep / heads, heads * tq, d] holds the queries so, acc_ref the weighted
    values in float32, m_ref and l_ref [.., heads * tq, 1] the running
    maximum and sum, all across the key steps."""
    from jax.experimental import pallas as pl

    tq, (tk, d) = q_ref.shape[0], k_ref.shape
    products, rows = qs_ref.shape[:2]
    heads = rows // tq
    i, step = pl.program_id(1), pl.program_id(2)
    row0 = start_ref[0] + i * tq
    first, last = _chunk_tile_range(row0, tq, tk, window, tiles)
    tile = first + step

    @pl.when(step == 0)
    def _():
        for r in range(products * heads):
            qs_ref[r // heads, pl.ds((r % heads) * tq, tq), :] = \
                q_ref[:, r * d:(r + 1) * d]
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(masked: bool):
        k, v = k_ref[...], v_ref[...]

        def product(c, _):
            s = jax.lax.dot_general(
                qs_ref[c], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                t = row0 + jax.lax.broadcasted_iota(
                    jnp.int32, (heads, tq, tk), 1).reshape(rows, tk)
                j = tile * tk + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, tk), 1)
                ok = j <= t
                if window:
                    ok = ok & (t - j < window)
                s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[c]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # a row no key of this tile is visible to: exp(0) of every key
            # if none was before (a later tile's alpha of 0 wipes it: its own
            # position is visible to every row), 0 if one was
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[c] = alpha * l_ref[c] + p.sum(axis=-1, keepdims=True)
            acc_ref[c] = alpha * acc_ref[c] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[c] = m_new

        jax.lax.fori_loop(0, products, product, None)

    # the diagonal crosses the tile, or the window's edge does
    crossed = (tile + 1) * tk - 1 > row0
    if window:
        crossed = crossed | (row0 + tq - 1 - tile * tk >= window)
    live = tile <= last
    pl.when(live & crossed)(lambda: visit(True))
    pl.when(live & jnp.logical_not(crossed))(lambda: visit(False))

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        for r in range(products * heads):
            at = pl.ds((r % heads) * tq, tq)
            o = acc_ref[r // heads, at, :] / l_ref[r // heads, at, :]
            o_ref[:, r * d:(r + 1) * d] = o.astype(o_ref.dtype)


#: Tiles of ``chunk_attention``, timed alone on one TPU v5e at Command A+'s
#: and granite's shapes (a chunk of 2048 rows of 128 / 32 heads of 128 over 8
#: K/V heads, behind 0 to 24 576 positions; PR 59, PERF.md section 5). Keys a
#: tile: 1024 everywhere (a full layer behind 24 576 positions 26.4 ms,
#: where 512 keys take 41-50, 256 take 71-97 and 2048 take 28-29: what a key
#: step costs beside its two products, the accumulator read, scaled and
#: written back, does not shrink with the tile). Rows of queries a tile and
#: heads a product hardly matter from 512 stacked rows up (26.1-27.4 ms over
#: 128-512 rows x 512-2048 stacked), so they are the most that fit the VMEM
#: every kernel gets unasked.
_CHUNK_Q_CAP = 256          # rows of queries a tile
_CHUNK_K_CAP = 1024         # keys a tile
_CHUNK_ROWS_CAP = 1024      # rows a product: whole heads of a group, stacked
#: What a call may hold in VMEM: the compiler's own limit for a kernel's
#: scope. A kernel that asks for more (``vmem_limit_bytes``) is given it out
#: of what XLA keeps resident for the fusions AROUND it: with 64 MiB asked,
#: granite's chunk program lost the prefetches of its nine Mamba layers'
#: in-projections and ran 6.7 ms longer for 0.6 ms of attention saved.
_CHUNK_VMEM_BYTES = 16 * 2 ** 20


def chunk_attention_tiles(N: int, T: int, rep: int, d: int, itemsize: int):
    """(query rows a tile, keys a tile, heads a product) of
    ``chunk_attention`` from the shapes alone: the keys' tile the largest
    under its cap that tiles ``T``, then the most query rows and the most
    heads a product whose call fits ``_CHUNK_VMEM_BYTES`` by the count below
    (Mosaic accepted and refused as it says at every tile tried: AOT, PR 59);
    None where ``N`` queries over ``T`` buffered rows are not whole tiles."""
    if N % 128 or T % 128:
        return None
    tk = _block(_CHUNK_K_CAP, T)
    tq = _block(_CHUNK_Q_CAP, N)
    while tq >= 128:
        group = rep * tq    # rows of every head of a K/V head
        held = (group * d * (5 * itemsize + 4)      # q and o twice, qs; acc
                + 2 * group * 128 * 4               # maximum and sum, a lane
                + 4 * tk * d * itemsize)            # K and V, twice
        fits = [h for h in range(1, rep + 1) if rep % h == 0
                and h * tq <= _CHUNK_ROWS_CAP
                and held + h * tq * tk * (4 + itemsize) <= _CHUNK_VMEM_BYTES]
        if fits:
            return tq, tk, max(fits)
        tq //= 2
    return None


@functools.partial(jax.jit, static_argnames=("window", "tiles", "interpret"))
def _chunk_attention(q, buf_k, buf_v, start, window, tiles, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, d = q.shape
    T, kvh, _ = buf_k.shape
    rep = H // kvh
    tq, tk, heads = tiles
    if N % tq or T % tk or rep % heads:
        raise ValueError(f"tiles {tiles} do not divide q{q.shape} over "
                         f"{buf_k.shape}")
    n_tiles = T // tk
    # the key tiles one query tile can need: a window layer's span of
    # window + tq - 1 keys, a full layer's whole buffer
    steps = min(n_tiles, (window + tq - 3) // tk + 2) if window else n_tiles

    def kv_map(g, i, step, start_ref):
        first, last = _chunk_tile_range(start_ref[0] + i * tq, tq, tk,
                                        window, n_tiles)
        return jnp.minimum(first + step, last), g

    rows = heads * tq
    kernel = functools.partial(_chunk_kernel, scale=d ** -0.5, window=window,
                               tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kvh, N // tq, steps),
            in_specs=[pl.BlockSpec((tq, rep * d), lambda g, i, s, _: (i, g)),
                      pl.BlockSpec((tk, d), kv_map),
                      pl.BlockSpec((tk, d), kv_map)],
            out_specs=pl.BlockSpec((tq, rep * d), lambda g, i, s, _: (i, g)),
            scratch_shapes=[
                pltpu.VMEM((rep // heads, rows, d), q.dtype),
                pltpu.VMEM((rep // heads, rows, 1), jnp.float32),
                pltpu.VMEM((rep // heads, rows, 1), jnp.float32),
                pltpu.VMEM((rep // heads, rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, H * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ray_tpu_chunk_attention",     # the kernel's name in a profile
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q.reshape(N, H * d),
      buf_k.reshape(T, kvh * d), buf_v.reshape(T, kvh * d))


def chunk_attention_form(N: int, T: int, n_heads: int, n_kv_heads: int,
                         head_dim: int, dtype) -> str:
    """The implementation of a prompt chunk's attention over its prefix
    (``models/cohere2_moe._prompt_attention``), from what the call sees of
    its input and nothing else: ``"kernel"``, ``chunk_attention``, where a
    head is whole lanes, the ``N`` queries and the ``T`` buffered rows are
    whole tiles, the dtype is floating and the platform is a TPU
    (``_on_tpu``, the one function a test replaces); ``"loop"``, the XLA loop
    over key blocks, everywhere else: every CPU run, a head of 64."""
    takes = head_dim % 128 == 0 and jnp.issubdtype(dtype, jnp.floating)
    return "kernel" if (takes and chunk_attention_tiles(
        N, T, n_heads // n_kv_heads, head_dim, jnp.dtype(dtype).itemsize)
        and _on_tpu()) else "loop"


def chunk_attention(q, buf_k, buf_v, start, window: int = 0,
                    interpret: bool = False) -> jax.Array:
    """A chunk's queries over a buffer of every position so far, as a Pallas
    flash kernel. q [N, H, d] at positions ``start ..`` (``start`` traced: one
    program serves every chunk); buf_k, buf_v [T, kvh, d] hold every
    position's row up to the chunk's end. Key ``j`` is visible to query ``i``
    iff ``j <= i`` and, with a ``window``, ``i - j < window``. -> o [N, H *
    d] in q's dtype.

    The grid is (K/V head, query tile, key step). The ``H / kvh`` query heads
    of a K/V head share each key tile: q is read as ``[N, H * d]`` and the
    buffers as ``[T, kvh * d]``, a block one K/V head's lanes, so K and V are
    read once a group. ``start`` comes by scalar prefetch: a query tile's
    first and last key tile follow from it, the tile's rows and ``window``;
    the key steps walk that range and then stay on its last tile (a block
    that is named again is not copied again) with their arithmetic skipped,
    so the masked work of the loop, whose every row visits every key block up
    to the chunk's end, is not done. The mask is applied on the tiles the
    diagonal or the window's edge crosses and on no other. Scores are float32
    from operands in their own dtype, times ``d ** -0.5``; the running
    maximum, sum and weighted values stay in VMEM in float32 across the key
    steps; the weights are cast to V's dtype for the second product and
    divided once, exactly: the loop's arithmetic, in tiles
    (``chunk_attention_tiles``)."""
    N, H, d = q.shape
    tiles = chunk_attention_tiles(N, buf_k.shape[0], H // buf_k.shape[1], d,
                                  q.dtype.itemsize)
    if tiles is None:
        raise ValueError(f"chunk_attention takes whole tiles of 128 rows; "
                         f"got q{tuple(q.shape)} over {tuple(buf_k.shape)}")
    return _chunk_attention(q, buf_k, buf_v, start, window, tiles, interpret)
