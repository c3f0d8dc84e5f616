"""Decode reads of a page pool as Pallas TPU kernels: each slot's LIVE pages
walked once, where they lie.

A decode step's read of a paged cache in plain XLA is a gather: the pages of
a block of every slot's context are copied out of the pool into an array in
HBM, which is written, and read again by each contraction and each pass of
the softmax. A kernel here takes the pool itself (in HBM, as the step's
donated argument holds it), the tables and the lengths as scalars, and copies
a slot's live pages into VMEM, the next turn's copies in flight while this
one is contracted; the scores never leave VMEM, and a page past a slot's last
live block is never touched.

``kv_decode`` is the read of a K pool and a V pool kept as rows of whole
lanes (``models/paged_ops.lane_pool_shape``), which
``paged_ops.paged_attention`` picks by what it can see of its pools. The
absorbed read of a latent pool (``paged_ops.attend_latent``: live pages in
blocks, plain XLA, since PR 61) is to join it here (ROADMAP S1 e, way b).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF


def kv_block_pages(P: int, page: int) -> int:
    """The table columns a block of ``kv_decode`` holds, from the shapes
    alone (a table's columns, a page's positions): the ONE rule of the kernel
    and of the engine's count of what a step reads (``models/paged.py``
    ``_read_block``).

    A block is what a slot's read is rounded up to: its pages are copied
    together and waited for as one, and the blocks past a slot's last live
    one are not copied at all. A page's copy costs its bytes' time whatever
    the block (~42 ns for 16 KB of K and of V on a v5e), so a block is
    narrow, for the rounding's sake: 128 positions, at which 64 slots of
    0.3k-5.3k positions read 1.03 times what is live (1.07 at 256, 1.15 at
    512; 64 positions cost a fifth more time for 1.02: chip, PR 55, PERF.md
    section 5), and never wider than the table."""
    block = 1
    while 2 * block * page <= 128 and 2 * block <= P:
        block *= 2
    return block


#: Blocks a turn of ``kv_decode``'s loop contracts at once. A turn costs
#: ~0.5 us whatever it holds (two products and a softmax that wait for each
#: other), so the kernel's time at narrow turns is their count: 116 000
#: positions took 0.80 / 0.47 / 0.44 / 0.43 ms at 1 / 4 / 8 / 16 blocks a
#: turn, for 0.29 ms of bytes (chip, PR 55: PERF.md section 5).
KV_TURN = 4
#: Turns in VMEM at once: one contracted, the next one's pages in flight. A
#: deeper ring bought nothing (2 to 32 buffers within 1 % of each other).
KV_DEPTH = 2


def _kv_kernel(tables_ref, lengths_ref, slot_ref, turn_ref, total_ref,
               q_ref, k_own_ref, v_own_ref, pool_k_ref, pool_v_ref, o_ref,
               kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
               *, scale, B, T, P, depth, kvh):
    """Every live turn of every slot, in the list's order (slot by slot, a
    slot's turns in order: ``slot_ref`` / ``turn_ref``, ``total_ref[0]`` of
    them). A turn is ``T`` blocks of ``B`` table columns, of which the LIVE
    ones are copied, page by page, into one buffer of a ring of ``depth``;
    the copies of the ``depth - 1`` turns after a turn (the slot's next, or
    the next slots' first) have started before it is waited for. The rows
    of a block that was not copied are what an earlier turn left there and
    are masked by position (the values' buffers start from zeros: a weight
    of 0 times whatever VMEM held is not 0 if that was no number)."""
    total = total_ref[0]
    H, W = q_ref.shape[1:]
    R = pool_k_ref.shape[1]             # positions a page
    block, rows = B * R, T * B * R
    d = W // kvh
    rep = H // kvh

    def live_blocks(t, each):
        """``each(u)`` for the blocks of turn ``t`` that hold positions
        before its slot's own."""
        left = lengths_ref[slot_ref[t]] - turn_ref[t] * rows
        for u in range(T):
            pl.when(u * block < left)(functools.partial(each, u))

    def start(t):
        which = jax.lax.rem(t, depth)
        base = slot_ref[t] * P + turn_ref[t] * (T * B)

        def pages(u):
            for j in range(u * B, (u + 1) * B):
                pg = tables_ref[base + j]
                at = pl.ds(j * R, R)
                pltpu.make_async_copy(pool_k_ref.at[pg], kbuf.at[which, at],
                                      sem.at[0, which]).start()
                pltpu.make_async_copy(pool_v_ref.at[pg], vbuf.at[which, at],
                                      sem.at[1, which]).start()

        live_blocks(t, pages)

    def wait(t, buf, kv):
        which = jax.lax.rem(t, depth)

        def one(u):     # one wait for a block's B copies: a semaphore
            at = buf.at[which, pl.ds(u * block, block)]     # counts bytes
            pltpu.make_async_copy(at, at, sem.at[kv, which]).wait()

        live_blocks(t, one)

    vbuf[...] = jnp.zeros_like(vbuf)
    for t in range(depth - 1):
        pl.when(t < total)(functools.partial(start, t))

    col = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)

    def body(t, _):
        pl.when(t + depth - 1 < total)(lambda: start(t + depth - 1))
        which = jax.lax.rem(t, depth)
        s, b = slot_ref[t], turn_ref[t]
        length = lengths_ref[s]
        q = q_ref[s]                                            # [H, W]

        @pl.when(b == 0)
        def _():    # the query's own row: one key of weight 1 to start from
            m_ref[...] = jnp.sum(
                q.astype(jnp.float32) * k_own_ref[s].astype(jnp.float32),
                axis=-1, keepdims=True) * scale
            l_ref[...] = jnp.ones_like(l_ref)
            acc_ref[...] = jnp.broadcast_to(
                v_own_ref[s].astype(jnp.float32), acc_ref.shape)

        wait(t, kbuf, 0)
        sc = jax.lax.dot_general(q, kbuf[which], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        sc = jnp.where(col < length - b * rows, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)         # a masked key: exp(-1e30 - m) = 0
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        wait(t, vbuf, 1)
        v = vbuf[which]
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when((b + 1) * rows >= length)
        def _():    # the slot's last turn: each head keeps its own lanes
            o = acc_ref[...] / l_ref[...]
            for g in range(kvh):
                o_ref[s, pl.ds(g * rep, rep), :] = o[g * rep:(g + 1) * rep,
                                                     g * d:(g + 1) * d]

    jax.lax.fori_loop(0, total, body, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_decode(q, k_own, v_own, pool_k, pool_v, tables, lengths,
              interpret=False):
    """Each slot's query over its live pages of a K and a V pool kept as rows
    of a position's heads side by side, each live page read once.

    q [S, H, d]; k_own, v_own [S, kvh, d]: each slot's row at its query's own
    position ``lengths``, as the pool holds it (the softmax starts from it);
    pool_k, pool_v ``[num_pages, page, kvh * d]``; tables int32 [S, P];
    lengths int32 [S]: the positions a slot reads from its pages are ``0 ..
    lengths - 1``, in ``ceil(lengths / block)`` blocks of ``kv_block_pages``
    table columns, so a slot of length 0 (an idle one) reads no page.

    The pages are contracted AS STORED, ``kvh * d`` wide: the queries are
    laid block-diagonally over the K/V heads (head ``h``'s values in the
    lanes of K/V head ``h // rep``, zeros elsewhere), so the scores of a
    turn's ``KV_TURN`` blocks are one product ``[H, kvh d] x [rows, kvh
    d]^T`` and their weighted values one ``[H, rows] x [rows, kvh d]``, of
    which each head keeps its own ``d`` lanes at the end: ``kvh`` times the
    arithmetic of the grouped form, and no array of part of a lane anywhere.
    Scores are float32 from operands in their own dtype (the queries' is the
    pools'), scaled by ``d ** -0.5``; a head's maximum, sum and weighted
    values are carried in float32 from turn to turn; the
    weights are cast to the pool's dtype for the second product and divided
    once, exactly. -> o [S, H, d] float32:
    ``paged_ops.attend_pages_blocked(..., own=(k_own, v_own))``'s result."""
    S, H, d = q.shape
    kvh = k_own.shape[1]
    rep = H // kvh
    R, W = pool_k.shape[1:]
    B = kv_block_pages(tables.shape[1], R)
    T = min(KV_TURN, -(-tables.shape[1] // B))
    P = -(-tables.shape[1] // (T * B)) * T * B
    tables = jnp.pad(tables, ((0, 0), (0, P - tables.shape[1])))
    rows = T * B * R                    # positions a turn
    # queries block-diagonal over the K/V heads: [S, H, kvh * d]
    own_head = (jnp.arange(kvh)[:, None, None]
                == jnp.arange(kvh)[None, None, :])          # [kvh, 1, kvh]
    q_bd = jnp.where(own_head[None, ..., None],
                     q.reshape(S, kvh, rep, 1, d), 0).reshape(S, H, W)
    # every live turn, slot by slot: an item's slot and its turn of it
    need = (lengths + rows - 1) // rows
    ends = jnp.cumsum(need)
    item = jnp.arange(S * (P // (T * B)))
    slot = jnp.minimum(jnp.searchsorted(ends, item, side="right",
                                        method="compare_all"), S - 1)
    turn = item - (ends - need)[slot]
    kernel = functools.partial(_kv_kernel, scale=d ** -0.5, B=B, T=T, P=P,
                               depth=KV_DEPTH, kvh=kvh)
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[whole(S, H, W), whole(S, 1, W), whole(S, 1, W),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole(S, H, d),
            scratch_shapes=[pltpu.VMEM((KV_DEPTH, rows, W), pool_k.dtype),
                            pltpu.VMEM((KV_DEPTH, rows, W), pool_v.dtype),
                            pltpu.SemaphoreType.DMA((2, KV_DEPTH)),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, W), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ray_tpu_kv_decode",       # the kernel's name in a profile
    )(tables.reshape(-1), lengths, slot.astype(jnp.int32),
      turn.astype(jnp.int32), ends[-1:].astype(jnp.int32),
      q_bd, k_own.reshape(S, 1, W), v_own.reshape(S, 1, W), pool_k, pool_v)
    # an idle slot stood in no turn: its own row alone, weight 1
    idle = jnp.repeat(v_own.astype(jnp.float32), rep, axis=1)
    return jnp.where((lengths > 0)[:, None, None], o, idle)
