"""Device arithmetic of the page pool that every family's step shares:
one K/V row of every slot written at its (page, offset), and each slot's
query attended over its LIVE pages (``paged_attention``: the row it writes,
then the positions before it in blocks of table columns through
``attend_pages_blocked``, each slot's own blocks and no others, an online
softmax over them; no array as wide as the table, and an idle slot reads
nothing; on a TPU a pool kept as ``lane_pool_shape`` is read by the Pallas
kernel ``ops.paged_decode.kv_decode`` instead, which gathers nothing at
all). ``attend_pages`` is the same read as ONE gather of every slot's
whole table: the dense branch of a block-sparse layer takes it over the
table's first columns, and the tests hold the blocked read to it. Beside
it the read path of a block-sparse layer, whose block is a page: a
compressed-key pool written as windows of keys complete
(``write_ckeys``: the indexer's cache), the choice of blocks from it
(``choose_blocks``, shared with the prefill of such a family) and attention
over the chosen pages only (``attend_chosen``). And the read path of a
latent (MLA) layer, whose cache is ONE pool of one row a position (the
compressed latent and the one rotary key all heads share), two positions
side by side: ``write_latent`` and ``attend_latent``, the absorbed form,
which never expands a cached row to per-head keys and values and walks each
slot's live pages in blocks, as the K/V read does. And a window
layer's K/V as a per-slot RING of the window's width (``write_ring``,
``ring_rows``, ``attend_ring``: no table, no gather, no allocator), beside
which such a model's full layers read their page pool through
``attend_pages_blocked`` too."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops import attention
from ..ops.paged_decode import kv_block_pages, kv_decode


def _quant_kv(vec, qmax=127.0):
    """Per-head-vector symmetric int8: vec [..., d] -> (int8, scale).
    ``qmax`` is always 127; a caller may pass it as a traced operand.
    Under jit XLA divides by the constant as a multiplication with its
    reciprocal, by an operand as a division (what eager code does), and
    the two scales can differ in their last bit."""
    amax = jnp.max(jnp.abs(vec.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(vec.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale[..., 0].astype(jnp.float32)


def _lane_rows(pool):
    """A K/V pool [num_pages, page, kvh, d] as a step's scatter and gather
    index it. Where a head is narrower than the chip's 128 lanes (64 at
    ``LLAMA3_1B``'s widths) that is with a position's heads side by side,
    ``[num_pages, page, kvh * d]``: the chip keeps such a pool with the
    PAGES as its minor axis, since a minor axis of half a lane would be
    padded to twice the size, and must turn it whole into a layout a page
    can be taken from, and back, in every step. Over the 4-D shape the
    turned copies are padded, one for the scatter and one for the gather,
    and with the read in loops all of them stood to the program's end (AOT,
    PR 44: 3.19 GB of temporaries beside a 1.07 GB cache at depth 16; the
    table-wide read's 0.37); a row of ``kvh * d`` fills whole lanes, one
    turned copy serves both and is turned back when its layer's read ends
    (0.08 GB). At a head of 128 the pool is indexed as it is: it lies page
    by page already and no step copies it. A pool that IS ``[num_pages, page,
    kvh * d]`` (``lane_pool_shape``: a family whose engine keeps a narrow
    head's positions as whole lanes from the start) is indexed as it is too:
    nothing is turned, in no step."""
    if pool.ndim == 3:
        return pool
    pages, page, kvh, d = pool.shape
    return pool.reshape(pages, page, kvh * d) if d % 128 else pool


def lane_pool_shape(num_pages: int, page: int, kvh: int, d: int):
    """The shape of a K or V pool whose head is narrower than a lane, kept
    with a position's heads side by side: ``[num_pages, page, kvh * d]`` (8
    heads of 64 are four whole lanes; a toy's row is what it is).
    ``_lane_rows`` turns a 4-D pool of such heads into this view twice a
    step, each time a copy as wide as the pool; a pool that has this shape
    lies page by page as it is. ``write_kv`` and ``attend_pages_blocked``
    take either."""
    return (num_pages, page, kvh * d)


def _pool_heads(pool, d: int):
    """(positions a page, K/V heads a position) of a pool of either shape,
    given the head's width."""
    return pool.shape[1], math.prod(pool.shape[2:]) // d


def write_kv(k, v, pool_k, pool_v, scale_k, scale_v, page_idx, offs, kv_int8):
    """Each slot's K/V row [S, 1, kvh, d] into its (page_idx, offs) of a pool
    ``[num_pages, page, kvh, d]`` or ``[num_pages, page, kvh * d]``."""
    def put(pool, rows):
        flat = _lane_rows(pool)
        return flat.at[page_idx, offs].set(
            rows.astype(pool.dtype).reshape(-1, *flat.shape[2:])
        ).reshape(pool.shape)

    with jax.named_scope("kv_write"):
        if kv_int8:
            kq, ks = _quant_kv(k[:, 0])
            vq, vs = _quant_kv(v[:, 0])
            pool_k, pool_v = put(pool_k, kq), put(pool_v, vq)
            scale_k = scale_k.at[page_idx, offs].set(ks)
            scale_v = scale_v.at[page_idx, offs].set(vs)
        else:
            pool_k, pool_v = put(pool_k, k[:, 0]), put(pool_v, v[:, 0])
    return pool_k, pool_v, scale_k, scale_v


def attend_pages(q, pool_k, pool_v, scale_k, scale_v, tables, lengths,
                 kv_int8, dtype):
    """Each slot's query [S, 1, H, d] over every page of its table [S, P],
    keys masked by position (<= the query's). -> o [S, 1, H*d]."""
    S, P = tables.shape
    n_heads, head_dim = q.shape[2], q.shape[3]
    n_kv_heads = pool_k.shape[2]
    cap = P * pool_k.shape[1]
    with jax.named_scope("attention"):
        k_seq = pool_k[tables].reshape(S, cap, n_kv_heads, head_dim)
        v_seq = pool_v[tables].reshape(S, cap, n_kv_heads, head_dim)
        if kv_int8:     # dequantize each slot's gathered pages
            k_seq = (k_seq.astype(dtype)
                     * scale_k[tables].reshape(
                         S, cap, n_kv_heads, 1).astype(dtype))
            v_seq = (v_seq.astype(dtype)
                     * scale_v[tables].reshape(
                         S, cap, n_kv_heads, 1).astype(dtype))
        # Head h = g*rep + r shares K/V head g: contract the group against
        # K/V as gathered, at n_kv_heads and in their own dtype (float32
        # accumulation), never a copy repeated to every head.
        rep = n_heads // n_kv_heads
        qg = q.reshape(S, 1, n_kv_heads, rep, head_dim)
        s = jnp.einsum("sqgrd,skgd->sgrqk", qg, k_seq,
                       preferred_element_type=jnp.float32
                       ) * (head_dim ** -0.5)
        admit = (jnp.arange(cap)[None, :] <=
                 lengths[:, None])  # keys <= query position
        s = jnp.where(admit[:, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("sgrqk,skgd->sqgrd", p.astype(v_seq.dtype), v_seq)
        o = o.reshape(S, 1, n_heads * head_dim)
    return o


def block_pages_of(S: int, P: int, page: int, kvh: int, d: int, dtype) -> int:
    """The table columns a block of the blocked read holds, from the shapes
    alone (slots, a table's columns, a page's positions, a position's K/V
    heads and their width as the queries' ``dtype`` holds them): the ONE
    rule of every step that reads through ``attend_pages_blocked`` and of
    the engine's count of what such a step reads. An eighth of the table's
    width, so that a slot at a sixth of its table (what a decode mix holds on
    average) is one or two items of the read's list and a full one eight;
    and no more than 64 MiB of gathered keys a pass of ``S`` blocks, past
    which a pass no longer stays on the chip (32 blocks of 2048 positions x
    2 KiB took 2.3 times what twice as many of 1024 did). What a pass costs
    is its gathered pages, ~55 ns each whatever a page holds, and ~10 us of
    its own (PERF.md section 5, PR 44): narrower blocks round a slot up by
    less and take more passes, and between an eighth and a sixteenth of a
    2048-wide table the two cancel."""
    row_bytes = kvh * d * jnp.dtype(dtype).itemsize
    return max(1, min(P // 8, (64 << 20) // (S * page * row_bytes)))


def attend_pages_blocked(q, pool_k, pool_v, tables, lengths, block_pages,
                         scale_k=None, scale_v=None, own=None):
    """``attend_pages``' result without its table-wide gather, and without a
    slot paying for a longer one's context. A slot's context is cut into
    blocks of ``block_pages`` table columns; the blocks of all slots stand
    in ONE list (slot by slot: as many of each as hold its positions up to
    the query's own), and a ``fori_loop`` takes ``S`` of them at a time, as
    many times as the list is long: it gathers those blocks' pages (``[S, block_pages * page, kvh,
    d]`` of keys and of values, whatever the table's width), gives each its
    own softmax statistics, and folds them into their slots' running
    maximum, sum and weighted values (an online softmax whose blocks arrive
    in no fixed number a slot). At 32 slots of 32 768 positions the whole
    gather would be 2.1 GB of keys and as much of values in one layer; this
    reads what is live, rounded up to a block a slot. The rest of a slot's
    last block gathers its table's padding (page 0) and is masked.

    ``scale_k`` / ``scale_v``: the scales of int8 pools, gathered block by
    block beside their pages and applied in the queries' dtype. ``own``:
    each slot's K/V row ``(k, v)`` [S, kvh, d] at its query's own position
    ``lengths``, as the pool holds it; given, the running softmax STARTS
    from that row and the loop reads only the positions before it
    (``ceil(lengths / block)`` blocks), so a slot of length 0 (an idle one)
    gathers nothing and a slot at a block's edge does not open the next.
    q [S, 1, H, d] -> o [S, 1, H * d]."""
    with jax.named_scope("attention"):
        S, P = tables.shape
        d = q.shape[-1]
        page, kvh = _pool_heads(pool_k, d)
        Bp = min(block_pages, P)
        if P % Bp:
            tables = jnp.pad(tables, ((0, 0), (0, -P % Bp)))
        Bk = Bp * page
        qg = q.reshape(S, kvh, -1, d)
        rep = qg.shape[2]
        scale = d ** -0.5
        n = lengths + (own is None)         # positions read from the pool
        need = (n + Bk - 1) // Bk           # blocks that hold 0 .. n - 1
        ends = jnp.cumsum(need)
        # Every block the tables could hold, in the list's order, worked out
        # once, before the loop and for every layer that shares the tables
        # and lengths: an item's slot, its block of the slot's context, its
        # table columns, and which slot it folds into. The loop only slices.
        item = jnp.arange(-(-P // Bp) * S)
        live = item < ends[-1]
        slot = jnp.minimum(jnp.searchsorted(ends, item, side="right",
                                            method="compare_all"), S - 1)
        blk = jnp.where(live, item - (ends - need)[slot], 0)
        cols = tables.reshape(S, -1, Bp)[slot, blk]     # a block's columns
        left = jnp.where(live, n[slot] - blk * Bk, 0)   # positions to admit
        mine = (slot[:, None] == jnp.arange(S)[None, :]) & live[:, None]

        def pages(pool, scales, cols):
            rows = _lane_rows(pool)[cols].reshape(S, Bk, kvh, d)
            if scales is None:
                return rows
            return rows.astype(q.dtype) * scales[cols].reshape(
                S, Bk, kvh, 1).astype(q.dtype)

        def blocks(it, carry):
            m, l, acc = carry
            slot_i, cols_i, left_i, mine_i = (
                jax.lax.dynamic_slice_in_dim(a, it * S, S)
                for a in (slot, cols, left, mine))
            k = pages(pool_k, scale_k, cols_i)
            v = pages(pool_v, scale_v, cols_i)
            s = jnp.einsum("igrd,ikgd->igrk", qg[slot_i], k,
                           preferred_element_type=jnp.float32) * scale
            ok = (jnp.arange(Bk)[None, :]
                  < left_i[:, None])[:, None, None, :]
            s = jnp.where(ok, s, -1e30)
            m_i = s.max(axis=-1)                           # [items, kvh, rep]
            p = jnp.where(ok, jnp.exp(s - m_i[..., None]), 0.0)
            acc_i = jnp.einsum("igrk,ikgd->igrd", p.astype(v.dtype), v,
                               preferred_element_type=jnp.float32)
            # each item into its slot (several of one slot may stand here)
            to = mine_i.T[:, :, None, None]                # [S, items, 1, 1]
            m_new = jnp.maximum(m, jnp.max(
                jnp.where(to, m_i[None], -1e30), axis=1))
            w = jnp.where(to, jnp.exp(m_i[None] - m_new[:, None]), 0.0)
            fix = jnp.exp(m - m_new)
            l = l * fix + jnp.sum(w * p.sum(axis=-1)[None], axis=1)
            acc = acc * fix[..., None] + jnp.sum(
                w[..., None] * acc_i[None], axis=1)
            return m_new, l, acc

        if own is None:
            init = (jnp.full((S, kvh, rep), -1e30, jnp.float32),
                    jnp.zeros((S, kvh, rep), jnp.float32),
                    jnp.zeros((S, kvh, rep, d), jnp.float32))
        else:       # the query's own row: one key of weight 1 to start from
            k_own, v_own = own
            init = (jnp.einsum("sgrd,sgd->sgr", qg, k_own,
                               preferred_element_type=jnp.float32) * scale,
                    jnp.ones((S, kvh, rep), jnp.float32),
                    jnp.broadcast_to(v_own.astype(jnp.float32)[:, :, None],
                                     (S, kvh, rep, d)))
        _, l, acc = jax.lax.fori_loop(0, (ends[-1] + S - 1) // S, blocks,
                                      init)
        return (acc / l[..., None]).astype(q.dtype).reshape(S, 1, -1)


def _kernel_reads(pool) -> bool:
    """Whether ``ops.paged_decode.kv_decode`` reads this K or V pool: a pool
    the caller keeps as ``lane_pool_shape`` (rank 3 as it is passed, before
    ``_lane_rows``: a 4-D pool of narrow heads that is only VIEWED so stays
    on the XLA read), in a floating dtype (int8 pages have scales, which the
    kernel does not take), of rows that are whole lanes and pages that are
    whole sublane tiles of them, which is what the kernel's copies and
    products tile; on a TPU (the platform answers through
    ``ops.attention._on_tpu``, the one function a test replaces). Every other
    pool, and every pool on any other platform, is read by
    ``attend_pages_blocked``."""
    return (pool.ndim == 3 and jnp.issubdtype(pool.dtype, jnp.floating)
            and pool.shape[2] % 128 == 0
            and pool.shape[1] % (32 // jnp.dtype(pool.dtype).itemsize) == 0
            and attention._on_tpu())


def read_block_pages(pool, S: int, P: int, d: int, dtype) -> int:
    """The table columns a block of ``paged_attention``'s read of ``pool``
    holds, whichever form reads it (``S`` slots of ``P`` columns, queries of
    heads ``d`` wide in ``dtype``): what a slot's read is rounded up to, for
    the step and for the engine's count of what the step reads."""
    if _kernel_reads(pool):
        return kv_block_pages(P, pool.shape[1])
    return block_pages_of(S, P, *_pool_heads(pool, d), d, dtype)


def paged_attention(q, k, v, pool_k, pool_v, scale_k, scale_v, tables,
                    lengths, page_idx, offs, kv_int8, dtype):
    """One layer's cache write and attention for every slot.

    q [S, 1, H, d], k and v [S, 1, kvh, d] (rotated already where the family
    rotates); pool_* [num_pages, page, kvh, d] (or ``lane_pool_shape``'s);
    tables [S, P]. Writes each slot's row at (page_idx, offs), in place, and
    reads each slot's LIVE pages: the query's own row from the arguments, the
    positions before it block by block, masked by position (keys <= the
    query's); a slot of length 0 reads no page. No array as wide as the
    table. What the pools show picks the read (``_kernel_reads``): on a TPU
    a lane pool goes through the Pallas kernel ``ops.paged_decode.kv_decode``
    (each live page copied once into VMEM and contracted as stored, where
    the gathered blocks, their reshape to heads of half a lane and the
    float32 scores in HBM were 4.7 of a step's 18.9 ms at 64 slots of
    118 600 positions and the kernel is 0.9: PERF.md section 5, PR 55);
    every other pool through
    ``attend_pages_blocked``, the form that runs wherever there is no Mosaic
    compiler and the plain statement of the arithmetic that the kernel is
    tested against (``tests/test_paged_decode.py``). Returns (o [S, 1,
    H*d], pool_k, pool_v, scale_k, scale_v)."""
    pool_k, pool_v, scale_k, scale_v = write_kv(
        k, v, pool_k, pool_v, scale_k, scale_v, page_idx, offs, kv_int8)
    if kv_int8:     # the row as the pool now holds it: quantised, and back
        own = tuple(r.astype(dtype) * s[..., None].astype(dtype)
                    for r, s in map(_quant_kv, (k[:, 0], v[:, 0])))
    else:
        own = k[:, 0].astype(pool_k.dtype), v[:, 0].astype(pool_v.dtype)
    if _kernel_reads(pool_k):
        with jax.named_scope("attention"):
            o = kv_decode(q[:, 0], *own, pool_k, pool_v, tables, lengths)
            o = o.astype(q.dtype).reshape(q.shape[0], 1, -1)
    else:
        o = attend_pages_blocked(
            q, pool_k, pool_v, tables, lengths,
            read_block_pages(pool_k, *tables.shape, q.shape[-1], q.dtype),
            scale_k, scale_v, own)
    return o, pool_k, pool_v, scale_k, scale_v


# ------------------------------------------------ a block-sparse layer's reads
def block_scores(logits, n, sizes):
    """Each query's score of every block. logits [N, kvh, rep, J] float32: the
    query heads of each K/V group against the J compressed keys (key ``j``
    is the mean of keys ``[stride j, stride j + kernel)``), scaled; n [N]:
    each query's context length (its position + 1). ``sizes`` has
    ``block``, ``stride``, ``kernel`` (= 2 stride), ``topk``,
    ``init_blocks`` and ``window`` (positions).

    Per head a softmax over the compressed keys the context holds whole;
    summed over the group's heads; a block's score is the largest over the
    windows that overlap it (a max-pool of width block/stride + 1, stride
    block/stride, padding 1); the first ``init_blocks`` and the last
    ``window / block`` blocks up to the query's own are forced (+inf), the
    blocks past the query's own are out (-inf); the ``topk`` highest are
    read (``choose_blocks`` / ``choose_block_mask``). -> [N, kvh, J / r]."""
    block, stride, kernel = sizes.block, sizes.stride, sizes.kernel
    r = block // stride
    N, kvh, _, J = logits.shape
    j = jnp.arange(J)
    whole = (j * stride + kernel)[None, :] <= n[:, None]            # [N, J]
    a = jax.nn.softmax(jnp.where(whole[:, None, None, :], logits, -1e30), -1)
    A = jnp.where(whole[:, None, :], a.sum(axis=2), -jnp.inf)   # [N, kvh, J]
    Ab = A.reshape(N, kvh, J // r, r)
    before = jnp.concatenate(
        [jnp.full((N, kvh, 1), -jnp.inf), Ab[:, :, :-1, r - 1]], axis=2)
    B = jnp.maximum(Ab.max(axis=-1), before)
    b = jnp.arange(J // r)[None, :]
    cur = ((n - 1) // block)[:, None]
    forced = (b < sizes.init_blocks) | (b > cur - sizes.window // block)
    B = jnp.where(forced[:, None, :], jnp.inf, B)
    return jnp.where((b <= cur)[:, None, :], B, -jnp.inf)


def choose_blocks(logits, n, sizes):
    """The ``topk`` blocks of highest score, as indices (equal scores: the
    lower block first). -> (idx int32 [N, kvh, topk], the block scores)."""
    B = block_scores(logits, n, sizes)
    _, idx = jax.lax.top_k(B, sizes.topk)
    return idx.astype(jnp.int32), B


def choose_block_mask(logits, n, sizes):
    """The same choice as a mask [N, kvh, J / r], without a sort (a sort of
    704 scores for each of a prompt's queries was a quarter of a prefill:
    PERF.md section 6, PR 32): the ``topk``-th highest score by bisection
    over the scores' bits, everything above it, and of the scores equal to
    it the lowest blocks, which is ``lax.top_k``'s set exactly."""
    B = block_scores(logits, n, sizes)
    bits = jax.lax.bitcast_convert_type(B, jnp.int32)
    # float order as unsigned order: flip all bits of a negative, the sign
    # bit of a positive
    key = jax.lax.bitcast_convert_type(
        bits ^ jnp.where(bits < 0, -1, jnp.int32(-2 ** 31)), jnp.uint32)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= sizes.topk
        return jnp.where(enough, cand, t)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(B.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    level = key == kth[..., None]
    room = sizes.topk - jnp.sum(above, axis=-1)
    return above | (level & (jnp.cumsum(level, axis=-1) <= room[..., None]))


def write_ckeys(pool_c, pool_k, tables, lengths, sizes):
    """Complete a compressed key where a window of keys does: for a slot
    whose context after this step's write, ``n = length + 1``, is a multiple
    of ``stride`` and at least ``kernel``, key ``j = (n - kernel) / stride``
    becomes the mean of the pool's K rows ``[n - kernel, n)`` (they may lie
    on two pages), written at row ``j % r`` of page ``tables[j // r]`` of
    ``pool_c`` [num_pages, r, kvh, d]. Every other slot's write is dropped.
    An inactive slot (length 0) has ``n = 1``."""
    with jax.named_scope("ckey_write"):
        page = pool_k.shape[1]
        r = page // sizes.stride
        n = lengths + 1
        due = (n % sizes.stride == 0) & (n >= sizes.kernel)
        start = jnp.maximum(n - sizes.kernel, 0)
        pos = start[:, None] + jnp.arange(sizes.kernel)[None, :]  # [S, kernel]
        pg = jnp.take_along_axis(tables, pos // page, axis=1)
        rows = pool_k[pg, pos % page]                   # [S, kernel, kvh, d]
        ck = jnp.mean(rows.astype(jnp.float32), axis=1).astype(pool_c.dtype)
        j = start // sizes.stride
        at = jnp.take_along_axis(tables, (j // r)[:, None], axis=1)[:, 0]
        at = jnp.where(due, at, pool_c.shape[0])        # past the pool: dropped
        return pool_c.at[at, j % r].set(ck, mode="drop")


def select_pages(q, pool_c, tables, lengths, sizes):
    """q [S, 1, H, d] against each slot's compressed keys (gathered through
    its table: [S, P*r, kvh, d]) -> the chosen blocks [S, kvh, topk], which
    index the slot's table."""
    with jax.named_scope("sparse_select"):
        S, P = tables.shape
        kvh, d = pool_c.shape[2], pool_c.shape[3]
        ck = pool_c[tables].reshape(S, -1, kvh, d)
        qg = q.reshape(S, kvh, -1, d)
        logits = jnp.einsum("sgrd,sjgd->sgrj", qg, ck,
                            preferred_element_type=jnp.float32) * (d ** -0.5)
        idx, _ = choose_blocks(logits, lengths + 1, sizes)
        return idx


def attend_chosen(q, pool_k, pool_v, tables, idx, lengths):
    """Each slot's query over its chosen pages only. q [S, 1, H, d], idx
    [S, kvh, topk] entries of the slot's table -> o [S, 1, H*d]. Keys past
    the query's position are masked, so a block chosen beyond the context
    (fewer blocks than ``topk``) adds nothing."""
    with jax.named_scope("sparse_attn"):
        S = tables.shape[0]
        page, kvh, d = pool_k.shape[1], pool_k.shape[2], pool_k.shape[3]
        topk = idx.shape[-1]
        pg = jnp.take_along_axis(tables, idx.reshape(S, -1), axis=1
                                 ).reshape(S, kvh, topk)
        # whole pages as the pool lays them out (both K/V heads of a page:
        # a gather of one head's slices makes the compiler transpose the
        # whole pool every step), then each group's own head of its pages
        g = jnp.arange(kvh)
        k_sel = pool_k[pg][:, g, :, :, g].reshape(kvh, S, topk * page, d)
        v_sel = pool_v[pg][:, g, :, :, g].reshape(kvh, S, topk * page, d)
        k_sel, v_sel = k_sel.swapaxes(0, 1), v_sel.swapaxes(0, 1)
        pos = (idx[..., None] * page + jnp.arange(page)).reshape(S, kvh, -1)
        qg = q.reshape(S, kvh, -1, d)
        s = jnp.einsum("sgrd,sgkd->sgrk", qg, k_sel,
                       preferred_element_type=jnp.float32) * (d ** -0.5)
        s = jnp.where((pos <= lengths[:, None, None])[:, :, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("sgrk,sgkd->sgrd", p.astype(v_sel.dtype), v_sel)
        return o.reshape(S, 1, -1)


# ------------------------------------------------- a latent (MLA) layer's reads
# A position's row is ``W = kv_lora_rank + qk_rope_head_dim`` values (576 at
# the published widths: 4.5 lanes of 128). A pool ``[num_pages, page, W]``
# would be kept by the chip with the PAGES as its minor axis (no padding
# that way) and copied whole to a gatherable layout in every step (AOT, PR
# 34: eight copies of 302 MB each way and 5.7 GB of temporaries), so the
# pool holds TWO positions a row: ``[num_pages, page / 2, 2 W]``, 1152 = 9
# lanes, position ``t`` of a page in row ``t // 2`` at ``(t % 2) W``.
def latent_pool_shape(num_pages: int, page: int, width: int):
    if page % 2:
        raise ValueError("a latent pool keeps two positions a row: page_size "
                         "must be even")
    return (num_pages, page // 2, 2 * width)


def latent_pages(rows, page: int):
    """A sequence's cache rows [T, W] as whole pages [T / page, page / 2,
    2 W], as a latent pool lays them out."""
    return rows.reshape(-1, page // 2, 2 * rows.shape[-1])


def write_latent(row, pool, page_idx, offs):
    """Each slot's latent row [S, W] (the normed, scaled latent, then the
    rotated rotary key) at position ``offs`` of its page ``page_idx``: the
    pair's row is read, its half replaced, and written back."""
    with jax.named_scope("latent_write"):
        W = row.shape[-1]
        old = pool[page_idx, offs // 2]                         # [S, 2 W]
        row = row.astype(pool.dtype)
        new = jnp.where((offs % 2 == 0)[:, None],
                        jnp.concatenate([row, old[:, W:]], axis=-1),
                        jnp.concatenate([old[:, :W], row], axis=-1))
        return pool.at[page_idx, offs // 2].set(new)


def write_latent_rows(rows, pool, tables, lengths):
    """``write_latent`` for ``R`` rows a slot, rows [S, R, W] at positions
    ``lengths .. lengths + R - 1`` of each slot's table, one after another
    (two positions share a pool row, so the second write reads what the
    first left)."""
    page = 2 * pool.shape[1]
    for r in range(rows.shape[1]):
        at = lengths + r
        page_idx = jnp.take_along_axis(tables, (at // page)[:, None],
                                       axis=1)[:, 0]
        pool = write_latent(rows[:, r], pool, page_idx, at % page)
    return pool


#: what a pass of ``attend_latent`` may hold in float32 scores and weighted
#: rows (``[items, score rows, a block's pool rows + 2 W]``). Under it the
#: compiler keeps both in VMEM between the two products, beside the pass's
#: gathered rows; GigaChat's read at 11 blocks a pass (23.1 MB) took 2.49 ms a
#: layer and at 12 (25.2 MB) 3.46 (chip, PR 61: PERF.md section 5)
_LATENT_PASS_BYTES = 20 << 20


def latent_pass_shape(S: int, P: int, pool, score_rows: int):
    """(the table columns a block of ``attend_latent``'s read holds, the
    blocks a pass of its loop takes), from the shapes alone (``S`` slots of
    ``P`` columns over ``pool``, ``score_rows`` rows of scores a slot: two a
    query row and head): the ONE rule of the read and of the step rows'
    ``latent_positions_read``. A block is an eighth of the table's width
    (``block_pages_of``'s reasons: a slot is rounded up by half a block on
    average, and what a block costs beside its rows, the queries gathered to
    it and its weighted rows added to its slot, is the same however few rows
    it holds: at GigaChat's 224 columns 14, 28 and 56 read 0.30, 0.32 and
    0.38 of the table in 2.46, 2.20 and 2.45 ms). A pass is as many blocks,
    ``S`` at most, as keep its float32 arrays under ``_LATENT_PASS_BYTES``,
    in whole tiles of 8 where there are that many."""
    block = max(1, P // 8)
    item_bytes = 4 * score_rows * (block * pool.shape[1] + pool.shape[2])
    items = max(1, min(S, _LATENT_PASS_BYTES // item_bytes))
    return block, (items - items % 8 if items > 8 else items)


def _latent_need(pool, tables, lengths, R: int, H: int):
    """-> (a block's table columns, the blocks a pass takes, the blocks of
    each slot [S] that hold its positions ``0 .. lengths + R - 1``: at least
    one)."""
    Bp, items = latent_pass_shape(*tables.shape, pool, 2 * R * H)
    Bk = Bp * 2 * pool.shape[1]
    return Bp, items, (lengths + R + Bk - 1) // Bk


def latent_positions_read(pool, tables, lengths, R: int, H: int):
    """The positions ``attend_latent`` gathers in one read with ``R`` query
    rows of ``H`` heads a slot: each slot's blocks, whole (int32 scalar)."""
    Bp, _, need = _latent_need(pool, tables, lengths, R, H)
    return jnp.sum(need) * (Bp * 2 * pool.shape[1])


def attend_latent(q_nope, q_rope, w_uk, w_uv, pool, tables, lengths, scale):
    """Each slot's ``R`` query rows over its LIVE pages, in the ABSORBED
    form.

    q_nope [S, R, H, dn], q_rope [S, R, H, dr] (rotated): row ``r`` stands at
    position ``lengths + r`` and sees the cached rows up to its own, those of
    the rows before it among them (the caller has written all ``R``; a step
    of one token a slot brings ``R = 1``, one that verifies a draft 2); w_uk
    [C, H, dn] and w_uv [C, H, dv]: the up-projection of the latent to each
    head's keys and values; pool [num_pages, page / 2, 2 (C + dr)]; tables
    [S, P]. Per head ``q~ = W_UK q_nope`` in the latent's C dims, the score
    of a cached row is ``(q~ . c + q_rope . k_r) * scale``, rows past the
    query's position are masked, the softmax weights sum the LATENTS, and
    ``W_UV`` is applied once a head. The same mathematics as attention over
    ``k = [c W_UK | k_r]``, ``v = c W_UV``, at ``2 H (2 C + dr)`` operations
    a cached position and query row where expanding one would cost ``2 C H
    (dn + dv)``.

    The read is ``attend_pages_blocked``'s: a slot's context is cut into
    blocks of table columns, the blocks of all slots stand in ONE list (slot
    by slot, as many of each as hold its positions ``0 .. lengths + R - 1``;
    a slot of length 0 has one) and a ``fori_loop`` takes a few of them a
    pass (``latent_pass_shape`` says how wide and how many), as many passes
    as the list is long. A pass gathers its blocks' pages (``[items, block
    rows, 2 W]``, whatever the table's width; a column past its slot's last
    page names the block's first page again, so that nothing past a slot's
    context is read at all), scores them, takes every slot's new running
    maximum from its items' maxima, exponentiates against THAT, so an item's
    weights and weighted rows are on its slot's scale already, and adds them
    to their slots through a one-hot contraction over the items (a broadcast
    of ``[slots, items]`` against a slot's ``R H x C`` float32 accumulator
    would be gigabytes a pass). The list is built from the tables and
    lengths alone: every layer of a step builds the same and the compiler
    keeps one.

    The gathered pages are contracted as the pool stores them, two positions
    a row: the queries stand twice, ``[q, 0]`` against a row's first half and
    ``[0, q]`` against its second (at ``R = 1`` the 2 H rows fill the
    128-wide unit that H = 64 would leave half empty), and the weighted sum
    of rows is read from the matching halves. No copy of the gathered rows is
    sliced or reshaped, and the ``2 R H`` rows of a slot meet a block's pages
    in ONE product each way, so the pages are read once whatever ``R``.
    -> o [S, R, H * dv]."""
    with jax.named_scope("latent_attn"):
        S, P = tables.shape
        R, H, C = q_nope.shape[1], q_nope.shape[2], w_uk.shape[0]
        half, W = pool.shape[1], pool.shape[2] // 2
        dt = pool.dtype
        q_lat = jnp.einsum("srhd,chd->srhc", q_nope, w_uk,
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat.astype(dt), q_rope.astype(dt)], axis=-1)
        z = jnp.zeros_like(q)
        q2 = jnp.stack([jnp.concatenate([q, z], axis=-1),
                        jnp.concatenate([z, q], axis=-1)], axis=1)
        q2 = q2.reshape(S, 2 * R * H, 2 * W)

        Bp, I, need = _latent_need(pool, tables, lengths, R, H)
        if P % Bp:
            tables = jnp.pad(tables, ((0, 0), (0, -P % Bp)))
        Bn, Bk = Bp * half, Bp * 2 * half   # a block's pool rows, positions
        ends = jnp.cumsum(need)
        # every block the tables could hold, in the list's order (and whole
        # passes of them): whether a slot has it, its slot, its block of the
        # slot's context, where the slot's first query row stands in it, its
        # table columns
        item = jnp.arange(-(-(-(-P // Bp) * S) // I) * I)
        live = item < ends[-1]
        slot = jnp.minimum(jnp.searchsorted(ends, item, side="right",
                                            method="compare_all"), S - 1)
        blk = jnp.where(live, item - (ends - need)[slot], 0)
        at = jnp.where(live, lengths[slot] - blk * Bk, -R)
        cols = tables.reshape(S, -1, Bp)[slot, blk]
        cols = jnp.where(jnp.arange(Bp)[None, :] * (2 * half)
                         < (at + R)[:, None], cols, cols[:, :1])
        pos = 2 * jnp.arange(Bn)[None, :] + jnp.arange(2)[:, None]  # [2, Bn]

        def blocks(it, carry):
            # Every slot has a block, so a pass's ``I`` items are of at most
            # ``I`` slots, one after another: the pass works on that window
            # of the queries and of the running softmax and leaves the rest
            # of the ``S`` where it is.
            live_i, slot_i, cols_i, at_i = (
                jax.lax.dynamic_slice_in_dim(a, it * I, I)
                for a in (live, slot, cols, at))
            first = jnp.minimum(slot_i[0], S - I)
            mine = live_i[:, None] & (
                slot_i[:, None] - first == jnp.arange(I)[None, :])
            to = jnp.minimum(slot_i - first, I - 1)     # an item's slot there
            q_w, m, l, acc = (      # [I, ..]: 2 R H x 2 W; R, H; R, H; R H C
                jax.lax.dynamic_slice_in_dim(a, first, I)
                for a in (q2,) + carry)
            rows = pool[cols_i].reshape(I, Bn, 2 * W)
            s = jnp.einsum("igw,inw->ign", q_w[to], rows,
                           preferred_element_type=jnp.float32) * scale
            s = s.reshape(I, 2, R, H, Bn)
            last = at_i[:, None] + jnp.arange(R)[None, :]           # [I, R]
            ok = (pos[None, :, None, :]
                  <= last[:, None, :, None])[:, :, :, None, :]
            s = jnp.where(ok, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(jnp.where(
                mine.T[:, :, None, None], s.max(axis=(1, 4))[None], -1e30),
                axis=1))
            # against the slot's NEW maximum: an item's weights and weighted
            # rows are on its slot's scale as they are made
            p = jnp.where(ok, jnp.exp(
                s - m_new[to][:, None, :, :, None]), 0.0)
            o2 = jnp.einsum("ign,inw->igw",
                            p.reshape(I, 2 * R * H, Bn).astype(dt), rows,
                            preferred_element_type=jnp.float32)
            o2 = o2.reshape(I, 2, R, H, 2 * W)
            o_i = o2[:, 0, :, :, :C] + o2[:, 1, :, :, W:W + C]
            add = lambda x: jnp.einsum(     # noqa: E731  items into slots
                "is,ix->sx", mine.astype(jnp.float32), x.reshape(I, -1),
                precision=jax.lax.Precision.HIGHEST)
            fix = jnp.exp(m - m_new)
            new = (m_new,
                   l * fix + add(p.sum(axis=(1, 4))).reshape(l.shape),
                   (acc.reshape(I, R, H, C) * fix[..., None]
                    ).reshape(I, -1) + add(o_i))
            return tuple(jax.lax.dynamic_update_slice_in_dim(a, w, first, 0)
                         for a, w in zip(carry, new))

        # the weighted rows of a slot as ONE row of the accumulator: the
        # slots are its major axis in any layout, so a pass updates its
        # window in place
        init = (jnp.full((S, R, H), -1e30, jnp.float32),
                jnp.zeros((S, R, H), jnp.float32),
                jnp.zeros((S, R * H * C), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (ends[-1] + I - 1) // I, blocks,
                                      init)
        acc = acc.reshape(S, R, H, C)
        o = jnp.einsum("srhc,chd->srhd", (acc / l[..., None]).astype(dt),
                       w_uv)
        return o.reshape(S, R, -1)


# ---------------------------------------------------- window layers as rings
# A window layer's cache of a slot is a ring ``[kvh, W, d]``: position ``p``
# lies at index ``p mod W`` and is overwritten by position ``p + W``, which
# is the first that no longer sees it. Index ``r`` of a slot whose newest
# position is ``q`` therefore holds position ``q - ((q - r) mod W)``; where
# that is negative nothing has been written there yet. The K/V heads stand
# BEFORE the positions (``[S, kvh, W, d]``, not the pools' ``[.., W, kvh,
# d]``): a head's ``[W, d]`` is what the two products contract, and with the
# positions outside the heads the chip's compiler kept the rings in that
# layout anyway and copied every ring whole into it and back in each step
# (AOT, PR 43: two copies of 268 MB a ring a step).
def write_ring(k, v, ring_k, ring_v, lengths):
    """Each slot's K/V row [S, kvh, d] of position ``lengths`` into its own
    ring [S, kvh, W, d] at ``lengths mod W``. An inactive slot (length 0)
    writes index 0 of its own ring, which its next admission rewrites. The
    scatter runs over the rings seen as ``[S kvh, W, d]``, one row a (slot,
    head): over ``[S, kvh, W, d]`` its indices would stand on both sides of
    the heads, and the compiler then wants the heads inside the positions
    for it, which is the layout the read does not want (a whole copy each
    way again)."""
    with jax.named_scope("ring_write"):
        S, kvh, W, d = ring_k.shape
        rows = jnp.arange(S * kvh)
        at = jnp.repeat(lengths % W, kvh)

        def put(ring, new):
            flat = ring.reshape(S * kvh, W, d).at[rows, at].set(
                new.reshape(S * kvh, d).astype(ring.dtype))
            return flat.reshape(S, kvh, W, d)

        return put(ring_k, k), put(ring_v, v)


def ring_rows(rows, n, W):
    """A sequence's rows [T, kvh, d] as its ring [kvh, W, d] after ``n``
    positions: of those the last ``W`` (all of them where there are fewer),
    position ``p`` at index ``p mod W``. An index no position has reached
    keeps a clamped row: its position reads negative until the step that
    writes it."""
    r = jnp.arange(W)
    src = jnp.maximum(n - 1 - (n - 1 - r) % W, 0)
    return rows[src].swapaxes(0, 1)


def attend_ring(q, ring_k, ring_v, lengths):
    """Each slot's query [S, H, d] at position ``lengths`` over its ring
    where it lies: every index whose position is not negative, which are the
    last ``W`` positions up to the query's own (the window's bound ``q - k <
    W`` is the ring's width). Grouped as ``attend_pages``: the ``H / kvh``
    query heads of a K/V head against the ring in its own dtype, float32
    accumulation. -> o [S, H * d]."""
    with jax.named_scope("ring_attn"):
        S, kvh, W, d = ring_k.shape
        qg = q.reshape(S, kvh, -1, d)
        s = jnp.einsum("sgrd,sgwd->sgrw", qg, ring_k,
                       preferred_element_type=jnp.float32) * (d ** -0.5)
        r = jnp.arange(W)[None, :]
        pos = lengths[:, None] - (lengths[:, None] - r) % W         # [S, W]
        s = jnp.where((pos >= 0)[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("sgrw,sgwd->sgrd", p.astype(ring_v.dtype), ring_v)
        return o.reshape(S, -1)
