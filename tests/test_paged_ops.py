"""``paged_ops.paged_attention`` against a plain per-slot float32 reference
(K/V repeated to every head, full softmax over the admitted positions),
over heads a group, pool dtypes and slot lengths: first on a table one block
of the read holds whole, then on tables of several blocks, at the two
groupings the benchmark's step programs run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.paged_ops import (_quant_kv, block_pages_of,
                                      paged_attention)

RUN = jax.jit(paged_attention, static_argnames=("kv_int8", "dtype"))

PAGE, P, D = 8, 4, 16           # a slot's table: 4 pages of 8 positions
CAP = PAGE * P
NUM_PAGES = 12
KVH = 2

# One slot a case of the issue's list: an empty slot (it attends to the row
# it writes), one that ends inside a page, one whose row opens a new page,
# and one that writes the last position its table holds.
LENGTHS = {"empty": 0, "mid_page": 11, "page_edge": 2 * PAGE,
           "full_table": CAP - 1}
# The slots' pages lie scattered in the pool. Every table is full: the
# entries past a slot's length name pages of OTHER slots (live K/V there),
# which only the position mask keeps out.
TABLES = np.array([[3, 7, 1, 10], [5, 0, 7, 3], [9, 2, 6, 5],
                   [11, 4, 8, 0]], np.int32)

# Tolerances, counted from the roundings the function makes that the float32
# reference does not. With model dtype bf16 (8 significand bits: a rounding
# moves a value by at most u = 2**-9 of itself), q, k and v arrive rounded
# and the reference takes the same rounded values, so the scores differ by
# summation order only. What remains: the probabilities are cast to bf16 for
# the second product (each off by <= u, so o by <= u * sum(p * |v|) <=
# u * max|v|), and the output is rounded to bf16 (<= u * |o|): with normal
# values under 4 in size, 2 * 2**-9 * 4 = 0.016. int8 pages: the reference
# reads the same quantised pool dequantised in float32; the function's
# dequantisation rounds the scale and the product to bf16 (2u on K and on
# V), which moves a score of size <= 4 by <= 0.016 and so each probability
# by <= 1.6 %: about twice the bf16 bound in all. In float32 only the
# summation order differs (d = 16 and cap = 32 terms at 6e-8 each).
# Read on this CPU: bf16 <= 0.0041, int8 <= 0.0117, float32 <= 6e-7.
TOL = {"bfloat16": 0.02, "int8": 0.04, "float32": 2e-5}


def _reference(q, pool_k, pool_v, tables, lengths):
    """Per slot, in float32: the first length+1 rows of the slot's pages,
    K/V repeated to every query head, softmax, weighted sum."""
    n_heads, rep, d = q.shape[2], q.shape[2] // pool_k.shape[2], q.shape[3]
    cap = tables.shape[1] * pool_k.shape[1]
    out = np.zeros((q.shape[0], 1, n_heads * d), np.float32)
    for s, n in enumerate(lengths):
        k = pool_k[tables[s]].reshape(cap, -1, d)[:n + 1]
        v = pool_v[tables[s]].reshape(cap, -1, d)[:n + 1]
        k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
        score = np.einsum("hd,khd->hk", q[s, 0], k) * d ** -0.5
        p = np.exp(score - score.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[s, 0] = np.einsum("hk,khd->hd", p, v).reshape(-1)
    return out


def _case(rep, pool, seed=0, kvh=KVH, S=len(LENGTHS), pages=NUM_PAGES,
          page=PAGE, d=D):
    """Inputs in the model's dtype and a pool full of live rows."""
    dtype = jnp.float32 if pool == "float32" else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (S, 1, kvh * rep, d)).astype(dtype)
    k = jax.random.normal(ks[1], (S, 1, kvh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (S, 1, kvh, d)).astype(dtype)
    full_k = jax.random.normal(ks[3], (pages, page, kvh, d))
    full_v = jax.random.normal(ks[4], (pages, page, kvh, d))
    if pool == "int8":
        pool_k, scale_k = _quant_kv(full_k)
        pool_v, scale_v = _quant_kv(full_v)
    else:
        pool_k, pool_v = full_k.astype(dtype), full_v.astype(dtype)
        scale_k = scale_v = None
    return dtype, q, k, v, pool_k, pool_v, scale_k, scale_v


@pytest.mark.parametrize("slot", list(LENGTHS))
@pytest.mark.parametrize("pool", ["bfloat16", "int8", "float32"])
@pytest.mark.parametrize("rep", [1, 4, 16])
def test_paged_attention_equals_the_repeated_float32_reference(rep, pool,
                                                               slot):
    dtype, q, k, v, pool_k, pool_v, scale_k, scale_v = _case(rep, pool)
    lengths = np.array(list(LENGTHS.values()), np.int32)
    page_idx = TABLES[np.arange(len(lengths)), lengths // PAGE]
    offs = lengths % PAGE
    o, new_k, new_v, new_sk, new_sv = RUN(
        q, k, v, pool_k, pool_v, scale_k, scale_v, jnp.asarray(TABLES),
        jnp.asarray(lengths), jnp.asarray(page_idx), jnp.asarray(offs),
        kv_int8=pool == "int8", dtype=dtype)
    assert o.dtype == dtype and o.shape == (len(lengths), 1, KVH * rep * D)

    # The reference reads the pool AFTER the write, as the values the
    # function is to attend over (dequantised in float32 for int8 pages).
    f32 = lambda a: np.asarray(a.astype(jnp.float32))   # noqa: E731
    ref_k, ref_v = f32(new_k), f32(new_v)
    if pool == "int8":
        ref_k = ref_k * np.asarray(new_sk)[..., None]
        ref_v = ref_v * np.asarray(new_sv)[..., None]
    want = _reference(f32(q), ref_k, ref_v, TABLES, lengths)
    i = list(LENGTHS).index(slot)
    assert np.all(np.isfinite(f32(o)[i]))
    np.testing.assert_allclose(f32(o)[i], want[i], rtol=0, atol=TOL[pool])

    # The slot's row went where (page_idx, offs) says, and nowhere else.
    if pool == "int8":
        back = ref_k[page_idx[i], offs[i]]
        np.testing.assert_allclose(back, f32(k)[i, 0], rtol=0,
                                   atol=np.abs(f32(k)[i, 0]).max() / 127)
    else:
        np.testing.assert_array_equal(f32(new_k)[page_idx[i], offs[i]],
                                      f32(k)[i, 0])
    untouched = np.ones((NUM_PAGES, PAGE), bool)
    untouched[page_idx, offs] = False
    np.testing.assert_array_equal(np.asarray(new_k)[untouched],
                                  np.asarray(pool_k)[untouched])


@pytest.mark.parametrize("rep", [1, 4, 16])
def test_foreign_pages_past_the_length_change_nothing(rep):
    """The mask, not the gather, keeps out what lies past a slot's length:
    overwrite every row the slots do not own yet with large values, and the
    outputs stay bit for bit what they were."""
    dtype, q, k, v, pool_k, pool_v, _, _ = _case(rep, "bfloat16", seed=1)
    lengths = np.array([0, 11, 16, 20], np.int32)
    tables = np.array([[3, 7, 1, 10], [5, 0, 7, 3], [9, 2, 6, 1],
                       [11, 4, 8, 0]], np.int32)
    page_idx = tables[np.arange(4), lengths // PAGE]
    offs = lengths % PAGE
    args = (None, None, jnp.asarray(tables), jnp.asarray(lengths),
            jnp.asarray(page_idx), jnp.asarray(offs))
    o, *_ = RUN(q, k, v, pool_k, pool_v, *args, kv_int8=False, dtype=dtype)

    owned = np.zeros((NUM_PAGES, PAGE), bool)
    for s, n in enumerate(lengths):
        pos = np.arange(n + 1)
        owned[tables[s, pos // PAGE], pos % PAGE] = True
    loud = jnp.where(jnp.asarray(owned)[:, :, None, None], pool_k, 1e4)
    loud_v = jnp.where(jnp.asarray(owned)[:, :, None, None], pool_v, -1e4)
    o2, *_ = RUN(q, k, v, loud.astype(dtype), loud_v.astype(dtype), *args,
                 kv_int8=False, dtype=dtype)
    np.testing.assert_array_equal(np.asarray(o.astype(jnp.float32)),
                                  np.asarray(o2.astype(jnp.float32)))


# ------------------------------------------------- tables of several blocks
# The read takes a slot's context in blocks of ``block_pages_of`` table
# columns, an eighth of the table's width; the table above is narrower than
# eight columns, so one block holds it. Here a table of 44 pages of 16 holds
# eight blocks of 5 and four columns of a ninth (which runs past the table:
# the read pads its columns), at the groupings of the two step programs that
# read through this function (the dense family's 4 heads on each of 8 K/V
# heads, the hybrid family's 16 on each of 2).
B_PAGE, B_P = 16, 44
B_CAP = B_P * B_PAGE
B_BLOCK = block_pages_of(8, B_P, B_PAGE, 8, D, jnp.bfloat16) * B_PAGE
assert B_BLOCK == 5 * B_PAGE


@pytest.mark.parametrize("cell, shape, columns", [
    ("serve-decode-heavy", (16, 128, 16, 8, 128), 16),
    ("serve-nemotron-decode", (32, 80, 16, 2, 128), 10),
    ("serve-commanda-mixed-ctx-decode", (32, 512, 64, 8, 128), 16)])
def test_block_width_at_the_cells_shapes(cell, shape, columns):
    """An eighth of the table for the dense and the hybrid engine; Command
    A+'s 64 MiB a pass, the 16 columns its configuration's ``page_block``
    named until the rule took over (its cell was measured at that width)."""
    assert block_pages_of(*shape, jnp.bfloat16) == columns


# One batch a case: the query at the last position of a block (it reads one
# position short of a block), at a block's edge (a whole block and no
# more), one past it (the next block opens for one row), at the end of the
# table, nothing but idle slots beside one short one, and slots of every
# kind side by side, idle ones among them.
BATCHES = {
    "empty": [0, 0, 5, 0],
    "under_edge": [B_BLOCK - 1, 3, 2 * B_BLOCK - 1, 0],
    "at_edge": [B_BLOCK, 2 * B_BLOCK, 0, 1],
    "over_edge": [B_BLOCK + 1, 0, 2 * B_BLOCK + 1, B_PAGE],
    "full_table": [B_CAP - 1, B_CAP - 1, 0, B_CAP - 1],
    "mixed_idle": [0, 37, 0, B_BLOCK + 5, 2 * B_BLOCK, 0, B_CAP - 1, 3],
}


def _blocks_case(kvh, rep, pool, lengths, seed=0, d=D):
    """As ``_case``, with each slot's pages scattered over a pool that holds
    a full table for every slot; a table's entries past its slot's length
    name pages of other slots, live rows that only the mask keeps out."""
    S, pages = len(lengths), len(lengths) * B_P + 1
    case = _case(rep, pool, seed, kvh, S, pages, B_PAGE, d)
    rng = np.random.default_rng(seed)
    tables = rng.permutation(np.arange(1, pages)).reshape(S, B_P)
    for s, n in enumerate(lengths):     # past the slot's pages: a neighbour's
        own = n // B_PAGE + 1
        tables[s, own:] = tables[(s + 1) % S, :B_P - own]
    return case + (tables.astype(np.int32),)


def _run_blocks(case, lengths, pool):
    dtype, q, k, v, pool_k, pool_v, scale_k, scale_v, tables = case
    lengths = np.asarray(lengths, np.int32)
    page_idx = tables[np.arange(len(lengths)), lengths // B_PAGE]
    return RUN(q, k, v, pool_k, pool_v, scale_k, scale_v,
               jnp.asarray(tables), jnp.asarray(lengths),
               jnp.asarray(page_idx), jnp.asarray(lengths % B_PAGE),
               kv_int8=pool == "int8", dtype=dtype)


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("pool", ["bfloat16", "int8", "float32"])
@pytest.mark.parametrize("kvh, rep", [(8, 4), (2, 16)])
def test_paged_attention_over_several_blocks_equals_the_reference(
        kvh, rep, pool, batch):
    lengths = BATCHES[batch]
    case = _blocks_case(kvh, rep, pool, lengths)
    dtype, q, tables = case[0], case[1], case[-1]
    o, new_k, new_v, new_sk, new_sv = _run_blocks(case, lengths, pool)
    assert o.dtype == dtype and o.shape == (len(lengths), 1, kvh * rep * D)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))   # noqa: E731
    ref_k, ref_v = f32(new_k), f32(new_v)
    if pool == "int8":
        ref_k = ref_k * np.asarray(new_sk)[..., None]
        ref_v = ref_v * np.asarray(new_sv)[..., None]
    want = _reference(f32(q), ref_k, ref_v, tables, lengths)
    assert np.all(np.isfinite(f32(o)))
    # TOL's bounds hold per probability and per output, whatever the count
    # of admitted positions (read on this CPU over up to 704 of them: bf16
    # <= 0.0062, int8 <= 0.0127, float32 <= 6e-7)
    np.testing.assert_allclose(f32(o), want, rtol=0, atol=TOL[pool])


@pytest.mark.parametrize("pool", ["bfloat16", "int8", "float32"])
@pytest.mark.parametrize("kvh, rep", [(8, 4), (2, 16)])
def test_a_head_of_a_whole_lane_is_read_from_the_pool_as_it_lies(kvh, rep,
                                                                 pool):
    """Every case above has a head of 16, narrower than the chip's 128 lanes,
    whose pool the write and the read index a position a row
    (``paged_ops._lane_rows``: ``[pages, page, kvh * d]``). The benchmark's
    models have a head of 128 and their pools are indexed as they lie: the
    same answers there, and no array of the other form in either program."""
    lengths, d = BATCHES["mixed_idle"], 128
    case = _blocks_case(kvh, rep, pool, lengths, d=d)
    o, new_k, new_v, new_sk, new_sv = _run_blocks(case, lengths, pool)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))   # noqa: E731
    ref_k, ref_v = f32(new_k), f32(new_v)
    if pool == "int8":
        ref_k = ref_k * np.asarray(new_sk)[..., None]
        ref_v = ref_v * np.asarray(new_sv)[..., None]
    want = _reference(f32(case[1]), ref_k, ref_v, case[-1], lengths)
    np.testing.assert_allclose(f32(o), want, rtol=0, atol=TOL[pool])

    def rows_wide(d):
        case = _blocks_case(kvh, rep, pool, lengths, d=d)
        pages, page = case[4].shape[:2]
        text = str(jax.make_jaxpr(
            lambda: _run_blocks(case, lengths, pool))())
        return f"[{pages},{page},{kvh * d}]" in text

    assert rows_wide(D) and not rows_wide(d)


@pytest.mark.parametrize("kvh, rep", [(8, 4), (2, 16)])
def test_foreign_pages_past_the_length_change_nothing_in_any_block(kvh, rep):
    """The same guard as above for a read in blocks: a slot's last block
    gathers whatever its table names past the slot's length (the engine's
    tables name page 0 there, these name other slots' pages), and the blocks
    beyond it are not visited at all; loud rows there leave every output bit
    for bit what it was."""
    lengths = BATCHES["mixed_idle"]
    case = _blocks_case(kvh, rep, "bfloat16", lengths, seed=1)
    dtype, pool_k, pool_v, tables = case[0], case[4], case[5], case[-1]
    o, *_ = _run_blocks(case, lengths, "bfloat16")
    owned = np.zeros(pool_k.shape[:2], bool)
    for s, n in enumerate(lengths):
        pos = np.arange(n + 1)
        owned[tables[s, pos // B_PAGE], pos % B_PAGE] = True
    mask = jnp.asarray(owned)[:, :, None, None]
    loud = case[:4] + (jnp.where(mask, pool_k, 1e4).astype(dtype),
                       jnp.where(mask, pool_v, -1e4).astype(dtype)) + case[6:]
    o2, *_ = _run_blocks(loud, lengths, "bfloat16")
    np.testing.assert_array_equal(np.asarray(o.astype(jnp.float32)),
                                  np.asarray(o2.astype(jnp.float32)))
