"""``ops.paged_decode.kv_decode`` in ``interpret`` mode on the CPU, against
``paged_ops.attend_pages_blocked`` (the XLA read it stands in for on the chip)
and against the repeated float32 reference of ``tests/test_paged_ops.py``, at
a pool of the benchmark's row (``[16, 512]`` bfloat16: 8 K/V heads of 64, four
whole lanes) and at a toy row that is no multiple of 128. The Mosaic compile
at the real widths is ``tests/test_tpu_compile.py``'s; the chip's numbers are
PERF.md's."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_paged_ops import TOL, _reference

from ray_tpu.models import paged, paged_ops
from ray_tpu.models import lfm2_moe as lm
from ray_tpu.ops import attention, paged_decode

PAGE, P = 16, 44                # a table of 44 pages: 704 positions
#: the kernel's block at these shapes, what a slot's read is rounded up to: 8
#: table columns, 128 positions; a turn of its loop contracts four, and a
#: table of 44 columns is two turns, the second's last five columns padding
BLOCK = PAGE * paged_decode.kv_block_pages(P, PAGE)
TURN = BLOCK * paged_decode.KV_TURN
assert (BLOCK, TURN) == (128, 512)
FULL = P * PAGE - 1

# One batch a case: idle slots (they read no page), the first positions, a
# page's edge, a block's edge (a whole block and no more; one past it opens
# the next for one row), a turn's edge likewise, several blocks and turns,
# and slots of very different lengths side by side, idle ones among them.
BATCHES = {
    "idle": [0, 0, 0],
    "first-rows": [1, 2, 3],
    "page-edge": [PAGE - 1, PAGE, PAGE + 1],
    "block-edge": [BLOCK - 1, BLOCK, BLOCK + 1],
    "turn-edge": [TURN - 1, TURN, TURN + 1],
    "several-blocks": [2 * BLOCK, 3 * BLOCK + 5, FULL],
    "mixed": [FULL, 0, BLOCK - 1, 1, 100, 0, TURN + BLOCK + PAGE],
}
# (K/V heads, heads a group, a head's width, dtype): the benchmark's row in
# its dtype, at one head a group too, and toy rows of 32 and 48 values
ROWS = {
    "lfm2-8x64-rep4": (8, 4, 64, jnp.bfloat16),
    "8x64-rep1": (8, 1, 64, jnp.bfloat16),
    "toy-2x16-rep4": (2, 4, 16, jnp.float32),
    "toy-3x16-rep1": (3, 1, 16, jnp.bfloat16),
}


def _case(lengths, kvh, rep, d, dtype, seed=0):
    """Queries, own rows and lane pools whose pages lie out of order; a
    table's entries past its slot's pages name a neighbour's (live rows that
    only the mask and the walk keep out)."""
    S, pages = len(lengths), len(lengths) * P + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (S, kvh * rep, d)).astype(dtype)
    k_own = jax.random.normal(ks[1], (S, kvh, d)).astype(dtype)
    v_own = jax.random.normal(ks[2], (S, kvh, d)).astype(dtype)
    shape = paged_ops.lane_pool_shape(pages, PAGE, kvh, d)
    pool_k = jax.random.normal(ks[3], shape).astype(dtype)
    pool_v = jax.random.normal(ks[4], shape).astype(dtype)
    rng = np.random.default_rng(seed)
    tables = rng.permutation(np.arange(1, pages)).reshape(S, P)
    for s, n in enumerate(lengths):
        own = n // PAGE + 1     # with the page its own row goes to
        tables[s, own:] = tables[(s + 1) % S, :P - own]
    return (q, k_own, v_own, pool_k, pool_v,
            jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32))


def _written(case):
    """The pools with each slot's own row at its position, 4-D, in float32:
    what ``_reference`` reads (positions ``0 .. length``)."""
    q, k_own, v_own, pool_k, pool_v, tables, lengths = case
    kvh, d = k_own.shape[1:]
    at = (tables[jnp.arange(len(lengths)), lengths // PAGE], lengths % PAGE)
    f32 = lambda pool, own: np.asarray(    # noqa: E731
        pool.reshape(*pool.shape[:2], kvh, d).at[at].set(own)
        .astype(jnp.float32))
    return f32(pool_k, k_own), f32(pool_v, v_own)


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("row", list(ROWS))
def test_the_kernel_is_the_blocked_read_and_the_float32_reference(row, batch):
    kvh, rep, d, dtype = ROWS[row]
    lengths = BATCHES[batch]
    case = _case(lengths, kvh, rep, d, dtype, seed=len(lengths))
    q, k_own, v_own, pool_k, pool_v, tables, lens = case
    got = paged_decode.kv_decode(*case, interpret=True)
    assert got.shape == q.shape and got.dtype == jnp.float32
    got = np.asarray(got)
    assert np.isfinite(got).all()
    want = paged_ops.attend_pages_blocked(
        q[:, None], pool_k, pool_v, tables, lens, 3, own=(k_own, v_own))
    tol = TOL[jnp.dtype(dtype).name]
    np.testing.assert_allclose(
        got.reshape(len(lengths), -1),
        np.asarray(want[:, 0].astype(jnp.float32)), rtol=0, atol=tol)
    ref = _reference(np.asarray(q[:, None].astype(jnp.float32)),
                     *_written(case), np.asarray(tables), lengths)
    np.testing.assert_allclose(got.reshape(len(lengths), 1, -1), ref,
                               rtol=0, atol=tol)
    # foreign pages past the length change nothing: loud rows in every page
    # a slot does not own (page 0, which pads its last block's columns, and
    # its neighbours' pages, which its table names past its own) leave every
    # output bit for bit what it was
    owned = np.zeros(pool_k.shape[:2], bool)
    for s, n in enumerate(lengths):
        pos = np.arange(n)
        owned[np.asarray(tables)[s, pos // PAGE], pos % PAGE] = True
    mask = jnp.asarray(owned)[:, :, None]
    loud = case[:3] + (jnp.where(mask, pool_k, 1e4).astype(dtype),
                       jnp.where(mask, pool_v, -1e4).astype(dtype)) + case[5:]
    np.testing.assert_array_equal(
        got, np.asarray(paged_decode.kv_decode(*loud, interpret=True)))


@pytest.mark.parametrize("P_, page, block", [
    (384, 16, 8),       # the benchmark's: 128 positions
    (44, 16, 8),        # this file's
    (6, 4, 4),          # a toy engine's: never wider than the table
    (64, 64, 2),
    (8, 256, 1)])
def test_the_blocks_rule(P_, page, block):
    assert paged_decode.kv_block_pages(P_, page) == block


@pytest.mark.parametrize("on_tpu, dtype, shape, kernel", [
    (True, jnp.bfloat16, (9, 16, 512), True),
    (False, jnp.bfloat16, (9, 16, 512), False),     # no Mosaic compiler
    (True, jnp.float32, (9, 8, 128), True),
    (True, jnp.bfloat16, (9, 16, 8, 64), False),    # viewed, not kept
    (True, jnp.bfloat16, (9, 16, 8, 128), False),   # the 4-D pools
    (True, jnp.int8, (9, 32, 512), False),          # int8 pages: scales
    (True, jnp.bfloat16, (9, 16, 192), False),      # a row of 1.5 lanes
    (True, jnp.bfloat16, (9, 8, 512), False)],      # half a sublane tile
    ids=["tpu-lane-pool", "cpu", "float32-tiles", "narrow-4d", "wide-4d",
         "int8", "part-lane", "part-tile"])
def test_what_the_pools_show_picks_the_read(monkeypatch, on_tpu, dtype, shape,
                                            kernel):
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    pool = jnp.zeros(shape, dtype)
    assert paged_ops._kernel_reads(pool) is kernel
    S, P_, d = 64, 384, shape[-1] if len(shape) == 4 else 64
    want = (paged_decode.kv_block_pages(P_, shape[1]) if kernel else
            paged_ops.block_pages_of(S, P_, *paged_ops._pool_heads(pool, d),
                                     d, jnp.bfloat16))
    assert paged_ops.read_block_pages(pool, S, P_, d, jnp.bfloat16) == want


def test_paged_attention_through_the_kernel_is_paged_attention(monkeypatch):
    """``paged_attention`` with the platform answered as the chip (the kernel
    interpreted here) against its XLA read on the same bfloat16 lane pools:
    the same output, and the same rows written."""
    kvh, rep, d, dtype = ROWS["lfm2-8x64-rep4"]
    lengths = BATCHES["mixed"]
    q, k_own, v_own, pool_k, pool_v, tables, lens = _case(
        lengths, kvh, rep, d, dtype, seed=3)
    args = (q[:, None], k_own[:, None], v_own[:, None], pool_k, pool_v, None,
            None, tables, lens,
            tables[jnp.arange(len(lengths)), lens // PAGE], lens % PAGE,
            False, dtype)
    want = paged_ops.paged_attention(*args)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(paged_ops, "kv_decode", functools.partial(
        paged_decode.kv_decode, interpret=True))
    got = paged_ops.paged_attention(*args)
    assert got[0].shape == want[0].shape and got[0].dtype == want[0].dtype
    np.testing.assert_allclose(np.asarray(got[0].astype(jnp.float32)),
                               np.asarray(want[0].astype(jnp.float32)),
                               rtol=0, atol=TOL["bfloat16"])
    for a, b in zip(got[1:3], want[1:3]):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("on_tpu", [False, True], ids=["cpu", "tpu"])
def test_the_engines_count_is_in_the_blocks_of_the_read_that_runs(
        monkeypatch, on_tpu, prompt_device):
    """A lane-pool engine's ``_read_block`` is the rule of the read its step
    runs: the XLA read's off the chip, the kernel's on it; and a step row's
    ``kv_positions_read`` is each held slot's positions before its own in
    whole blocks of it, by hand."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(paged_ops, "kv_decode", functools.partial(
        paged_decode.kv_decode, interpret=True))
    # conv conv attention, a row of 2 heads of 64: one whole lane
    cfg = dataclasses.replace(
        lm.LFM2_MOE_DEBUG, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=64,
        dtype=jnp.bfloat16)
    params = jax.jit(lambda k: lm.init_params(cfg, k))(jax.random.PRNGKey(0))
    eng = paged.PagedEngine(params, cfg, max_slots=2, max_len=512,
                            page_size=16, num_pages=80)
    assert eng.pools_k[0].shape == (80, 16, 128)
    block = 16 * (paged_decode.kv_block_pages(32, 16) if on_tpu else
                  paged_ops.block_pages_of(2, 32, 16, 2, 64, jnp.bfloat16))
    assert eng._read_block == block == (128 if on_tpu else 64)
    rng = np.random.default_rng(0)
    for rid, n in (("a", 150), ("b", 30)):
        eng.submit(rid, rng.integers(1, 96, n).tolist(), max_new_tokens=8,
                   eos_id=-1)
    eng.step()      # admits both, and decodes their first step
    eng.step()
    held = np.array([s.length for s in eng.slots]) - 1  # at that dispatch
    assert sorted(held.tolist()) == [31, 151]
    assert eng._kv_positions == (
        int(np.sum(-(-held // block) * block)), int(held.sum()))
    assert eng._kv_positions[0] == (256 + 128 if on_tpu else 192 + 64)
