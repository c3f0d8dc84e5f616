"""``paged_ops.paged_attention`` against a plain per-slot float32 reference
(K/V repeated to every head, full softmax over the admitted positions),
over heads a group, pool dtypes and slot lengths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.paged_ops import _quant_kv, paged_attention

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
    n_heads, rep = q.shape[2], q.shape[2] // pool_k.shape[2]
    out = np.zeros((q.shape[0], 1, n_heads * D), np.float32)
    for s, n in enumerate(lengths):
        k = pool_k[tables[s]].reshape(CAP, -1, D)[:n + 1]
        v = pool_v[tables[s]].reshape(CAP, -1, D)[:n + 1]
        k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
        score = np.einsum("hd,khd->hk", q[s, 0], k) * D ** -0.5
        p = np.exp(score - score.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[s, 0] = np.einsum("hk,khd->hd", p, v).reshape(-1)
    return out


def _case(rep, pool, seed=0):
    """Inputs in the model's dtype and a pool full of live rows."""
    dtype = jnp.float32 if pool == "float32" else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    S, H = len(LENGTHS), KVH * rep
    q = jax.random.normal(ks[0], (S, 1, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (S, 1, KVH, D)).astype(dtype)
    v = jax.random.normal(ks[2], (S, 1, KVH, D)).astype(dtype)
    full_k = jax.random.normal(ks[3], (NUM_PAGES, PAGE, KVH, D))
    full_v = jax.random.normal(ks[4], (NUM_PAGES, PAGE, KVH, D))
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
