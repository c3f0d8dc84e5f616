"""Device arithmetic of the page pool that every family's step shares:
one K/V row of every slot written at its (page, offset), and each slot's
query attended over its gathered pages."""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def paged_attention(q, k, v, pool_k, pool_v, scale_k, scale_v, tables,
                    lengths, page_idx, offs, kv_int8, dtype):
    """One layer's cache write and attention for every slot.

    q [S, 1, H, d], k and v [S, 1, kvh, d] (rotated already where the family
    rotates); pool_* [num_pages, page, kvh, d]; tables [S, P]. Writes each
    slot's row at (page_idx, offs), gathers each slot's pages into its
    [P*page, kvh, d] view and masks by position (keys <= the query's).
    Returns (o [S, 1, H*d], pool_k, pool_v, scale_k, scale_v)."""
    S, P = tables.shape
    n_heads, head_dim = q.shape[2], q.shape[3]
    n_kv_heads = k.shape[2]
    cap = P * pool_k.shape[1]
    with jax.named_scope("kv_write"):
        if kv_int8:
            kq, ks = _quant_kv(k[:, 0])
            vq, vs = _quant_kv(v[:, 0])
            pool_k = pool_k.at[page_idx, offs].set(kq)
            pool_v = pool_v.at[page_idx, offs].set(vq)
            scale_k = scale_k.at[page_idx, offs].set(ks)
            scale_v = scale_v.at[page_idx, offs].set(vs)
        else:
            pool_k = pool_k.at[page_idx, offs].set(
                k[:, 0].astype(pool_k.dtype))
            pool_v = pool_v.at[page_idx, offs].set(
                v[:, 0].astype(pool_v.dtype))
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
    return o, pool_k, pool_v, scale_k, scale_v
