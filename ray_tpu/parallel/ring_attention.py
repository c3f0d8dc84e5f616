"""Ring attention: exact attention over sequences sharded on the ``sp`` axis.

Absent from the reference entirely (SURVEY.md §5 "Long-context /
sequence parallelism: absent") — the reference only exposes NCCL p2p
channels that external libraries could build this on. Here it is native:
KV blocks rotate around the ``sp`` ring via ``ppermute`` while each device
holds its Q shard, accumulating softmax online (flash-attention style
running max/denominator), so attention over length L costs L/sp memory per
device and the KV transfer overlaps compute on ICI.

Use inside ``jax.shard_map`` with sequence dim sharded on ``sp``:

    out = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis="sp", causal=True),
        mesh=mesh,
        in_specs=P(("dp","fsdp"), "sp", None, None), ...)
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size as _axis_size

NEG_INF = -1e30

# Per-core VMEM the ``auto`` gate lets the flash kernel's resident K/V
# shard occupy (TPU VMEM is ~16 MiB/core; half leaves headroom for the
# Q/O tiles and double buffering). Shards whose ~Lk*D*8B footprint
# exceeds this fall back to the dense ring step instead of failing at
# runtime. Override: RAY_TPU_FLASH_KV_VMEM_BUDGET (bytes).
_FLASH_KV_VMEM_BUDGET = int(
    os.environ.get("RAY_TPU_FLASH_KV_VMEM_BUDGET", 8 << 20))


def _ppermute(x, axis, perm):
    """Every KV ring rotation goes through this seam (the mirror of
    ``ulysses._all_to_all``): tests interpose a byte-accounting spy here
    to pin the GQA bandwidth contract — K/V blocks (and their ring'd
    gradient shards in the flash backward) transit the ring at their
    TRUE kv-head count, never repeated to the query-head width first.
    Repeat-before-rotate would silently inflate ICI bytes by the group
    factor while still producing correct numbers."""
    return lax.ppermute(x, axis, perm)


def _block_attn(q, k, v, bias, scale):
    """One q-block x kv-block attention with running-softmax stats.

    Returns (unnormalized_out, row_max, row_sumexp). Shapes:
      q: [B, Lq, H, D], k/v: [B, Lk, H, D]
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # [B, H, Lq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # [B, H, Lq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return o, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis: str = "sp", causal: bool = False,
                   scale: Optional[float] = None,
                   segment_ids: Optional[jax.Array] = None,
                   block_impl: str = "auto") -> jax.Array:
    """Exact attention with KV rotating around the ``axis`` ring.

    Args (per-device shards, inside shard_map):
      q, k, v: [B, L_local, H, D]
      causal: apply causal mask in *global* coordinates.
      block_impl: the per-ring-step attention —
        * ``"dense"``: einsum scores (materializes [B,H,Lq,Lk] fp32 per
          step — fine at short shards, the CPU-test oracle);
        * ``"flash"``: the in-tree Pallas stats kernel
          (``ops.attention.flash_attention_stats``): O(block) memory, so
          the per-device footprint stays O(L_local·D) even at long
          shards — flash WITHIN the shard, ring ACROSS shards;
        * ``"auto"`` (default): flash on TPU when shapes tile (L_local
          a multiple of 128, D >= 64) AND the resident K/V shard fits
          the per-core VMEM budget (``_FLASH_KV_VMEM_BUDGET``), dense
          otherwise. The flash path
          is DIFFERENTIABLE via a ring-level custom VJP (standard ring
          backward: probabilities reconstructed from the final merged
          stats, block grads chunked over keys, (dk, dv) rotating home
          with their blocks).
    Returns: [B, L_local, H, D]
    """
    if segment_ids is not None:
        raise NotImplementedError(
            "ring_attention does not apply segment masking; use "
            "dense_attention(segment_ids=...) or pad documents apart "
            "(silently ignoring the mask would cross document "
            "boundaries)")
    B, Lq, H, D = q.shape
    # GQA KV stays in grouped form while rotating around the ring (1/group
    # the ICI bytes); heads are repeated per-block inside _block_attn.
    kv_rep = H // k.shape[2]
    n = _axis_size(axis)
    my_idx = lax.axis_index(axis)
    if scale is None:
        scale = D ** -0.5
    if block_impl == "auto":
        from ray_tpu.ops.attention import _on_tpu

        # The flash stats kernel keeps the full per-head K/V shard
        # resident in VMEM (~Lk*D*8B for fp32 K+V); above the per-core
        # budget it would OOM/spill at runtime where dense gridding would
        # not — fall back to dense until the kernel grids K/V into
        # block_k_major tiles.
        kv_resident_bytes = k.shape[1] * D * 8
        block_impl = ("flash" if _on_tpu() and Lq % 128 == 0 and D >= 64
                      and kv_resident_bytes <= _FLASH_KV_VMEM_BUDGET
                      else "dense")

    if block_impl == "flash":
        return _ring_attention_flash(q, k, v, axis, causal, scale)

    q32 = q.astype(jnp.float32)

    def step(carry, i):
        o_acc, m_acc, l_acc, kv = carry
        k_blk, v_blk = kv
        src_idx = (my_idx - i) % n  # whose KV block we currently hold
        Lk = k_blk.shape[1]
        if kv_rep > 1:
            k_cmp = jnp.repeat(k_blk, kv_rep, axis=2)
            v_cmp = jnp.repeat(v_blk, kv_rep, axis=2)
        else:
            k_cmp, v_cmp = k_blk, v_blk
        bias = None
        if causal:
            # Global positions: q row r on this device = my_idx*Lq + r;
            # kv col c in this block = src_idx*Lk + c.
            q_pos = my_idx * Lq + jnp.arange(Lq)
            k_pos = src_idx * Lk + jnp.arange(Lk)
            mask = q_pos[:, None] >= k_pos[None, :]
            bias = jnp.where(mask, 0.0, NEG_INF)[None, None]
        o_blk, m_blk, l_blk = _block_attn(
            q32, k_cmp.astype(jnp.float32), v_cmp.astype(jnp.float32),
            bias, scale)
        # Online-softmax merge of (o_acc, m_acc, l_acc) with the new block.
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)  # rescale old accumulator
        beta = jnp.exp(m_blk - m_new)
        l_new = l_acc * alpha + l_blk * beta
        o_new = (o_acc * alpha.transpose(0, 2, 1)[..., None]
                 + o_blk * beta.transpose(0, 2, 1)[..., None])
        # Rotate KV to the next ring position (overlaps with next compute).
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = _ppermute(k_blk, axis, perm)
        v_nxt = _ppermute(v_blk, axis, perm)
        return (o_new, m_new, l_new, (k_nxt, v_nxt)), None

    o0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    (o, m, l, _), _ = lax.scan(
        step, (o0, m0, l0, (k, v)), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ------------------------------------------------------------------ flash
# Trainable flash ring: custom VJP at the RING level. Forward runs the
# stats-kernel scan (O(block) memory per step); backward is the standard
# ring-attention backward — normalized probabilities are RECONSTRUCTED
# from the final merged (m, l) stats (the flash-bwd trick), the block
# gradient is computed chunked over keys, and (dk, dv) rotate around the
# ring WITH their (k, v) blocks so after n steps every gradient shard is
# home. This avoids defining cotangents for the kernel's raw (o, m, l)
# outputs (the merge's max/exp coupling makes that error-prone); the
# only primal output differentiated is the normalized attention.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention_flash(q, k, v, axis, causal, scale):
    out, _, _ = _ring_flash_forward(q, k, v, axis, causal, scale)
    return out


def _ring_flash_forward(q, k, v, axis, causal, scale):
    from ray_tpu.ops.attention import flash_attention_stats

    B, Lq, H, D = q.shape
    n = _axis_size(axis)
    my_idx = lax.axis_index(axis)

    def step(carry, i):
        o_acc, m_acc, l_acc, kv = carry
        k_blk, v_blk = kv
        Lk = k_blk.shape[1]
        vis_row = _visible_rows(my_idx, (my_idx - i) % n, Lq, Lk, causal)
        visible = jnp.broadcast_to(vis_row[None, None, :], (B, H, Lq))
        o_blk, m_blk, l_blk = flash_attention_stats(
            q, k_blk, v_blk, visible, scale=scale)
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_blk - m_new)
        l_new = l_acc * alpha + l_blk * beta
        o_new = (o_acc * alpha.transpose(0, 2, 1)[..., None]
                 + o_blk * beta.transpose(0, 2, 1)[..., None])
        perm = [(j, (j + 1) % n) for j in range(n)]
        return (o_new, m_new, l_new,
                (_ppermute(k_blk, axis, perm),
                 _ppermute(v_blk, axis, perm))), None

    o0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    (o, m, l, _), _ = lax.scan(step, (o0, m0, l0, (k, v)), jnp.arange(n))
    l = jnp.maximum(l, 1e-30)
    out = (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    return out, m, l


def _visible_rows(my_idx, src_idx, Lq, Lk, causal):
    """Per-q-row count of visible key columns of the ``src_idx`` block,
    in the block's local coordinates (global causal order)."""
    if not causal:
        return jnp.full((Lq,), Lk, jnp.int32)
    q_pos = my_idx * Lq + jnp.arange(Lq)
    return jnp.clip(q_pos - src_idx * Lk + 1, 0, Lk).astype(jnp.int32)


def _ring_flash_fwd(q, k, v, axis, causal, scale):
    out, m, l = _ring_flash_forward(q, k, v, axis, causal, scale)
    return out, (q, k, v, out, m, l)


def _ring_flash_bwd(axis, causal, scale, res, dout):
    q, k, v, out, m, l = res
    B, Lq, H, D = q.shape
    Hk = k.shape[2]
    rep = H // Hk
    n = _axis_size(axis)
    my_idx = lax.axis_index(axis)
    q32 = q.astype(jnp.float32)
    do = dout.astype(jnp.float32)
    # D_i = do_i . out_i  — the softmax-grad rowsum, from final stats.
    Di = jnp.einsum("bqhd,bqhd->bhq", do, out.astype(jnp.float32))
    linv = 1.0 / l  # [B, H, Lq]

    def block_grads(k_blk, v_blk, vis_row):
        """(dq_partial, dk_blk, dv_blk) for one ring block, chunked over
        keys so peak scratch is [B,H,Lq,C] with C<=512 (flash-class
        memory in backward too)."""
        Lk = k_blk.shape[1]
        # Largest 128-multiple chunk <= 512 that DIVIDES Lk (shards like
        # 640 pass the auto gate but 512 would not tile them).
        C = next((c for c in (512, 384, 256, 128) if Lk % c == 0),
                 Lk)
        k_rep = jnp.repeat(k_blk, rep, axis=2).astype(jnp.float32)
        v_rep = jnp.repeat(v_blk, rep, axis=2).astype(jnp.float32)
        kc = k_rep.reshape(B, Lk // C, C, H, D)
        vc = v_rep.reshape(B, Lk // C, C, H, D)

        def chunk(carry, idx):
            dq_acc = carry
            kcb = kc[:, idx]
            vcb = vc[:, idx]
            cols = idx * C + jnp.arange(C)
            mask = (cols[None, None, None, :]
                    < vis_row[None, None, :, None])
            s = jnp.einsum("bqhd,bkhd->bhqk", q32, kcb) * scale
            # Mask BEFORE exp: a fully-masked row carries m = NEG_INF,
            # and exp(s - NEG_INF) would be inf (inf*0 = nan downstream);
            # masked-to-NEG_INF entries stay finite and are zeroed below.
            s = jnp.where(mask, s, NEG_INF)
            p = jnp.where(mask, jnp.exp(s - m[..., None])
                          * linv[..., None], 0.0)
            dv_c = jnp.einsum("bhqk,bqhd->bkhd", p, do)
            dp = jnp.einsum("bqhd,bkhd->bhqk", do, vcb)
            ds = p * (dp - Di[..., None])
            dq_acc = dq_acc + jnp.einsum(
                "bhqk,bkhd->bqhd", ds, kcb) * scale
            dk_c = jnp.einsum("bhqk,bqhd->bkhd", ds, q32) * scale
            return dq_acc, (dk_c, dv_c)

        dq0 = jnp.zeros((B, Lq, H, D), jnp.float32)
        dq_p, (dk_chunks, dv_chunks) = lax.scan(
            chunk, dq0, jnp.arange(Lk // C))
        dk_rep = dk_chunks.transpose(1, 0, 2, 3, 4).reshape(B, Lk, H, D)
        dv_rep = dv_chunks.transpose(1, 0, 2, 3, 4).reshape(B, Lk, H, D)
        # GQA: fold the repeated query-head groups back onto the kv head.
        dk_blk = dk_rep.reshape(B, Lk, Hk, rep, D).sum(axis=3)
        dv_blk = dv_rep.reshape(B, Lk, Hk, rep, D).sum(axis=3)
        return dq_p, dk_blk, dv_blk

    def step(carry, i):
        dq_acc, k_blk, v_blk, dk_blk, dv_blk = carry
        src_idx = (my_idx - i) % n
        Lk = k_blk.shape[1]
        vis_row = _visible_rows(my_idx, src_idx, Lq, Lk, causal)
        dq_p, dk_p, dv_p = block_grads(k_blk, v_blk, vis_row)
        dq_acc = dq_acc + dq_p
        dk_blk = dk_blk + dk_p
        dv_blk = dv_blk + dv_p
        # Rotate (k, v) AND their gradient shards together: after n
        # steps every (dk, dv) lands back on its owner.
        perm = [(j, (j + 1) % n) for j in range(n)]
        return (dq_acc,
                _ppermute(k_blk, axis, perm),
                _ppermute(v_blk, axis, perm),
                _ppermute(dk_blk, axis, perm),
                _ppermute(dv_blk, axis, perm)), None

    dq0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    dk0 = jnp.zeros((B, k.shape[1], Hk, D), jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(n))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_ring_attention_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def make_ring_attention(mesh, *, causal: bool = True, axis: str = "sp",
                        batch_axes=("dp", "fsdp"), head_axis: str = "tp",
                        block_impl: str = "auto"):
    """shard_map-wrapped ring attention over a full mesh.

    q/k/v are global arrays [B, L, H, D]; batch sharded over ``batch_axes``,
    sequence over ``axis``, heads over ``head_axis``. ``block_impl``
    selects the per-step attention (see ``ring_attention``).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    spec = P(batch_axes, axis, head_axis, None)
    fn = functools.partial(ring_attention, axis=axis, causal=causal,
                           block_impl=block_impl)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
