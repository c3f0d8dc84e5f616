"""Ulysses (DeepSpeed-style) sequence parallelism via all-to-all.

Absent from the reference (SURVEY.md §5). Complements ring attention: where
ring keeps heads local and rotates KV, Ulysses all-to-alls activations so
each device holds *all* tokens for a slice of heads, runs dense attention
locally, then transposes back. Cheaper than ring when H >= sp and sequences
are moderate; ring wins at extreme lengths. Both ride the same ``sp`` axis.

GQA: K/V carry ``n_kv_heads < n_q_heads``. Repeating K/V up to the query
head count BEFORE the all-to-all inflates the K/V transpose bytes by the
group factor (8 q-heads over 2 kv-heads move 4x the wire bytes for zero
information). When ``n_kv_heads % sp == 0`` the head blocks stay aligned
through the transpose, so the repeat commutes with the all-to-all: move
the TRUE kv heads, repeat locally after. The non-divisible case falls
back to repeat-before (correctness over bandwidth).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map

# Indirection point: the byte-count assertion test (CPU interpreter
# path) wraps this to account per-shard all-to-all bytes without
# touching device internals.
_all_to_all = lax.all_to_all


def _seq_to_heads(x: jax.Array, axis: str) -> jax.Array:
    """[B, L/n, H, D] -> [B, L, H/n, D] over the sp ring."""
    return _all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)


def _heads_to_seq(x: jax.Array, axis: str) -> jax.Array:
    """[B, L, H/n, D] -> [B, L/n, H, D]."""
    return _all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis: str = "sp", causal: bool = False,
                      scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None,
                      sp_size: Optional[int] = None) -> jax.Array:
    """Sequence-parallel attention via head/sequence all-to-all.

    Per-device shards inside shard_map: q/k/v [B, L_local, H, D] with H
    divisible by the sp degree. ``attn_fn(q, k, v, causal, scale)`` runs the
    local dense attention (defaults to a flash-style jax implementation).

    ``sp_size`` (the sp axis degree — ``make_ulysses_attention`` passes
    it from the mesh) enables the GQA bandwidth fix: with
    ``n_kv_heads % sp_size == 0`` K/V transit the all-to-all at their
    true head count and are repeated to the query head count AFTER the
    transpose. Device i's post-transpose q heads
    ``[i*Hq/n, (i+1)*Hq/n)`` group onto kv heads
    ``[i*Hkv/n, (i+1)*Hkv/n)`` exactly when ``Hkv % n == 0``, so the
    local repeat reproduces the repeat-before-transpose layout bit for
    bit. Without ``sp_size`` (or indivisible kv heads) the safe
    repeat-before path runs.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    rep = 1
    if k.shape[2] != q.shape[2]:  # GQA: kv heads < q heads
        rep = q.shape[2] // k.shape[2]
        if not (sp_size and k.shape[2] % sp_size == 0):
            # Misaligned head blocks: repeat BEFORE the transpose (pays
            # the group factor on the wire, but always correct).
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            rep = 1
    qh = _seq_to_heads(q, axis)
    kh = _seq_to_heads(k, axis)
    vh = _seq_to_heads(v, axis)
    if rep > 1:
        kh = jnp.repeat(kh, rep, axis=2)
        vh = jnp.repeat(vh, rep, axis=2)
    if attn_fn is None:
        # flash_attention == the Mosaic kernel (differentiable) on TPU,
        # dense elsewhere — after the all-to-all each device holds the
        # FULL sequence for its head subset, which is exactly the
        # single-chip flash shape.
        from ..ops.attention import flash_attention

        out = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        out = attn_fn(qh, kh, vh, causal=causal, scale=scale)
    return _heads_to_seq(out, axis)


def make_ulysses_attention(mesh, *, causal: bool = True, axis: str = "sp",
                           batch_axes=("dp", "fsdp")):
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, axis, None, None)
    fn = functools.partial(ulysses_attention, axis=axis, causal=causal,
                           sp_size=int(mesh.shape[axis]))
    return _shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
