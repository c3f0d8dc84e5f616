"""Lightning (linear) attention: chunkwise for prompts, one token for decode.

Per head ``h`` (key and value size d) with a fixed decay ``l_h = exp(-s_h)``:

    S_t = l_h S_(t-1) + k_t^T v_t,      o_t = q_t S_t        (no normaliser)

The state ``S`` [d, d] is float32 whatever the activations are. ``q`` arrives
scaled and, where the family rotates, rotated.

``chunkwise`` is the block form: inside a block of B positions the recurrence
is a masked matrix product ``(Q K^T * D) V`` with ``D_ij = l^(i-j)`` for
``i >= j``, between blocks the carried state. A position at or past
``n_valid`` neither adds to the state nor decays it (its exponent stands
still), so the state returned is the state AT ``n_valid``, as ``ops/ssm.py``
keeps with ``dt = 0``. ``recurrent_step`` is the recurrence itself for one
token of every slot, and ``recurrent_sequential`` runs it over time: the
oracle the block form is tested against. Plain XLA, no kernel.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def decay_slopes(n_heads: int, layer: int, n_layers: int) -> jax.Array:
    """``s_h`` of Lightning Attention's convention for head ``h`` of layer
    ``layer`` out of ``n_layers``: ``2^(-8 (h+1) / H) (1 - layer / (n_layers
    - 1) + 1e-5)``; the decay is ``exp(-s_h)``. float32 [H]."""
    h = jnp.arange(1, n_heads + 1, dtype=F32)
    return (2.0 ** (-8.0 * h / n_heads)
            * (1.0 - layer / max(n_layers - 1, 1) + 1e-5)).astype(F32)


def chunkwise(q: jax.Array, k: jax.Array, v: jax.Array, slopes: jax.Array,
              state: jax.Array, n_valid, block: int
              ) -> Tuple[jax.Array, jax.Array]:
    """q, k, v [L, H, d], slopes [H], state [H, d, d] float32 (the state
    before position 0) -> (o [L, H, d] float32, the state at ``n_valid``).
    ``L`` need not be a multiple of ``block``: the tail is padded with
    positions past ``n_valid``, which change nothing."""
    L, H, d = q.shape
    n_valid = jnp.minimum(jnp.asarray(n_valid, jnp.int32), L)
    pad = (-L) % block
    if pad:
        q, k, v = (jnp.pad(t, ((0, pad), (0, 0), (0, 0))) for t in (q, k, v))
    nb = (L + pad) // block
    blocks = tuple(t.reshape(nb, block, H, d) for t in (q, k, v))
    i = jnp.arange(block)
    tri = (i[:, None] >= i[None, :])[None]                    # [1, Bi, Bj]
    s = slopes.astype(F32)

    def one(S, xs):
        qb, kb, vb, b = xs
        cnt = jnp.clip(n_valid - b * block, 0, block)
        e = jnp.minimum(i + 1, cnt).astype(F32)     # valid steps up to i
        kb = jnp.where((i < cnt)[:, None, None], kb, jnp.zeros((), kb.dtype))
        seg = (e[:, None] - e[None, :])[None] * -s[:, None, None]
        decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))        # [H, Bi, Bj]
        scores = jnp.einsum("ihd,jhd->hij", qb, kb,
                            preferred_element_type=F32) * decay
        o = jnp.einsum("hij,jhd->ihd", scores.astype(vb.dtype), vb,
                       preferred_element_type=F32)
        carried = jnp.exp(-s[None, :] * e[:, None])           # [B, H]
        o = o + jnp.einsum("ihd,hde->ihe",
                           qb.astype(F32) * carried[..., None], S,
                           precision=_HIGHEST)
        to_end = jnp.exp(-s[None, :] * (cnt.astype(F32) - e)[:, None])
        S = (jnp.exp(-s * cnt.astype(F32))[:, None, None] * S
             + jnp.einsum("jhd,jhe->hde", kb.astype(F32) * to_end[..., None],
                          vb.astype(F32), precision=_HIGHEST))
        return S, o

    state, o = jax.lax.scan(one, state.astype(F32),
                            blocks + (jnp.arange(nb),))
    return o.reshape(nb * block, H, d)[:L], state


def recurrent_step(state: jax.Array, q: jax.Array, k: jax.Array,
                   v: jax.Array, slopes: jax.Array, active: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """One token of every slot. state [S, H, d, d] float32, q, k, v
    [S, H, d], slopes [H], active bool[S] -> (o [S, H, d] float32, state);
    an inactive lane's state stands still."""
    decay = jnp.exp(-slopes.astype(F32))[None, :, None, None]
    new = decay * state + (k.astype(F32)[..., :, None]
                           * v.astype(F32)[..., None, :])
    o = jnp.einsum("shd,shde->she", q.astype(F32), new, precision=_HIGHEST)
    return o, jnp.where(active[:, None, None, None], new, state)


def recurrent_sequential(q: jax.Array, k: jax.Array, v: jax.Array,
                         slopes: jax.Array, state: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over time, one sequence: q, k, v [L, H, d], state
    [H, d, d] -> (o [L, H, d] float32, final state)."""
    on = jnp.ones((1,), bool)

    def one(S, xs):
        qt, kt, vt = xs
        o, S = recurrent_step(S[None], qt[None], kt[None], vt[None], slopes,
                              on)
        return S[0], o[0]

    state, o = jax.lax.scan(one, state.astype(F32), (q, k, v))
    return o, state
