"""Mamba-2 (SSD) state-space scan: chunked for prompts, one-token for decode.

Per head ``h`` (head size P, state size N, group ``g = h // (H // G)``):

    H_t = a_t H_(t-1) + dt_t x_t (x) B_t,   a_t = exp(dt_t A),   A < 0
    y_t = H_t C_t + D x_t

``dt`` arrives AFTER its softplus. A position whose ``dt`` is 0 leaves the
state untouched (``a = 1``, nothing added): a padded prompt tail is masked so,
and the state a chunked scan returns is then the state at the last valid
position. The state is float32 whatever the activations are.

``ssd_chunked`` is the structured-state-space-duality form: inside a chunk
the recurrence is a masked matrix product, between chunks one small
recurrence over the chunks' states. ``ssm_step`` is the recurrence itself
for one token of every slot. ``ssm_sequential`` is the recurrence over time,
the oracle the chunked form is tested against. Plain XLA, no kernel.

The depthwise causal convolution that precedes the scan lives here too
(``causal_conv`` for a prompt, ``conv_step`` for one token over the kept
tail of ``K - 1`` inputs); a gated short convolution (``models/lfm2_moe.py``)
takes the same three with no bias, and a prompt's chunk its left edge.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _grouped(t: jax.Array, groups: int) -> jax.Array:
    """[..., H, P] -> [..., G, H/G, P]."""
    *lead, h, p = t.shape
    return t.reshape(*lead, groups, h // groups, p)


def _heads(t: jax.Array, groups: int) -> jax.Array:
    """[..., H] -> [..., G, H/G]."""
    return t.reshape(*t.shape[:-1], groups, -1)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """x [L, H, P], dt [L, H] (>= 0), A [H], B and C [L, G, N] ->
    (y [L, H, P] float32 WITHOUT the ``D x`` term, final state [H, P, N]).
    ``L`` need not be a multiple of ``chunk``: the tail is padded with
    ``dt = 0`` positions, which change nothing."""
    L, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    pad = (-L) % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                       for t in (x, dt, B, C))
    nc = (L + pad) // chunk
    x = x.astype(F32).reshape(nc, chunk, H, P)
    dt = dt.astype(F32).reshape(nc, chunk, H)
    B = B.astype(F32).reshape(nc, chunk, G, N)
    C = C.astype(F32).reshape(nc, chunk, G, N)
    a = dt * A.astype(F32)                          # log decay, <= 0
    cum = jnp.cumsum(a, axis=1)                     # [nc, Q, H]
    xdt = x * dt[..., None]                         # [nc, Q, H, P]

    # inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    seg = cum[:, :, None, :] - cum[:, None, :, :]   # [nc, Qi, Qj, H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    cb = jnp.einsum("cign,cjgn->cijg", C, B)        # [nc, Qi, Qj, G]
    m = _heads(decay, G) * cb[..., None]          # [nc, Qi, Qj, G, Hg]
    y = jnp.einsum("cijgh,cjghp->cighp", m, _grouped(xdt, G))

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:, :] - cum)          # [nc, Q, H]
    states = jnp.einsum("cjgn,cjghp->cghpn", B,
                        _grouped(xdt * to_end[..., None], G))

    # between chunks: H_c = exp(sum of chunk c's a) H_(c-1) + states_c
    total = _heads(jnp.exp(cum[:, -1, :]), G)       # [nc, G, Hg]
    init = (jnp.zeros((G, H // G, P, N), F32) if h0 is None
            else h0.astype(F32).reshape(G, H // G, P, N))

    def carry(h, inp):
        dec, s = inp
        return dec[..., None, None] * h + s, h      # emits the state BEFORE

    final, before = jax.lax.scan(carry, init, (total, states))
    into = _heads(jnp.exp(cum), G)                  # [nc, Q, G, Hg]
    y = y + jnp.einsum("cign,cghpn->cighp", C, before) * into[..., None]
    return (y.reshape(nc * chunk, H, P)[:L],
            final.reshape(H, P, N))


def ssm_sequential(x, dt, A, B, C, h0=None):
    """The recurrence over time, one position after another: the oracle of
    ``ssd_chunked`` (same arguments, same returns)."""
    L, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    init = (jnp.zeros((H, P, N), F32) if h0 is None else h0.astype(F32))

    def one(h, inp):
        xt, dtt, bt, ct = inp
        y, h = ssm_step(h[None], xt[None], dtt[None], A, bt[None], ct[None])
        return h[0], y[0]

    final, y = jax.lax.scan(one, init, (x, dt, B, C))
    return y, final


def ssm_step(state: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
             B: jax.Array, C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token of every slot. state [S, H, P, N] float32, x [S, H, P],
    dt [S, H], B and C [S, G, N] -> (y [S, H, P] float32 without ``D x``,
    new state)."""
    S, H, P, N = state.shape
    G = B.shape[1]
    rep = H // G
    dt = dt.astype(F32)
    a = jnp.exp(dt * A.astype(F32))                              # [S, H]
    bh = jnp.repeat(B.astype(F32), rep, axis=1)                  # [S, H, N]
    ch = jnp.repeat(C.astype(F32), rep, axis=1)
    xdt = x.astype(F32) * dt[..., None]                          # [S, H, P]
    new = (state * a[..., None, None]
           + xdt[..., None] * bh[:, :, None, :])
    y = jnp.sum(new * ch[:, :, None, :], axis=-1)
    return y, new


def causal_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                left: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution over time. x [L, C], w [K, C] (``w[K-1]``
    multiplies the current input), b [C] or None (no bias) -> [L, C] in
    float32. ``left`` [K-1, C]: the inputs before ``x[0]``, oldest first (a
    chunk's left edge is the tail of the chunk before it); zeros where None."""
    K = w.shape[0]
    L = x.shape[0]
    xp = (jnp.pad(x.astype(F32), ((K - 1, 0), (0, 0))) if left is None
          else jnp.concatenate([left.astype(F32), x.astype(F32)]))
    out = 0.0 if b is None else b.astype(F32)[None, :]
    for k in range(K):
        out = out + xp[k:k + L] * w[k].astype(F32)[None, :]
    return out


def conv_tail(x: jax.Array, n_valid, K: int,
              left: Optional[jax.Array] = None) -> jax.Array:
    """The last ``K - 1`` VALID inputs of a padded prompt, oldest first
    (zeros where the prompt is shorter): what ``conv_step`` continues from.
    x [L, C], n_valid traced -> [K-1, C]. With ``left`` [K-1, C] (the inputs
    before ``x[0]``) ``x`` is one chunk of a prompt and ``n_valid`` counts
    from the chunk's first position: clipped to the chunk, so a chunk the
    prompt runs through hands on its own last inputs, and one that holds
    fewer than ``K - 1`` valid ones the rest from ``left``."""
    if left is None:
        xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([left.astype(x.dtype), x])
        n_valid = jnp.clip(n_valid, 0, x.shape[0])
    return jax.lax.dynamic_slice_in_dim(xp, n_valid, K - 1, axis=0)


def conv_step(tail: jax.Array, x: jax.Array, w: jax.Array,
              b: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """One token of every slot. tail [S, K-1, C] (oldest first), x [S, C] ->
    (out [S, C] float32, new tail)."""
    window = jnp.concatenate([tail, x[:, None, :].astype(tail.dtype)], axis=1)
    out = jnp.einsum("skc,kc->sc", window.astype(F32), w.astype(F32))
    if b is not None:
        out = out + b.astype(F32)[None, :]
    return out, window[:, 1:]
