"""The sampler and the single-sequence prefill program.

What the serving engine (``models/paged.py::PagedEngine``) shares with
the benchmark's reference checks. ``_pick_tokens`` picks one token for
every slot of a step from its [S, V] logits, and is where greedy and
sampled decoding part: one ``lax.cond`` on the batch, inside the program,
runs the sort-and-sample path (``_pick_token`` under ``vmap``: temperature
with top-k and nucleus top-p over one [V] row) only when an active slot has
a temperature above 0, and an ``argmax`` otherwise. Both step programs call
it, and ``_pick_one`` is the same picker on the one row of an admission's
first token. ``_prefill_one`` runs one padded prompt through a fresh
single-sequence cache and returns the next-token logits and the dense
per-layer K/V that the engine scatters into its pages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .llama import _decode_step


def _pick_token(logits, temp, top_k, top_p, key):
    """Per-slot sampling: temp<=0 is greedy; otherwise temperature +
    top-k + nucleus (top-p) over one [V] logit row. k/p are traced, so
    masks come from one descending sort instead of static-k top_k."""
    greedy = jnp.argmax(logits)
    order = jnp.argsort(-logits)                 # descending
    ranks = jnp.argsort(order)                   # rank of each token
    scaled = logits / jnp.maximum(temp, 1e-6)
    sorted_probs = jax.nn.softmax(scaled[order])
    cum = jnp.cumsum(sorted_probs)
    k_mask = jnp.where(top_k > 0, ranks < top_k, True)
    # nucleus: keep tokens whose PRECEDING cumulative mass < p (always
    # keeps the top token)
    p_mask = (cum - sorted_probs)[ranks] < top_p
    masked = jnp.where(k_mask & p_mask, scaled, -1e30)
    sampled = jax.random.categorical(key, masked)
    return jnp.where(temp <= 0.0, greedy, sampled)


@jax.named_scope("sampling")    # HLO metadata only: names the ops' phase
def _pick_tokens(logits, temps, top_ks, top_ps, keys, lengths):
    """One token for each slot from logits [S, V]. The vocabulary is
    sorted only if a slot that is active (``lengths > 0``; a freed slot
    keeps its last request's temperature) samples (``temps > 0``): the
    predicate is one scalar for the batch, outside the ``vmap``, so the
    program runs one side. A greedy slot beside a sampling one gets its
    ``argmax`` from ``_pick_token`` as before."""
    greedy = jnp.argmax(logits, axis=-1)
    return jax.lax.cond(
        jnp.any((temps > 0.0) & (lengths > 0)),
        lambda: jax.vmap(_pick_token)(logits, temps, top_ks, top_ps, keys),
        lambda: greedy)


@jax.jit
def _pick_one(logits, temp, top_k, top_p, key):
    """An admission's first token: the batch picker on its one row."""
    return _pick_tokens(logits[None], temp[None], top_k[None], top_p[None],
                        key[None], jnp.ones(1, jnp.int32))[0]


@functools.partial(jax.jit, static_argnames=("cfg", "total", "pad_len"))
def _prefill_one(params, prompt_padded, n_valid, total, cfg, cos, sin,
                 pad_len):
    """Prefill one request on a fresh single-sequence cache. The padded
    tail writes stale K/V beyond ``n_valid``, which is harmless: decode
    overwrites position p before any query can attend it (the causal
    position mask admits keys <= the query position only), and the
    next-token logits are read AT position ``n_valid - 1``."""
    caches = [
        (jnp.zeros((total, cfg.n_kv_heads, cfg.head_dim), cfg.dtype),
         jnp.zeros((total, cfg.n_kv_heads, cfg.head_dim), cfg.dtype))
        for _ in range(cfg.n_layers)
    ]
    b_caches = [(kc[None], vc[None]) for kc, vc in caches]
    logits, new = _decode_step(params, prompt_padded[None], b_caches, 0,
                               cfg, cos, sin)
    return logits[0, n_valid - 1], [(kc[0], vc[0]) for kc, vc in new]
