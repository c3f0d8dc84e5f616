"""The sampler and the single-sequence prefill program.

What the serving engine (``models/paged.py::PagedEngine``) shares with
the benchmark's reference checks: ``_pick_token`` samples one token from
one [V] logit row (greedy, or temperature with top-k and nucleus top-p),
under ``vmap`` inside the step programs and, as ``_pick_one``, alone for
an admission's first token; ``_prefill_one`` runs one padded prompt
through a fresh single-sequence cache and returns the next-token logits
and the dense per-layer K/V that the engine scatters into its pages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .llama import _decode_step


@jax.named_scope("sampling")    # HLO metadata only: names the ops' phase
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


_pick_one = jax.jit(_pick_token)


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
