"""The sampler and the single-sequence prefill program.

What the serving engine (``models/paged.py::PagedEngine``) shares with
the benchmark's reference checks. ``_pick_tokens`` picks one token for
every slot of a step from its [S, V] logits, and is where greedy and
sampled decoding part: one ``lax.cond`` on the batch, inside the program,
runs the sort-and-sample path (``_pick_token`` under ``vmap``: temperature
with top-k and nucleus top-p over one [V] row) only when an active slot has
a temperature above 0, and an ``argmax`` otherwise. Every family's step
program ends in it (``_sample``), and ``_pick_one`` is the same picker on
the one row of an admission's first token. ``_prefill_one`` runs one padded
prompt through a fresh single-sequence cache and returns the next-token
logits and the dense per-layer K/V that the engine scatters into its pages;
``prefill_in_chunks`` is the host loop of the families whose prompts are
admitted chunk by chunk instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def _sample(logits, temps, top_ks, top_ps, keys, lengths, counts=None):
    """The end of every family's step program, traced inline: each slot's
    key split, one token picked from logits [S, V]. -> (int32[S + len(counts)]:
    the tokens, then what the family counted in the step, so that one transfer
    fetches all; the keys after the step; the tokens alone, int32[S], as the
    next step takes them: with the keys they let the engine dispatch that
    step before it has fetched this one's)."""
    splits = jax.vmap(jax.random.split)(keys)
    picked = _pick_tokens(logits, temps, top_ks, top_ps, splits[:, 1],
                          lengths).astype(jnp.int32)
    out = picked if counts is None else jnp.concatenate([picked, counts])
    return out, splits[:, 0], picked


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


def prefill_in_chunks(program, params, prompt, chunk: int, carry: tuple, cfg,
                      keep: bool = False):
    """Prefill one request chunk by chunk: a host loop over ONE program, so
    the work grows with the prompt in steps of ``chunk`` and nothing compiles
    per length. ``program(params, tokens [chunk], start, n_valid, *carry, cfg)
    -> (logits, *carry, extra)`` sees the prompt padded to whole chunks, the
    chunk's first position and the prompt's length. -> (the last chunk's
    logits: the row at ``len(prompt) - 1`` lies there; the carry after it;
    with ``keep`` every prompt position's ``extra``, each chunk's
    [layers, chunk, ...] joined along the positions, else None)."""
    n = len(prompt)
    chunks = -(-n // chunk)
    padded = np.zeros(chunks * chunk, np.int32)
    padded[:n] = prompt
    extras = []
    for c in range(chunks):
        first, *carry, extra = program(
            params, padded[c * chunk:(c + 1) * chunk], np.int32(c * chunk),
            np.int32(n), *carry, cfg)
        if keep:
            extras.append(extra)
    kept = np.asarray(jnp.concatenate(extras, 1))[:, :n] if keep else None
    return first, carry, kept
