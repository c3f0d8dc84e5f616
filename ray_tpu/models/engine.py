"""Continuous-batching generation engine.

The serving-side decode loop (the role vLLM plays for the reference;
here framework-native and TPU-shaped): S cache slots share one jitted
step, requests join/leave between steps — a long request never blocks a
short one, and the chip sees a full [S, 1] decode batch every step
instead of per-request batch-1 decodes.

Per-slot cache positions differ, so the step vmaps the single-sequence
cached attention over the slot axis (per-slot write offsets +
position-masked reads); XLA lowers that to batched scatters/gathers.
Inactive slots still flow through the math (their outputs are ignored)
— static shapes, one compilation.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..util import events as plane_events
from .llama import LlamaConfig, _decode_step, rope_frequencies


def _single_step(params, caches, tok, length, cfg, cos, sin):
    """One token for ONE sequence: caches are per-layer (k, v) WITHOUT a
    batch axis; ``length`` is this sequence's current position."""
    b_caches = [(kc[None], vc[None]) for kc, vc in caches]
    logits, new = _decode_step(params, tok[None, None], b_caches, length,
                               cfg, cos, sin)
    out = [(kc[0], vc[0]) for kc, vc in new]
    return logits[0, -1], out


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


@functools.partial(jax.jit, static_argnames=("cfg",))
def _step_all(params, caches, toks, lengths, temps, top_ks, top_ps,
              keys, cfg, cos, sin):
    """Vmapped engine step: every slot advances one token at its own
    position with its own sampling params. caches: per-layer
    (k [S,total,h,d], v [S,total,h,d])."""
    fn = jax.vmap(
        lambda c, t, l: _single_step(params, c, t, l, cfg, cos, sin),
        in_axes=(0, 0, 0))
    logits, new_caches = fn(caches, toks, lengths)
    splits = jax.vmap(jax.random.split)(keys)     # [S, 2, 2]
    toks_out = jax.vmap(_pick_token)(logits, temps, top_ks, top_ps,
                                     splits[:, 1])
    return toks_out, new_caches, splits[:, 0]


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


@dataclass
class _Slot:
    request_id: str
    length: int              # tokens currently in the slot's cache
    max_new: int             # emit exactly this many (or stop at eos)
    eos_id: Optional[int]
    emitted: List[int] = field(default_factory=list)
    done: bool = False


class GenerationEngine:
    """Slot-based continuous batching over one model replica.

    ``submit`` enqueues a request; ``step`` advances every active slot
    one token and returns the (request_id, token) events produced this
    step — token ``None`` marks completion (the serving layer streams
    these out). ``run_to_completion`` drives the loop synchronously for
    non-streaming callers.
    """

    def __init__(self, params, cfg: LlamaConfig, *, max_slots: int = 4,
                 max_len: int = 512):
        self.params = params
        self.cfg = cfg
        self.S = max_slots
        self.total = max_len
        self.cos, self.sin = rope_frequencies(cfg.head_dim, max_len,
                                              cfg.rope_theta)
        self.caches = [
            (jnp.zeros((self.S, max_len, cfg.n_kv_heads, cfg.head_dim),
                       cfg.dtype),
             jnp.zeros((self.S, max_len, cfg.n_kv_heads, cfg.head_dim),
                       cfg.dtype))
            for _ in range(cfg.n_layers)
        ]
        self.slots: List[Optional[_Slot]] = [None] * self.S
        self.last_tok = np.zeros(self.S, dtype=np.int32)
        self.temps = np.zeros(self.S, dtype=np.float32)   # 0 = greedy
        self.top_ks = np.zeros(self.S, dtype=np.int32)    # 0 = off
        self.top_ps = np.ones(self.S, dtype=np.float32)
        self.keys = np.stack([np.asarray(jax.random.PRNGKey(i))
                              for i in range(self.S)])
        self.pending: List[tuple] = []
        self._admit_events: List[tuple] = []
        # one padded-prefill compilation per bucket, not per prompt len
        self._prefill_buckets = (16, 64, 256)
        # what this step() did, for its ``serve.engine.step`` row
        self._steps = self._admitted = 0

    # ------------------------------------------------------------ admit
    def submit(self, request_id: str, prompt: List[int], *,
               max_new_tokens: int = 32, eos_id: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: Optional[int] = None) -> None:
        """``temperature=0`` (default) is greedy; otherwise temperature
        sampling with optional top-k and nucleus top-p, deterministic
        per ``seed``."""
        if len(prompt) + max_new_tokens + 1 > self.total:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) "
                f"exceeds engine max_len {self.total}")
        self.pending.append((request_id, list(prompt), max_new_tokens,
                             eos_id, float(temperature), int(top_k),
                             float(top_p), seed, time.perf_counter_ns()))

    def _admit(self):
        while self.pending and any(s is None for s in self.slots):
            self._admit_one(*self.pending.pop(0))

    def _admit_one(self, rid, prompt, max_new, eos_id, temp, top_k,
                   top_p, seed, submitted_ns):
        n = len(prompt)
        pad = next((b for b in self._prefill_buckets if b >= n),
                   self.total)
        # the paged engine's vocabulary (it adds the phases inside)
        with plane_events.span("serve.engine.admit", "serve",
                               rid=str(rid)[:8], prompt_len=n,
                               bucket=pad) as sp:
            if sp.sid:      # recorder on: submit() to this span's start
                sp.set(waited_ns=sp.t0_ns - submitted_ns)
            self._admitted += 1
            idx = self.slots.index(None)
            self.temps[idx] = temp
            self.top_ks[idx] = top_k
            self.top_ps[idx] = top_p
            if seed is not None:
                self.keys[idx] = np.asarray(jax.random.PRNGKey(seed))
            padded = jnp.asarray(
                prompt + [0] * (pad - n), dtype=jnp.int32)
            first_logits, seq_caches = _prefill_one(
                self.params, padded, n, self.total, self.cfg, self.cos,
                self.sin, pad)
            key = jnp.asarray(self.keys[idx], dtype=jnp.uint32)
            key, sub = jax.random.split(key)
            self.keys[idx] = np.array(key)
            first = _pick_one(first_logits, jnp.float32(temp),
                              jnp.int32(top_k), jnp.float32(top_p), sub)
            for li, (kc, vc) in enumerate(seq_caches):
                bk, bv = self.caches[li]
                self.caches[li] = (bk.at[idx].set(kc), bv.at[idx].set(vc))
            slot = _Slot(rid, length=n, max_new=max_new, eos_id=eos_id)
            # One scalar fetch per ADMITTED request (prefill emit);
            # the decode loop fetches one np.asarray batch per step.
            tok = int(first)  # raylint: disable=RTL111
            slot.emitted.append(tok)
            self.last_tok[idx] = tok
            self._admit_events.append((rid, tok))
            if (eos_id is not None and tok == eos_id) or \
                    len(slot.emitted) >= max_new:
                slot.done = True  # reaped by the next step()
            self.slots[idx] = slot

    # ------------------------------------------------------------- step
    def step(self) -> List[tuple]:
        """Admit pending, advance active slots one token. Returns the
        (request_id, token) events emitted this step in order; a token
        of ``None`` marks that request's completion."""
        self._admitted = 0
        with plane_events.span("serve.engine.step", "serve",
                               k=self._steps) as sp:
            self._steps += 1
            events, active = self._step()
            sp.set(active=active, admitted=self._admitted,
                   tokens=sum(1 for _, tok in events if tok is not None),
                   pending=len(self.pending))
        return events

    def _step(self):
        """-> (events, slots that decoded)."""
        self._admit()
        events: List[tuple] = list(self._admit_events)
        self._admit_events = []
        # reap slots finished at admit time (short max_new / instant eos)
        for i, s in enumerate(self.slots):
            if s is not None and s.done:
                events.append((s.request_id, None))
                self.slots[i] = None
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return events, 0
        lengths = np.array([self.slots[i].length if self.slots[i] else 0
                            for i in range(self.S)], dtype=np.int32)
        toks, self.caches, new_keys = _step_all(
            self.params, self.caches, jnp.asarray(self.last_tok),
            jnp.asarray(lengths), jnp.asarray(self.temps),
            jnp.asarray(self.top_ks), jnp.asarray(self.top_ps),
            jnp.asarray(self.keys, dtype=jnp.uint32), self.cfg,
            self.cos, self.sin)
        toks = np.asarray(toks)
        self.keys = np.array(new_keys)  # writable copy
        for i in active:
            s = self.slots[i]
            tok = int(toks[i])
            s.length += 1
            s.emitted.append(tok)
            self.last_tok[i] = tok
            events.append((s.request_id, tok))
            if (s.eos_id is not None and tok == s.eos_id) or \
                    len(s.emitted) >= s.max_new:
                s.done = True
                events.append((s.request_id, None))
                self.slots[i] = None
        return events, len(active)

    def has_work(self) -> bool:
        return bool(self.pending) or any(s is not None
                                         for s in self.slots)

    def run_to_completion(self) -> Dict[str, List[int]]:
        """Drive until every submitted request finishes; returns each
        request's full token list."""
        results: Dict[str, List[int]] = {}
        acc: Dict[str, List[int]] = {}
        while self.has_work():
            for rid, tok in self.step():
                if tok is None:
                    results[rid] = acc.pop(rid, [])
                else:
                    acc.setdefault(rid, []).append(tok)
        return results
