"""LLM serving: a deployment hosting the continuous-batching engine.

The reference serves LLMs by embedding vLLM inside Serve deployments;
the TPU-native equivalent pairs ``models/paged.py``'s slot-based
continuous batching over a paged KV cache with an ordinary Serve
deployment: unary calls get the full token list, streaming calls get
tokens as the engine emits them, and concurrent requests share every
decode step.
"""

from __future__ import annotations

import asyncio
import time
import uuid
from typing import Any, Dict, Optional

from ray_tpu.util import events as plane_events

# NB: `serve.deployment` the attribute shadows the submodule; import
# the decorator from the module itself.
from .deployment import deployment as _deployment


class LLMServer:
    """Serve callable hosting one :class:`~ray_tpu.models.paged.PagedEngine`.

    Construct via ``build_llm_app`` (which wraps it in a deployment) or
    directly inside ``@serve.deployment`` with a params/config factory —
    the factory runs replica-side, so weights never ride the deploy RPC.
    Requests: ``{"prompt": [token ids], "max_new_tokens": n,
    "eos_id": optional, "stream": bool}``.

    ``num_pages=None`` sizes the pool so that ``max_slots`` sequences of
    ``max_len`` fit at once and no request waits for memory; a smaller
    pool admits by pages and preempts by recompute when it runs dry.

    ``generation_defaults`` (``{"temperature": ...}``, the one field a
    configuration has needed) is the deployment's sampling for a request that
    does not say: what a model's ``generation_config.json`` is to a serving
    system. A request's own field wins; without the keyword a silent request
    is greedy, as before.

    Whether a step drafts is the model's own: a family whose tree holds an
    MTP module (``DeepseekV3Config.n_nextn``) is stepped with its drafts
    inside the engine, one or two tokens a slot a step, with no keyword here
    (``draft_factory`` is the older batch-1 path beside the engine).
    """

    def __init__(self, model_factory, *, max_slots: int = 4,
                 max_len: int = 512, kv_cache: str = "paged",
                 num_pages: Optional[int] = None, page_size: int = 16,
                 enable_prefix_cache: bool = False,
                 kv_dtype: str = "model",
                 draft_factory=None, draft_k: int = 4,
                 generation_defaults: Optional[Dict[str, Any]] = None):
        unknown = set(generation_defaults or ()) - {"temperature"}
        if unknown:
            raise ValueError(f"generation_defaults: unknown {sorted(unknown)}")
        self._default_temperature = float(
            (generation_defaults or {}).get("temperature", 0.0))
        # Selects nothing: accepted for callers that still pass the one
        # value left (the benchmark's configuration files).
        if kv_cache != "paged":
            raise ValueError(
                f"kv_cache={kv_cache!r}: PagedEngine (models/paged.py) is "
                f"the only serving engine; leave the keyword out")
        with plane_events.span("serve.replica.weights", "serve"):
            params, cfg = model_factory()
        # Speculative decoding: a replica-side draft factory (a distilled
        # checkpoint loader, or models.speculative.truncated_draft over
        # the target). Requests opting in with {"speculative": true} run
        # the verify-k loop instead of the slot engine — batch-1 latency
        # path; batched throughput stays on the engine.
        self._spec = None
        self._max_len = max_len
        self._max_slots = max_slots
        self._spec_sem: Optional[asyncio.Semaphore] = None
        self._cfg = cfg
        self._draft_factory = draft_factory
        self._weights_version = 1
        # Speculative serving counters (surfaced via {"_admin": "stats"}):
        # the inflight peak proves the _spec_sem admission bound held,
        # the round/accept totals are the replica's REAL acceptance
        # telemetry (device-computed, one fetch per generation).
        self._spec_inflight = 0
        self._spec_peak = 0
        self._spec_requests = 0
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        if draft_factory is not None:
            draft_params, draft_cfg = draft_factory(params, cfg)
            self._spec = (params, cfg, draft_params, draft_cfg, draft_k)
        from ray_tpu.models.paged import PagedEngine

        if num_pages is None:   # every slot at max_len, + scratch page 0
            num_pages = max_slots * (max_len // page_size) + 1
        # pools, tables and the first device allocations
        with plane_events.span("serve.replica.engine", "serve",
                               slots=max_slots, pages=num_pages):
            self.engine = PagedEngine(
                params, cfg, max_slots=max_slots, num_pages=num_pages,
                page_size=page_size, max_len=max_len,
                enable_prefix_cache=enable_prefix_cache, kv_dtype=kv_dtype)
        self._queues: Dict[str, asyncio.Queue] = {}
        self._loop_task: Optional[asyncio.Task] = None
        # Serializes engine stepping against live weight refresh: step()
        # runs in an executor thread while a controller-path reconfigure
        # runs in ANOTHER executor thread — an unsynchronized
        # invalidate_prefix_cache could free a page mid-_admit
        # (double-alloc + double-free) or let an old-weight admit
        # re-register prefix pages AFTER the invalidation wiped them.
        import threading

        self._engine_lock = threading.Lock()

    # ----------------------------------------------------- engine pump
    def _ensure_loop(self):
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(
                self._engine_loop())

    def _locked_step(self):
        """-> (events, ns the executor waited for the engine lock, the
        monotonic instant step() returned: the pump's hand-off starts
        there, in this thread)."""
        t_enter = time.perf_counter_ns()
        with self._engine_lock:
            t_locked = time.perf_counter_ns()
            events = self.engine.step()
            return events, t_locked - t_enter, time.perf_counter_ns()

    async def _engine_loop(self):
        loop = asyncio.get_running_loop()
        while self.engine.has_work():
            # The jitted step is device-bound; run it off the event loop
            # so health checks / new submissions stay responsive.
            events, lock_wait_ns, t_step_end = await loop.run_in_executor(
                None, self._locked_step)
            tokens = 0
            for rid, tok in events:
                q = self._queues.get(rid)
                if q is not None:
                    q.put_nowait(tok)
                    tokens += tok is not None
            if tokens:
                # step()'s return in the executor thread -> the last
                # token queued on the event loop: the hop the engine's
                # own spans cannot see.
                plane_events.span_done(
                    "serve.pump.deliver", "serve", t_step_end,
                    tokens=tokens, lock_wait_ns=lock_wait_ns)
            await asyncio.sleep(0)

    def _submit(self, body: dict) -> str:
        rid = uuid.uuid4().hex
        self._queues[rid] = asyncio.Queue()
        # The request's first row in the replica, on the engine rows'
        # clock and under their ``rid``.
        plane_events.emit("serve.req.queue", plane="serve",
                          tenant=str(body.get("tenant") or ""),
                          rid=rid[:8], prompt_len=len(body["prompt"]),
                          weights_version=self._weights_version,
                          queued=len(self._queues),
                          t0_ns=time.perf_counter_ns())
        try:
            self.engine.submit(rid, [int(t) for t in body["prompt"]],
                               max_new_tokens=int(
                                   body.get("max_new_tokens", 32)),
                               eos_id=body.get("eos_id"),
                               temperature=float(body.get(
                                   "temperature",
                                   self._default_temperature)),
                               top_k=int(body.get("top_k", 0)),
                               top_p=float(body.get("top_p", 1.0)),
                               seed=body.get("seed"))
        except Exception:
            # A rejected submit (bad prompt, over max_len) must not
            # strand its freshly-inserted queue entry forever.
            self._queues.pop(rid, None)
            raise
        self._ensure_loop()
        return rid

    @staticmethod
    def _body(request: Any) -> dict:
        if isinstance(request, dict):
            return request
        if hasattr(request, "json"):
            return request.json()
        raise TypeError(f"unsupported request: {type(request)}")

    # ------------------------------------------------------- handlers
    async def __call__(self, request: Any):
        body = self._body(request)
        if body.get("_admin"):
            return self._admin(body)
        if body.get("speculative"):
            return await self._speculative(body)
        if body.get("stream"):
            return self._stream(body)
        t0_ns = time.perf_counter_ns()
        rid = self._submit(body)
        q = self._queues[rid]
        toks = []
        try:
            while True:
                tok = await q.get()
                if tok is None:
                    break
                if not toks:
                    self._first_token(body, rid, t0_ns)
                toks.append(tok)
        finally:
            self._queues.pop(rid, None)
        return {"tokens": toks, "num_tokens": len(toks)}

    def _first_token(self, body: dict, rid: str, t0_ns: int):
        """Handler entry -> first token out of the engine's queue."""
        plane_events.span_done(
            "serve.req.first_token", "serve", t0_ns,
            tenant=str(body.get("tenant") or ""), rid=rid[:8],
            weights_version=self._weights_version)

    async def _speculative(self, body: dict):
        """Batch-1 speculative decode; response carries the round stats
        (acceptance rate, tokens per target forward) so callers can see
        the draft's real speedup, not an assumed one."""
        if self._spec is None:
            raise ValueError(
                "speculative request but no draft_factory configured")
        import asyncio as _asyncio

        import jax.numpy as jnp

        from ray_tpu.models.speculative import generate_speculative

        params, cfg, dparams, dcfg, k = self._spec
        prompt = jnp.asarray([[int(t) for t in body["prompt"]]], jnp.int32)
        max_new = int(body.get("max_new_tokens", 32))
        k = int(body.get("k", k))
        # Same admission bound as the engine path (PagedEngine.submit):
        # the speculative KV caches are sized prompt + max_new + k + 1.
        total = prompt.shape[1] + max_new + k + 1
        if k < 1 or total > self._max_len:
            raise ValueError(
                f"prompt+max_new_tokens+k+1 = {total} exceeds engine "
                f"max_len {self._max_len} (or k < 1)")
        # Same admission budget as the engine: at most max_slots
        # speculative decodes in flight (each allocates its own target +
        # draft KV caches); excess requests queue on the semaphore.
        if self._spec_sem is None:
            self._spec_sem = _asyncio.Semaphore(self._max_slots)
        loop = _asyncio.get_running_loop()
        async with self._spec_sem:
            self._spec_inflight += 1
            self._spec_peak = max(self._spec_peak, self._spec_inflight)
            try:
                toks, stats = await loop.run_in_executor(
                    None, lambda: generate_speculative(
                        params, dparams, prompt, cfg, dcfg,
                        max_new=max_new, k=k))
            finally:
                self._spec_inflight -= 1
        self._spec_requests += 1
        self._spec_rounds += stats["rounds"]
        self._spec_drafted += stats["drafted"]
        self._spec_accepted += stats["accepted"]
        # toks is the single device fetch's host array — int() here is a
        # plain numpy read, not a per-token D2H sync.
        out = [int(t) for t in toks[0]]
        return {"tokens": out, "num_tokens": len(out),
                "speculative_stats": stats}

    # ------------------------------------------- admin / weight refresh
    def _admin(self, body: dict):
        op = body["_admin"]
        if op == "stats":
            from ray_tpu._private.jax_platform import device_report

            drafted = max(self._spec_drafted, 1)
            return {
                # platform, device_kind, device_count, pid, bytes in use
                # of the process that holds the model
                "device": device_report(),
                "weights_version": self._weights_version,
                "active_requests": len(self._queues),
                "spec_requests": self._spec_requests,
                "spec_inflight": self._spec_inflight,
                "spec_inflight_peak": self._spec_peak,
                "spec_rounds": self._spec_rounds,
                "spec_drafted": self._spec_drafted,
                "spec_accepted": self._spec_accepted,
                "spec_acceptance_rate": self._spec_accepted / drafted,
                "spec_admission_bound": self._max_slots,
            }
        raise ValueError(f"unknown _admin op {op!r}")

    def reconfigure(self, user_config):
        """Live weight refresh (controller ``reconfigure`` fan-out or a
        direct ``handle.reconfigure.remote``): ``{"weights_ref": ref}``
        replaces the engine's and the speculative pair's parameters
        without dropping in-flight requests. The ref rides the
        cooperative-broadcast object plane — the driver puts the new
        checkpoint ONCE and every replica pulls chunks peer-to-peer —
        so a mid-load refresh never funnels N full copies through the
        source node.

        Loop-aware: the controller fan-out calls this from an executor
        thread (blocking fetch is fine); a handle-routed call lands ON
        the replica's event loop, where a blocking ``ray_tpu.get``
        would deadlock the loop that must deliver the object — so that
        path gets a coroutine (awaited by the async dispatcher) that
        offloads the fetch to the executor."""
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return self._refresh_weights(user_config)

        async def _run():
            await asyncio.get_running_loop().run_in_executor(
                None, self._refresh_weights, user_config)

        return _run()

    def _refresh_weights(self, user_config):
        if not isinstance(user_config, dict):
            return
        params = user_config.get("weights")
        ref = user_config.get("weights_ref")
        if ref is not None:
            import ray_tpu

            params = ray_tpu.get(ref)
        if params is None:
            return
        import jax
        import jax.numpy as jnp

        # Store views deserialize as host arrays; commit them to device
        # once, NOT per engine step.
        params = jax.tree_util.tree_map(jnp.asarray, params)
        # Atomic w.r.t. engine steps (the pump holds the same lock):
        # the param swap and the prefix-cache invalidation land BETWEEN
        # steps, so no in-flight _admit can allocate a just-freed page
        # or re-register old-weight pages after the wipe.
        with self._engine_lock:
            self.engine.params = params
            # Cached prefix pages hold K/V computed with the OLD
            # weights — a post-refresh hit would seed sequences with
            # stale state matching neither checkpoint's greedy.
            self.engine.invalidate_prefix_cache()
        if self._spec is not None:
            dparams, dcfg = self._draft_factory(params, self._cfg)
            # Single-writer handoff: reconfigure calls are serialized by
            # the serve controller, and the loop-side readers
            # (_speculative, _admin) deref the tuple exactly once — they
            # see the old or the new weights atomically, never a mix.
            self._spec = (params, self._cfg, dparams, dcfg,  # raylint: disable=RTL151 (single-writer atomic tuple rebind; readers deref once)
                          self._spec[4])
        self._weights_version += 1  # raylint: disable=RTL151 (single-writer counter — reconfigures are controller-serialized)

    async def _stream(self, body: dict):
        t0_ns = time.perf_counter_ns()
        rid = self._submit(body)
        q = self._queues[rid]
        first = True
        try:
            while True:
                tok = await q.get()
                if tok is None:
                    return
                if first:
                    first = False
                    self._first_token(body, rid, t0_ns)
                yield tok
        finally:
            self._queues.pop(rid, None)


def build_llm_app(model_factory, *, max_slots: int = 4,
                  max_len: int = 512, num_replicas: int = 1,
                  num_pages: Optional[int] = None, page_size: int = 16,
                  enable_prefix_cache: bool = False,
                  kv_dtype: str = "model",
                  draft_factory=None, draft_k: int = 4,
                  generation_defaults: Optional[Dict[str, Any]] = None):
    """Bind an LLM serving app (reference shape: ``serve.llm``
    builders): ``serve.run(build_llm_app(factory))`` serves from
    ``models/paged.py``'s engine, its page pool sized so that no request
    waits for memory unless ``num_pages`` says otherwise (see
    :class:`LLMServer`). ``draft_factory=(params, cfg) -> (draft_params,
    draft_cfg)`` enables the speculative request path (e.g. ``lambda p,
    c: truncated_draft(p, c, n_layers)``).

    On a cluster that reports ``TPU`` chips each replica asks for one: the
    scheduler then starts it in a worker that may own the chip (every
    other worker is pinned to the CPU) and never beside another chip
    holder. Chips appear in ``cluster_resources()`` once the node's probe
    has run, so build the app after that."""
    import ray_tpu

    on_tpu = (ray_tpu.is_initialized()
              and ray_tpu.cluster_resources().get("TPU", 0) >= 1)
    dep = _deployment(LLMServer, num_replicas=num_replicas,
                      ray_actor_options={"num_tpus": 1} if on_tpu else None)
    return dep.bind(model_factory, max_slots=max_slots, max_len=max_len,
                    num_pages=num_pages, page_size=page_size,
                    enable_prefix_cache=enable_prefix_cache,
                    kv_dtype=kv_dtype,
                    draft_factory=draft_factory, draft_k=draft_k,
                    generation_defaults=generation_defaults)
