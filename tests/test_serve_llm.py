"""LLM serving (serve/llm.py): continuous-batching engine behind a Serve
deployment — unary and streaming, concurrent requests sharing decode
steps, outputs exactly matching per-request greedy decode."""

import threading

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import LlamaConfig, generate_greedy, init_params


def tiny_model():
    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def _ref(prompt, n):
    params, cfg = tiny_model()
    return generate_greedy(
        params, jnp.asarray(prompt, jnp.int32)[None, :], cfg,
        max_new=n)[0].tolist()


@pytest.fixture(scope="module")
def llm_app():
    from ray_tpu.serve.llm import build_llm_app

    ray_tpu.init(num_cpus=4, probe_tpu=False, ignore_reinit_error=True)
    handle = serve.run(build_llm_app(tiny_model, max_slots=3,
                                     max_len=96),
                       name="llm-app", route_prefix="/llm")
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


def test_unary_generation(llm_app):
    got = llm_app.remote({"prompt": [1, 2, 3],
                          "max_new_tokens": 10}).result(timeout=120)
    assert got["tokens"] == _ref([1, 2, 3], 10)
    assert got["num_tokens"] == 10


def test_concurrent_requests_share_the_engine(llm_app):
    reqs = {"a": ([4, 5, 6, 7], 8), "b": ([9], 12), "c": ([11, 12], 5)}
    futs = {rid: llm_app.remote({"prompt": p, "max_new_tokens": n})
            for rid, (p, n) in reqs.items()}
    for rid, (p, n) in reqs.items():
        got = futs[rid].result(timeout=120)
        assert got["tokens"] == _ref(p, n), rid


def test_streaming_generation(llm_app):
    import asyncio

    async def collect():
        return [t async for t in llm_app.stream(
            {"prompt": [20, 21, 22], "max_new_tokens": 6,
             "stream": True})]

    toks = asyncio.run(collect())
    assert toks == _ref([20, 21, 22], 6)


def test_http_llm_endpoint(llm_app):
    import requests

    port = serve.get_proxy_port()
    r = requests.post(f"http://127.0.0.1:{port}/llm",
                      json={"prompt": [1, 2, 3], "max_new_tokens": 4},
                      timeout=120)
    assert r.status_code == 200
    assert r.json()["tokens"] == _ref([1, 2, 3], 4)


def test_sampled_request(llm_app):
    a = llm_app.remote({"prompt": [1, 2, 3], "max_new_tokens": 8,
                        "temperature": 0.9, "top_k": 20,
                        "seed": 5}).result(timeout=120)
    b = llm_app.remote({"prompt": [1, 2, 3], "max_new_tokens": 8,
                        "temperature": 0.9, "top_k": 20,
                        "seed": 5}).result(timeout=120)
    assert a["tokens"] == b["tokens"]  # seeded sampling is reproducible
    assert len(a["tokens"]) == 8


def test_paged_llm_app(llm_app):
    from ray_tpu.serve.llm import build_llm_app

    handle = serve.run(build_llm_app(tiny_model, max_slots=4,
                                     num_pages=24,   # < 4 x 96 positions
                                     page_size=8, max_len=96),
                       name="llm-paged", route_prefix=None)
    got = handle.remote({"prompt": [2, 3, 4],
                         "max_new_tokens": 9}).result(timeout=120)
    assert got["tokens"] == _ref([2, 3, 4], 9)


def test_submit_failure_does_not_leak_queue(llm_app):
    """A rejected submit (prompt over max_len) must pop its freshly
    inserted response queue — before the fix, every bad request grew
    ``_queues`` forever."""
    with pytest.raises(Exception):
        llm_app.remote({"prompt": list(range(120)),
                        "max_new_tokens": 50}).result(timeout=120)
    stats = llm_app.remote({"_admin": "stats"}).result(timeout=120)
    assert stats["active_requests"] == 0
    # Service is intact after the rejected request.
    got = llm_app.remote({"prompt": [5, 6], "max_new_tokens": 4}
                         ).result(timeout=120)
    assert got["tokens"] == _ref([5, 6], 4)


def test_speculative_admission_bounded_by_spec_sem(llm_app):
    """Concurrent speculative requests stay bounded by the _spec_sem
    admission semaphore (max_slots): the replica-side inflight peak —
    tracked inside the semaphore — never exceeds the bound, and every
    request still returns the exact greedy tokens."""
    from ray_tpu.models.speculative import truncated_draft
    from ray_tpu.serve.llm import build_llm_app

    handle = serve.run(
        build_llm_app(tiny_model, max_slots=2, max_len=96,
                      draft_factory=lambda p, c: truncated_draft(p, c, 1),
                      draft_k=3),
        name="llm-spec-sem", route_prefix="/llm-spec-sem")
    futs = [handle.remote({"prompt": [1, 2, 3], "max_new_tokens": 8,
                           "speculative": True}) for _ in range(6)]
    ref = _ref([1, 2, 3], 8)
    for f in futs:
        got = f.result(timeout=300)
        assert got["tokens"] == ref
        assert got["speculative_stats"]["host_fetches"] == 1
    stats = handle.remote({"_admin": "stats"}).result(timeout=120)
    assert stats["spec_requests"] == 6
    assert stats["spec_inflight"] == 0
    assert 1 <= stats["spec_inflight_peak"] <= 2, stats
    assert stats["spec_admission_bound"] == 2


def test_live_weight_refresh_via_reconfigure(llm_app):
    """reconfigure({"weights_ref": ref}) swaps the replica's weights
    from an object-plane ref (the broadcast path: one driver put, every
    replica pulls) without redeploy: post-refresh outputs match the NEW
    checkpoint's greedy decode exactly and the version counter bumps."""
    import numpy as np

    from ray_tpu.models import generate_greedy, init_params
    from ray_tpu.serve.llm import build_llm_app

    handle = serve.run(build_llm_app(tiny_model, max_slots=2,
                                     max_len=96),
                       name="llm-refresh", route_prefix="/llm-refresh")
    before = handle.remote({"prompt": [7, 8, 9],
                            "max_new_tokens": 8}).result(timeout=120)
    assert before["tokens"] == _ref([7, 8, 9], 8)

    _, cfg = tiny_model()
    new_params = init_params(cfg, jax.random.PRNGKey(1))
    host_tree = jax.tree_util.tree_map(lambda a: np.asarray(a),
                                       new_params)
    ref = ray_tpu.put(host_tree)
    assert handle.reconfigure.remote(
        {"weights_ref": ref}).result(timeout=120) is None
    after = handle.remote({"prompt": [7, 8, 9],
                           "max_new_tokens": 8}).result(timeout=120)
    want = generate_greedy(
        new_params, jnp.asarray([[7, 8, 9]], jnp.int32), cfg,
        max_new=8)[0].tolist()
    assert after["tokens"] == want
    assert after["tokens"] != before["tokens"]
    stats = handle.remote({"_admin": "stats"}).result(timeout=120)
    assert stats["weights_version"] == 2


def test_weight_refresh_invalidates_prefix_cache(llm_app):
    """Paged engine + prefix cache + live refresh: cached K/V pages were
    computed with the OLD weights, so a post-refresh prefix hit would
    seed the sequence with stale state (output matching NEITHER
    checkpoint). The refresh must invalidate the cache — the repeated
    prompt's output must be the NEW checkpoint's exact greedy decode."""
    import numpy as np

    from ray_tpu.models import generate_greedy, init_params
    from ray_tpu.serve.llm import build_llm_app

    handle = serve.run(
        build_llm_app(tiny_model, max_slots=2,
                      num_pages=24, page_size=8, max_len=96,
                      enable_prefix_cache=True),
        name="llm-paged-refresh", route_prefix="/llm-paged-refresh")
    # Page-aligned prompt so its full pages land in the prefix cache.
    prompt = list(range(10, 26))  # 16 tokens = 2 full pages
    before = handle.remote({"prompt": prompt,
                            "max_new_tokens": 8}).result(timeout=120)
    assert before["tokens"] == _ref(prompt, 8)
    # Warm the cache hit path (same prompt again, old weights: same out).
    again = handle.remote({"prompt": prompt,
                           "max_new_tokens": 8}).result(timeout=120)
    assert again["tokens"] == before["tokens"]

    _, cfg = tiny_model()
    new_params = init_params(cfg, jax.random.PRNGKey(2))
    ref = ray_tpu.put(jax.tree_util.tree_map(lambda a: np.asarray(a),
                                             new_params))
    handle.reconfigure.remote({"weights_ref": ref}).result(timeout=120)
    after = handle.remote({"prompt": prompt,
                           "max_new_tokens": 8}).result(timeout=120)
    want = generate_greedy(
        new_params, jnp.asarray([prompt], jnp.int32), cfg,
        max_new=8)[0].tolist()
    assert after["tokens"] == want  # stale pages would break this


def test_speculative_request_path(llm_app):
    """serve.llm speculative wiring (VERDICT r4 directive #8): a replica-
    side draft_factory (truncated-layer draft of the target) serves
    {"speculative": true} requests with exact engine-greedy parity and
    reports real round stats."""
    from ray_tpu.models.speculative import truncated_draft
    from ray_tpu.serve.llm import build_llm_app

    handle = serve.run(
        build_llm_app(tiny_model, max_slots=2, max_len=96,
                      draft_factory=lambda p, c: truncated_draft(p, c, 1),
                      draft_k=3),
        name="llm-spec", route_prefix="/llm-spec")
    got = handle.remote({"prompt": [1, 2, 3], "max_new_tokens": 10,
                         "speculative": True}).result(timeout=180)
    assert got["tokens"] == _ref([1, 2, 3], 10)
    stats = got["speculative_stats"]
    assert stats["rounds"] >= 1
    assert 0.0 <= stats["acceptance_rate"] <= 1.0
    # The engine path (no speculative flag) must agree token-for-token.
    plain = handle.remote({"prompt": [1, 2, 3],
                           "max_new_tokens": 10}).result(timeout=180)
    assert plain["tokens"] == got["tokens"]
    # No draft configured -> explicit error, not silent fallback.
    with pytest.raises(Exception):
        llm_app.remote({"prompt": [1], "max_new_tokens": 4,
                        "speculative": True}).result(timeout=120)


# ------------------------------------- the replica nobody configured
# In-process, no cluster: the constructor and the engine it builds.

def test_default_replica_is_paged_and_never_waits_for_pages():
    """``LLMServer(factory)`` sizes its pool from ``max_slots`` and
    ``max_len``: every slot can run to ``max_len`` at once, so no request
    waits for a page and none is preempted."""
    from ray_tpu.models.paged import PagedEngine
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(tiny_model, max_slots=2, max_len=64)
    eng = server.engine
    assert isinstance(eng, PagedEngine)
    assert eng.num_pages == 2 * (64 // 16) + 1
    prompts = {"a": [1, 2, 3], "b": [7, 8, 9, 10, 11]}
    for rid, prompt in prompts.items():     # each fills its max_len
        eng.submit(rid, prompt, max_new_tokens=64 - len(prompt) - 1)
    got = {rid: [] for rid in prompts}
    fewest_free = eng.num_pages
    while eng.has_work():
        for rid, tok in eng.step():
            if tok is not None:
                got[rid].append(tok)
        assert eng._preempted == 0 and not eng.pending
        fewest_free = min(fewest_free, len(eng.free_pages))
    assert fewest_free == 0     # the pool is that size and no larger
    for rid, prompt in prompts.items():
        assert got[rid] == _ref(prompt, 64 - len(prompt) - 1), rid


def test_explicit_num_pages_is_used_as_given():
    """A configuration that sized its pool to the chip (every cell of the
    benchmark) gets that pool, not the derived one."""
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(tiny_model, max_slots=4, max_len=96, num_pages=24,
                       page_size=8)
    assert server.engine.num_pages == 24 < 4 * (96 // 8) + 1
    assert len(server.engine.free_pages) == 23      # page 0 is scratch


def test_kv_cache_other_than_paged_is_refused():
    """One engine: the keyword the benchmark's configuration files still
    pass is accepted with the one value left, and selects nothing."""
    from ray_tpu.models.paged import PagedEngine
    from ray_tpu.serve.llm import LLMServer

    with pytest.raises(ValueError, match="PagedEngine"):
        LLMServer(tiny_model, kv_cache="dense")
    server = LLMServer(tiny_model, max_slots=2, max_len=64,
                       kv_cache="paged")
    assert isinstance(server.engine, PagedEngine)
