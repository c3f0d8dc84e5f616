"""Continuous-batching engine (models/paged.py) and its sampler
(models/engine.py): interleaved requests of different lengths must
produce EXACTLY what per-request greedy decode produces, and slots must
recycle."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import LlamaConfig, generate_greedy, init_params
from ray_tpu.models.paged import PagedEngine


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _ref(params, cfg, prompt, n):
    out = generate_greedy(params,
                          jnp.asarray(prompt, jnp.int32)[None, :], cfg,
                          max_new=n)
    return out[0].tolist()


@pytest.mark.parametrize("page_size", [4, 16])
def test_batched_equals_sequential(model, page_size):
    """Request "d" takes the slot "b" leaves: with pages of 4 both cross
    page boundaries, with pages of 16 each lives inside one page."""
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=3, max_len=96,
                      page_size=page_size, num_pages=3 * 96 // page_size + 1)
    prompts = {
        "a": ([1, 2, 3, 4], 12),
        "b": ([7, 8], 5),            # finishes early, frees its slot
        "c": ([10, 11, 12, 13, 14, 15], 9),
        "d": ([20, 21], 7),          # admitted once a slot frees
    }
    for rid, (p, n) in prompts.items():
        eng.submit(rid, p, max_new_tokens=n)
    got = eng.run_to_completion()
    assert set(got) == set(prompts)
    for rid, (p, n) in prompts.items():
        assert got[rid] == _ref(params, cfg, p, n), rid


def test_eos_stops_early(model):
    cfg, params = model
    ref = _ref(params, cfg, [5, 6, 7], 20)
    eos = ref[4]  # force an early stop at the 5th generated token
    eng = PagedEngine(params, cfg, max_slots=2, max_len=96)
    eng.submit("x", [5, 6, 7], max_new_tokens=20, eos_id=eos)
    got = eng.run_to_completion()
    assert got["x"] == ref[:5]


def test_capacity_guard(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=1, max_len=32)
    with pytest.raises(ValueError, match="exceeds per-sequence capacity"):
        eng.submit("big", list(range(20)), max_new_tokens=20)


def test_sampling_deterministic_and_bounded(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, max_len=64)
    eng.submit("s1", [1, 2, 3], max_new_tokens=10, temperature=0.8,
               top_k=10, seed=42)
    eng.submit("greedy", [1, 2, 3], max_new_tokens=10)  # temp 0
    got = eng.run_to_completion()
    # greedy slot unchanged by its sampled neighbor
    assert got["greedy"] == _ref(params, cfg, [1, 2, 3], 10)
    assert len(got["s1"]) == 10
    # same seed -> same sample; different seed -> (almost surely) differs
    eng2 = PagedEngine(params, cfg, max_slots=1, max_len=64)
    eng2.submit("s1", [1, 2, 3], max_new_tokens=10, temperature=0.8,
                top_k=10, seed=42)
    assert eng2.run_to_completion()["s1"] == got["s1"]
    eng3 = PagedEngine(params, cfg, max_slots=1, max_len=64)
    eng3.submit("s1", [1, 2, 3], max_new_tokens=10, temperature=0.8,
                top_k=10, seed=7)
    assert eng3.run_to_completion()["s1"] != got["s1"]


def test_top_p_and_top_k_masks(model):
    cfg, params = model
    import numpy as np

    from ray_tpu.models.engine import _pick_token

    logits = jnp.asarray([0.0, 10.0, 9.0, -5.0, 8.0])
    # top_k=1 at any temperature is argmax
    for seed in range(5):
        t = _pick_token(logits, jnp.float32(1.0), jnp.int32(1),
                        jnp.float32(1.0), jax.random.PRNGKey(seed))
        assert int(t) == 1
    # tiny top_p keeps only the top token
    for seed in range(5):
        t = _pick_token(logits, jnp.float32(5.0), jnp.int32(0),
                        jnp.float32(1e-6), jax.random.PRNGKey(seed))
        assert int(t) == 1
    # top_k=3 never samples outside {1, 2, 4}
    seen = {int(_pick_token(logits, jnp.float32(5.0), jnp.int32(3),
                            jnp.float32(1.0), jax.random.PRNGKey(s)))
            for s in range(30)}
    assert seen <= {1, 2, 4} and len(seen) > 1
