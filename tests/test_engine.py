"""Continuous-batching engine (models/paged.py) and its sampler
(models/engine.py): interleaved requests of different lengths must
produce EXACTLY what per-request greedy decode produces, and slots must
recycle."""

import jax
import jax.numpy as jnp
import numpy as np
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


# ------------------------------------------------------ the batch picker
# One case a batch: (temps, top_ks, top_ps, lengths) for S = 6 slots.
_S = 6
_BATCHES = {
    "all_greedy": ([0.0] * _S, [0] * _S, [1.0] * _S, [5, 1, 9, 2, 7, 3]),
    "sampling_k0_p1": ([0.8] * _S, [0] * _S, [1.0] * _S, [5, 1, 9, 2, 7, 3]),
    "sampling_k3_p1": ([0.8] * _S, [3] * _S, [1.0] * _S, [5, 1, 9, 2, 7, 3]),
    "sampling_k0_p.5": ([1.3] * _S, [0] * _S, [0.5] * _S, [5, 1, 9, 2, 7, 3]),
    "sampling_k3_p.5": ([1.3] * _S, [3] * _S, [0.5] * _S, [5, 1, 9, 2, 7, 3]),
    "mixed": ([0.0, 0.8, 0.0, 2.0, 0.0, 0.3], [0, 3, 0, 0, 5, 40],
              [1.0, 1.0, 0.5, 0.5, 1.0, 0.9], [5, 1, 0, 2, 7, 3]),
    "one_sampler_beside_empty_slots": (
        [0.0, 0.0, 0.7, 0.0, 0.0, 0.0], [0, 0, 4, 0, 0, 0],
        [1.0] * _S, [0, 0, 6, 0, 0, 0]),
    # the only temperature above 0 is what a finished request left in a
    # slot that is now empty: the batch is greedy
    "stale_temperature": ([0.0, 0.9, 0.0, 0.0, 1.5, 0.0], [0, 3, 0, 0, 0, 0],
                          [1.0, 0.5, 1.0, 1.0, 1.0, 1.0], [5, 0, 9, 2, 0, 3]),
}
_GREEDY_BATCHES = {"all_greedy", "stale_temperature"}


def _logit_rows(seed, vocab=128):
    """bf16-rounded logits with exact ties among them, the top two of row
    0 included: the picker must break them as the row function does."""
    rows = jax.random.normal(jax.random.PRNGKey(seed), (_S, vocab)) * 3.0
    rows = rows.astype(jnp.bfloat16).astype(jnp.float32)
    top = jnp.max(rows[0])
    rows = rows.at[0, 17].set(top).at[0, 90].set(top)
    rows = rows.at[3, 5:9].set(rows[3, 40])
    assert int(jnp.sum(rows[0] == top)) >= 2
    return rows


def _batch(name):
    temps, top_ks, top_ps, lengths = _BATCHES[name]
    return (jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32),
            jax.random.split(jax.random.PRNGKey(3), _S),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("name", list(_BATCHES))
def test_batch_picker_equals_the_row_function_under_vmap(name):
    from ray_tpu.models.engine import _pick_token, _pick_tokens

    temps, top_ks, top_ps, keys, lengths = _batch(name)
    live = lengths > 0              # an empty slot's token is never read
    stale_differs = False
    for seed in range(4):
        logits = _logit_rows(seed)
        rows = jax.vmap(_pick_token)(logits, temps, top_ks, top_ps, keys)
        got = jax.jit(_pick_tokens)(logits, temps, top_ks, top_ps, keys,
                                    lengths)
        assert got.dtype == rows.dtype and got.shape == (_S,)
        if name in _GREEDY_BATCHES:
            # the argmax side ran: every slot holds the argmax, the empty
            # ones too, whose stale temperature the row function obeys
            assert got.tolist() == jnp.argmax(logits, axis=-1).tolist()
            assert got[live].tolist() == rows[live].tolist()
            stale_differs |= got.tolist() != rows.tolist()
        else:
            assert got.tolist() == rows.tolist()
    if name == "stale_temperature":
        assert stale_differs    # the row function would have sampled there


def _eqns(jaxpr, into_cond):
    """Every equation of a jaxpr with nested calls opened; the branches
    of a ``cond`` only where asked: what is left out then runs whatever
    the ``cond`` chooses."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, into_cond)


def _names(jaxpr, into_cond=True):
    return [e.primitive.name for e in _eqns(jaxpr, into_cond)]


_SORT_PATH = {"sort", "cumsum", "gather", "random_bits", "exp"}


@pytest.mark.parametrize("picker", ["_pick_tokens", "_pick_one"])
def test_the_sort_lives_under_one_cond_on_the_batch(picker):
    """A later edit that hoists the sort, the gathers or the cumulative
    sum back out of the ``cond`` (or puts the ``cond`` under the ``vmap``,
    where it lowers to a ``select`` that runs both sides) fails here and
    not only in a chip run."""
    from ray_tpu.models import engine

    temps, top_ks, top_ps, keys, lengths = _batch("mixed")
    logits = _logit_rows(0)
    if picker == "_pick_tokens":
        jaxpr = jax.make_jaxpr(engine._pick_tokens)(
            logits, temps, top_ks, top_ps, keys, lengths)
    else:
        jaxpr = jax.make_jaxpr(engine._pick_one)(
            logits[0], temps[0], top_ks[0], top_ps[0], keys[0])
    outside = _names(jaxpr.jaxpr, into_cond=False)
    assert outside.count("cond") == 1
    assert "argmax" in outside and not _SORT_PATH & set(outside)
    assert "select_n" not in outside        # no per-slot choice out here
    cond = next(e for e in _eqns(jaxpr.jaxpr, False)
                if e.primitive.name == "cond")
    assert cond.invars[0].aval.shape == ()  # one predicate for the batch
    greedy, sample = sorted((_names(b.jaxpr) for b in
                             cond.params["branches"]), key=len)
    assert not greedy                       # hands on the argmax
    assert {"sort", "cumsum", "gather"} <= set(sample)


# ------------------------------------------------- the one chunked admission
def _toy_chunk_program(seen):
    """A chunk program as ``prefill_in_chunks`` calls one: (params, tokens
    [chunk], start, n_valid, *carry, cfg) -> (logits, *carry, extra). Its
    logits are the chunk's tokens, its carry counts calls and sums tokens,
    its extra is [layers = 2, chunk] of the tokens' positions."""
    def program(params, tokens, start, n_valid, calls, total, cfg):
        seen.append((tokens.copy(), start, n_valid, cfg))
        extra = np.stack([start + np.arange(len(tokens))] * 2)
        return tokens * params, calls + 1, total + tokens.sum(), extra
    return program


@pytest.mark.parametrize("n, chunks", [(1, 1), (7, 1), (8, 1), (9, 2),
                                       (24, 3)],
                         ids=["one-token", "short", "exactly-one-chunk",
                              "one-token-over", "three-chunks"])
@pytest.mark.parametrize("keep", [False, True], ids=["plain", "keep"])
def test_prefill_in_chunks_is_one_loop_over_the_chunk_program(n, chunks,
                                                              keep):
    from ray_tpu.models.engine import prefill_in_chunks

    prompt, seen = list(range(1, n + 1)), []
    first, carry, kept = prefill_in_chunks(
        _toy_chunk_program(seen), 3, prompt, 8, (0, 0), "cfg", keep)
    # padded to whole chunks of 8, one call a chunk, in order
    assert [s[1] for s in seen] == [8 * c for c in range(chunks)]
    padded = np.concatenate([s[0] for s in seen])
    assert padded.dtype == np.int32 and len(padded) == 8 * chunks
    assert padded[:n].tolist() == prompt and not padded[n:].any()
    for _, start, n_valid, cfg in seen:     # as the jitted programs take them
        assert type(start) is np.int32 and type(n_valid) is np.int32
        assert n_valid == n and cfg == "cfg"
    # the LAST chunk's logits, the carry after it
    assert first.tolist() == (3 * seen[-1][0]).tolist()
    assert carry == [chunks, sum(prompt)]
    if keep:    # every prompt position's extra, the padded tail cut
        assert kept.shape == (2, n) and kept[0].tolist() == list(range(n))
    else:
        assert kept is None


def _debug_configs():
    from ray_tpu.models import LLAMA_DEBUG
    from ray_tpu.models.cohere2_moe import COHERE2_MOE_DEBUG
    from ray_tpu.models.deepseek_v3 import DEEPSEEK_V3_DEBUG
    from ray_tpu.models.granite_moe_hybrid import GRANITE_MOE_HYBRID_DEBUG
    from ray_tpu.models.lfm2_moe import LFM2_MOE_DEBUG
    from ray_tpu.models.longcat_flash import LONGCAT_FLASH_DEBUG
    from ray_tpu.models.minicpm_sala import MINICPM_SALA_DEBUG
    from ray_tpu.models.nemotron_h import NEMOTRON_H_DEBUG

    return {"dense": LLAMA_DEBUG, "hybrid": NEMOTRON_H_DEBUG,
            "sparse": MINICPM_SALA_DEBUG, "latent": LONGCAT_FLASH_DEBUG,
            "window-full": COHERE2_MOE_DEBUG, "conv-attention": LFM2_MOE_DEBUG,
            "mamba-moe": GRANITE_MOE_HYBRID_DEBUG,
            "latent-mtp": DEEPSEEK_V3_DEBUG}


@pytest.mark.parametrize("name", ["dense", "hybrid", "sparse", "latent",
                                  "window-full", "conv-attention",
                                  "mamba-moe", "latent-mtp"])
def test_every_family_is_a_whole_row_of_the_one_engine(name):
    from ray_tpu.models import paged

    cfg = _debug_configs()[name]
    assert len(paged._FAMILIES) == 8
    eng = PagedEngine(None, cfg, max_slots=2, num_pages=24, page_size=8,
                      max_len=96)
    row = eng.family
    assert row is paged._FAMILIES[type(cfg)]
    for must in (row.n_kv, row.state, row.prefill, row.step, row.scatter,
                 row.write_state):
        assert callable(must)
    for may in (row.counts, row.landed, row.admit_fields, row.pool_shape):
        assert may is None or callable(may)
    # chunked: no buckets, and the config says the chunk
    chunked = name in ("sparse", "latent", "window-full", "conv-attention",
                       "mamba-moe", "latent-mtp")
    assert row.chunked is chunked and hasattr(cfg, "prefill_chunk") is chunked
    assert eng._prefill_buckets == row.buckets == (
        () if chunked else (16, 64, 256))
    # int8 pages where the step reads them, a prefix cache for the dense row
    assert (row.no_int8 is None) is (name in ("dense", "hybrid"))
    assert (row.no_prefix_cache is None) is (name == "dense")
    # a step's counts ride with its tokens for every row but the dense one
    assert (row.counts is None) is (name == "dense")
    assert eng.n_kv == row.n_kv(cfg) == len(eng.pools_k)
    # a pool of the family's own shape may still be K beside V (a head
    # narrower than a lane kept as whole-lane rows): ``one_pool`` says
    assert row.one_pool is (name in ("latent", "latent-mtp"))
    assert len(eng.pools_v) == (0 if row.one_pool else eng.n_kv)
    assert (row.pool_shape is not None) is (name in (
        "latent", "conv-attention", "latent-mtp"))
    # a step commits one token a slot, or up to two where the row drafts AND
    # the model holds an MTP module: no engine keyword says so
    assert (row.first_draft is not None) is (name == "latent-mtp")
    assert eng._reach == (2 if name == "latent-mtp" else 1)
    if name == "latent-mtp":
        import dataclasses

        plain = PagedEngine(None, dataclasses.replace(cfg, n_nextn=0),
                            max_slots=2, num_pages=24, page_size=8,
                            max_len=96)
        assert plain._reach == 1 and plain.n_kv == eng.n_kv - 1


def test_a_subclass_of_a_rows_config_takes_that_row():
    """What a configuration file's own class over ``LlamaConfig`` relies on
    (``tests/perfbench/test_extensibility.py`` serves one)."""
    import dataclasses

    from ray_tpu.models import LLAMA_DEBUG, paged

    @dataclasses.dataclass(frozen=True)
    class OwnConfig(LlamaConfig):
        layer_pattern: str = "AM"

    cfg = OwnConfig(**dataclasses.asdict(LLAMA_DEBUG))
    eng = PagedEngine(None, cfg, max_slots=2, num_pages=24, page_size=8,
                      max_len=96)
    assert eng.family is paged._FAMILIES[LlamaConfig]


def test_a_config_without_a_row_is_refused():
    with pytest.raises(TypeError, match="object has no row in _FAMILIES"):
        PagedEngine(None, object())


@pytest.mark.parametrize("body, want", [
    ({}, (0.7, 0, 1.0)), ({"temperature": 0.0}, (0.0, 0, 1.0)),
    ({"top_k": 5, "top_p": 0.9}, (0.7, 5, 0.9)),
    ({"temperature": 1.3, "top_k": 2, "top_p": 0.5}, (1.3, 2, 0.5))],
    ids=["silent", "greedy-asked", "cuts-asked", "all-asked"])
def test_generation_defaults_apply_to_the_fields_a_request_lacks(model, body,
                                                                  want):
    """A deployment's default sampling (a model's ``generation_config.json``)
    reaches a request that says nothing; a request's own field wins."""
    import asyncio

    from ray_tpu.serve.llm import LLMServer

    cfg, params = model
    server = LLMServer(lambda: (params, cfg), max_slots=2, max_len=64,
                       generation_defaults={"temperature": 0.7})
    seen = []
    server.engine.submit = lambda rid, prompt, **kw: seen.append(kw)
    server._ensure_loop = lambda: None

    async def one():
        server._submit({"prompt": [1, 2, 3], "max_new_tokens": 4, **body})

    asyncio.run(one())
    assert (seen[0]["temperature"], seen[0]["top_k"], seen[0]["top_p"]) == want


def test_generation_defaults_refuse_a_field_they_do_not_know(model):
    from ray_tpu.serve.llm import LLMServer

    cfg, params = model
    with pytest.raises(ValueError, match="top_k"):   # no configuration's yet
        LLMServer(lambda: (params, cfg), generation_defaults={"top_k": 5})
    silent = LLMServer(lambda: (params, cfg), max_slots=2, max_len=64)
    assert silent._default_temperature == 0.0
