"""Paged KV engine (models/paged.py): shared page pool, on-demand
allocation, parity with per-request greedy decode and with the page
loop the scatter program replaced; the step and the scatter write the
pools they are given in place."""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, generate_greedy, init_params, paged
from ray_tpu.models.paged import (PagedEngine, _paged_step, _quant_kv,
                                  _scatter_pages)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _ref(params, cfg, prompt, n):
    return generate_greedy(
        params, jnp.asarray(prompt, jnp.int32)[None, :], cfg,
        max_new=n)[0].tolist()


def test_paged_matches_greedy(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=3, num_pages=24,
                      page_size=8, max_len=64)
    reqs = {"a": ([1, 2, 3, 4], 12), "b": ([7, 8], 5),
            "c": ([10, 11, 12, 13, 14, 15], 9), "d": ([20, 21], 7)}
    for rid, (p, n) in reqs.items():
        eng.submit(rid, p, max_new_tokens=n)
    got = eng.run_to_completion()
    for rid, (p, n) in reqs.items():
        assert got[rid] == _ref(params, cfg, p, n), rid
    # every page returned to the pool (page 0 stays reserved)
    assert sorted(eng.free_pages) == list(range(1, 24))


@pytest.mark.parametrize("kw, request_, match", [
    # 20 + 20 + 1 positions fit max_len 64 but need 3 of the pool's 2 pages
    (dict(num_pages=3), ([1] * 20, 20), "more pages than the pool holds"),
    (dict(kv_dtype="fp8"), None, "kv_dtype must be"),
])
def test_what_cannot_be_served_is_refused_in_words(model, kw, request_, match):
    cfg, params = model
    with pytest.raises(ValueError, match=match):
        eng = PagedEngine(params, cfg, max_slots=1, max_len=64, **kw)
        eng.submit("r", request_[0], max_new_tokens=request_[1])


def test_pages_allocated_on_demand(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=16,
                      page_size=4, max_len=32)
    eng.submit("x", [1, 2, 3], max_new_tokens=10)
    eng.step()  # admit: 1 page for 4 positions
    slot = next(s for s in eng.slots if s is not None)
    assert len(slot.pages) == 1
    while eng.has_work():
        eng.step()
    # 3 prompt + 10 generated = 13 positions -> needed 4 pages at peak
    assert sorted(eng.free_pages) == list(range(1, 16))


def test_pool_admits_more_than_dense_equivalent(model):
    cfg, params = model
    # 8 sequences of ~8 tokens each share 10 pages x 4 = 40 positions;
    # a dense cache would need 8 slots x 32 = 256 positions.
    eng = PagedEngine(params, cfg, max_slots=8, num_pages=11,
                      page_size=4, max_len=32)
    for i in range(8):
        eng.submit(f"r{i}", [i + 1, i + 2], max_new_tokens=4)
    got = eng.run_to_completion()
    assert len(got) == 8
    for i in range(8):
        assert got[f"r{i}"] == _ref(params, cfg, [i + 1, i + 2], 4)


def test_sampled_paged(model):
    cfg, params = model
    a = PagedEngine(params, cfg, max_slots=2, num_pages=16,
                    page_size=8, max_len=64)
    a.submit("s", [3, 4], max_new_tokens=8, temperature=0.8, top_k=12,
             seed=11)
    b = PagedEngine(params, cfg, max_slots=2, num_pages=16,
                    page_size=8, max_len=64)
    b.submit("s", [3, 4], max_new_tokens=8, temperature=0.8, top_k=12,
             seed=11)
    assert a.run_to_completion()["s"] == b.run_to_completion()["s"]


def test_prefix_cache_reuse_and_parity(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=32,
                      page_size=4, max_len=64, enable_prefix_cache=True)
    shared_prefix = list(range(1, 13))  # 12 tokens = 3 full pages
    # First request computes + registers the prefix pages.
    eng.submit("a", shared_prefix + [20], max_new_tokens=6)
    got_a = eng.run_to_completion()["a"]
    assert eng.prefix_misses == 1 and eng.prefix_hits == 0
    # Second request with the same prefix borrows those pages.
    eng.submit("b", shared_prefix + [30, 31], max_new_tokens=6)
    got_b = eng.run_to_completion()["b"]
    assert eng.prefix_hits == 1
    # Outputs identical to non-cached greedy decode.
    assert got_a == _ref(params, cfg, shared_prefix + [20], 6)
    assert got_b == _ref(params, cfg, shared_prefix + [30, 31], 6)


def test_prefix_cache_eviction_under_pressure(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=1, num_pages=8,
                      page_size=4, max_len=32, enable_prefix_cache=True)
    # Fill the cache with distinct prefixes, forcing LRU eviction.
    for i in range(4):
        p = [40 + i] * 8 + [3]  # 2 full pages each
        eng.submit(f"p{i}", p, max_new_tokens=3)
        out = eng.run_to_completion()[f"p{i}"]
        assert out == _ref(params, cfg, p, 3), i
    # Engine never deadlocked and parity held throughout; some cached
    # prefixes were LRU-evicted to keep admitting (7 usable pages
    # < 4 prefixes x 2 pages + 3 working pages).
    assert len(eng._prefix) < 8


def test_prefix_cache_shared_pages_not_freed_while_borrowed(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=32,
                      page_size=4, max_len=64, enable_prefix_cache=True)
    prefix = list(range(50, 58))  # 2 full pages
    eng.submit("x", prefix + [1], max_new_tokens=12)
    eng.submit("y", prefix + [2], max_new_tokens=3)
    got = eng.run_to_completion()
    assert got["x"] == _ref(params, cfg, prefix + [1], 12)
    assert got["y"] == _ref(params, cfg, prefix + [2], 3)
    # After both finish, cached pages have refcount 0 but stay resident.
    assert all(e[1] == 0 for e in eng._prefix.values())


def test_int8_kv_cache(model):
    cfg, params = model
    import numpy as np

    ref = _ref(params, cfg, [5, 6, 7, 8], 10)
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=24,
                      page_size=4, max_len=64, kv_dtype="int8")
    eng.submit("q", [5, 6, 7, 8], max_new_tokens=10)
    got = eng.run_to_completion()["q"]
    # int8 KV is CLOSE, not bit-identical: most greedy tokens agree on
    # this small model; the run must complete at full length regardless.
    assert len(got) == 10
    agree = sum(a == b for a, b in zip(got, ref)) / 10
    assert agree >= 0.6, (got, ref)
    # pool bytes actually halved (+ f32 scales, 1/d the size)
    assert eng.pools_k[0].dtype.name == "int8"
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedEngine(params, cfg, kv_dtype="fp4")


def test_int8_kv_with_prefix_cache(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=32,
                      page_size=4, max_len=64, kv_dtype="int8",
                      enable_prefix_cache=True)
    prefix = list(range(60, 68))
    eng.submit("a", prefix + [1], max_new_tokens=6)
    got_a = eng.run_to_completion()["a"]
    eng.submit("b", prefix + [1], max_new_tokens=6)
    got_b = eng.run_to_completion()["b"]
    # identical request through the cached-prefix path reproduces the
    # cold run exactly (same quantized pages, same math)
    assert got_a == got_b
    assert eng.prefix_hits == 1


# ------------------------------------------- one jitted scatter (ISSUE 26)

def _loop_scatter(eng, seq_caches, pages, n_shared):
    """The plain reference: the per-layer, per-page eager loop that
    ``PagedEngine._scatter`` was before it became one program."""
    for li, (kc, vc) in enumerate(seq_caches):
        pk, pv = eng.pools_k[li], eng.pools_v[li]
        for pi in range(n_shared, len(pages)):
            lo = pi * eng.page
            pg = pages[pi]
            ks = kc[lo:lo + eng.page]
            vs = vc[lo:lo + eng.page]
            if eng.kv_int8:
                kq, ksc = _quant_kv(ks)
                vq, vsc = _quant_kv(vs)
                pk = pk.at[pg].set(kq)
                pv = pv.at[pg].set(vq)
                eng.scales_k[li] = eng.scales_k[li].at[pg].set(ksc)
                eng.scales_v[li] = eng.scales_v[li].at[pg].set(vsc)
            else:
                pk = pk.at[pg].set(ks)
                pv = pv.at[pg].set(vs)
        eng.pools_k[li], eng.pools_v[li] = pk, pv


class _LoopEngine(PagedEngine):
    """The engine with the reference scatter in place of the program."""

    def _scatter(self, seq_caches, pages, n_shared):
        _loop_scatter(self, seq_caches, pages, n_shared)


@pytest.fixture(scope="module")
def bf16_model():
    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.bfloat16)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _pool_state(eng):
    """Every pool and scale of the engine as host arrays."""
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(
        (eng.pools_k, eng.pools_v, eng.scales_k, eng.scales_v))]


def _held_arrays(eng):
    """Every device array the engine holds in its own attributes."""
    return {id(x) for x in jax.tree_util.tree_leaves(
        [v for v in vars(eng).values()
         if isinstance(v, (list, tuple, dict, jax.Array))])}


def _drive(eng, late):
    """Step the engine until it is idle, submitting ``late[k]`` = (rid,
    prompt, max_new) before call ``k``: admissions between decode steps.
    -> (tokens by request, preemptions seen)."""
    acc, k, preempted = {}, 0, 0
    while eng.has_work() or k <= max(late):
        if k in late:
            rid, prompt, n = late[k]
            eng.submit(rid, prompt, max_new_tokens=n)
        for rid, tok in eng.step():
            if tok is not None:
                acc.setdefault(rid, []).append(tok)
        preempted += eng._preempted
        k += 1
    return acc, preempted


def _same(a, b):
    return len(a) == len(b) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(a, b))


def _noise_pools(eng, seed):
    """Fill pools and scales with seeded noise, so a page the scatter
    must leave alone shows if it did not."""
    rng = np.random.default_rng(seed)

    def noise(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape, np.int8))
        return jnp.asarray(rng.standard_normal(a.shape, np.float32)
                           ).astype(a.dtype)

    (eng.pools_k, eng.pools_v, eng.scales_k,
     eng.scales_v) = jax.tree_util.tree_map(
        noise, (eng.pools_k, eng.pools_v, eng.scales_k, eng.scales_v))


# page 8, max_len 64: mid-page, on a page edge (n + 1 takes a further,
# all-padding page), and the longest prompt a table holds
@pytest.mark.parametrize("n_prompt", [13, 16, 63])
@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_scatter_program_equals_page_loop(bf16_model, kv_dtype, n_prompt):
    cfg, params = bf16_model
    kw = dict(max_slots=2, num_pages=24, page_size=8, max_len=64,
              kv_dtype=kv_dtype)
    eng, ref = PagedEngine(params, cfg, **kw), _LoopEngine(params, cfg, **kw)
    _noise_pools(eng, 5)
    _noise_pools(ref, 5)
    before = _pool_state(ref)
    prompt = [(7 * i + 3) % cfg.vocab_size for i in range(n_prompt)]
    for e in (eng, ref):
        e.submit("r", prompt, max_new_tokens=0)
        e._admit()
    assert eng.slots[0].pages == ref.slots[0].pages
    assert len(eng.slots[0].pages) == -(-(n_prompt + 1) // 8)
    assert _same(_pool_state(eng), _pool_state(ref))
    assert not _same(_pool_state(ref), before)
    assert eng.pools_k[0].dtype == (jnp.int8 if kv_dtype == "int8"
                                    else jnp.bfloat16)


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_scatter_leaves_shared_and_foreign_pages_alone(bf16_model, kv_dtype):
    cfg, params = bf16_model
    kw = dict(max_slots=3, num_pages=32, page_size=4, max_len=64,
              kv_dtype=kv_dtype, enable_prefix_cache=True)
    eng, ref = PagedEngine(params, cfg, **kw), _LoopEngine(params, cfg, **kw)
    prefix = list(range(50, 58))                 # two full pages
    for e in (eng, ref):
        _noise_pools(e, 9)
        # an ``eos_id`` no token equals: every step is fetched in its own
        # call, so ``_admit`` below finds nothing in flight
        e.submit("x", prefix + [1, 2], max_new_tokens=12,
                 eos_id=cfg.vocab_size)
        e.submit("z", [9, 8, 7], max_new_tokens=12)
        for _ in range(3):
            e.step()
    before = _pool_state(eng)
    for e in (eng, ref):
        e.submit("y", prefix + [3], max_new_tokens=5)
        e._admit()
    slot = next(s for s in eng.slots if s and s.request_id == "y")
    assert slot.n_shared == 2 and eng.prefix_hits == 1
    x = next(s for s in eng.slots if s and s.request_id == "x")
    assert slot.pages[:2] == x.pages[:2]
    own = slot.pages[2:]
    assert own and not set(own) & set(x.pages)
    after = _pool_state(eng)
    others = [p for p in range(32) if p not in own]
    for a, b in zip(before, after):
        assert np.array_equal(a[others], b[others])     # page axis first
        assert not np.array_equal(a[own], b[own])
    assert _same(after, _pool_state(ref))
    got, want = eng.run_to_completion(), ref.run_to_completion()
    assert got == want
    assert got["y"] == _ref(params, cfg, prefix + [3], 5) \
        or kv_dtype == "int8"


@pytest.mark.parametrize("kw", [
    {}, {"kv_dtype": "int8"}, {"enable_prefix_cache": True},
    {"kv_dtype": "int8", "enable_prefix_cache": True}],
    ids=["model", "int8", "model-prefix", "int8-prefix"])
def test_mixed_batch_greedy_identical_to_loop_engine(bf16_model, kw):
    """Admissions between decode steps, a pool so small that one request
    is preempted and resumed: token for token the reference engine's."""
    cfg, params = bf16_model
    late = {2: ("c", [5, 6, 7, 8, 9, 10, 11, 12, 13], 6),
            5: ("d", [1, 2, 3, 4, 20], 4)}
    outs = []
    for cls in (PagedEngine, _LoopEngine):
        eng = cls(params, cfg, max_slots=2, num_pages=7, page_size=4,
                  max_len=32, **kw)
        eng.submit("a", [1, 2, 3, 4, 5], max_new_tokens=11)
        eng.submit("b", [1, 2, 3, 4, 6], max_new_tokens=11)
        acc, preempted = _drive(eng, late)
        assert preempted >= 1
        assert {r: len(t) for r, t in acc.items()} == {
            "a": 11, "b": 11, "c": 6, "d": 4}
        outs.append(acc)
    assert outs[0] == outs[1]


def test_scatter_compiles_once_whatever_the_prompt(model):
    """Prompts of 1, 17, 255 and 700 tokens (four prefill buckets, 1 to
    44 pages), with none and with two shared prefix pages, decode steps
    between them: ONE entry in the program's cache."""
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=131,
                      page_size=16, max_len=1024,
                      enable_prefix_cache=True)
    seen, scatter = [], eng._scatter
    eng._scatter = lambda caches, pages, n_shared: seen.append(
        (len(pages), n_shared)) or scatter(caches, pages, n_shared)
    base = _scatter_pages._cache_size()
    head = [(3 * i + 1) % cfg.vocab_size for i in range(32)]   # two pages
    for i, n in enumerate([1, 17, 255, 700, 40, 700]):
        prompt = (head + [(5 * j + i) % cfg.vocab_size
                          for j in range(n)])[:n]
        eng.submit(f"r{i}", prompt, max_new_tokens=3)
        eng.step()          # admits it, and decodes one token
        assert _scatter_pages._cache_size() == base + 1, (i, n)
        eng.run_to_completion()
    assert [p for p, _ in seen] == [1, 2, 16, 44, 3, 44]
    assert [s for _, s in seen] == [0, 0, 1, 2, 2, 2]
    assert _scatter_pages._cache_size() == base + 1


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_scatter_donates_the_pools_and_keeps_no_handle(model, kv_dtype):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=24,
                      page_size=8, max_len=64, kv_dtype=kv_dtype,
                      enable_prefix_cache=True)
    prefix = list(range(1, 17))
    # an ``eos_id`` no token equals: the step is fetched in its own call,
    # so ``_admit`` below finds nothing in flight
    eng.submit("a", prefix + [20], max_new_tokens=3, eos_id=cfg.vocab_size)
    eng.step()
    eng.submit("b", prefix + [30], max_new_tokens=3)  # gathers its prefix
    given = jax.tree_util.tree_leaves(
        (eng.pools_k, eng.pools_v, eng.scales_k, eng.scales_v))
    assert len(given) == (8 if kv_dtype == "int8" else 4)
    with warnings.catch_warnings():
        # a backend that cannot donate says so and copies: still correct
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        eng._admit()
    assert eng.prefix_hits == 1
    assert not _held_arrays(eng) & {id(a) for a in given}
    if jax.default_backend() in ("cpu", "tpu"):  # these donate
        assert all(a.is_deleted() for a in given)
    got = eng.run_to_completion()
    assert got["b"] == _ref(params, cfg, prefix + [30], 3) \
        or kv_dtype == "int8"


# ------------------------------ the step writes its pools in place (ISSUE 42)

@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_step_program_aliases_every_pool_to_its_output(model, kv_dtype):
    """Lowered at toy widths: each K and V pool (and each int8 scale) is an
    argument the program may overwrite, tied to the output that replaces
    it; the weights and the slots' uploads are not."""
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=24, page_size=8,
                      max_len=64, kv_dtype=kv_dtype)
    S, P = eng.S, eng.P
    scales = ((eng.scales_k, eng.scales_v) if eng.kv_int8
              else ([0] * eng.n_kv,) * 2)
    lowered = _paged_step.lower(
        params, eng.pools_k, eng.pools_v, *scales,
        np.zeros((S, P), np.int32), np.zeros(S, np.int32),
        np.zeros(S, np.int32), np.zeros(S, np.float32),
        np.zeros(S, np.int32), np.ones(S, np.float32),
        np.zeros((S, 2), np.uint32), cfg, eng.cos, eng.sin, eng.page,
        eng.kv_int8)
    args = lowered.args_info[0]

    def donated(tree):
        return [a.donated for a in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda a: hasattr(a, "donated"))]

    assert donated(args[1:5]) == [True] * 4 * cfg.n_layers
    assert not any(donated((args[0], args[5:])))
    # the outputs the pools (and scales) alias: 1.. after the tokens
    text = lowered.as_text()
    pooled = 4 * cfg.n_layers if eng.kv_int8 else 2 * cfg.n_layers
    aliased = {int(i) for i in re.findall(
        r"tf\.aliasing_output = (\d+)", text)}
    assert set(range(1, 1 + pooled)) <= aliased, sorted(aliased)


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_step_consumes_the_pools_and_the_engine_holds_the_new(model,
                                                              kv_dtype):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=24, page_size=8,
                      max_len=64, kv_dtype=kv_dtype)
    eng.submit("a", [1, 2, 3, 4], max_new_tokens=6)
    got = [tok for _, tok in eng.step()]    # admits, decodes one token
    for _ in range(2):              # each step runs on the last one's pools
        given = jax.tree_util.tree_leaves(
            (eng.pools_k, eng.pools_v, eng.scales_k, eng.scales_v))
        assert len(given) == (4 if eng.kv_int8 else 2) * cfg.n_layers
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # every donated buffer is used
            got += [tok for _, tok in eng.step()]
        assert not _held_arrays(eng) & {id(a) for a in given}
        if jax.default_backend() in ("cpu", "tpu"):  # these donate
            assert all(a.is_deleted() for a in given)
    got += eng.run_to_completion()["a"]
    assert len(got) == 6
    assert got == _ref(params, cfg, [1, 2, 3, 4], 6) or kv_dtype == "int8"


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_prefix_hit_between_decode_steps_reads_the_rebound_pools(
        bf16_model, kv_dtype):
    """Decode steps, then an admission whose prefix is gathered from the
    cached pages, then more of both: the gather in ``_prefill`` reads the
    pools the last step returned (a donated one would raise), and the
    tokens are the reference engine's, which writes page by page."""
    cfg, params = bf16_model
    prefix = list(range(1, 17))             # two full pages of 8
    late = {3: ("b", prefix + [30, 31], 5), 6: ("c", prefix + [40], 4),
            9: ("d", prefix[:8] + [50, 51, 52], 4)}
    reqs = {"a": (prefix + [20], 14),
            **{rid: (prompt, n) for rid, prompt, n in late.values()}}
    outs = []
    for cls in (PagedEngine, _LoopEngine):
        eng = cls(params, cfg, max_slots=3, num_pages=24, page_size=8,
                  max_len=64, kv_dtype=kv_dtype, enable_prefix_cache=True)
        eng.submit("a", reqs["a"][0], max_new_tokens=reqs["a"][1])
        outs.append(_drive(eng, late)[0])
        assert eng.prefix_hits == 3 and eng.prefix_misses == 1
    assert outs[0] == outs[1]
    for rid, (prompt, n) in reqs.items():
        assert len(outs[0][rid]) == n
        assert outs[0][rid] == _ref(params, cfg, prompt, n) \
            or kv_dtype == "int8", rid


# ------------------ the step reads each slot's live pages in blocks (ISSUE 44)

def _wide_engine(model, kv_dtype="model", **kw):
    """A table of eight of the read's blocks: 512 positions a slot."""
    cfg, params = model
    return PagedEngine(params, cfg, max_slots=3, num_pages=80, page_size=16,
                       max_len=512, kv_dtype=kv_dtype, **kw)


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_step_program_gathers_no_slots_whole_table(model, kv_dtype):
    """The jaxpr of the step at a toy shape whose table holds eight of
    the read's blocks: no array of ``[S, P * page, kvh, d]`` (each slot's whole
    table gathered, in the pool's dtype or dequantised) nor the int8 pages'
    scales at that width, one ``while`` a layer, and a gathered block of
    ``[S, block, kvh, d]`` in it."""
    cfg, _ = model
    eng = _wide_engine(model, kv_dtype)
    S, P = eng.S, eng.P
    scales = ((eng.scales_k, eng.scales_v) if eng.kv_int8
              else ([0] * eng.n_kv,) * 2)
    text = str(jax.make_jaxpr(
        lambda *a: _paged_step(*a, cfg, eng.cos, eng.sin, eng.page,
                               eng.kv_int8))(
        eng.params, eng.pools_k, eng.pools_v, *scales,
        np.zeros((S, P), np.int32), np.zeros(S, np.int32),
        np.zeros(S, np.int32), np.zeros(S, np.float32),
        np.zeros(S, np.int32), np.ones(S, np.float32),
        np.zeros((S, 2), np.uint32))).replace(" ", "")
    block = eng._read_block
    assert block < eng.max_len
    wide = f"[{S},{eng.max_len},{cfg.n_kv_heads}"
    assert wide not in text and f"[{S},{P},{eng.page}," not in text
    assert f"[{S},{block},{cfg.n_kv_heads},{cfg.head_dim}]" in text
    assert text.count("while[") == cfg.n_layers


def test_paged_matches_greedy_across_the_reads_blocks(model):
    """Slots whose contexts end in the read's first and in its second block,
    one of them crossing the edge while it decodes, beside a slot that stays
    idle: each streams what ``generate_greedy`` does."""
    cfg, params = model
    cfg = type(cfg)(**{**cfg.__dict__, "max_seq_len": 512})
    eng = _wide_engine((cfg, params))
    rng = np.random.default_rng(0)
    reqs = {"short": (rng.integers(1, 96, 7).tolist(), 6),
            "crosses": (rng.integers(1, 96, 250).tolist(), 12)}
    for rid, (p, n) in reqs.items():
        eng.submit(rid, p, max_new_tokens=n)
    got = eng.run_to_completion()
    for rid, (p, n) in reqs.items():
        assert got[rid] == _ref(params, cfg, p, n), rid


# ------------------- the engine runs ahead of the device (ISSUE 49: S6)

def _ahead_engine(model, **kw):
    cfg, params = model
    kw = {"max_slots": 2, "num_pages": 24, "page_size": 8, "max_len": 64,
          **kw}
    return PagedEngine(params, cfg, **kw)


AHEAD_REQS = {"long": (list(range(1, 20)), 19), "short": ([7, 8, 9], 13)}


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
@pytest.mark.parametrize("how", [
    {}, {"temperature": 0.8, "top_k": 5, "seed": 3},
    {"temperature": 1.0, "top_p": 0.9, "seed": 11}],
    ids=["greedy", "top_k", "top_p"])
def test_running_ahead_streams_what_the_synchronous_loop_streams(
        model, how, kv_dtype, slow_device, streams):
    """``_paged_step``'s tokens are the array the next step takes, so
    without an ``eos_id`` the dense family dispatches each step on the
    tokens and keys the last one left on the device and fetches tokens
    ``_STEPS_AHEAD`` steps behind (both slots are held); with an ``eos_id``
    no token equals, every step is fetched in the call that dispatched it.
    Both stream the same tokens, sampled ones too: the keys are the same
    chain; the int8 scales are rebound at every dispatch."""
    ahead, deepest = streams(_ahead_engine(model, kv_dtype=kv_dtype),
                             AHEAD_REQS, **how)
    sync, none = streams(_ahead_engine(model, kv_dtype=kv_dtype),
                         AHEAD_REQS, eos_id=model[0].vocab_size, **how)
    assert ahead == sync and [len(v) for v in ahead.values()] == [19, 13]
    assert deepest == paged._STEPS_AHEAD and none == 0
    if not how and kv_dtype == "model":
        for r, (prompt, n) in AHEAD_REQS.items():
            assert ahead[r] == _ref(model[1], model[0], prompt, n), r


@pytest.mark.parametrize("slots, depth", [
    (2, paged._STEPS_AHEAD), (3, paged._STEPS_FREE_SLOT)],
    ids=["every_slot_held", "a_slot_free"])
def test_the_depth_follows_whether_a_slot_is_free(model, slots, depth,
                                                  slow_device, streams):
    """With every slot held no arrival could be admitted before a stream
    ends, and the engine keeps ``_STEPS_AHEAD`` steps dispatched; beside a
    free slot it keeps the two that hide the host's part of a call, which
    is all that an arrival then waits for."""
    got, deepest = streams(_ahead_engine(model, max_slots=slots),
                           AHEAD_REQS)
    assert deepest == depth
    for r, (prompt, n) in AHEAD_REQS.items():
        assert got[r] == _ref(model[1], model[0], prompt, n), r


def test_a_step_that_has_ended_lands_in_the_call_that_finds_it(
        model, monkeypatch, streams):
    monkeypatch.setattr(paged._Flight, "ended", lambda self: True)
    reqs = {"long": AHEAD_REQS["long"]}
    got, deepest = streams(_ahead_engine(model), reqs)
    assert deepest == 0
    assert got["long"] == _ref(model[1], model[0], *reqs["long"])


def test_steps_in_flight_land_before_an_admission_and_are_work(
        model, slow_device):
    cfg, params = model
    eng = _ahead_engine(model)
    a, b = list(range(1, 12)), [30, 31, 32]
    eng.submit("a", a, max_new_tokens=14)
    got = {"a": [], "b": []}

    def step():
        events = eng.step()
        for rid, tok in events:
            if tok is not None:
                got[rid].append(tok)
        return events

    step()                                   # admits, dispatches step 1
    assert got["a"] == _ref(params, cfg, a, 1)
    assert len(eng._flights) == 1 and eng.has_work()
    assert eng.slots[0].length == 12 and len(eng.slots[0].emitted) == 1
    assert step() == []                      # step 2, none fetched
    # a slot is free: the third dispatch lands the oldest
    assert [rid for rid, _ in step()] == ["a"] and len(eng._flights) == 2
    eng.submit("b", b, max_new_tokens=4)
    # a slot is free and b waits: no step is dispatched until the two in
    # flight have landed, one a call; the call that lands the last admits
    # b and dispatches a step for both
    assert [rid for rid, _ in step()] == ["a"] and len(eng._flights) == 1
    assert eng.slots[0].length == 14 and eng.slots[1] is None
    assert [rid for rid, _ in step()] == ["a", "b"]
    assert len(eng._flights) == 1 and eng._flights[0].active == [0, 1]
    assert len(got["a"]) == 4 and eng.slots[0].length == 15
    while eng.has_work():
        step()
    assert got["a"] == _ref(params, cfg, a, 14)
    assert got["b"] == _ref(params, cfg, b, 4)
    assert not eng._flights and sorted(eng.free_pages) == list(range(1, 24))


@pytest.mark.parametrize("case", ["prefix_cache", "preemption"])
def test_a_cache_hit_and_a_preemption_stream_what_they_streamed_before(
        model, case, slow_device):
    """Admissions read and write the prefix cache, and a slot is preempted,
    only with nothing in flight (``_runs_ahead`` wants a page to spare for
    every held slot): the streams are the synchronous loop's and the
    reference's, the hits and the preemptions happen all the same."""
    cfg, params = model
    prefix = list(range(1, 17))             # two full pages of 8
    if case == "prefix_cache":
        kw = dict(max_slots=3, enable_prefix_cache=True)
        first = ("a", prefix + [20], 14)
        late = {3: ("b", prefix + [30, 31], 5), 6: ("c", prefix + [40], 4),
                9: ("d", prefix[:8] + [50, 51, 52], 4)}
    else:       # 7 pages for two sequences that grow to 5 each
        kw = dict(num_pages=8)
        first = ("x", [1, 2, 3, 4, 5, 6], 30)
        late = {0: ("y", [9, 8, 7, 6, 5], 30)}
    outs = []
    for eos in (None, cfg.vocab_size):
        eng = _ahead_engine(model, **kw)
        eng.submit(first[0], first[1], max_new_tokens=first[2], eos_id=eos)
        acc, preempted = _drive(eng, late)
        outs.append(acc)
        assert not eng._flights and not eng.tables.any()
        if case == "prefix_cache":
            assert eng.prefix_hits == 3 and eng.prefix_misses == 1
        else:
            assert preempted > 0
    assert outs[0] == outs[1]
    for rid, prompt, n in [first, *late.values()]:
        assert outs[0][rid] == _ref(params, cfg, prompt, n), rid
