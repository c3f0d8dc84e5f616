"""The lightning / block-sparse family: its forward pass, the sparse layer's
read path over pages, and ``PagedEngine`` serving it, each against the plain
reference (``perfbench/reference/minicpm_sala.py``) at toy sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import minicpm_sala as ref
from ray_tpu.models import minicpm_sala as ms
from ray_tpu.models import paged, paged_ops
from ray_tpu.models.paged import PagedEngine
from ray_tpu.util import events

CFG = ms.MINICPM_SALA_DEBUG          # dense_len 32, block 8, top-k 5


def shape_of(cfg):
    """The reference's ``shape`` keys, as a configuration file names them."""
    return dict(
        rms_norm_eps=cfg.norm_eps, published_depth=cfg.n_layers_published,
        scale_depth=cfg.scale_depth, scale_emb=cfg.scale_emb,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        lightning_nh=cfg.lightning_heads, rope_theta=cfg.rope_theta,
        mixer_types=list(cfg.mixer_types), layer_offset=cfg.layer_offset,
        hidden_size=cfg.d_model, dim_model_base=cfg.dim_model_base,
        sparse_kernel_size=cfg.kernel, sparse_kernel_stride=cfg.stride,
        sparse_block_size=cfg.block, sparse_topk=cfg.topk,
        sparse_init_blocks=cfg.init_blocks, sparse_window_size=cfg.window,
        sparse_dense_len=cfg.dense_len)


@pytest.fixture(scope="module")
def params():
    return ms.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def _reference(params, tokens, **kw):
    return ref.forward(ref.from_program_tree(params), tokens, shape_of(CFG),
                       block_rows=16, **kw)


def _engine(params, **kw):
    kw = {"max_slots": 3, "num_pages": 64, "page_size": 8, "max_len": 96,
          **kw}
    return PagedEngine(params, CFG, **kw)


def _alone(params, prompt, n):
    eng = _engine(params)
    eng.submit("alone", prompt, max_new_tokens=n)
    return eng.run_to_completion()["alone"]


# ----------------------------------------------------------------- the model
def test_param_count_is_the_published_9_48_billion():
    whole = ms.MiniCPMSALAConfig()
    assert whole.n_layers == 32 and whole.n_sparse_layers == 8
    assert abs(whole.param_count() - 9.48e9) < 0.01e9
    stage = dataclasses.replace(
        whole, mixer_types=whole.mixer_types[8:24], layer_offset=8)
    assert (stage.n_lightning_layers, stage.n_sparse_layers) == (12, 4)
    assert abs(stage.param_count() - 5.04e9) < 0.01e9
    tree = jax.eval_shape(lambda: ms.init_params(CFG, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == CFG.param_count()


def test_config_refuses_sizes_the_programs_cannot_cut():
    with pytest.raises(ValueError, match="kernel"):
        dataclasses.replace(CFG, kernel=6)
    with pytest.raises(ValueError, match="published depth"):
        dataclasses.replace(CFG, layer_offset=6)
    with pytest.raises(ValueError, match="mixer_types"):
        dataclasses.replace(CFG, mixer_types=("attention",))


@pytest.mark.parametrize("L", [20, 77, 100])
def test_forward_is_the_reference_with_selection_live(params, L):
    tokens = _tokens(L, seed=L)
    got = np.asarray(ms.forward(params, jnp.asarray(tokens, jnp.int32), CFG))
    want = _reference(params, tokens)
    np.testing.assert_allclose(got, np.asarray(want["logits"]), atol=2e-4)
    own = np.asarray(want["own_selection"])
    assert own.shape == (1, L, CFG.n_kv_heads, CFG.topk)
    # past dense_len the reference chose blocks; the forced ones are among them
    assert (own[0, :CFG.dense_len] == -1).all()
    for t in range(CFG.dense_len, L):
        for g in range(CFG.n_kv_heads):
            chosen = set(own[0, t, g].tolist())
            assert {0, t // CFG.block, t // CFG.block - 1} <= chosen
            assert max(chosen) <= t // CFG.block


def test_the_reference_under_an_imposed_selection_reads_those_blocks(params):
    tokens = _tokens(90, seed=4)
    free = _reference(params, tokens)
    own = np.asarray(free["own_selection"])
    same = _reference(params, tokens, selection=own)
    np.testing.assert_allclose(same["logits"], free["logits"], atol=1e-5)
    assert float(np.asarray(same["under"]).max()) == 0.0
    # swap the weakest free block of one late query for an unchosen one
    other = own.copy()
    t, g = 85, 1
    unchosen = sorted(set(range(t // CFG.block + 1)) - set(own[0, t, g]))
    other[0, t, g, -1] = unchosen[0]
    moved = _reference(params, tokens, selection=other)
    assert float(np.asarray(moved["under"])[0, t, g]) > 0.0
    assert np.abs(np.asarray(moved["logits"])[t]
                  - np.asarray(free["logits"])[t]).max() > 1e-6
    np.testing.assert_allclose(np.asarray(moved["logits"])[:t],
                               np.asarray(free["logits"])[:t], atol=1e-5)


# ------------------------------------------------ the sparse layer over pages
def _pool_case(seed, lengths, P=12, page=8):
    """Pools whose pages hold random K/V at each slot's positions, scattered
    over shuffled page ids."""
    rng = np.random.default_rng(seed)
    S, kvh, d = len(lengths), CFG.n_kv_heads, CFG.head_dim
    num_pages = S * P + 1
    tables = rng.permutation(np.arange(1, num_pages)).reshape(S, P)
    pool_k = rng.normal(size=(num_pages, page, kvh, d)).astype(np.float32)
    pool_v = rng.normal(size=(num_pages, page, kvh, d)).astype(np.float32)
    q = rng.normal(size=(S, 1, CFG.n_heads, d)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32))


def _dense_keys(pool, tables):
    return np.asarray(pool)[np.asarray(tables)].reshape(
        tables.shape[0], -1, *pool.shape[2:])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_mask_without_a_sort_is_top_ks_set(seed):
    """``choose_block_mask`` (bisection over the scores' bits) against
    ``choose_blocks`` (``lax.top_k``): the same set for every query, with
    equal scores among the candidates (the lower block wins), forced blocks
    at +inf, and contexts that hold fewer blocks than ``topk``."""
    rng = np.random.default_rng(seed)
    N, kvh, rep, J = 40, CFG.n_kv_heads, 2, 48        # 12 blocks of 8
    logits = rng.normal(size=(N, kvh, rep, J)).astype(np.float32)
    logits[: N // 2] = np.round(logits[: N // 2])     # many equal scores
    logits[3] = 0.0                                   # ... and all equal
    n = rng.integers(1, J * CFG.stride + 1, N)
    n[:4] = [1, 9, 33, 96]
    idx, B = paged_ops.choose_blocks(jnp.asarray(logits), jnp.asarray(n), CFG)
    mask = np.asarray(paged_ops.choose_block_mask(
        jnp.asarray(logits), jnp.asarray(n), CFG))
    assert mask.shape == (N, kvh, J // 4) and (mask.sum(-1) == CFG.topk).all()
    want = np.zeros_like(mask)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(mask, want)
    B = np.asarray(B)
    assert np.isposinf(B[3, 0, 0]) and np.isneginf(B[0, 0, 1:]).all()


def test_attention_over_chosen_pages_is_the_masked_dense_computation():
    lengths = [50, 93, 41, 64]
    q, pool_k, pool_v, tables, lens = _pool_case(1, lengths)
    rng = np.random.default_rng(2)
    idx = np.stack([[rng.permutation(t // 8 + 1)[:CFG.topk]
                     for _ in range(CFG.n_kv_heads)] for t in lengths])
    got = np.asarray(paged_ops.attend_chosen(
        q, pool_k, pool_v, tables, jnp.asarray(idx, jnp.int32), lens))
    k_seq, v_seq = _dense_keys(pool_k, tables), _dense_keys(pool_v, tables)
    rep = CFG.n_heads // CFG.n_kv_heads
    for s, t in enumerate(lengths):
        for h in range(CFG.n_heads):
            g = h // rep
            pos = np.arange(k_seq.shape[1])
            ok = np.isin(pos // 8, idx[s, g]) & (pos <= t)
            sc = k_seq[s, :, g] @ np.asarray(q)[s, 0, h] / np.sqrt(
                CFG.head_dim)
            p = np.exp(sc - sc[ok].max()) * ok
            want = (p / p.sum()) @ v_seq[s, :, g]
            np.testing.assert_allclose(
                got[s, 0, h * CFG.head_dim:(h + 1) * CFG.head_dim], want,
                atol=2e-5)


@pytest.mark.parametrize("page", [8, 16])
def test_compressed_keys_complete_at_every_length_across_pages(page):
    """Keys written one position at a time: after each write the pool of
    compressed keys holds exactly the reference's means of every window the
    context holds whole, wherever the window lies on the pages."""
    cfg = dataclasses.replace(CFG, block=page, window=2 * page,
                              query_block=page, key_block=page,
                              prefill_chunk=2 * page)
    rng = np.random.default_rng(page)
    S, P, kvh, d = 2, 6, cfg.n_kv_heads, cfg.head_dim
    r = page // cfg.stride
    tables = jnp.asarray(rng.permutation(np.arange(1, S * P + 1)
                                         ).reshape(S, P), jnp.int32)
    keys = rng.normal(size=(S, P * page, kvh, d)).astype(np.float32)
    pool_k = jnp.zeros((S * P + 1, page, kvh, d), jnp.float32)
    pool_c = jnp.full((S * P + 1, r, kvh, d), np.nan, jnp.float32)
    for n in range(P * page):
        lengths = jnp.asarray([n, max(n - 3, 0)], jnp.int32)
        page_idx = jnp.take_along_axis(tables, (lengths // page)[:, None],
                                       axis=1)[:, 0]
        row = jnp.asarray(keys[np.arange(S), np.asarray(lengths)])[:, None]
        pool_k, _, _, _ = paged_ops.write_kv(
            row, row, pool_k, pool_k, None, None, page_idx, lengths % page,
            False)
        pool_c = paged_ops.write_ckeys(pool_c, pool_k, tables, lengths, cfg)
        got = np.asarray(pool_c)[np.asarray(tables)].reshape(S, P * r, kvh, d)
        for s, ln in enumerate(np.asarray(lengths) + 1):
            if s == 1 and n < 3:
                continue            # that slot writes position 0 repeatedly
            whole = max((ln - cfg.kernel) // cfg.stride + 1, 0)
            for j in range(whole):
                want = keys[s, j * cfg.stride:j * cfg.stride + cfg.kernel
                            ].mean(axis=0)
                np.testing.assert_allclose(got[s, j], want, atol=1e-6)
            assert np.isnan(got[s, whole:]).all()   # nothing written early


def test_prefill_in_chunks_carries_what_one_chunk_computes(params):
    """K/V, compressed keys, lightning states and the last row: chunks of 16
    against ONE chunk over the whole prompt, at a length that fills no
    chunk."""
    prompt = _tokens(75, seed=6)
    first, (kv, ck), states = ms.prefill(params, prompt, 96, CFG)
    one = dataclasses.replace(CFG, prefill_chunk=96, query_block=32,
                              key_block=32)
    first1, (kv1, ck1), states1 = ms.prefill(params, prompt, 96, one)
    np.testing.assert_allclose(first, first1, atol=2e-5)
    for a, b in zip(states, states1):
        np.testing.assert_allclose(a, b, atol=2e-5)
    n_ck = (75 - CFG.kernel) // CFG.stride + 1
    for (k, v), (k1, v1), c, c1 in zip(kv, kv1, ck, ck1):
        np.testing.assert_allclose(k[:75], k1[:75], atol=2e-5)
        np.testing.assert_allclose(v[:75], v1[:75], atol=2e-5)
        np.testing.assert_allclose(c[1:n_ck + 1], c1[1:n_ck + 1], atol=2e-5)
        want = np.stack([np.asarray(k)[j * CFG.stride:j * CFG.stride
                                       + CFG.kernel].mean(axis=0)
                         for j in range(n_ck)])
        np.testing.assert_allclose(np.asarray(c)[1:n_ck + 1], want, atol=1e-6)
    # the state is the state AT the prompt's end: the next token's logits
    # from it are the reference's
    seq = prompt + [int(jnp.argmax(first))]
    want = np.asarray(_reference(params, seq)["logits"])
    np.testing.assert_allclose(first, want[74], atol=2e-4)


# -------------------------------------------------------------------- engine
def _decode_rows(params, prompt, n):
    """The engine's decode logits row by row: ``_decode_logits`` over the
    engine's own pools and state before each step it dispatches. The engine
    runs ahead, so the token a step takes is the one the last step
    dispatched left on the device."""
    eng = _engine(params)
    eng.submit("r", prompt, max_new_tokens=n)
    rows, toks = [], []
    while eng.has_work():
        slot, row = eng.slots[0], None
        if slot is not None and not slot.done:
            lengths = np.zeros(eng.S, np.int32)
            lengths[0] = at = slot.length
            tables = eng.tables.copy()
            if at % eng.page == 0:   # the page ``_grow_tables`` will take
                tables[0, at // eng.page] = eng.free_pages[-1]
            last = (eng._flights[-1].next_tok if eng._flights
                    else jnp.asarray(eng.last_tok))
            row = np.asarray(ms._decode_logits(
                eng.params, eng.pools_k, eng.pools_v, eng.pools_c, eng.ssm,
                jnp.asarray(tables), last, jnp.asarray(lengths), CFG,
                eng.page)[0][0])
        toks += [t for _, t in eng.step() if t is not None]
        if row is not None and eng.slots[0] is slot \
                and slot.length == at + 1:      # the call dispatched a step
            rows.append(row)
    return toks, rows


def test_engine_decode_logits_are_the_references_rows_across_dense_len(
        params):
    """Prefill in chunks (21 tokens: two chunks, dense regime), then decode
    through the engine across ``dense_len`` 32 and three page boundaries:
    every decode row against the reference's full forward pass."""
    prompt = _tokens(21, seed=8)
    toks, rows = _decode_rows(params, prompt, 30)
    # the first step() admits AND decodes: the rows begin at the second
    assert len(toks) == 30 and len(rows) == 28
    seq = prompt + toks
    assert len(seq) - 1 > CFG.dense_len + 2 * CFG.block
    want = np.asarray(_reference(params, seq[:-1])["logits"])
    assert toks[:2] == want[20:22].argmax(-1).tolist()
    for i, row in enumerate(rows):
        np.testing.assert_allclose(row, want[22 + i], atol=3e-4)
        assert toks[i + 2] == int(want[22 + i].argmax())


def test_engine_streams_the_greedy_continuation_of_a_long_prompt(params):
    prompt = _tokens(45, seed=5)              # admitted past dense_len
    out = _alone(params, prompt, 20)
    seq = prompt + out
    want = np.asarray(_reference(params, seq[:-1])["logits"])
    assert out == want[44:].argmax(-1).tolist()


def test_requests_admitted_at_different_steps_stream_what_each_streams_alone(
        params):
    reqs = {"a": (_tokens(40, 1), 12), "b": (_tokens(2, 2), 5),
            "c": (_tokens(21, 3), 19), "d": (_tokens(35, 4), 7)}
    eng = _engine(params, max_slots=2)        # c and d wait for a slot
    got = {r: [] for r in reqs}
    eng.submit("a", reqs["a"][0], max_new_tokens=reqs["a"][1])
    for _ in range(3):                        # b joins three steps later
        for rid, tok in eng.step():
            if tok is not None:
                got[rid].append(tok)
    for r in "bcd":
        eng.submit(r, reqs[r][0], max_new_tokens=reqs[r][1])
    while eng.has_work():
        for rid, tok in eng.step():
            if tok is not None:
                got[rid].append(tok)
    for r, (prompt, n) in reqs.items():
        assert got[r] == _alone(params, prompt, n), r
    assert eng._available_pages() == 63       # page 0 is reserved


@pytest.mark.parametrize("how", [
    {}, {"temperature": 0.8, "top_k": 5, "seed": 3},
    {"temperature": 1.0, "top_p": 0.9, "seed": 11}],
    ids=["greedy", "top_k", "top_p"])
def test_running_ahead_streams_what_the_synchronous_loop_streams(
        params, how, slow_device, streams):
    """Without an ``eos_id`` only the count of tokens ends a stream, so the
    engine dispatches each step on the tokens and keys the last one left on
    the device and fetches tokens ``_STEPS_AHEAD`` steps behind; with an
    ``eos_id`` (one no token equals) every step is fetched in the call that
    dispatched it. Both stream the same tokens, sampled ones too: the keys
    are the same chain. Two requests of unequal lengths, one past
    ``dense_len`` from its first step, one crossing it; the shorter ends
    while the other goes on. Every slot is held, so the depth is
    ``_STEPS_AHEAD``."""
    reqs = {"long": (_tokens(40, 1), 19), "short": (_tokens(21, 3), 13)}
    ahead, deepest = streams(_engine(params, max_slots=2), reqs, **how)
    sync, none = streams(_engine(params, max_slots=2), reqs,
                         eos_id=CFG.vocab_size, **how)
    assert ahead == sync and [len(v) for v in ahead.values()] == [19, 13]
    assert deepest == paged._STEPS_AHEAD and none == 0


def test_a_step_that_has_ended_lands_in_the_call_that_finds_it(
        params, monkeypatch, streams):
    """Where every step has ended by the time the engine asks, each call
    fetches the step it dispatched: nothing stays in flight, the same
    stream."""
    monkeypatch.setattr(paged._Flight, "ended", lambda self: True)
    reqs = {"long": (_tokens(40, 1), 19)}
    got, deepest = streams(_engine(params), reqs)
    assert deepest == 0 and got["long"] == _alone(params, _tokens(40, 1), 19)


def test_steps_in_flight_land_before_an_admission_and_are_work(
        params, slow_device):
    eng = _engine(params, max_slots=2)
    eng.submit("a", _tokens(40, 1), max_new_tokens=14)
    got = {"a": [], "b": []}

    def step():
        events = eng.step()
        for rid, tok in events:
            if tok is not None:
                got[rid].append(tok)
        return events

    step()                                   # admits, dispatches step 1
    assert got["a"] == _alone(params, _tokens(40, 1), 1)
    assert len(eng._flights) == 1 and eng.has_work()
    assert eng.slots[0].length == 41 and len(eng.slots[0].emitted) == 1
    assert step() == []                      # step 2, none fetched
    assert len(eng._flights) == 2 and eng.slots[0].length == 42
    # a slot is free: two in flight hide the host's part of a call, and a
    # third dispatch lands the oldest
    assert [rid for rid, _ in step()] == ["a"]
    assert len(eng._flights) == 2 and eng.slots[0].length == 43
    eng.submit("b", _tokens(9, 2), max_new_tokens=4)
    # a slot is free and b waits: no step is dispatched until the two in
    # flight have landed, one a call; the call that lands the last admits
    # b and dispatches a step for both
    assert [rid for rid, _ in step()] == ["a"] and len(eng._flights) == 1
    assert eng.slots[0].length == 43
    assert [rid for rid, _ in step()] == ["a", "b"]
    assert len(eng._flights) == 1 and eng._flights[0].active == [0, 1]
    assert len(got["a"]) == 4 and eng.slots[0].length == 44
    while eng.has_work():
        step()
    assert got["a"] == _alone(params, _tokens(40, 1), 14)
    assert got["b"] == _alone(params, _tokens(9, 2), 4)
    assert not eng._flights and eng._available_pages() == 63


def test_preemption_by_recompute_resumes_exactly(params):
    """A pool too small for both sequences: one is preempted, requeued with
    prompt + emitted, prefilled again in chunks (lightning state, K/V and
    compressed keys recomputed at the new length) and goes on exactly."""
    reqs = {"x": (_tokens(30, 7), 30), "y": (_tokens(27, 8), 30)}
    eng = _engine(params, max_slots=2, num_pages=12, page_size=8, max_len=64)
    for r, (p, n) in reqs.items():
        eng.submit(r, p, max_new_tokens=n)
    got, preempted = {r: [] for r in reqs}, 0
    while eng.has_work():
        for rid, tok in eng.step():
            if tok is not None:
                got[rid].append(tok)
        preempted += eng._preempted
    assert preempted > 0
    for r, (p, n) in reqs.items():
        assert got[r] == _alone(params, p, n), r


def test_what_the_engine_refuses_for_this_family(params):
    with pytest.raises(ValueError, match="recurrent"):
        _engine(params, enable_prefix_cache=True)
    with pytest.raises(ValueError, match="block is the page"):
        _engine(params, page_size=16, num_pages=32)
    with pytest.raises(ValueError, match="int8.*in the model's dtype"):
        _engine(params, kv_dtype="int8")
    with pytest.raises(ValueError, match="whole chunks"):
        _engine(params, max_len=88)           # not whole chunks of 16


def test_the_other_families_hold_no_compressed_keys():
    from ray_tpu.models import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    eng = PagedEngine(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                      max_slots=2, num_pages=24, page_size=8, max_len=64)
    assert eng.family is paged._FAMILIES[LlamaConfig]
    assert eng.family.no_prefix_cache is None   # the dense row: a prefix
    assert not hasattr(eng, "pools_c")          # cache, no compressed keys
    assert eng._prefill_buckets == eng.family.buckets == (16, 64, 256)


# --------------------------------------------------------------------- spans
@pytest.fixture
def _clean_ring():
    events.reset()
    yield
    events._enabled = True
    events.reset()


def test_spans_and_the_step_rows_counters(params, _clean_ring, slow_device):
    eng = _engine(params)
    eng.submit("req-aaaa-long", _tokens(45, 2), max_new_tokens=6)
    eng.submit("req-bbbb-short", _tokens(9, 3), max_new_tokens=6)
    eng.run_to_completion()
    rows = [events.row_to_dict(r) for r in events.drain()[0]]
    by = {}
    for r in rows:
        by.setdefault(r["name"], []).append(r["fields"])
    admits = by["serve.engine.admit"]
    prefill, scatter, state = (by["serve.admit.prefill"],
                               by["serve.admit.scatter"],
                               by["serve.admit.state"])
    assert [p["chunks"] for p in prefill] == [3, 1]
    assert all(s["ckeys"] == (96 // CFG.stride) * CFG.n_sparse_layers
               and s["dispatches"] == 1 for s in scatter)
    assert all(s["layers"] == CFG.n_lightning_layers and s["dispatches"] == 1
               for s in state)
    assert [p["parent"] for p in prefill] == [a["sid"] for a in admits]
    order = [r["name"] for r in rows if r["name"].startswith("serve.admit.")]
    assert order[:4] == ["serve.admit.prefill", "serve.admit.scatter",
                         "serve.admit.state", "serve.admit.sample"]
    # the engine runs ahead of the device: a row carries the counts of the
    # step whose tokens its call fetched, dispatched some calls earlier
    steps = by["serve.engine.step"]
    landed = [f for f in steps if "sparse_slots" in f]
    assert len(landed) == 5 and len([f for f in steps if f["active"]]) == 5
    assert "sparse_slots" not in steps[0] and steps[0]["admitted"] == 2
    assert steps[-1]["active"] == 0 and steps[-1]["tokens"] == 2
    per = CFG.n_sparse_layers * CFG.n_kv_heads
    for f in landed:
        # the long request alone is past dense_len (45 > 32)
        assert f["sparse_slots"] == 1
        assert f["sparse_pages_read"] == CFG.topk * per
        assert f["sparse_pages_live"] in (6 * per, 7 * per)
        assert f["sparse_pages_read"] < f["sparse_pages_live"]
    assert eng.last_selection.shape == (CFG.n_sparse_layers, 3,
                                        CFG.n_kv_heads, CFG.topk)


def test_greedy_identical_with_recorder_on_and_off(params, _clean_ring):
    prompt = _tokens(40, 6)
    on = _alone(params, prompt, 6)
    assert events.pending() > 0
    events.reset()
    events._enabled = False
    off = _alone(params, prompt, 6)
    assert on == off and events.pending() == 0
