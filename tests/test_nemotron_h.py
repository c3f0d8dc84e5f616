"""The hybrid family (models/nemotron_h.py) through ``PagedEngine``: the
Mamba-2 scan (ops/ssm.py), the per-slot recurrent state beside the page
pool, the expert layer that holds a share (parallel/moe.py), at toy sizes on
the CPU. The plain reference is ``perfbench/reference/nemotron_h.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import nemotron_h as ref
from ray_tpu.models import nemotron_h as nh
from ray_tpu.models import paged
from ray_tpu.models.paged import PagedEngine
from ray_tpu.ops import ssm
from ray_tpu.parallel import moe
from ray_tpu.util import events

CFG = nh.NEMOTRON_H_DEBUG
SHAPE = {"norm_eps": CFG.norm_eps, "hybrid_override_pattern": CFG.pattern,
         "num_experts_per_tok": CFG.top_k, "mamba_num_heads": CFG.mamba_heads,
         "mamba_head_dim": CFG.mamba_head_dim, "n_groups": CFG.n_groups,
         "ssm_state_size": CFG.ssm_state,
         "num_attention_heads": CFG.n_heads,
         "num_key_value_heads": CFG.n_kv_heads,
         "routed_scaling_factor": CFG.routed_scale,
         "norm_topk_prob": CFG.norm_topk, "expert_offset": 0}


@pytest.fixture(scope="module")
def params():
    return nh.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, CFG.vocab_size, n).tolist()


def _engine(params, cfg=CFG, **kw):
    kw = {"max_slots": 3, "num_pages": 24, "page_size": 8, "max_len": 64,
          **kw}
    return PagedEngine(params, cfg, **kw)


def _alone(params, prompt, n, cfg=CFG):
    eng = _engine(params, cfg)
    eng.submit("r", prompt, max_new_tokens=n)
    return eng.run_to_completion()["r"]


# ------------------------------------------------------------------- scan
def _scan_inputs(L, H=8, P=4, G=2, N=8, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (L, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (L, H)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (L, G, N)),
            jax.random.normal(k[4], (L, G, N)),
            jax.random.normal(k[5], (H, P, N)))


@pytest.mark.parametrize("L", [1, 7, 8, 9, 37, 64])
@pytest.mark.parametrize("with_h0", [False, True])
def test_chunked_scan_is_the_sequential_recurrence(L, with_h0):
    x, dt, A, B, C, h0 = _scan_inputs(L)
    h0 = h0 if with_h0 else None
    y, s = ssm.ssd_chunked(x, dt, A, B, C, 8, h0)
    y_seq, s_seq = ssm.ssm_sequential(x, dt, A, B, C, h0)
    np.testing.assert_allclose(y, y_seq, atol=2e-5)
    np.testing.assert_allclose(s, s_seq, atol=2e-5)


@pytest.mark.parametrize("n_valid", [1, 5, 8, 13])
def test_a_padded_tail_leaves_state_and_tail_untouched(n_valid):
    """dt = 0 past ``n_valid``: the state a padded scan returns is the
    state at ``n_valid``, and the convolution tail is the last three VALID
    inputs (zeros where the prompt is shorter)."""
    x, dt, A, B, C, _ = _scan_inputs(16)
    masked = jnp.where((jnp.arange(16) < n_valid)[:, None], dt, 0.0)
    _, padded = ssm.ssd_chunked(x, masked, A, B, C, 8)
    _, short = ssm.ssm_sequential(x[:n_valid], dt[:n_valid], A,
                                  B[:n_valid], C[:n_valid])
    np.testing.assert_allclose(padded, short, atol=2e-5)
    seq = jnp.arange(16 * 3, dtype=jnp.float32).reshape(16, 3) + 1.0
    tail = np.asarray(ssm.conv_tail(seq, n_valid, 4))
    want = np.concatenate([np.zeros((3, 3)), np.asarray(seq[:n_valid])])[-3:]
    np.testing.assert_array_equal(tail, want)


def test_conv_step_continues_the_causal_convolution():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (9, 5))
    w, b = jax.random.normal(k[1], (4, 5)), jax.random.normal(k[2], (5,))
    full = ssm.causal_conv(x, w, b)
    tail = ssm.conv_tail(x, 6, 4)[None]
    for t in range(6, 9):
        out, tail = ssm.conv_step(tail, x[t][None], w, b)
        np.testing.assert_allclose(out[0], full[t], atol=1e-5)


# ----------------------------------------------------- forward, reference
def test_forward_matches_the_plain_reference(params):
    toks = _tokens(29)
    got = nh.forward(params, jnp.asarray(toks, jnp.int32), CFG)
    want = ref.logits(ref.from_program_tree(params), toks, SHAPE)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert float(jnp.std(want)) > 0.5


@pytest.mark.parametrize("n_prompt", [3, 9, 16, 21])
def test_prefill_then_decode_matches_the_reference_row_by_row(params,
                                                              n_prompt):
    """A prompt in a PADDED bucket through the prefill program, its state
    written into a slot beside two others, then the decode step over that
    state: every logits row against the reference's full forward."""
    toks = _tokens(n_prompt + 6, seed=n_prompt)
    want = np.asarray(ref.logits(ref.from_program_tree(params), toks, SHAPE))
    page, P, S, slot = 8, 8, 3, 1
    pad = 16 if n_prompt <= 16 else 64
    padded = jnp.asarray(toks[:n_prompt] + [0] * (pad - n_prompt), jnp.int32)
    first, caches, state, _ = nh._hybrid_prefill(params, padded, n_prompt,
                                                 P * page, CFG, pad)
    np.testing.assert_allclose(first, want[n_prompt - 1], atol=2e-4)
    pools_k = [jnp.zeros((24, page, CFG.n_kv_heads, CFG.head_dim))
               for _ in range(CFG.n_attn_layers)]
    pools_v = [jnp.zeros_like(p) for p in pools_k]
    tables = np.zeros((S, P), np.int32)
    tables[slot] = np.arange(1, P + 1)
    for li, (k, v) in enumerate(caches):     # rows of the slot's own pages
        pools_k[li] = pools_k[li].at[tables[slot]].set(
            k.reshape(P, page, *k.shape[1:]))
        pools_v[li] = pools_v[li].at[tables[slot]].set(
            v.reshape(P, page, *v.shape[1:]))
    ssm_s, conv = nh.init_state(CFG, S)
    ssm_s, conv = nh._write_state(ssm_s, conv, state, np.int32(slot))
    none = [0] * CFG.n_attn_layers
    step = jax.jit(nh._decode_logits,
                   static_argnames=("cfg", "page", "kv_int8"))
    for t in range(n_prompt, len(toks)):
        lengths = np.zeros(S, np.int32)
        lengths[slot] = t
        last = np.zeros(S, np.int32)
        last[slot] = toks[t]
        (logits, pools_k, pools_v, _, _, ssm_s, conv, load, routing) = step(
            params, pools_k, pools_v, none, none, ssm_s, conv,
            jnp.asarray(tables), jnp.asarray(last), jnp.asarray(lengths),
            cfg=CFG, page=page, kv_int8=False)
        np.testing.assert_allclose(logits[slot], want[t], atol=3e-4)
        assert 0 < int(load[0]) <= CFG.n_moe_layers * CFG.top_k
        assert int(load[1]) == 1        # one active token: one row an expert
        assert routing.shape == (CFG.n_moe_layers, S, CFG.top_k)


# ---------------------------------------------------------------- engine
def test_engine_streams_the_greedy_continuation(params):
    prompt = _tokens(11, seed=5)
    seq = list(prompt)
    for _ in range(8):
        lg = nh.forward(params, jnp.asarray(seq, jnp.int32), CFG)
        seq.append(int(jnp.argmax(lg[-1])))
    assert _alone(params, prompt, 8) == seq[len(prompt):]


def test_requests_admitted_at_different_steps_stream_what_each_streams_alone(
        params):
    reqs = {"a": (_tokens(4, 1), 12), "b": (_tokens(2, 2), 5),
            "c": (_tokens(21, 3), 9), "d": (_tokens(17, 4), 7)}
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
    # every page is free or a reclaimable full prompt page (page 0 reserved)
    assert eng._available_pages() == 23


def test_preemption_by_recompute_resumes_a_recurrent_sequence_exactly(params):
    """A pool too small for both sequences: one is preempted, requeued with
    prompt + emitted, prefilled again (state recomputed at the new length)
    and goes on exactly where it paused."""
    reqs = {"x": (_tokens(6, 7), 30), "y": (_tokens(5, 8), 30)}
    eng = _engine(params, max_slots=2, num_pages=8, page_size=8, max_len=64)
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


def test_prefix_cache_is_refused_for_a_model_with_recurrent_layers(params):
    with pytest.raises(ValueError, match="recurrent"):
        _engine(params, enable_prefix_cache=True)


def test_int8_pages_stay_close(params):
    prompt = _tokens(12, 9)
    eng = _engine(params, kv_dtype="int8")
    eng.submit("q", prompt, max_new_tokens=6)
    out = eng.run_to_completion()["q"]
    assert len(out) == 6 and out[0] == _alone(params, prompt, 1)[0]


def test_the_dense_family_still_takes_its_own_programs():
    from ray_tpu.models import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    eng = PagedEngine(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                      max_slots=2, num_pages=24, page_size=8, max_len=64)
    row = eng.family
    assert row is paged._FAMILIES[LlamaConfig] and eng.n_kv == 2
    # a prefix cache (the one family that has one), int8 pages, buckets
    assert row.no_prefix_cache is None and row.no_int8 is None
    assert not row.chunked and row.buckets == (16, 64, 256)
    assert not hasattr(eng, "ssm")
    eng.submit("d", [1, 2, 3], max_new_tokens=4)
    events.reset()
    assert len(eng.run_to_completion()["d"]) == 4
    names = {events.row_to_dict(r)["name"] for r in events.drain()[0]}
    assert "serve.admit.state" not in names


def test_step_program_gathers_no_slots_whole_table(params):
    """The jaxpr of the hybrid step at a toy shape whose table holds eight of
    the read's blocks: no array of ``[S, P * page, kvh, d]`` (each slot's
    whole table gathered), a ``while`` an attention layer and a gathered
    block of ``[S, block, kvh, d]`` in it; and a request whose context
    crosses a block's edge while it decodes streams what the model's
    ``forward`` continues with."""
    eng = _engine(params, max_slots=2, num_pages=40, page_size=16,
                  max_len=512)
    S, P = eng.S, eng.P
    text = str(jax.make_jaxpr(
        lambda *a: nh._hybrid_step(*a, CFG, eng.page, False))(
        params, eng.pools_k, eng.pools_v, [0] * eng.n_kv, [0] * eng.n_kv,
        eng.ssm, eng.conv, np.zeros((S, P), np.int32), np.zeros(S, np.int32),
        np.zeros(S, np.int32), np.zeros(S, np.float32),
        np.zeros(S, np.int32), np.ones(S, np.float32),
        np.zeros((S, 2), np.uint32))).replace(" ", "")
    block = eng._read_block
    assert block < eng.max_len
    assert f"[{S},{eng.max_len},{CFG.n_kv_heads}" not in text
    assert f"[{S},{P},{eng.page}," not in text
    assert f"[{S},{block},{CFG.n_kv_heads},{CFG.head_dim}]" in text
    assert text.count("while[") == CFG.n_attn_layers

    prompt = _tokens(block - 3, seed=12)
    eng.submit("crosses", prompt, max_new_tokens=6)
    seq = list(prompt)
    for _ in range(6):
        lg = nh.forward(params, jnp.asarray(seq, jnp.int32), CFG)
        seq.append(int(jnp.argmax(lg[-1])))
    assert eng.run_to_completion()["crosses"] == seq[len(prompt):]


# ----------------------------------------------------------------- spans
@pytest.fixture
def _clean_ring():
    events.reset()
    yield
    events._enabled = True
    events.reset()


def test_state_write_span_and_expert_load_on_the_step_row(
        params, _clean_ring, prompt_device):
    eng = _engine(params)
    eng.submit("req-aaaa-long", _tokens(9, 2), max_new_tokens=4)
    eng.run_to_completion()
    rows = [events.row_to_dict(r) for r in events.drain()[0]]
    by = {}
    for r in rows:
        by.setdefault(r["name"], []).append(r["fields"])
    (admit,), (state,) = by["serve.engine.admit"], by["serve.admit.state"]
    assert state["parent"] == admit["sid"] and state["rid"] == "req-aaaa"
    assert state["layers"] == CFG.n_mamba_layers and state["dispatches"] == 1
    order = [r["name"] for r in rows if r["name"].startswith("serve.admit.")]
    assert order == ["serve.admit.prefill", "serve.admit.scatter",
                     "serve.admit.state", "serve.admit.sample"]
    decoded = [f for f in by["serve.engine.step"] if f["active"]]
    assert decoded
    for f in decoded:
        assert 1 <= f["experts_hit"] <= CFG.n_moe_layers * CFG.top_k
        assert f["expert_tokens_max"] == 1
    idle = [f for f in by["serve.engine.step"] if not f["active"]]
    assert all("experts_hit" not in f for f in idle)


def test_a_sampled_request_streams_the_parents_tokens_and_is_counted(
        params, _clean_ring, prompt_device):
    """The hybrid step through the same picker: the sampled tokens are
    the parent commit's for this seed, alone and beside a greedy
    neighbour, and ``sampling`` falls to 0 on the rows after the sampling
    request left its slot empty with its temperature still in it."""
    samp = dict(max_new_tokens=4, temperature=0.8, top_k=10, top_p=0.9,
                seed=5)
    alone = _engine(params)
    alone.submit("req-samp", _tokens(9, 2), **samp)
    assert alone.run_to_completion() == {"req-samp": [63, 78, 37, 76]}
    events.reset()
    eng = _engine(params)
    eng.submit("req-samp", _tokens(9, 2), **samp)
    eng.submit("req-greedy", _tokens(5, 3), max_new_tokens=9)
    assert eng.run_to_completion() == {
        "req-samp": [63, 78, 37, 76],
        "req-greedy": [57, 18, 42, 24, 0, 57, 71, 33, 16]}
    assert eng.slots == [None] * 3 and eng.temps[0] > 0     # left stale
    rows = [events.row_to_dict(r) for r in events.drain()[0]]
    steps = [r["fields"] for r in rows if r["name"] == "serve.engine.step"]
    assert [f["sampling"] for f in steps] == [1, 1, 1, 0, 0, 0, 0, 0]
    assert [f["active"] for f in steps] == [2, 2, 2, 1, 1, 1, 1, 1]


# ------------------- the engine runs ahead of the device (ISSUE 49: S6)
AHEAD_REQS = {"long": (_tokens(19, 1), 19), "short": (_tokens(5, 3), 13)}


@pytest.mark.parametrize("how", [
    {}, {"temperature": 0.8, "top_k": 5, "seed": 3},
    {"temperature": 1.0, "top_p": 0.9, "seed": 11}],
    ids=["greedy", "top_k", "top_p"])
def test_running_ahead_streams_what_the_synchronous_loop_streams(
        params, how, slow_device, streams):
    """``_hybrid_step`` hands its tokens on alone beside the tokens with
    the two counts, so without an ``eos_id`` the engine dispatches each
    step on the tokens and keys the last one left on the device, the
    recurrent state and the pools going from step to step donated, and
    fetches tokens ``_STEPS_AHEAD`` steps behind (both slots are held);
    with an ``eos_id`` no token equals, every step is fetched in the call
    that dispatched it. The same tokens, sampled ones too."""
    ahead, deepest = streams(_engine(params, max_slots=2), AHEAD_REQS,
                             **how)
    sync, none = streams(_engine(params, max_slots=2), AHEAD_REQS,
                         eos_id=CFG.vocab_size, **how)
    assert ahead == sync and [len(v) for v in ahead.values()] == [19, 13]
    assert deepest == paged._STEPS_AHEAD and none == 0


@pytest.mark.parametrize("slots, depth", [
    (2, paged._STEPS_AHEAD), (3, paged._STEPS_FREE_SLOT)],
    ids=["every_slot_held", "a_slot_free"])
def test_the_depth_follows_whether_a_slot_is_free(params, slots, depth,
                                                  slow_device, streams):
    got, deepest = streams(_engine(params, max_slots=slots), AHEAD_REQS)
    assert deepest == depth
    for r, (prompt, n) in AHEAD_REQS.items():
        assert got[r] == _alone(params, prompt, n), r


def test_a_step_that_has_ended_lands_in_the_call_that_finds_it(
        params, prompt_device, streams):
    reqs = {"long": AHEAD_REQS["long"]}
    got, deepest = streams(_engine(params), reqs)
    assert deepest == 0 and got["long"] == _alone(params, *reqs["long"])


def test_steps_in_flight_land_before_an_admission_and_are_work(
        params, slow_device):
    eng = _engine(params, max_slots=2)
    eng.submit("a", _tokens(11, 1), max_new_tokens=14)
    got = {"a": [], "b": []}

    def step():
        events = eng.step()
        for rid, tok in events:
            if tok is not None:
                got[rid].append(tok)
        return events

    step()                                   # admits, dispatches step 1
    assert got["a"] == _alone(params, _tokens(11, 1), 1)
    assert len(eng._flights) == 1 and eng.has_work()
    assert eng.last_routing is None          # nothing has landed
    assert step() == []                      # step 2, none fetched
    # a slot is free: the third dispatch lands the oldest
    assert [rid for rid, _ in step()] == ["a"] and len(eng._flights) == 2
    assert eng.last_routing is not None      # step 1's
    eng.submit("b", _tokens(9, 2), max_new_tokens=4)
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
    assert got["a"] == _alone(params, _tokens(11, 1), 14)
    assert got["b"] == _alone(params, _tokens(9, 2), 4)
    assert not eng._flights and eng._available_pages() == 23


def test_last_routing_is_the_landed_steps(params, monkeypatch):
    """The reference check reads ``engine.last_routing`` after the call
    that returned a decode token (``nemotron_h_check.program_out``): with
    steps in flight that is the routing of the step whose token the call
    returned, not of the newest dispatched, so the check collects what it
    collects from an engine that fetches every step at once."""
    from perfbench.reference.nemotron_h_check import program_out

    prompt = _tokens(9, 4)
    emitted = _alone(params, prompt, 9)
    outs = []
    for ended in (True, False):
        monkeypatch.setattr(paged._Flight, "ended", lambda self: ended)
        eng = _engine(params)
        depths = []
        step = eng.step
        monkeypatch.setattr(
            eng, "step", lambda: (step(), depths.append(len(eng._flights)))[0])
        outs.append(program_out(eng, prompt, emitted))
        assert max(depths) == (0 if ended else paged._STEPS_FREE_SLOT)
    (row_a, toks_a, routes_a), (row_b, toks_b, routes_b) = outs
    assert toks_a == toks_b == emitted and np.array_equal(row_a, row_b)
    assert routes_a.shape == (CFG.n_moe_layers, 9 + 9 - 1, CFG.top_k)
    assert np.array_equal(routes_a, routes_b)
    # the decode steps' choices differ from step to step: a routing read
    # one step late or early would not pass
    assert len({routes_a[:, k].tobytes() for k in range(9, 17)}) > 1


def test_a_row_that_lands_two_steps_carries_one_steps_expert_load(
        params, _clean_ring, monkeypatch):
    """``experts_hit`` is one step's figure on every ``serve.engine.step``
    row (the benchmark's reader averages rows): the call that lands the
    last step in flight, admits a stream with an ``eos_id`` and fetches the
    step it dispatches lands two, and its row holds their mean."""
    def rows(ended):
        monkeypatch.setattr(paged._Flight, "ended", lambda self: ended)
        events.reset()
        eng = _engine(params, max_slots=2)
        eng.submit("a", _tokens(11, 1), max_new_tokens=8)
        landed = [(eng.step(), eng._landed)[1] for _ in range(2)]
        eng.submit("b", _tokens(9, 2), max_new_tokens=4,
                   eos_id=CFG.vocab_size)
        landed += [(eng.step(), eng._landed)[1] for _ in range(2)]
        steps = [r[6] for r in events.drain()[0]
                 if r[1] == "serve.engine.step"]
        return landed, [(f.get("experts_hit"), f.get("expert_tokens_max"))
                        for f in steps]

    landed, (s1, s2, s3, _) = rows(True)    # a step a row
    assert landed == [1, 1, 1, 1]
    landed, loads = rows(False)
    # two calls dispatch s1 and s2; b waits beside a free slot: one call
    # lands s1, the next lands s2, admits b and fetches s3 at once
    assert landed == [0, 0, 1, 2]
    assert loads == [(None, None), (None, None), s1,
                     ((s2[0] + s3[0]) // 2, (s2[1] + s3[1]) // 2)]


def test_greedy_identical_with_recorder_on_and_off(params, _clean_ring):
    prompt = _tokens(10, 6)
    on = _alone(params, prompt, 6)
    assert events.pending() > 0
    events.reset()
    events._enabled = False
    off = _alone(params, prompt, 6)
    assert on == off and events.pending() == 0


# ------------------------------------------------ the layer holding a share
def _layer(key, T=24, D=32, E=16, F=40, Fs=48):
    k = jax.random.split(key, 7)
    x = jax.random.normal(k[0], (T, D))
    layer = {"w_router": jax.random.normal(k[1], (D, E)) / np.sqrt(D),
             "router_bias": 0.1 * jax.random.normal(k[2], (E,)),
             "w_up": jax.random.normal(k[3], (E, D, F)) / np.sqrt(D),
             "w_down": jax.random.normal(k[4], (E, F, D)) / np.sqrt(F),
             "ws_up": jax.random.normal(k[5], (D, Fs)) / np.sqrt(D),
             "ws_down": jax.random.normal(k[6], (Fs, D)) / np.sqrt(Fs)}
    return x, layer


# a decode step's rows and a prompt's: one form, whatever the count
ROWS = {"few_rows": 24, "many_rows": 512}


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("rows", [32, 1280],
                         ids=["decode_step", "prompt_1280"])
def test_a_share_holds_no_grouped_product_and_no_sort(rows):
    """At the cell's widths, traced only: a step's 32 rows and a 1280-row
    prompt alike go through two batched products and one gather."""
    D, F, Eh, k = 2688, 1856, 16, 6
    sd = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda x, v, i, up, down, m: moe.moe_ffn_share(
            x, v, i, {"w_up": up, "w_down": down}, 32, m))(
        sd((rows, D), jnp.bfloat16), sd((rows, k), jnp.float32),
        sd((rows, k), jnp.int32), sd((Eh, D, F), jnp.bfloat16),
        sd((Eh, F, D), jnp.bfloat16), sd((rows,), bool))
    names = list(_primitives(jaxpr.jaxpr))
    assert (names.count("dot_general"), names.count("ragged_dot_general"),
            names.count("sort")) == (2, 0, 0)
    # the weights enter the products as they are stored: nothing transposes
    # or converts an [Eh, D, F] array on the way
    big = [e for e in jaxpr.jaxpr.eqns if e.primitive.name != "dot_general"
           and any(getattr(v.aval, "shape", None)
                   in ((Eh, D, F), (Eh, F, D)) for v in e.invars)]
    assert not big


@pytest.mark.parametrize("rows", ROWS.values(), ids=ROWS.keys())
@pytest.mark.parametrize("routing", ["even", "uneven", "one_expert"])
def test_eight_shares_add_up_to_the_whole_layer(routing, rows):
    """Each of eight chips holds two of sixteen experts. Their routed parts,
    plus the shared expert counted ONCE, are the uncut reference's whole
    layer, and the routed parts alone are ``moe_ffn_dense``'s under the
    same scores; however uneven the routing and however many the rows, no
    token is dropped."""
    x, layer = _layer(jax.random.PRNGKey(11), T=rows)
    T, E, k = x.shape[0], 16, 3
    if routing == "uneven":     # the bias sends most tokens to experts 0-2
        layer["router_bias"] = layer["router_bias"].at[:3].add(0.6)
    if routing == "one_expert":  # ... and expert 5 gets every token
        layer["router_bias"] = layer["router_bias"].at[5].add(5.0)
    vals, idx = moe.sigmoid_gates(x, layer["w_router"], layer["router_bias"],
                                  k, 2.5)
    if routing == "one_expert":
        assert bool((idx == 5).any(axis=-1).all())
    parts, hits, most = [], 0, 0
    for share in range(8):
        held = {n: layer[n][2 * share:2 * share + 2]
                for n in ("w_up", "w_down")}
        out, hit, m = moe.moe_ffn_share(x, vals, idx, held, 2 * share)
        parts.append(out)
        hits += int(hit)
        most = max(most, int(m))
    routed = sum(parts)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
    assert hits == int((counts > 0).sum()) and most == counts.max()
    if routing == "one_expert":
        assert most == T
    dense, _ = moe.moe_ffn_dense(
        x[None], layer["w_router"],
        {"w_up": layer["w_up"], "w_down": layer["w_down"]}, k,
        gates=(vals[None], idx[None]))
    np.testing.assert_allclose(routed, dense[0], atol=2e-5)
    shared = moe.relu2(x @ layer["ws_up"]) @ layer["ws_down"]
    ref_w = {**layer, "norm": jnp.ones((x.shape[1],))}
    whole, own, under = ref.expert_block(
        x, ref_w, jnp.zeros((T, k), jnp.int32), 0, top_k=k, offset=0,
        scale=2.5, norm=True, eps=0.0)
    # the reference normalises its input; feed it rows of unit mean square
    unit = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    vals_u, idx_u = moe.sigmoid_gates(unit, layer["w_router"],
                                      layer["router_bias"], k, 2.5)
    routed_u = sum(moe.moe_ffn_share(
        unit, vals_u, idx_u, {n: layer[n][2 * s:2 * s + 2]
                              for n in ("w_up", "w_down")}, 2 * s)[0]
        for s in range(8))
    shared_u = moe.relu2(unit @ layer["ws_up"]) @ layer["ws_down"]
    np.testing.assert_allclose(whole - x, routed_u + shared_u, atol=3e-5)
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(idx_u, -1))
    assert float(jnp.abs(shared).max()) > 0 and float(under.max()) == 0.0


def test_a_masked_token_reaches_no_expert():
    x, layer = _layer(jax.random.PRNGKey(12), T=6)
    vals, idx = moe.sigmoid_gates(x, layer["w_router"], layer["router_bias"],
                                  3, 2.5)
    held = {n: layer[n] for n in ("w_up", "w_down")}
    mask = jnp.asarray([True, False, True, False, False, True])
    out, hit, most = moe.moe_ffn_share(x, vals, idx, held, 0, mask)
    full, _, _ = moe.moe_ffn_share(x, vals, idx, held, 0)
    np.testing.assert_allclose(out[mask], full[mask], atol=1e-5)
    assert float(jnp.abs(out[~mask]).max()) == 0.0
    counts = np.bincount(np.asarray(idx)[np.asarray(mask)].ravel(),
                         minlength=16)
    assert int(hit) == (counts > 0).sum() and int(most) == counts.max()


@pytest.mark.parametrize("rows", ROWS.values(), ids=ROWS.keys())
@pytest.mark.parametrize("case", ["no_held_expert_chosen", "lanes_left_out",
                                  "experts_8_to_11"])
def test_a_share_counts_and_leaves_out_pair_by_pair(case, rows):
    """A share nobody is routed to gives zeros and counts nothing; lanes the
    mask leaves out reach no expert and are not counted; a share that starts
    past expert 0 computes its own pairs only. ``hit`` and ``most`` are
    ``numpy.bincount``'s over the pairs that count, and the result is the
    pair-by-pair sum in float32."""
    x, layer = _layer(jax.random.PRNGKey(13), T=rows)
    offset, Eh, k = (8, 4, 3) if case == "experts_8_to_11" else (2, 3, 3)
    if case == "no_held_expert_chosen":
        layer["router_bias"] = layer["router_bias"].at[2:5].add(-9.0)
    vals, idx = moe.sigmoid_gates(x, layer["w_router"], layer["router_bias"],
                                  k, 2.5)
    mask = None
    if case == "lanes_left_out":
        mask = jnp.arange(rows) % 3 != 1
    held = {n: layer[n][offset:offset + Eh] for n in ("w_up", "w_down")}
    out, hit, most = moe.moe_ffn_share(x, vals, idx, held, offset, mask)
    lanes = np.ones(rows, bool) if mask is None else np.asarray(mask)
    counts = np.bincount(np.asarray(idx)[lanes].ravel(),
                         minlength=16)[offset:offset + Eh]
    assert int(hit) == (counts > 0).sum()
    assert int(most) == counts.max()
    want = np.zeros(x.shape, np.float32)
    for e in range(offset, offset + Eh):
        y = moe.relu2(x @ layer["w_up"][e]) @ layer["w_down"][e]
        gate = jnp.sum(jnp.where(idx == e, vals, 0.0), axis=-1)
        want += np.asarray(y * gate[:, None]) * lanes[:, None]
    np.testing.assert_allclose(out, want, atol=3e-5)
    if case == "no_held_expert_chosen":
        assert int(hit) == 0 and float(jnp.abs(out).max()) == 0.0
    if case == "lanes_left_out":
        assert float(jnp.abs(out[~mask]).max()) == 0.0


@pytest.mark.parametrize("rows", ROWS.values(), ids=ROWS.keys())
def test_bfloat16_products_are_gated_and_summed_in_float32(rows):
    """bfloat16 operands as the cell's: each pair's row is the expert's two
    bfloat16 products, the gate multiplies it in float32 AFTER the second
    product and a token's ``k`` rows are summed in float32 in gate order:
    bit for bit the pair-by-pair computation rounded once at the end."""
    x, layer = _layer(jax.random.PRNGKey(17), T=rows)
    k, bf = 3, jnp.bfloat16
    vals, idx = moe.sigmoid_gates(x, layer["w_router"], layer["router_bias"],
                                  k, 2.5)
    x, up, down = x.astype(bf), layer["w_up"].astype(bf), \
        layer["w_down"].astype(bf)
    out, _, _ = moe.moe_ffn_share(x, vals, idx,
                                  {"w_up": up, "w_down": down}, 0)
    assert out.dtype == bf
    ys = jnp.stack([moe.relu2(x @ up[e]) @ down[e] for e in range(16)])
    want = jnp.zeros(x.shape, jnp.float32)
    for j in range(k):
        row = ys[idx[:, j], jnp.arange(rows)].astype(jnp.float32)
        want = want + row * vals[:, j, None]
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want.astype(bf), np.float32))


def test_a_share_of_the_model_is_the_reference_with_the_same_share(params):
    """The engine's family on experts 4-7 of 16 against the reference given
    the same share: what the absent experts would add is left out alike."""
    cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=4)
    share = nh.expert_share(params, 4, 4)
    toks = _tokens(19, 4)
    got = nh.forward(share, jnp.asarray(toks, jnp.int32), cfg)
    want = ref.logits(ref.from_program_tree(share), toks,
                      {**SHAPE, "expert_offset": 4})
    np.testing.assert_allclose(got, want, atol=2e-4)
    whole = nh.forward(params, jnp.asarray(toks, jnp.int32), CFG)
    assert float(jnp.abs(whole - got).max()) > 1e-2
