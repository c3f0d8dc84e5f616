"""The latent-attention / zero-expert family: its forward pass, the two
attention forms, the expert layer's share with its zero-compute experts, and
``PagedEngine`` serving it, each against the plain reference
(``perfbench/reference/longcat_flash.py``) at toy sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import longcat_flash as ref
from ray_tpu.models import longcat_flash as lc
from ray_tpu.models import paged, paged_ops
from ray_tpu.models.paged import PagedEngine
from ray_tpu.parallel import moe
from ray_tpu.util import events

CFG = lc.LONGCAT_FLASH_DEBUG     # 2 double layers, chunk 16, 24 + 12 experts


def shape_of(cfg):
    """The reference's ``shape`` keys, as a configuration file names them."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        router_width=cfg.router_width, zero_expert_num=cfg.zero_expert_num,
        expert_offset=cfg.expert_offset, moe_topk=cfg.top_k,
        routed_scaling_factor=cfg.routed_scale)


@pytest.fixture(scope="module")
def params():
    return lc.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def _reference(params, tokens, cfg=CFG, **kw):
    return ref.forward(ref.from_program_tree(params), tokens, shape_of(cfg),
                       **kw)


def _engine(params, **kw):
    kw = {"max_slots": 3, "num_pages": 64, "page_size": 4, "max_len": 96,
          **kw}
    return PagedEngine(params, CFG, **kw)


def _alone(params, prompt, n):
    eng = _engine(params)
    eng.submit("alone", prompt, max_new_tokens=n)
    return eng.run_to_completion()["alone"]


# ------------------------------------------------------------ configuration
@pytest.mark.parametrize("cut, billions", [
    ({}, 560.7),
    ({"n_layers": 4, "experts_held": 16, "vocab_size": 16384}, 5.173)],
    ids=["published", "one-chip-share"])
def test_param_count_is_the_published_560_7_billion_and_the_cut(cut,
                                                                billions):
    cfg = lc.LongcatFlashConfig(**cut)
    assert (cfg.router_width, cfg.n_real, cfg.latent_width) == (768, 512, 576)
    assert round(cfg.param_count() / 1e9, 3 if cut else 1) == billions


def test_param_count_counts_the_tree(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == CFG.param_count()
    moe_ = params["layers"][0]["moe"]
    assert moe_["w_router"].dtype == moe_["router_bias"].dtype == jnp.float32


def test_config_refuses_sizes_the_programs_cannot_cut():
    with pytest.raises(ValueError, match="published depth"):
        dataclasses.replace(CFG, n_layers=5)
    with pytest.raises(ValueError, match="computing experts"):
        dataclasses.replace(CFG, expert_offset=4)
    with pytest.raises(ValueError, match="key_block"):
        dataclasses.replace(CFG, key_block=6)


def test_the_calibrated_bias_spreads_the_picks_over_all_outputs(params):
    """All ``router_width`` outputs, zero experts among them, are picked
    about alike over a fresh sequence: a third of the picks are zero picks."""
    one = dataclasses.replace(CFG, prefill_chunk=512, key_block=64)
    routing = lc.prefill(params, _tokens(512, 5), 512, one,
                         keep_routing=True)[2]
    for layer in routing:
        counts = np.bincount(layer.ravel(), minlength=CFG.router_width)
        want = 512 * CFG.top_k / CFG.router_width
        # 96 distinct tokens: loose, but every output is picked
        assert counts.min() > 0 and counts.max() < 4 * want
    zero = (routing >= CFG.n_real).mean()
    assert abs(zero - CFG.zero_expert_num / CFG.router_width) < 0.08


# --------------------------------------------------------------- the forward
@pytest.mark.parametrize("L", [7, 33, 50])
def test_forward_is_the_reference(params, L):
    tokens = _tokens(L, seed=L)
    got = np.asarray(lc.forward(params, jnp.asarray(tokens), CFG))
    want = _reference(params, tokens)
    np.testing.assert_allclose(got, np.asarray(want["logits"]), atol=2e-4)
    # zero experts and absent experts are among the picks
    own = np.asarray(want["own_routing"])
    assert (own >= CFG.n_real).any() and (own < CFG.n_real).any()


def test_the_reference_under_an_imposed_routing_uses_those_experts(params):
    tokens = _tokens(20, seed=2)
    free = _reference(params, tokens)
    own = np.asarray(free["own_routing"])
    same = _reference(params, tokens, routing=own)
    np.testing.assert_allclose(np.asarray(same["logits"]),
                               np.asarray(free["logits"]), atol=1e-5)
    assert float(np.asarray(same["under"]).max()) == 0.0
    other = own.copy()
    other[0, 3] = (own[0, 3] + 7) % CFG.router_width
    moved = _reference(params, tokens, routing=other, rows=np.arange(3, 20))
    assert np.asarray(moved["under"])[0, 3] > 0
    assert np.abs(np.asarray(moved["logits"])
                  - np.asarray(free["logits"])[3:]).max() > 1e-3


# ----------------------------------------------------- the two attention forms
def _attention_case(seed, lengths, page=4, P=6):
    """Queries of S slots and a latent pool filled from dense rows."""
    rng = np.random.default_rng(seed)
    S, H = len(lengths), CFG.n_heads
    C, dr, dn, dv = (CFG.kv_lora_rank, CFG.qk_rope_head_dim,
                     CFG.qk_nope_head_dim, CFG.v_head_dim)
    W = C + dr
    cap = P * page
    dense = rng.normal(size=(S, cap, W)).astype(np.float32)
    tables = rng.permutation(np.arange(1, S * P + 1)).reshape(S, P)
    pool = np.zeros(paged_ops.latent_pool_shape(S * P + 1, page, W),
                    np.float32)
    for s in range(S):
        pool[tables[s]] = np.asarray(paged_ops.latent_pages(
            jnp.asarray(dense[s]), page))
    q_nope = rng.normal(size=(S, H, dn)).astype(np.float32)
    q_rope = rng.normal(size=(S, H, dr)).astype(np.float32)
    w = rng.normal(size=(C, H, dn + dv)).astype(np.float32) / np.sqrt(C)
    return (dense, jnp.asarray(pool), jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32), q_nope, q_rope, w)


def _expanded_form(dense, q_nope, q_rope, w, lengths):
    """Attention over the keys and values EXPANDED from each slot's rows, in
    float32, slot by slot and head by head. -> [S, H * dv]."""
    C, dn = CFG.kv_lora_rank, CFG.qk_nope_head_dim
    out = []
    for s, n in enumerate(lengths):
        rows = dense[s, :n + 1]                         # keys <= the query's
        kv = np.einsum("kc,chd->khd", rows[:, :C], w)
        sc = (np.einsum("hd,khd->hk", q_nope[s], kv[..., :dn])
              + np.einsum("hr,kr->hk", q_rope[s], rows[:, C:])
              ) * CFG.attn_scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hk,khd->hd", p, kv[..., dn:]).reshape(-1))
    return np.stack(out)


def _absorbed_form(pool, tables, lens, q_nope, q_rope, w):
    dn = CFG.qk_nope_head_dim
    return np.asarray(paged_ops.attend_latent(
        jnp.asarray(q_nope)[:, None], jnp.asarray(q_rope)[:, None],
        jnp.asarray(w[..., :dn]), jnp.asarray(w[..., dn:]), pool, tables,
        lens, CFG.attn_scale))[:, 0]


@pytest.mark.parametrize("seed, lengths", [(0, [5, 23, 0]), (1, [16, 8, 11]),
                                           (2, [1, 2, 22])])
def test_the_absorbed_form_is_the_expanded_form_row_by_row(seed, lengths):
    """``attend_latent`` over pages (never a per-head key or value) against
    attention over the keys and values EXPANDED from the same rows, in
    float32, slot by slot and head by head."""
    dense, pool, tables, lens, q_nope, q_rope, w = _attention_case(
        seed, lengths)
    got = _absorbed_form(pool, tables, lens, q_nope, q_rope, w)
    np.testing.assert_allclose(
        got, _expanded_form(dense, q_nope, q_rope, w, lengths), atol=2e-5)


# The step's read walks each slot's blocks of ``latent_pass_shape`` table
# columns. A table of 10 pages of 4 positions here, at blocks of 2 pages (8
# positions; 3 do not divide the table) and passes of as many blocks as
# slots or fewer: lengths anywhere and a 0 among them, queries ON a block's
# last position (the next is not opened) and one past it (it is, for one
# row), a slot at the table's end, a batch of one.
@pytest.mark.parametrize("block_pages, items", [(2, 4), (2, 3), (3, 4),
                                                (1, 1), (10, 4)])
@pytest.mark.parametrize("lengths", [
    [5, 23, 0, 30], [7, 15, 23, 31], [8, 16, 24, 32], [39, 0, 39, 1], [13]],
    ids=["ragged_with_a_zero", "on_a_blocks_last_position",
         "one_past_a_blocks_edge", "the_tables_last_position", "one_slot"])
def test_the_blocked_read_is_the_expanded_form(monkeypatch, lengths,
                                               block_pages, items):
    dense, pool, tables, lens, q_nope, q_rope, w = _attention_case(
        3, lengths, P=10)
    monkeypatch.setattr(paged_ops, "latent_pass_shape",
                        lambda *a: (block_pages, min(items, len(lengths))))
    got = _absorbed_form(pool, tables, lens, q_nope, q_rope, w)
    np.testing.assert_allclose(
        got, _expanded_form(dense, q_nope, q_rope, w, lengths), atol=2e-5)


def test_pages_past_a_slots_last_are_never_gathered(monkeypatch):
    """The table's columns past a slot's context name page 0 in the engine,
    and a neighbour's pages here: all NaN, as is the rest of the pool. A
    gathered NaN would reach the output through ``0 x NaN``."""
    lengths = [5, 23, 9, 38]
    dense, pool, tables, lens, q_nope, q_rope, w = _attention_case(
        4, lengths, P=10)
    monkeypatch.setattr(paged_ops, "latent_pass_shape", lambda *a: (2, 3))
    want = _absorbed_form(pool, tables, lens, q_nope, q_rope, w)
    tables, live = np.asarray(tables).copy(), np.zeros(pool.shape[0], bool)
    for s, n in enumerate(lengths):
        live[tables[s, :n // 4 + 1]] = True
        tables[s, n // 4 + 1:] = 0
    pool = jnp.where(jnp.asarray(live)[:, None, None], pool, jnp.nan)
    got = _absorbed_form(pool, jnp.asarray(tables), lens, q_nope, q_rope, w)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cell, shape, rows, want", [
    ("serve-longcat-agent-decode", (16, 288, 4609), 1, (36, 16)),
    ("serve-gigachat-reasoning-mtp", (64, 224, 6655), 2, (28, 8)),
    ("the MTP draft's admission call", (1, 224, 6655), 1, (28, 1))])
def test_pass_shape_at_the_cells_shapes(cell, shape, rows, want):
    """An eighth of the table's columns a block; as many blocks a pass as
    slots, or as keep a pass's float32 scores and weighted rows where the
    chip holds them (the widths the two cells were measured at)."""
    S, P, pages = shape
    pool = jax.ShapeDtypeStruct(paged_ops.latent_pool_shape(pages, 64, 576),
                                jnp.bfloat16)
    assert paged_ops.latent_pass_shape(S, P, pool, 2 * rows * 64) == want


@pytest.mark.parametrize("offs", [0, 1, 2, 3])
def test_a_latent_row_lands_on_its_half_of_its_pair(offs):
    """Two positions share a row of the pool; a write replaces its own half
    and leaves its neighbour's."""
    W = CFG.latent_width
    pool = jnp.arange(3 * 2 * 2 * W, dtype=jnp.float32).reshape(3, 2, 2 * W)
    row = -jnp.ones((1, W), jnp.float32)
    new = np.asarray(paged_ops.write_latent(
        row, pool, jnp.asarray([2]), jnp.asarray([offs])))
    want = np.asarray(pool).copy().reshape(3, 4, W)
    want[2, offs] = -1.0
    np.testing.assert_array_equal(new.reshape(3, 4, W), want)


def test_prefill_in_chunks_carries_what_one_chunk_computes(params):
    """37 tokens in three chunks of 16 (a later chunk re-expands the earlier
    positions) against the same prompt as one chunk: the logits, every
    sublayer's cache rows, the routing."""
    prompt = _tokens(37, seed=4)
    first, lats, routing = lc.prefill(params, prompt, 48, CFG,
                                      keep_routing=True)
    one = dataclasses.replace(CFG, prefill_chunk=48)
    first1, lats1, routing1 = lc.prefill(params, prompt, 48, one,
                                         keep_routing=True)
    np.testing.assert_allclose(np.asarray(first), np.asarray(first1),
                               atol=2e-4)
    assert len(lats) == CFG.n_sublayers == 4
    for a, b in zip(lats, lats1):
        np.testing.assert_allclose(np.asarray(a)[:37], np.asarray(b)[:37],
                                   atol=2e-4)
    assert routing.shape == (CFG.n_layers, 37, CFG.top_k)
    assert (np.sort(routing, -1) == np.sort(routing1, -1)).mean() > 0.99
    want = np.asarray(_reference(params, prompt)["logits"])[-1]
    np.testing.assert_allclose(np.asarray(first), want, atol=3e-4)


# ------------------------------------------------------------ the expert layer
def test_softmax_gates_are_the_references_router(params):
    """Unnormalised softmax scores x 6, chosen by score + bias."""
    moe_ = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.d_model))
    vals, idx = moe.softmax_gates(u, moe_["w_router"], moe_["router_bias"],
                                  CFG.top_k, CFG.routed_scale)
    gates, chosen, own, under = ref._route(
        u, moe_["w_router"], moe_["router_bias"],
        jnp.zeros((40, CFG.top_k), jnp.int32), 0, top_k=CFG.top_k,
        scale=CFG.routed_scale)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(own))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(gates), rtol=1e-5)
    s = jax.nn.softmax(u @ moe_["w_router"], -1)
    picked = np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(vals), 6.0 * picked, rtol=1e-5)
    assert float(vals.sum(-1).max()) < 6.0          # not normalised to one
    assert float(np.asarray(under).max()) == 0.0


def test_a_zero_pick_adds_exactly_gate_times_input():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    held = {"w_gate": jnp.ones((2, 8, 4)), "w_up": jnp.ones((2, 8, 4)),
            "w_down": jnp.ones((2, 4, 8))}
    idx = jnp.asarray([[6, 7]] * 5)         # both picks zero experts (>= 6)
    vals = jnp.asarray([[0.25, 0.5]] * 5)
    out, hit, most, zero = moe.moe_ffn_zero(x, vals, idx, held, 0, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(0.75 * x))
    assert (int(hit), int(most), int(zero)) == (0, 0, 10)
    # an absent computing expert (5: past the two held) adds nothing
    idx = idx.at[:, 0].set(5)
    out, _, _, zero = moe.moe_ffn_zero(x, vals, idx, held, 0, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(0.5 * x))
    assert int(zero) == 5
    # a masked lane is routed nowhere, zero experts included
    mask = jnp.asarray([True, False, True, True, False])
    out, _, _, zero = moe.moe_ffn_zero(x, vals, idx, held, 0, 6, mask)
    assert int(zero) == 3 and float(jnp.abs(out[1]).max()) == 0.0


def test_the_shares_with_the_zero_experts_once_sum_to_the_uncut_layer(params):
    """The test that ties the share to the model: the 24 computing experts
    in 6 shares of 4. Each share's held part (``moe_ffn_share``'s) summed,
    plus the zero experts' part counted ONCE, is the uncut reference's
    expert layer."""
    moe_ = params["layers"][0]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (64, CFG.d_model))
    vals, idx = moe.softmax_gates(u, moe_["w_router"], moe_["router_bias"],
                                  CFG.top_k, CFG.routed_scale)
    assert (np.asarray(idx) >= CFG.n_real).any()
    total = jnp.zeros_like(u)
    for offset in range(0, CFG.n_real, 4):
        held = {w: moe_[w][offset:offset + 4]
                for w in ("w_gate", "w_up", "w_down")}
        total = total + moe.moe_ffn_share(u, vals, idx, held, offset)[0]
    none = {w: moe_[w][:1] for w in ("w_gate", "w_up", "w_down")}
    absent = jnp.where(idx < CFG.n_real, -1, idx)       # held nowhere
    zero_part, _, _, n_zero = moe.moe_ffn_zero(u, vals, absent, none, 0,
                                               CFG.n_real)
    assert int(n_zero) == int((np.asarray(idx) >= CFG.n_real).sum())
    want, own, _ = ref.experts(u, moe_, shape_of(CFG),
                               jnp.zeros_like(idx), 0)
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(idx, -1))
    np.testing.assert_allclose(np.asarray(total + zero_part),
                               np.asarray(want), atol=2e-5)
    # one chip's tree is its share of the whole tree's experts
    share = lc.expert_share(params, 8, 4)
    np.testing.assert_array_equal(
        np.asarray(share["layers"][1]["moe"]["w_up"]),
        np.asarray(params["layers"][1]["moe"]["w_up"][8:12]))
    assert share["layers"][1]["moe"]["w_router"].shape[1] == CFG.router_width


def test_forward_of_a_share_is_the_reference_on_that_share(params):
    cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=8)
    share = lc.expert_share(params, 8, 4)
    tokens = _tokens(21, seed=6)
    got = np.asarray(lc.forward(share, jnp.asarray(tokens), cfg))
    want = np.asarray(_reference(share, tokens, cfg)["logits"])
    np.testing.assert_allclose(got, want, atol=2e-4)
    whole = np.asarray(_reference(params, tokens)["logits"])
    assert np.abs(want - whole).max() > 1e-2     # the absent experts' part


# ----------------------------------------------------------------- the engine
def _decode_rows(params, prompt, n):
    """The engine's decode logits row by row: ``_decode_logits`` over the
    engine's own pools before each step it dispatches. The engine runs
    ahead, so the token a step takes is the one the last step dispatched
    left on the device."""
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
            row = np.asarray(lc._decode_logits(
                eng.params, eng.pools_k, jnp.asarray(tables), last,
                jnp.asarray(lengths), CFG, eng.page)[0][0])
        toks += [t for _, t in eng.step() if t is not None]
        if row is not None and eng.slots[0] is slot \
                and slot.length == at + 1:      # the call dispatched a step
            rows.append(row)
    return toks, rows


def test_engine_decode_logits_are_the_references_rows(params):
    """Prefill in chunks (21 tokens: two chunks of 16, expanded form), then
    decode through the engine (absorbed form over pages of 4: seven page
    boundaries, and the chunk size crossed again at 32): every decode row
    against the reference's full forward pass."""
    prompt = _tokens(21, seed=8)
    toks, rows = _decode_rows(params, prompt, 30)
    # the first step() admits AND decodes: the rows begin at the second
    assert len(toks) == 30 and len(rows) == 28
    seq = prompt + toks
    want = np.asarray(_reference(params, seq[:-1])["logits"])
    assert toks[:2] == want[20:22].argmax(-1).tolist()
    for i, row in enumerate(rows):
        np.testing.assert_allclose(row, want[22 + i], atol=3e-4)
        assert toks[i + 2] == int(want[22 + i].argmax())


def test_the_engine_holds_one_pool_a_sublayer_and_no_v_pool(params):
    eng = _engine(params)
    assert eng.family and eng.n_kv == CFG.n_sublayers == 4
    assert eng.pools_v == [] and not hasattr(eng, "ssm")
    assert [p.shape for p in eng.pools_k] == \
        [(64, 2, 2 * CFG.latent_width)] * 4
    assert eng._prefill_buckets == ()


def test_requests_admitted_at_different_steps_stream_what_each_streams_alone(
        params, slow_device):
    reqs = {"a": (_tokens(40, 1), 12), "b": (_tokens(2, 2), 5),
            "c": (_tokens(21, 3), 19), "d": (_tokens(35, 4), 7)}
    eng = _engine(params, max_slots=2)        # c and d wait for a slot
    got, deepest = {r: [] for r in reqs}, 0
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
        deepest = max(deepest, len(eng._flights))
    for r, (prompt, n) in reqs.items():
        assert got[r] == _alone(params, prompt, n), r
    assert deepest >= 3                       # the engine ran ahead
    assert eng._available_pages() == 63       # page 0 is reserved


@pytest.mark.parametrize("how", [
    {}, {"temperature": 0.8, "top_k": 5, "seed": 3}],
    ids=["greedy", "top_k"])
def test_running_ahead_streams_what_the_synchronous_loop_streams(
        params, how, slow_device):
    reqs = {"long": (_tokens(40, 1), 19), "short": (_tokens(21, 3), 13)}

    def streams(**more):
        eng = _engine(params, max_slots=2)  # every slot held: the full depth
        for r, (prompt, n) in reqs.items():
            eng.submit(r, prompt, max_new_tokens=n, **how, **more)
        got, deepest = {r: [] for r in reqs}, 0
        while eng.has_work():
            for rid, tok in eng.step():
                if tok is not None:
                    got[rid].append(tok)
            deepest = max(deepest, len(eng._flights))
        return got, deepest

    ahead, deepest = streams()
    sync, none = streams(eos_id=CFG.vocab_size)
    assert ahead == sync and [len(v) for v in ahead.values()] == [19, 13]
    assert deepest == paged._STEPS_AHEAD and none == 0


def test_preemption_by_recompute_resumes_exactly(params):
    """A pool too small for both sequences: one is preempted, requeued with
    prompt + emitted, prefilled again in chunks and goes on exactly."""
    reqs = {"x": (_tokens(30, 7), 30), "y": (_tokens(27, 8), 30)}
    eng = _engine(params, max_slots=2, num_pages=24, page_size=4, max_len=64)
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


@pytest.mark.parametrize("kw, match", [
    ({"enable_prefix_cache": True}, "enable_prefix_cache"),
    ({"kv_dtype": "int8"}, "model's dtype"),
    ({"max_len": 88}, "whole chunks"),
    ({"page_size": 3, "max_len": 96}, "page_size must be even")],
    ids=["prefix-cache", "int8-pages", "max_len", "odd-page"])
def test_what_the_engine_refuses_for_this_family(params, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(params, **kw)


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
    prefill, scatter = by["serve.admit.prefill"], by["serve.admit.scatter"]
    assert [p["chunks"] for p in prefill] == [3, 1]
    assert all(s["latent_rows"] == 96 * CFG.n_sublayers
               and s["dispatches"] == 1 for s in scatter)
    assert "serve.admit.state" not in by        # the family has none
    assert [p["parent"] for p in prefill] == [a["sid"] for a in admits]
    steps = by["serve.engine.step"]
    landed = [f for f in steps if "latent_positions" in f]
    assert len(landed) == 5 and len([f for f in steps if f["active"]]) == 5
    assert "zero_picks" not in steps[0] and steps[0]["admitted"] == 2
    block = 4 * paged_ops.latent_pass_shape(
        3, 24, eng.pools_k[0], 2 * CFG.n_heads)[0]
    assert block == 12          # an eighth of the table's 24 pages of 4
    for k, f in enumerate(landed):
        assert f["landed"] == 1 and f["moe_rows"] == 2
        # positions 45 + k and 9 + k, and the row the step wrote
        assert f["latent_positions"] == 45 + 9 + 2 * (k + 1)
        # what a sublayer's read gathered: each slot's blocks whole, one
        # block for the idle third slot
        assert f["latent_positions_read"] == block * (
            -(-(45 + k + 1) // block) + -(-(9 + k + 1) // block) + 1)
        assert f["latent_positions"] <= f["latent_positions_read"]
        pairs = 2 * CFG.top_k * CFG.n_layers
        assert 0 <= f["zero_picks"] <= pairs
        assert 0 <= f["experts_hit"] <= CFG.n_layers * CFG.experts_held
        assert f["expert_tokens_max"] <= 2
    assert sum(f["zero_picks"] for f in landed) > 0
    assert eng.last_routing.shape == (CFG.n_layers, 3, CFG.top_k)


def test_greedy_identical_with_recorder_on_and_off(params, _clean_ring):
    prompt = _tokens(40, 6)
    on = _alone(params, prompt, 6)
    assert events.pending() > 0
    events.reset()
    events._enabled = False
    off = _alone(params, prompt, 6)
    assert on == off and events.pending() == 0


@pytest.mark.parametrize("T, overflow", [(16, False), (80, False),
                                         (80, True)],
                         ids=["step-rows", "prompt-rows", "more-than-cap"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_the_grouped_product_is_the_held_experts_on_every_row(T, overflow,
                                                              gated):
    """``moe_ffn_grouped`` (each held expert on its own rows, the first
    ``cap`` sorted pairs) against ``moe_ffn_share`` (every held expert on
    every row): the same result and the same counts, with a lane masked out,
    and where more pairs are held than ``cap`` takes (the ``cond``'s other
    side)."""
    k, E, Eh, D, F = 3, 36, 4, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(T + gated), 6)
    x = jax.random.normal(keys[0], (T, D))
    held = {"w_up": jax.random.normal(keys[1], (Eh, D, F)) / D ** 0.5,
            "w_down": jax.random.normal(keys[2], (Eh, F, D)) / F ** 0.5}
    if gated:
        held["w_gate"] = jax.random.normal(keys[3], (Eh, D, F)) / D ** 0.5
    # every pick held here (overflow), or a ninth of them
    hi = Eh if overflow else E
    idx = jax.random.randint(keys[4], (T, k), 0, hi) + 8
    vals = jax.random.uniform(keys[5], (T, k))
    mask = jnp.arange(T) != 3
    if T * k > max(T, 16 * k):
        assert (int(((idx >= 8) & (idx < 12))[mask].sum()) > T) == overflow
    want = moe.moe_ffn_share(x, vals, idx, held, 8, mask)
    got = jax.jit(moe.moe_ffn_grouped, static_argnums=4)(
        x, vals, idx, held, 8, mask)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-5)
    assert (int(got[1]), int(got[2])) == (int(want[1]), int(want[2]))
    assert float(jnp.abs(got[0][3]).max()) == 0.0


def _held_experts_f32(x, vals, idx, held, offset, mask):
    """The plain statement in float32: every pair whose expert is held and
    whose row is not masked adds ``gate x expert(row)``."""
    f32 = {n: np.asarray(w, np.float32) for n, w in held.items()}
    x, vals, idx = (np.asarray(a, np.float32) for a in (x, vals, idx))
    out = np.zeros_like(x)
    for e in range(f32["w_up"].shape[0]):
        u = x @ f32["w_up"][e]
        if "w_gate" in f32:
            g = x @ f32["w_gate"][e]
            u = g / (1 + np.exp(-g)) * u
        else:
            u = np.square(np.maximum(u, 0))
        gate = ((idx == e + offset) * vals).sum(-1) * np.asarray(mask)
        out += gate[:, None] * (u @ f32["w_down"][e])
    return out


def _kernel_case(routing, all_held, gated, T=128, k=2, D=128, F=256):
    """bf16 rows, gates, picks, four held experts (all the router has, or
    experts 2-5 of its 8), their offset and the mask of one case of the
    kernel form's tests: toy widths of whole lanes, ``T k`` two row tiles."""
    E, offset = (4, 0) if all_held else (8, 2)
    keys = jax.random.split(jax.random.PRNGKey(len(routing) + 2 * gated), 6)
    x = jax.random.normal(keys[0], (T, D)).astype(jnp.bfloat16)
    held = {"w_up": jax.random.normal(keys[1], (4, D, F)) / D ** 0.5,
            "w_down": jax.random.normal(keys[2], (4, F, D)) / F ** 0.5}
    if gated:
        held["w_gate"] = jax.random.normal(keys[3], (4, D, F)) / D ** 0.5
    held = {n: w.astype(jnp.bfloat16) for n, w in held.items()}
    vals = jax.random.uniform(keys[5], (T, k), minval=0.1)
    mask = jnp.ones((T,), bool)
    if routing in ("one-expert", "more-than-cap"):
        idx = jnp.full((T, k), offset + 1)  # every pair to one held expert
    elif routing == "empty-experts":    # held experts 1 and 2 get no pair
        idx = jnp.stack([jnp.full((T,), offset),
                         jnp.where(jnp.arange(T) % 3 == 0, offset + 3,
                                   (offset + 4) % E)], axis=1)
    else:   # distinct picks spread over the router's width
        idx = jnp.argsort(jax.random.uniform(keys[4], (T, E)), axis=-1)[:, :k]
    if routing == "masked-tail":    # a chunk's tail is no token
        mask = jnp.arange(T) < T - 37
    return x, vals, idx.astype(jnp.int32), held, offset, mask


def _forced_kernel_form(monkeypatch, offset, cap):
    """``moe_ffn_grouped`` jitted afresh (a trace of the other form is not
    found again) with its products forced to the kernel; what the predicate
    was asked."""
    asked = []
    monkeypatch.setattr(moe, "grouped_product_form",
                        lambda *a: asked.append(a) or "kernel")
    return jax.jit(lambda x, vals, idx, held, mask: moe.moe_ffn_grouped(
        x, vals, idx, held, offset, mask, cap)), asked


@pytest.mark.parametrize("routing", ["even", "one-expert", "empty-experts",
                                     "masked-tail", "more-than-cap"])
@pytest.mark.parametrize("all_held", [True, False],
                         ids=["all-held", "held-range"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_the_kernel_form_is_the_held_experts_on_every_row(
        routing, all_held, gated, monkeypatch):
    """``moe_ffn_grouped`` with its products as the Pallas grouped matmul
    (interpreted here; the form forced, since the predicate picks it on a
    TPU alone) against ``moe_ffn_share`` and the float32 statement: the same
    result within bf16 and the same counts, whether every expert is held or
    a range from ``expert_offset``, with even groups, one group of every
    pair, empty groups, a chunk's tail masked out, and more pairs held than
    ``cap`` takes (the ``cond``'s other side)."""
    x, vals, idx, held, offset, mask = _kernel_case(routing, all_held, gated)
    T, k = idx.shape
    cap = 128 if routing == "more-than-cap" else T * k
    run, asked = _forced_kernel_form(monkeypatch, offset, cap)
    got = run(x, vals, idx, held, mask)
    assert asked == [(cap, 128, 256, jnp.bfloat16)]
    want = moe.moe_ffn_share(x, vals, idx, held, offset, mask)
    plain = _held_experts_f32(x, vals, idx, held, offset, mask)
    for other in (np.asarray(want[0], np.float32), plain):
        np.testing.assert_allclose(np.asarray(got[0], np.float32), other,
                                   atol=0.02 * np.abs(plain).max())
    assert (int(got[1]), int(got[2])) == (int(want[1]), int(want[2]))
    if routing == "empty-experts":
        assert int(got[1]) == 2
    if routing == "more-than-cap":
        assert int(got[2]) == T * k > cap
    if routing == "masked-tail":
        assert float(jnp.abs(got[0][T - 37:]).max()) == 0.0


def test_rows_past_the_last_group_never_reach_the_kernel_forms_result(
        monkeypatch):
    """What lies past the last held pair is never multiplied into a result:
    the rows of a masked tail hold inf and NaN (they sort past the last
    group, into the boundary tile, whose other rows the kernel leaves as it
    found them, and into the tiles it skips), and the result is finite, the
    masked rows exactly zero, the others bit for bit what the same call
    gives with zeros there."""
    x, vals, idx, held, offset, mask = _kernel_case("masked-tail", False,
                                                    True)
    T, k = idx.shape
    bad = jnp.where(jnp.arange(T)[:, None] % 2 == 0, jnp.inf, jnp.nan)
    planted = jnp.where(mask[:, None], x, bad.astype(x.dtype))
    run, _ = _forced_kernel_form(monkeypatch, offset, T * k)
    got = run(planted, vals, idx, held, mask)
    want = run(jnp.where(mask[:, None], x, 0), vals, idx, held, mask)
    assert bool(jnp.isfinite(got[0].astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    assert float(jnp.abs(got[0][T - 37:]).max()) == 0.0


@pytest.mark.parametrize("cap, D, F, dtype, on_tpu, form", [
    (20480, 4096, 768, jnp.bfloat16, True, "kernel"),   # granite's chunk
    (8192, 2048, 1536, jnp.bfloat16, True, "kernel"),   # lfm2's
    (4096, 4096, 4096, jnp.bfloat16, True, "kernel"),   # Command A+'s
    (2048, 6144, 2048, jnp.bfloat16, True, "kernel"),   # LongCat's
    (192, 6144, 2048, jnp.bfloat16, True, "ragged"),    # LongCat's step
    (8192, 2048, 1536, jnp.bfloat16, False, "ragged"),  # no TPU
    (8192, 2048, 1536, jnp.float32, True, "kernel"),
    (8192, 2048, 1536, jnp.int8, True, "ragged"),       # no float
    (4096, 2688, 1856, jnp.bfloat16, True, "ragged"),   # no whole lanes
    (4096, 2048, 1856, jnp.bfloat16, True, "ragged"),
    (1100, 2048, 1536, jnp.bfloat16, True, "ragged"),   # no whole row tile
    (128, 2048, 1536, jnp.bfloat16, True, "kernel"),    # one row tile
    (4096, 32768, 1536, jnp.bfloat16, True, "ragged"),  # a tile past VMEM
    (4096, 256, 8192, jnp.bfloat16, True, "kernel"),    # narrow and wide
    (4096, 128, 16384, jnp.bfloat16, True, "ragged"),   # its way back past it
])
def test_the_grouped_products_form_follows_shapes_dtype_and_platform(
        cap, D, F, dtype, on_tpu, form, monkeypatch):
    """``grouped_product_form``'s table: the kernel where the sorted pairs
    are whole row tiles, both widths whole lanes, the
    dtype floating, a tile of the whole contraction fits, and the platform
    is a TPU; ``lax.ragged_dot`` everywhere else."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    assert moe.grouped_product_form(cap, D, F, dtype) == form
