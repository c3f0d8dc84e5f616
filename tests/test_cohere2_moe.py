"""The window / full attention family with held and shared experts: its
forward pass, the two reads of its two caches (a ring a slot for a window
layer, the page pool in blocks for a full layer), the expert layer's share,
and ``PagedEngine`` serving it, each against the plain reference
(``perfbench/reference/cohere2_moe.py``) at toy sizes: a window of 16, pages
of 4, chunks of 16, so a ring wraps more than twice in a test's few dozen
positions."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import cohere2_moe as ref
from ray_tpu.models import cohere2_moe as cm
from ray_tpu.models import paged, paged_ops
from ray_tpu.models.paged import PagedEngine
from ray_tpu.ops import layers
from ray_tpu.parallel import moe
from ray_tpu.util import events

CFG = cm.COHERE2_MOE_DEBUG      # 3 window + 1 full layer, window 16, chunk 16
W = CFG.sliding_window


def shape_of(cfg):
    """The reference's ``shape`` keys, as a configuration file names them."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        layer_types=list(cfg.layer_types), num_hidden_layers=cfg.n_layers,
        sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        layer_norm_eps=cfg.norm_eps, router_width=cfg.router_width,
        num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.top_k, num_shared_experts=cfg.n_shared,
        logit_scale=cfg.logit_scale)


@pytest.fixture(scope="module")
def params():
    return cm.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def _reference(params, tokens, cfg=CFG, **kw):
    return ref.forward(ref.from_program_tree(params), tokens, shape_of(cfg),
                       **kw)


def _engine(params, cfg=CFG, **kw):
    kw = {"max_slots": 3, "num_pages": 64, "page_size": 4, "max_len": 96,
          **kw}
    return PagedEngine(params, cfg, **kw)


def _alone(params, prompt, n):
    eng = _engine(params)
    eng.submit("alone", prompt, max_new_tokens=n)
    return eng.run_to_completion()["alone"]


# ------------------------------------------------------------ configuration
@pytest.mark.parametrize("cut, count", [
    ({}, 218_254_938_112),
    ({"n_layers": 4, "experts_held": 16, "vocab_size": 32768},
     4_733_292_544)], ids=["published", "one-chip-cut"])
def test_param_count_is_the_published_218_billion_and_the_cut(cut, count):
    cfg = cm.Cohere2MoeConfig(**cut)
    assert cfg.param_count() == count
    assert cfg.kinds[:4] == (cm.WINDOW,) * 3 + (cm.FULL,)
    assert cfg.n_full_layers * 4 == cfg.n_layers
    # 24.98 B active: eight of the routed experts a token
    active = dataclasses.replace(cm.Cohere2MoeConfig(), experts_held=8)
    assert round(active.param_count() / 1e9, 2) == 24.98


def test_param_count_counts_the_tree(params):
    held = sum(a.size for a in jax.tree.leaves(params))
    assert CFG.param_count() == held
    assert params["layers"][0]["moe"]["w_router"].dtype == jnp.float32
    assert "lm_head" not in params          # the head is the embedding


def test_config_refuses_sizes_the_programs_cannot_cut():
    with pytest.raises(ValueError, match="router's width"):
        dataclasses.replace(CFG, experts_held=9, expert_offset=8)
    with pytest.raises(ValueError, match="key_block"):
        dataclasses.replace(CFG, key_block=5)
    with pytest.raises(ValueError, match="layer types"):
        dataclasses.replace(CFG, layer_types=("chunked_attention",) * 4)
    with pytest.raises(ValueError, match="past layer_types"):
        dataclasses.replace(CFG, layer_types=(cm.FULL,) * 2)
    # a list from a configuration file becomes the hashable field
    cfg = dataclasses.replace(CFG, layer_types=[cm.FULL, cm.WINDOW] * 2)
    assert hash(cfg) and cfg.kinds == (cm.FULL, cm.WINDOW) * 2


def test_layer_norm_subtracts_the_mean_and_has_no_bias():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64)) * 3.0 + 2.0
    g = jax.random.normal(jax.random.PRNGKey(2), (64,)) * 0.1
    got = np.asarray(layers.layer_norm(x, g, 1e-5))
    xc = np.asarray(x) - np.asarray(x).mean(-1, keepdims=True)
    want = xc / np.sqrt((xc ** 2).mean(-1, keepdims=True) + 1e-5) \
        * (1.0 + np.asarray(g))
    np.testing.assert_allclose(got, want, atol=1e-5)
    # what rms_norm would leave in: the mean
    assert np.abs(np.asarray(layers.rms_norm(x, g, 1e-5)) - want).max() > 0.1
    np.testing.assert_allclose(
        np.asarray(layers.layer_norm(x + 7.0, g, 1e-5)), want, atol=1e-4)


def test_the_interleaved_rotary_turns_pairs_2j_2j_plus_1():
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 2, 8))
    cos, sin = layers.rope_rows(jnp.arange(6), 8, 50000.0)
    got = np.asarray(layers.rope_interleaved(x, cos, sin))
    np.testing.assert_allclose(got, np.asarray(ref._rotary(x, 50000.0)),
                               atol=1e-6)
    np.testing.assert_allclose(got[0], np.asarray(x[0]), atol=1e-7)


# ------------------------------------------------------------- forward pass
@pytest.mark.parametrize("L", [7, W, W + 1, 50],
                         ids=["under", "at", "one-past", "3x-window"])
def test_forward_is_the_reference(params, L):
    tokens = _tokens(L, seed=L)
    got = np.asarray(cm.forward(params, jnp.asarray(tokens, jnp.int32), CFG))
    out = _reference(params, tokens)
    np.testing.assert_allclose(got, np.asarray(out["logits"]), atol=3e-5)
    assert np.asarray(out["logits"]).std() > 0.3
    assert np.asarray(out["own_routing"]).shape == (4, L, CFG.top_k)


def test_forward_in_bfloat16_stays_near_the_reference():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = cm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = _tokens(40, seed=2)
    out = cm.forward(params, jnp.asarray(tokens, jnp.int32), cfg)
    assert out.dtype == jnp.bfloat16
    got = np.asarray(out.astype(jnp.float32))
    # under the routing the program chose: a near tie swaps a whole expert
    routing = cm.prefill(params, tokens, 48, dataclasses.replace(
        cfg, prefill_chunk=48), keep_routing=True)[2]
    want = np.asarray(_reference(params, tokens, cfg,
                                 routing=routing)["logits"])
    assert np.sqrt(np.mean((got - want) ** 2)) < 0.06 * want.std()


def test_the_reference_under_an_imposed_routing_uses_those_experts(params):
    tokens = _tokens(20, seed=4)
    free = _reference(params, tokens)
    own = np.asarray(free["own_routing"])
    same = _reference(params, tokens, routing=own)
    np.testing.assert_allclose(np.asarray(same["logits"]),
                               np.asarray(free["logits"]), atol=1e-6)
    assert float(np.asarray(same["under"]).max()) == 0.0
    other = (own + 1) % CFG.router_width
    moved = _reference(params, tokens, routing=other)
    assert float(np.asarray(moved["under"]).max()) > 0.0
    assert np.abs(np.asarray(moved["logits"])
                  - np.asarray(free["logits"])).max() > 1e-3


# --------------------------------------------------- the window and the ring
def _marked(n, at):
    """K = 0 (every visible key scores alike) and V zero but for a one at
    position ``at``: a query's output is 1 / visible where it sees ``at``."""
    shape = (n, CFG.n_kv_heads, CFG.head_dim)
    return jnp.zeros(shape), jnp.zeros(shape).at[at].set(1.0)


@pytest.mark.parametrize("start", [0, 16, 32])
def test_a_prompts_window_hides_key_q_minus_w_and_sees_the_next(start):
    """Queries of the chunk at ``start ..`` over 48 positions: each sees its
    own position and the ``W - 1`` before it, whatever chunk they lie in."""
    N, q = 16, jnp.ones((16, CFG.n_heads, CFG.head_dim))
    for i in (0, 5, 15):
        t = start + i
        for at, seen in ((t - W, False), (t - W + 1, True), (t, True),
                         (t + 1, False)):
            if at < 0 or at >= 48:
                continue
            k, v = _marked(48, at)
            o = np.asarray(cm._prompt_attention(q, k, v, jnp.int32(start), W,
                                                CFG))[i]
            want = 1.0 / min(t + 1, W) if seen else 0.0
            np.testing.assert_allclose(o, want, atol=1e-6, err_msg=str(
                (start, i, at)))
            full = np.asarray(cm._prompt_attention(
                q, k, v, jnp.int32(start), 0, CFG))[i]
            np.testing.assert_allclose(
                full, 1.0 / (t + 1) if at <= t else 0.0, atol=1e-6)
    assert N == CFG.prefill_chunk


@pytest.mark.parametrize("n", [3, W, W + 1, 2 * W + 5],
                         ids=["under", "at", "one-past", "wrapped-twice"])
def test_the_ring_holds_the_last_window_and_evicts_what_it_passed(n):
    """An admission's ring write, then decode writes: after each, the slot's
    query sees exactly positions ``q - W + 1 .. q`` (index ``p mod W``), and
    the neighbouring slots' rings are untouched."""
    S, kvh, d = 3, CFG.n_kv_heads, CFG.head_dim
    rings = ([jnp.full((S, kvh, W, d), 7.0)], [jnp.full((S, kvh, W, d), 7.0)])
    pos = jnp.arange(96, dtype=jnp.float32)
    rows = jnp.broadcast_to(pos[:, None, None], (96, kvh, d))
    rk, rv = cm._write_rings(*rings, [(jnp.zeros_like(rows), rows)],
                             np.int32(n), np.int32(1))
    assert float(jnp.abs(rk[0][0] - 7).max()) == 0 \
        and float(jnp.abs(rv[0][2] - 7).max()) == 0
    lengths = np.array([0, n, 0], np.int32)
    q = jnp.ones((S, CFG.n_heads, d))
    for step in range(W + 3):
        at = n + step
        k = jnp.zeros((S, kvh, d))
        v = jnp.full((S, kvh, d), float(at))
        rk[0], rv[0] = paged_ops.write_ring(k, v, rk[0], rv[0],
                                            jnp.asarray(lengths))
        # K = 0: the output is the mean of the visible positions' values
        o = np.asarray(paged_ops.attend_ring(q, rk[0], rv[0],
                                             jnp.asarray(lengths)))[1]
        lo = max(at - W + 1, 0)
        np.testing.assert_allclose(o, (lo + at) / 2.0, rtol=1e-5,
                                   err_msg=str((n, step)))
        held = np.asarray(rv[0][1, 0, :, 0])
        assert held[at % W] == at and (at < W or sorted(held) == list(
            range(at - W + 1, at + 1)))
        lengths[1] += 1


@pytest.mark.parametrize("kind", [cm.FULL, cm.WINDOW])
def test_a_full_layer_applies_no_position_and_a_window_layer_does(kind):
    """One layer of one kind: with NO position a causal query's output does
    not change when the tokens before it change places; with the rotary it
    does. And a full layer's cached K is the same row wherever it lies."""
    cfg = dataclasses.replace(CFG, n_layers=1, layer_types=(kind,),
                              sliding_window=64)
    params = cm.init_params(cfg, jax.random.PRNGKey(5))
    tokens = _tokens(12, seed=6)
    moved = tokens[:11][::-1] + tokens[11:]
    a = np.asarray(cm.forward(params, jnp.asarray(tokens, jnp.int32), cfg))
    b = np.asarray(cm.forward(params, jnp.asarray(moved, jnp.int32), cfg))
    h = jax.random.normal(jax.random.PRNGKey(7), (4, cfg.d_model))
    at = lambda p: cm._qkv(params["layers"][0], h, *layers.rope_rows(  # noqa: E731
        p + jnp.arange(4), cfg.head_dim, cfg.rope_theta), kind, cfg)
    if kind == cm.FULL:
        np.testing.assert_allclose(a[-1], b[-1], atol=2e-5)
        for x, y in zip(at(0), at(9)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    else:
        assert np.abs(a[-1] - b[-1]).max() > 1e-2
        assert np.abs(np.asarray(at(0)[1]) - np.asarray(at(9)[1])).max() > 0.1
        np.testing.assert_array_equal(np.asarray(at(0)[2]),
                                      np.asarray(at(9)[2]))    # V: never
    want = np.asarray(_reference(params, tokens, cfg)["logits"])
    np.testing.assert_allclose(a, want, atol=3e-5)


# ------------------------------------------------------- the blocked full read
def _pool_case(seed, lengths, page=4, P=8, pages=40):
    rng = np.random.default_rng(seed)
    S, kvh, d = len(lengths), CFG.n_kv_heads, CFG.head_dim
    pool_k = jnp.asarray(rng.normal(size=(pages, page, kvh, d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(pages, page, kvh, d)), jnp.float32)
    free = list(rng.permutation(np.arange(1, pages)))
    tables = np.zeros((S, P), np.int32)
    for s, n in enumerate(lengths):
        for j in range(n // page + 1 if n else 0):
            tables[s, j] = free.pop()
    q = jnp.asarray(rng.normal(size=(S, 1, CFG.n_heads, d)), jnp.float32)
    return q, pool_k, pool_v, jnp.asarray(tables), \
        jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("block_pages", [1, 2, 3, 8, 32])
@pytest.mark.parametrize("seed, lengths", [(0, [5, 23, 0]), (1, [31, 8, 11]),
                                           (2, [0, 0, 1])])
def test_the_blocked_read_is_attend_pages_on_the_same_pool(seed, lengths,
                                                           block_pages):
    q, pool_k, pool_v, tables, lens = _pool_case(seed, lengths)
    want = paged_ops.attend_pages(q, pool_k, pool_v, None, None, tables,
                                  lens, False, jnp.float32)
    got = paged_ops.attend_pages_blocked(q, pool_k, pool_v, tables, lens,
                                         block_pages)
    assert got.shape == want.shape == (3, 1, CFG.n_heads * CFG.head_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_blocked_read_visits_each_slots_own_blocks_and_no_others():
    """A block wholly past a slot's own context is never gathered, however
    long its neighbour's: the table's columns there point at a poisoned page
    (a gathered NaN would reach the output through ``0 x NaN``), and the
    result is still ``attend_pages``' on the clean table. The jaxpr holds one
    ``while`` and no gather of the table's full width."""
    q, pool_k, pool_v, tables, lens = _pool_case(0, [5, 23, 0], P=32,
                                                 pages=200)
    want = paged_ops.attend_pages(q, pool_k, pool_v, None, None, tables,
                                  lens, False, jnp.float32)
    poisoned = np.asarray(tables).copy()
    for s, n in enumerate([5, 23, 0]):          # blocks of 2 pages of 4
        poisoned[s, 2 * (n // 8 + 1):] = 199
    pool_k, pool_v = pool_k.at[199].set(jnp.nan), pool_v.at[199].set(jnp.nan)
    got = paged_ops.attend_pages_blocked(q, pool_k, pool_v,
                                         jnp.asarray(poisoned), lens, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    text = str(jax.make_jaxpr(lambda *a: paged_ops.attend_pages_blocked(
        *a, 4))(q, pool_k, pool_v, tables, lens)).replace(" ", "")
    S, cap = 3, 32 * 4
    assert "while" in text and "cumsum" in text
    assert f"[{S},{cap}," not in text
    assert f"[{S},16," in text              # a block: 4 pages of 4 positions


def test_the_blocked_read_folds_many_blocks_of_one_slot_in_one_pass():
    """One long slot beside short ones: a pass of ``S`` blocks then holds
    several blocks of the same slot, which fold into it together."""
    q, pool_k, pool_v, tables, lens = _pool_case(3, [2, 120, 0, 9], P=32,
                                                 pages=80)
    want = paged_ops.attend_pages(q, pool_k, pool_v, None, None, tables,
                                  lens, False, jnp.float32)
    for bp in (1, 3, 5):
        got = paged_ops.attend_pages_blocked(q, pool_k, pool_v, tables, lens,
                                             bp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)


# ----------------------------------------------------------- the expert layer
def test_sigmoid_gates_with_a_zero_bias_are_the_references_router(params):
    h = jax.random.normal(jax.random.PRNGKey(8), (30, CFG.d_model))
    w = params["layers"][0]["moe"]["w_router"]
    vals, idx = moe.sigmoid_gates(h, w, jnp.zeros((CFG.router_width,)),
                                  CFG.top_k, 1.0)
    gates, chosen, own, under = ref._route(
        h, w, jnp.zeros((30, CFG.top_k), jnp.int32), 0, top_k=CFG.top_k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(own))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(gates), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 1.0, atol=1e-6)
    assert float(under.max()) == 0.0


def test_the_shared_experts_are_averaged_not_summed(params):
    layer = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(9), (6, CFG.d_model))
    got = np.asarray(cm._shared(layer["shared"], h, CFG))
    each = [np.asarray(ref._expert(h, jnp.ones((6,)), *(
        layer["shared"][w][j] for w in ("w_gate", "w_up", "w_down"))))
        for j in range(CFG.n_shared)]
    np.testing.assert_allclose(got, sum(each) / CFG.n_shared, atol=1e-5)
    assert np.abs(got - sum(each)).max() > 1e-2


def test_the_shares_with_the_shared_experts_once_sum_to_the_uncut_layer(
        params):
    """model-configs section 4's test: the parts of an expert layer that all
    eight chips' shares give (two routed experts each of the toy's sixteen),
    with what every chip computes alike (the shared experts) counted once,
    add up to the uncut reference's layer."""
    layer = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(10), (25, CFG.d_model))
    zeros = jnp.zeros((25, CFG.top_k), jnp.int32)
    whole = np.asarray(ref.experts(
        h, ref.from_program_tree(params)["layers"][2], shape_of(CFG), zeros,
        0)[0])
    shared = np.asarray(cm._shared(layer["shared"], h, CFG))
    total, hit = shared.copy(), 0
    for chip in range(8):
        cfg = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * chip)
        share = cm.expert_share(params, 2 * chip, 2)["layers"][2]
        assert share["moe"]["w_up"].shape[0] == 2
        assert share["moe"]["w_router"].shape == (CFG.d_model, 16)
        out, idx, counts = cm._moe(share, h, jnp.ones((25,), bool), cfg)
        total += np.asarray(out) - shared
        hit += int(counts[0])
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert hit == len(np.unique(np.asarray(idx)))   # every picked expert once
    one = np.asarray(cm._moe(share, h, jnp.ones((25,), bool), cfg)[0])
    assert np.abs(one - whole).max() > 1e-2         # a share is not the layer


def test_forward_of_a_share_is_the_reference_on_that_share(params):
    cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=8)
    share = cm.expert_share(params, 8, 4)
    tokens = _tokens(30, seed=11)
    got = np.asarray(cm.forward(share, jnp.asarray(tokens, jnp.int32), cfg))
    want = np.asarray(_reference(share, tokens, cfg)["logits"])
    np.testing.assert_allclose(got, want, atol=3e-5)
    whole = np.asarray(_reference(params, tokens)["logits"])
    assert np.abs(want - whole).max() > 1e-2     # the absent experts' part


@pytest.mark.parametrize("T", [16, 80])
def test_the_grouped_products_default_cap_is_what_it_was(T):
    """``cap=None`` traces the program of before the keyword (the latent
    family's: ``min(T k, max(T, 16 k))``), and a caller's ``cap`` moves where
    the ``cond`` falls without moving the result."""
    k, E, Eh, D, F = 3, 36, 4, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(T), 6)
    x = jax.random.normal(keys[0], (T, D))
    held = {"w_up": jax.random.normal(keys[1], (Eh, D, F)) / D ** 0.5,
            "w_gate": jax.random.normal(keys[3], (Eh, D, F)) / D ** 0.5,
            "w_down": jax.random.normal(keys[2], (Eh, F, D)) / F ** 0.5}
    idx = jax.random.randint(keys[4], (T, k), 0, 8) + 8     # half held
    vals = jax.random.uniform(keys[5], (T, k))

    def program(**kw):
        return str(jax.make_jaxpr(lambda x, v, i: moe.moe_ffn_grouped(
            x, v, i, held, 8, None, **kw))(x, vals, idx))

    assert program() == program(cap=None) == program(cap=max(T, 16 * k))
    want = moe.moe_ffn_share(x, vals, idx, held, 8)
    for cap in (None, T // 2, 2 * T, 10 ** 6):
        got = moe.moe_ffn_grouped(x, vals, idx, held, 8, cap=cap)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=2e-5)
        assert (int(got[1]), int(got[2])) == (int(want[1]), int(want[2]))
    if T == 80:     # ~120 pairs held: over the default's 80, under 2 T
        assert "cond" in program() and "cond" in program(cap=2 * T)
        assert "cond" not in program(cap=10 ** 6)   # cap = T k: no other side


def test_both_expert_forms_give_the_layer(params, monkeypatch):
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(12), (40, CFG.d_model))
    mask = jnp.arange(40) != 7
    monkeypatch.setattr(cm, "GROUPED_FROM_ROWS", 10 ** 6)
    share = cm._moe(layer, h, mask, CFG)
    monkeypatch.setattr(cm, "GROUPED_FROM_ROWS", 1)
    grouped = cm._moe(layer, h, mask, CFG)
    np.testing.assert_allclose(np.asarray(share[0]), np.asarray(grouped[0]),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(share[2]),
                                  np.asarray(grouped[2]))
    assert int(share[2][0]) <= CFG.experts_held and int(share[2][1]) >= 1


# ----------------------------------------------------------------- the engine
def test_prefill_in_chunks_carries_what_one_chunk_computes(params):
    """41 tokens: three chunks of 16, the window passed in the second, against
    the one-chunk forward pass and the reference; the carried rows are every
    layer's K/V, a window layer's as a full one's."""
    prompt = _tokens(41, seed=13)
    first, bufs, routing = cm.prefill(params, prompt, 96, CFG,
                                      keep_routing=True)
    want = _reference(params, prompt)
    np.testing.assert_allclose(np.asarray(first),
                               np.asarray(want["logits"])[-1], atol=3e-5)
    np.testing.assert_array_equal(np.sort(routing, -1), np.sort(
        np.asarray(want["own_routing"]), -1))
    assert len(bufs) == 4 and bufs[0][0].shape == (96, CFG.n_kv_heads,
                                                   CFG.head_dim)
    one = cm.prefill(params, prompt, 96, dataclasses.replace(
        CFG, prefill_chunk=48, key_block=16))[1]
    for (k, v), (k1, v1) in zip(bufs, one):
        np.testing.assert_allclose(np.asarray(k[:41]), np.asarray(k1[:41]),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(v[:41]), np.asarray(v1[:41]),
                                   atol=2e-5)


def _decode_rows(params, prompt, n):
    """The engine's decode logits row by row: ``_decode_logits`` over the
    engine's own pools and rings before each step it dispatches. The engine
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
            row = np.asarray(cm._decode_logits(
                eng.params, eng.pools_k, eng.pools_v, eng.rings_k,
                eng.rings_v, jnp.asarray(tables), last, jnp.asarray(lengths),
                CFG, eng.page)[0][0])
        toks += [t for _, t in eng.step() if t is not None]
        if row is not None and eng.slots[0] is slot \
                and slot.length == at + 1:      # the call dispatched a step
            rows.append(row)
    return toks, rows


@pytest.mark.parametrize("n_prompt, new", [(5, 40), (21, 36), (41, 30)],
                         ids=["crosses-the-window-decoding", "two-chunks",
                              "admitted-wrapped"])
def test_engine_decode_logits_are_the_references_rows(params, n_prompt, new):
    """Prefill in chunks, then decode through the engine's rings and pages:
    every decode row against the reference's full forward pass. A request of
    5 tokens starts under the window of 16 and crosses it while decoding (its
    ring wraps more than twice by position 45); one of 41 is admitted with
    its ring already wrapped twice; pages of 4 and blocks of 2 pages are
    crossed throughout."""
    prompt = _tokens(n_prompt, seed=8 + n_prompt)
    toks, rows = _decode_rows(params, prompt, new)
    # the first step() admits AND decodes: the rows begin at the second
    assert len(toks) == new and len(rows) == new - 2
    seq = prompt + toks
    want = np.asarray(_reference(params, seq[:-1])["logits"])
    n = n_prompt
    assert toks[:2] == want[n - 1:n + 1].argmax(-1).tolist()
    for i, row in enumerate(rows):
        np.testing.assert_allclose(row, want[n + 1 + i], atol=5e-5)
        assert toks[i + 2] == int(want[n + 1 + i].argmax())


def test_the_engine_holds_pools_for_the_full_layers_and_rings_beside(params):
    eng = _engine(params)
    assert eng.family and eng.n_kv == CFG.n_full_layers == 1
    assert [p.shape for p in eng.pools_k + eng.pools_v] == \
        [(64, 4, CFG.n_kv_heads, CFG.head_dim)] * 2
    assert [r.shape for r in eng.rings_k + eng.rings_v] == \
        [(3, CFG.n_kv_heads, W, CFG.head_dim)] * 6
    assert eng._prefill_buckets == () and not hasattr(eng, "ssm")


def test_admission_and_release_leave_both_caches_as_they_found_them(params):
    """Pages come back to the allocator, tables to the scratch page; a ring
    has no allocator: the next admission to the slot rewrites it whole, so a
    request streams what it streams alone whatever the slot held before."""
    eng = _engine(params, max_slots=1)
    prompt = _tokens(23, seed=20)
    eng.submit("first", _tokens(44, seed=21), max_new_tokens=9)
    eng.run_to_completion()
    assert eng._available_pages() == 63 and not eng.tables.any()
    stale = np.asarray(eng.rings_k[0][0])
    assert np.abs(stale).sum() > 0          # the ring keeps its last rows
    eng.submit("second", prompt, max_new_tokens=20)
    got = eng.run_to_completion()["second"]
    assert got == _alone(params, prompt, 20)
    assert eng._available_pages() == 63 and not eng.tables.any()
    assert all(s is None for s in eng.slots) and not eng._flights


def test_requests_admitted_at_different_steps_stream_what_each_streams_alone(
        params, slow_device):
    reqs = {"a": (_tokens(40, 1), 12), "b": (_tokens(2, 2), 25),
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
    reqs = {"long": (_tokens(40, 1), 19), "short": (_tokens(9, 3), 13)}

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
    prompt + emitted, prefilled again in chunks (its rings rewritten from the
    new prefill's last positions) and goes on exactly."""
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
    ({"enable_prefix_cache": True}, "a ring is not shareable by page"),
    ({"kv_dtype": "int8"}, "rings are kept in the model's dtype"),
    ({"max_len": 88}, "whole chunks")],
    ids=["prefix-cache", "int8-pages", "max_len"])
def test_what_the_engine_refuses_for_this_family(params, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(params, **kw)


def test_the_other_families_refusal_keeps_its_reason():
    from ray_tpu.models import longcat_flash as lc

    cfg = lc.LONGCAT_FLASH_DEBUG
    with pytest.raises(ValueError, match="snapshots of recurrent state"):
        PagedEngine(None, cfg, enable_prefix_cache=True)


def test_llm_server_builds_the_engine_from_the_config_and_streams(params):
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(lambda: (params, CFG), max_slots=2, num_pages=64,
                       page_size=4, max_len=96)
    assert isinstance(server.engine, PagedEngine)
    assert server.engine.family is paged._FAMILIES[cm.Cohere2MoeConfig]
    prompt = _tokens(19, seed=30)
    server.engine.submit("r", prompt, max_new_tokens=6)
    assert server.engine.run_to_completion()["r"] == _alone(params, prompt, 6)


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
    state = by["serve.admit.state"]
    assert [p["chunks"] for p in prefill] == [3, 1]
    assert all(s["dispatches"] == 1 for s in scatter)
    # the last 16 of 45 positions, all 9 of 9, a window layer
    assert [(s["layers"], s["ring_positions"], s["dispatches"])
            for s in state] == [(3, 3 * W, 1), (3, 3 * 9, 1)]
    assert [p["parent"] for p in prefill] == [a["sid"] for a in admits] \
        == [s["parent"] for s in state]
    steps = by["serve.engine.step"]
    landed = [f for f in steps if "context_positions" in f]
    assert len(landed) == 5 and len([f for f in steps if f["active"]]) == 5
    assert "moe_hit" not in steps[0] and steps[0]["admitted"] == 2
    for k, f in enumerate(landed):
        assert f["landed"] == 1 and f["moe_rows"] == 2
        # positions 45 + k and 9 + k, and the row the step wrote
        assert f["context_positions"] == 45 + 9 + 2 * (k + 1)
        assert f["window_positions"] == W + min(9 + k + 1, W)
        assert 1 <= f["moe_hit"] <= CFG.n_layers * CFG.experts_held
        assert 1 <= f["moe_max"] <= 2
    assert eng.last_routing.shape == (CFG.n_layers, 3, CFG.top_k)


def test_greedy_identical_with_recorder_on_and_off(params, _clean_ring):
    prompt = _tokens(40, 6)
    on = _alone(params, prompt, 6)
    assert events.pending() > 0
    events.reset()
    events._enabled = False
    off = _alone(params, prompt, 6)
    assert on == off and events.pending() == 0
