"""The DeepSeek-V3 family (``ray_tpu/models/deepseek_v3.py``) against its plain
reference and through ``PagedEngine`` with its MTP module drafting: forward,
prefill then two-row steps through the cache, MTP logits, YaRN, group-limited
routing, the 16 shares, the draft rule, and an engine whose steps commit one
or two tokens a slot. Toy widths, float32, CPU."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import deepseek_v3 as ref
from ray_tpu.models import deepseek_v3 as ds
from ray_tpu.models import paged, paged_ops
from ray_tpu.models.paged import PagedEngine
from ray_tpu.ops import layers
from ray_tpu.parallel import moe
from ray_tpu.util import events

CFG = ds.DEEPSEEK_V3_DEBUG
#: a vocabulary of six: greedy drafts of seeded weights then agree with the
#: main model often enough that steps commit two tokens
SMALL = dataclasses.replace(CFG, vocab_size=6)


def shape_of(cfg):
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        yarn=list(cfg.yarn), n_group=cfg.n_group, topk_group=cfg.topk_group,
        num_experts_per_tok=cfg.top_k, routed_scaling_factor=cfg.routed_scale,
        expert_offset=cfg.expert_offset, vocab_size=cfg.vocab_size)


@pytest.fixture(scope="module")
def params():
    return ds.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def small():
    return ds.init_params(SMALL, jax.random.PRNGKey(1))


def _tokens(n, seed, vocab=CFG.vocab_size):
    return [int(t) for t in np.random.RandomState(seed).randint(0, vocab, n)]


def _engine(params, cfg=CFG, **kw):
    kw = {"max_slots": 3, "num_pages": 64, "page_size": 4, "max_len": 96,
          **kw}
    return PagedEngine(params, cfg, **kw)


def _without_mtp(params, cfg):
    """The same weights with no MTP module: the engine then steps one token
    a slot, as every other family's row."""
    return {**params, "mtp": []}, dataclasses.replace(cfg, n_nextn=0)


def _streams(eng, reqs, **how):
    for r, (prompt, n) in reqs.items():
        eng.submit(r, prompt, max_new_tokens=n, **how)
    got, deepest, calls = {r: [] for r in reqs}, 0, 0
    while eng.has_work():
        for rid, tok in eng.step():
            if tok is not None:
                got[rid].append(tok)
        deepest = max(deepest, len(eng._flights))
        calls += 1
    return got, deepest, calls


# ------------------------------------------------------------------ the model
@pytest.mark.parametrize("cut, billions", [
    ({}, 713.60), ({"n_nextn": 0}, 702.04),
    ({"n_layers": 5, "n_dense": 1, "experts_held": 16,
      "vocab_size": 16032}, 5.277)],
    ids=["whole", "without-mtp", "the-cell"])
def test_param_count_is_the_published_702_billion_and_the_cut(cut, billions):
    cfg = dataclasses.replace(ds.DeepseekV3Config(), **cut)
    assert cfg.param_count() / 1e9 == pytest.approx(billions, abs=0.01)


def test_param_count_counts_the_tree(params):
    held = sum(a.size for a in jax.tree.leaves(params))
    assert held == CFG.param_count()
    assert [("mlp" in lyr, "moe" in lyr) for lyr in params["layers"]] == [
        (True, False), (False, True), (False, True)]
    assert len(params["mtp"]) == 1 and "moe" in params["mtp"][0]["layer"]


@pytest.mark.parametrize("kw", [
    {"n_layers": 7}, {"n_dense": 4}, {"experts_held": 12, "expert_offset": 8},
    {"n_group": 3}, {"topk_group": 5}, {"prefill_chunk": 12}, {"n_nextn": 2},
    {"qk_rope_head_dim": 7}], ids=lambda kw: next(iter(kw)))
def test_config_refuses_sizes_the_programs_cannot_cut(kw):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **kw)


def test_the_calibrated_bias_spreads_the_picks_over_all_outputs(params):
    toks = jnp.asarray(_tokens(512, 4))
    one = dataclasses.replace(CFG, prefill_chunk=512)
    lats = ds.prefill_carry(one, 512)
    _, _, _, routing = ds._run_chunk(
        params, toks, jnp.roll(toks, -1), jnp.int32(0), jnp.int32(512), lats,
        one)
    assert routing.shape == (3, 512, CFG.top_k)    # two layers + the MTP's
    for layer in np.asarray(routing):
        share = np.bincount(layer.ravel(), minlength=CFG.n_experts) / (
            512 * CFG.top_k)
        assert share.min() > 0.3 / CFG.n_experts
        assert share.max() < 2.5 / CFG.n_experts
    raw = ds._seeded_params(CFG, jax.random.PRNGKey(0))
    assert float(jnp.abs(raw["layers"][1]["moe"]["router_bias"]).max()) == 0
    assert float(jnp.abs(params["mtp"][0]["layer"]["moe"]["router_bias"]
                         ).max()) > 0


@pytest.mark.parametrize("L", [7, 33, 50])
def test_forward_and_the_mtp_module_are_the_reference(params, L):
    toks = _tokens(L, L)
    logits, mtp = ds.forward(params, jnp.asarray(toks), CFG)
    want = ref.forward(ref.from_program_tree(params), toks, shape_of(CFG))
    assert float(want["logits"].std()) > 0.5
    np.testing.assert_allclose(logits, want["logits"], atol=2e-5, rtol=0)
    # row i of the module: (g_i, t_(i+1)); the last row has no follower
    np.testing.assert_allclose(mtp[:-1], want["mtp_logits"], atol=2e-5,
                               rtol=0)
    assert float(np.abs(np.asarray(mtp[:-1])
                        - np.asarray(logits[:-1])).max()) > 0.5


def test_the_reference_under_an_imposed_routing_uses_those_experts(params):
    toks = _tokens(20, 3)
    w, shape = ref.from_program_tree(params), shape_of(CFG)
    free = ref.forward(w, toks, shape)
    own = [np.asarray(o) for o in free["own_routing"]]
    assert [o.shape for o in own] == [(20, 3), (20, 3), (19, 3)]
    routing = np.zeros((3, 20, 3), np.int32)
    for e, o in enumerate(own):
        routing[e, :len(o)] = o
    imposed = np.ones((3, 20), bool)
    same = ref.forward(w, toks, shape, routing=routing, imposed=imposed)
    np.testing.assert_array_equal(same["logits"], free["logits"])
    assert max(float(np.asarray(u).max()) for u in same["under"]) == 0.0
    routing[0, 5] = (routing[0, 5] + 1) % CFG.n_experts      # another expert
    other = ref.forward(w, toks, shape, routing=routing, imposed=imposed)
    assert float(np.abs(np.asarray(other["logits"][5])
                        - np.asarray(free["logits"][5])).max()) > 1e-4
    np.testing.assert_array_equal(other["logits"][:5], free["logits"][:5])
    imposed[0, 5] = False       # ... unless the position chooses freely
    again = ref.forward(w, toks, shape, routing=routing, imposed=imposed)
    np.testing.assert_array_equal(again["logits"], free["logits"])


# ----------------------------------------------------------------------- YaRN
def test_yarn_frequencies_are_the_closed_form_at_the_published_numbers():
    inv = layers.yarn_inv_freq(64, 1e5, 64.0, 4096, 32.0, 1.0)
    f = 1e5 ** (-np.arange(32) / 32.0)

    def cd(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(1e5))

    low, high = math.floor(cd(32)), math.ceil(cd(1))
    assert (low, high) == (8, 19)
    np.testing.assert_allclose(inv[:9], f[:9], rtol=1e-12)    # kept
    np.testing.assert_allclose(inv[19:], f[19:] / 64, rtol=1e-12)
    j = 13
    ramp = (j - low) / (high - low)
    assert inv[j] == pytest.approx(f[j] / 64 * ramp + f[j] * (1 - ramp))
    assert np.all(np.diff(inv) < 0)
    assert layers.yarn_mscale(64, 1) == pytest.approx(1.41589, abs=1e-5)
    assert layers.yarn_mscale(1, 1) == 1.0
    cfg = ds.DeepseekV3Config()
    assert cfg.attn_scale == pytest.approx(0.14468, abs=1e-5)
    assert cfg.attn_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    np.testing.assert_allclose(ref.yarn_of({
        "yarn": [64, 4096, 32, 1, 1, 1], "qk_rope_head_dim": 64,
        "rope_theta": 1e5, "qk_nope_head_dim": 128})[0], inv, rtol=1e-12)


@pytest.mark.parametrize("mscale, all_dim, mult", [(1, 1, 1.0),
                                                   (1, 0, 1.41589)])
def test_yarn_rows_are_rope_rows_at_those_frequencies(mscale, all_dim, mult):
    pos = jnp.asarray([0, 1, 4095, 4096, 100_000])
    cos, sin = layers.yarn_rows(pos, 64, 1e5, 64.0, 4096, 32.0, 1.0, mscale,
                                all_dim)
    inv = layers.yarn_inv_freq(64, 1e5, 64.0, 4096, 32.0, 1.0)
    angle = np.asarray(pos, np.float64)[:, None] * inv[None, :]
    np.testing.assert_allclose(cos, np.cos(angle) * mult, atol=2e-2)
    np.testing.assert_allclose(cos[:3], (np.cos(angle) * mult)[:3], atol=1e-3)
    # a factor of 1 is plain rotary
    plain = layers.rope_rows(pos, 64, 1e5)
    again = layers.yarn_rows(pos, 64, 1e5, 1.0, 4096, 32.0, 1.0, 1, 1)
    np.testing.assert_allclose(again[0][:3], plain[0][:3], atol=2e-4)
    np.testing.assert_allclose(again[1][:3], plain[1][:3], atol=2e-4)


# ------------------------------------------------- two query rows over pages
def _attention_case(seed, lengths, R, page=4, P=6, S=None):
    rng = np.random.RandomState(seed)
    S = len(lengths)
    H, dn, dr, dv, C = 4, 16, 8, 24, 16
    W = C + dr
    pool = jnp.asarray(rng.randn(S * P + 1, page // 2, 2 * W), jnp.float32)
    tables = jnp.asarray(1 + np.arange(S * P).reshape(S, P), jnp.int32)
    return dict(
        q_nope=jnp.asarray(rng.randn(S, R, H, dn), jnp.float32),
        q_rope=jnp.asarray(rng.randn(S, R, H, dr), jnp.float32),
        w_uk=jnp.asarray(rng.randn(C, H, dn), jnp.float32),
        w_uv=jnp.asarray(rng.randn(C, H, dv), jnp.float32),
        pool=pool, tables=tables, lengths=jnp.asarray(lengths, jnp.int32),
        scale=0.2)


@pytest.mark.parametrize("seed, lengths", [(0, [5, 21, 0]), (1, [16, 8, 11]),
                                           (2, [22, 1, 7])])
def test_two_query_rows_are_two_calls_of_one(seed, lengths):
    a = _attention_case(seed, lengths, R=2)
    both = paged_ops.attend_latent(**a)
    assert both.shape == (3, 2, 4 * 24)
    for r in range(2):
        one = paged_ops.attend_latent(**{
            **a, "q_nope": a["q_nope"][:, r:r + 1],
            "q_rope": a["q_rope"][:, r:r + 1], "lengths": a["lengths"] + r})
        np.testing.assert_allclose(both[:, r], one[:, 0], atol=2e-5, rtol=0)
    # the second row sees one position more than the first
    assert float(jnp.abs(both[:, 0] - both[:, 1]).max()) > 1e-3


def _expanded(a):
    """Attention over the keys and values EXPANDED from the slot's cached
    rows, query row by query row, in numpy: what the absorbed form equals."""
    f = lambda k: np.asarray(a[k], np.float64)      # noqa: E731
    pool, tables, lengths = f("pool"), np.asarray(a["tables"]), a["lengths"]
    q_nope, q_rope, w_uk, w_uv = map(f, ("q_nope", "q_rope", "w_uk", "w_uv"))
    S, R, H, _ = q_nope.shape
    C, W = w_uk.shape[0], pool.shape[2] // 2
    out = np.zeros((S, R, H * w_uv.shape[2]))
    for s in range(S):
        rows = pool[tables[s]].reshape(-1, W)       # position by position
        k = np.einsum("kc,chd->khd", rows[:, :C], w_uk)
        v = np.einsum("kc,chd->khd", rows[:, :C], w_uv)
        for r in range(R):
            n = int(lengths[s]) + r + 1             # keys <= the row's own
            sc = (np.einsum("hd,khd->hk", q_nope[s, r], k[:n])
                  + np.einsum("hr,kr->hk", q_rope[s, r], rows[:n, C:])
                  ) * a["scale"]
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[s, r] = np.einsum("hk,khd->hd", p, v[:n]).reshape(-1)
    return out


def _pass_shape(monkeypatch, block_pages, items):
    """The read at a block of ``block_pages`` table columns, ``items`` blocks
    a pass, whatever the rule would give at a toy's shapes."""
    monkeypatch.setattr(paged_ops, "latent_pass_shape",
                        lambda *a: (block_pages, items))


# A table of 8 pages of 4 positions; at a block of 2 pages (8 positions) a
# slot's last query row stands, case by case: anywhere, a length of 0 among
# them; on a block's last position (the next block is not opened); with the
# slot's rows on both sides of a block's edge; on the table's last position;
# in a batch of one slot (the MTP draft's admission call).
P_READ, PAGE_READ, BK_READ = 8, 4, 8
READ_CASES = {
    "ragged_with_a_zero": lambda R: [5, 21, 0, 9, 14, 2, 29, 12],
    "at_a_blocks_edge": lambda R: [BK_READ - R, 2 * BK_READ - R,
                                   3 * BK_READ - R],
    "rows_straddle_an_edge": lambda R: [BK_READ - 1, 2 * BK_READ - 1, 4,
                                        3 * BK_READ - 1],
    "the_tables_last_position": lambda R: [P_READ * PAGE_READ - R, 3,
                                           P_READ * PAGE_READ - R],
    "one_slot": lambda R: [13],
}


@pytest.mark.parametrize("shape", [(2, None), (2, 3), (3, None), (1, 1)],
                         ids=["2pages", "2pages_3a_pass",
                              "3pages_not_a_divisor_of_8", "1page_1a_pass"])
@pytest.mark.parametrize("R", [1, 2])
@pytest.mark.parametrize("case", list(READ_CASES))
def test_the_blocked_read_is_the_expanded_form_row_by_row(monkeypatch, case,
                                                          R, shape):
    lengths = READ_CASES[case](R)
    a = _attention_case(7, lengths, R=R, page=PAGE_READ, P=P_READ)
    _pass_shape(monkeypatch, shape[0], min(shape[1] or 8, len(lengths)))
    got = paged_ops.attend_latent(**a)
    assert got.shape == (len(lengths), R, 4 * 24)
    np.testing.assert_allclose(got, _expanded(a), atol=3e-5, rtol=0)


@pytest.mark.parametrize("block_pages, items", [
    (8, 8), (4, 8), (2, 8), (1, 8), (3, 8), (5, 8), (2, 5), (2, 1), (1, 3),
    (3, 2)])
def test_every_block_width_and_pass_reads_the_same(monkeypatch, block_pages,
                                                   items):
    """One block as wide as the table and every slot's in one pass is the
    table-wide read; every narrower block and shorter pass gives its
    result (several blocks of one slot in a pass, a slot's blocks in two
    passes, a last pass partly empty)."""
    a = _attention_case(3, [5, 21, 0, 9, 14, 2, 30, 12], R=2, P=8)
    _pass_shape(monkeypatch, 8, 8)
    whole = paged_ops.attend_latent(**a)
    np.testing.assert_allclose(whole, _expanded(a), atol=3e-5, rtol=0)
    _pass_shape(monkeypatch, block_pages, items)
    np.testing.assert_allclose(paged_ops.attend_latent(**a), whole,
                               atol=3e-6, rtol=0)


@pytest.mark.parametrize("R", [1, 2])
def test_the_read_stops_at_each_slots_last_live_page(monkeypatch, R):
    """Nothing past a slot's context is gathered: not a block past its last
    live one, however long its neighbour's table, and not the padding of its
    last block (the engine's unreached columns all name page 0). Both hold
    NaN here, which a gathered row would carry into the output through ``0 x
    NaN``; the output is the clean pool's. The jaxpr holds one ``while`` and
    no gather of the table's width."""
    lengths = [5, 21, 9, 38, 1]
    a = _attention_case(5, lengths, R=R, page=4, P=10)
    _pass_shape(monkeypatch, 2, 5)
    want = paged_ops.attend_latent(**a)
    tables = np.asarray(a["tables"]).copy()
    pool = np.asarray(a["pool"]).copy()
    for s, n in enumerate(lengths):
        reached = (n + R - 1) // 4 + 1          # pages the slot's rows touch
        pool[tables[s, reached:]] = np.nan      # ... and every page past them
        tables[s, reached:] = 0
    pool[0] = np.nan
    got = paged_ops.attend_latent(**{**a, "pool": jnp.asarray(pool),
                                     "tables": jnp.asarray(tables)})
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    text = str(jax.make_jaxpr(lambda kw: paged_ops.attend_latent(**kw))(
        {k: v for k, v in a.items() if k != "scale"} | {"scale": 0.2}
    )).replace(" ", "")
    assert "while" in text and "cumsum" in text
    assert "[5,20," not in text             # a slot's table: 10 pages of 2 rows
    assert "[5,4,48]" in text               # a block: 2 pages of 2 rows


@pytest.mark.parametrize("R", [1, 2])
def test_a_slot_opens_the_blocks_its_rows_reach_and_no_more(R):
    """``latent_positions_read`` is the rule the read itself takes its blocks
    from: a slot of length 0 one block, a slot whose last row stands on a
    block's last position no block more, one position further the next."""
    a = _attention_case(0, [0, 1, 2], R=R, page=4, P=24)    # blocks of 3 pages
    block, items = paged_ops.latent_pass_shape(3, 24, a["pool"], 2 * R * 4)
    assert (block, items) == (3, 3)
    Bk = block * 4
    for lengths, blocks in [([0, 0, 0], 3), ([Bk - R, 2 * Bk - R, 0], 4),
                            ([Bk - R + 1, 2 * Bk - R + 1, 1], 6),
                            ([24 * 4 - R, 0, Bk], 8 + 1 + 2)]:
        read = paged_ops.latent_positions_read(
            a["pool"], a["tables"], jnp.asarray(lengths, jnp.int32), R, 4)
        assert int(read) == blocks * Bk, lengths


@pytest.mark.parametrize("start", [0, 1, 2, 3, 6, 7])
def test_two_latent_rows_land_one_after_the_other(start):
    page, W = 4, 6
    pool = jnp.zeros((5, page // 2, 2 * W), jnp.float32)
    tables = jnp.asarray([[3, 1, 4]], jnp.int32)
    rows = jnp.arange(2 * W, dtype=jnp.float32).reshape(1, 2, W) + 1
    out = paged_ops.write_latent_rows(rows, pool, tables,
                                      jnp.asarray([start], jnp.int32))
    flat = np.asarray(out)[np.asarray(tables[0])].reshape(-1, W)
    np.testing.assert_array_equal(flat[start:start + 2], rows[0])
    assert float(np.abs(flat).sum()) == float(np.abs(rows).sum())


# ------------------------------------------------------------------ the shares
def test_the_16_shares_with_the_shared_expert_once_sum_to_the_uncut_layer(
        params):
    """Each chip's routed part (its experts of the layer's sixteen) added up,
    and the shared expert once, is the uncut reference's layer."""
    layer = params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (40, CFG.d_model))
    shape = shape_of(CFG)
    w = ref.from_program_tree(params)["layers"][1]
    none = (jnp.zeros((40, 3), jnp.int32), jnp.zeros((40,), bool))
    want, _, _ = ref.experts(u, w["moe"], w["shared"], shape, *none)
    shared = ref.mlp(u, w["shared"])
    total = jnp.zeros_like(u)
    for offset in range(0, 16, 4):          # four chips of four experts
        cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=offset)
        held = moe.expert_share({"layers": [layer]}, offset, 4)["layers"][0]
        y, idx, counts = ds._moe(held, u, None, cfg)
        total = total + (y - shared)
        part, _, _ = ref.experts(
            u, {**w["moe"], **{k: w["moe"][k][offset:offset + 4]
                               for k in ("w_gate", "w_up", "w_down")}},
            w["shared"], {**shape, "expert_offset": offset}, *none)
        np.testing.assert_allclose(y, part, atol=2e-5, rtol=0)
        assert 0 < int(counts[0]) <= 4
    np.testing.assert_allclose(total + shared, want, atol=5e-5, rtol=0)
    assert float(jnp.abs(want - shared).max()) > 0.1


# --------------------------------------------------------------- the draft rule
def _plain_verify(p0, p1, q, d, u):
    """The speculative-sampling rule, written out for one row."""
    def draw(p, x):
        return min(int(np.searchsorted(np.cumsum(p), x * p.sum(),
                                       side="right")), len(p) - 1)

    if d >= 0 and u[0] * q[d] < p0[d]:
        return True, d, draw(p1, u[2])
    left = np.maximum(p0 - (q if d >= 0 else 0.0), 0.0)
    if left.sum() <= 0:
        left = p0
    return False, draw(left, u[1]), draw(p1, u[2])


def test_the_draft_rule_is_the_plain_rule_on_fixed_uniforms():
    rng = np.random.RandomState(0)
    N, V = 400, 7
    p0, p1, q = (rng.dirichlet(np.ones(V), N).astype(np.float32)
                 for _ in range(3))
    d = rng.randint(-1, V, N).astype(np.int32)
    u = rng.rand(N, 3).astype(np.float32)
    acc, first, second = ds.verify_draft(*map(jnp.asarray, (p0, p1, q, d, u)))
    want = [_plain_verify(p0[i], p1[i], q[i], d[i], u[i]) for i in range(N)]
    np.testing.assert_array_equal(acc, [w[0] for w in want])
    np.testing.assert_array_equal(first, [w[1] for w in want])
    np.testing.assert_array_equal(second, [w[2] for w in want])
    assert 0 < int(acc.sum()) < N and not acc[d < 0].any()


@pytest.mark.parametrize("seed", [0, 1])
def test_what_the_rule_emits_is_distributed_as_the_target(seed):
    """Whatever the drafts' distribution: the first token is distributed as
    p0, drafts are accepted sum(min(p0, q)) of the time, and the second token
    as p1."""
    rng = np.random.RandomState(seed)
    N, V = 200_000, 5
    p0, p1, q = (rng.dirichlet(np.ones(V)).astype(np.float32)
                 for _ in range(3))
    tile = lambda p: jnp.tile(jnp.asarray(p), (N, 1))   # noqa: E731
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    d = ds._draw(tile(q), jax.random.uniform(keys[0], (N,)))
    u = jax.random.uniform(keys[1], (N, 3))
    acc, first, second = ds.verify_draft(tile(p0), tile(p1), tile(q), d, u)
    freq = lambda t: np.bincount(np.asarray(t), minlength=V) / len(t)  # noqa: E731
    np.testing.assert_allclose(freq(d), q, atol=0.005)
    np.testing.assert_allclose(freq(first), p0, atol=0.005)
    np.testing.assert_allclose(freq(second), p1, atol=0.005)
    assert float(acc.mean()) == pytest.approx(np.minimum(p0, q).sum(),
                                              abs=0.005)


@pytest.mark.parametrize("top_k, top_p", [(0, 1.0), (3, 1.0), (0, 0.6),
                                          (4, 0.8)])
def test_a_rows_distribution_is_what_the_engines_picker_samples(top_k, top_p):
    from ray_tpu.models.engine import _pick_token

    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(2, 12) * 2, jnp.float32)
    temps = jnp.asarray([0.7, 1.3], jnp.float32)
    p = ds._distributions(logits, temps, jnp.full((2,), top_k, jnp.int32),
                          jnp.full((2,), top_p, jnp.float32))
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    keys = jax.random.split(jax.random.PRNGKey(0), 20_000)
    for row in range(2):
        drawn = jax.vmap(lambda k: _pick_token(
            logits[row], temps[row], jnp.int32(top_k), jnp.float32(top_p),
            k))(keys)
        freq = np.bincount(np.asarray(drawn), minlength=12) / len(keys)
        np.testing.assert_allclose(freq, p[row], atol=0.012)
        if top_k:
            assert int((np.asarray(p[row]) > 0).sum()) <= top_k


# ------------------------------------------------- the engine, two tokens a step
def test_prefill_in_chunks_carries_what_one_chunk_computes(params):
    prompt = _tokens(45, 9)
    first, g, lats = ds.prefill(params, prompt, 96, CFG)
    logits, _ = ds.forward(params, jnp.asarray(prompt), CFG)
    np.testing.assert_allclose(first, logits[-1], atol=2e-5, rtol=0)
    assert len(lats) == CFG.n_sublayers == 4 and lats[0].shape == (96, 24)
    one = dataclasses.replace(CFG, prefill_chunk=48)
    _, _, whole = ds.prefill(params, prompt, 96, one)
    for a, b in zip(lats, whole):
        np.testing.assert_allclose(a[:44], b[:44], atol=2e-5, rtol=0)


def test_the_steps_rows_and_the_mtp_rows_are_the_references(params):
    """A sampled stream, stepped synchronously so that each step's rows can
    be read: l_0 (and l_1 where the draft was accepted) against the
    reference's rows of the committed sequence, the MTP block's logits
    against the reference's module."""
    prompt = _tokens(21, 5)
    eng = _engine(params)
    eng.submit("r", prompt, max_new_tokens=30, temperature=1.0, seed=11,
               eos_id=CFG.vocab_size)
    toks, steps = [], []
    while eng.has_work():
        toks += [t for _, t in eng.step() if t is not None]
        if eng._landed:
            steps.append((np.asarray(eng.last_logits[0]),
                          bool(eng.last_accepted[0]),
                          np.asarray(eng.last_draft_logits[0])))
    assert len(toks) == 30
    seq = prompt + toks
    want = ref.forward(ref.from_program_tree(params), seq, shape_of(CFG))
    at, accepted = len(prompt), 0
    for logits, acc, q in steps:
        for r in range(1 + acc):
            if at + r < len(seq):
                np.testing.assert_allclose(
                    logits[r], want["logits"][at + r], atol=3e-5, rtol=0)
        if at + acc + 1 < len(seq):
            np.testing.assert_allclose(q, want["mtp_logits"][at + acc],
                                       atol=3e-5, rtol=0)
        at += 1 + acc
        accepted += acc
    assert 0 < accepted < len(steps)        # both kinds among them
    assert at >= len(seq) - 1


@pytest.mark.parametrize("how", ["ahead", "eos"])
def test_a_greedy_drafting_engine_emits_what_the_same_weights_emit_undrafted(
        small, how, slow_device):
    more = {"eos_id": 4} if how == "eos" else {}
    reqs = {"a": (_tokens(40, 1, 6), 25), "b": (_tokens(2, 2, 6), 14),
            "c": (_tokens(21, 3, 6), 31), "d": (_tokens(35, 4, 6), 9)}
    drafted, deepest, calls = _streams(
        _engine(small, SMALL, max_slots=2), reqs, **more)
    plain, _, plain_calls = _streams(
        _engine(*_without_mtp(small, SMALL), max_slots=2), reqs, **more)
    assert drafted == plain
    if how == "ahead":
        assert [len(v) for v in drafted.values()] == [25, 14, 31, 9]
        assert deepest >= 3
    else:
        assert any(v[-1] == 4 and len(v) < reqs[r][1]
                   for r, v in drafted.items())
        assert deepest == 0
    assert calls < plain_calls      # steps that committed two tokens


def test_ten_flights_ahead_with_every_slot_held(small, slow_device):
    reqs = {"long": (_tokens(40, 1, 6), 60), "short": (_tokens(21, 3, 6), 47)}
    big = {"max_slots": 2, "max_len": 128}
    ahead, deepest, _ = _streams(_engine(small, SMALL, **big), reqs)
    sync, none, _ = _streams(_engine(small, SMALL, **big), reqs,
                             eos_id=SMALL.vocab_size)
    plain, _, _ = _streams(_engine(*_without_mtp(small, SMALL), **big), reqs)
    assert ahead == sync == plain
    assert [len(v) for v in ahead.values()] == [60, 47]
    assert deepest == paged._STEPS_AHEAD and none == 0


@pytest.mark.parametrize("how", [
    {"temperature": 1.0, "seed": 3}, {"temperature": 0.8, "top_k": 4,
                                      "seed": 5},
    {"temperature": 1.2, "top_p": 0.7, "seed": 7}],
    ids=["temperature", "top_k", "top_p"])
def test_sampled_running_ahead_streams_what_the_synchronous_loop_streams(
        params, how, slow_device):
    """Positions, drafts and the drafts' distributions handed on the device
    give what the host's own lengths give."""
    reqs = {"long": (_tokens(40, 1), 33), "short": (_tokens(21, 3), 26)}
    ahead, deepest, calls = _streams(_engine(params, max_slots=2), reqs,
                                     **how)
    sync, none, _ = _streams(_engine(params, max_slots=2), reqs,
                             eos_id=CFG.vocab_size, **how)
    assert ahead == sync and [len(v) for v in ahead.values()] == [33, 26]
    assert deepest == paged._STEPS_AHEAD and none == 0
    assert all(0 <= t < CFG.vocab_size for v in ahead.values() for t in v)


def test_a_sampling_engine_accepts_about_half_its_drafts(params,
                                                          _clean_ring):
    eng = _engine(params, max_slots=3)
    reqs = {f"r{i}": (_tokens(10 + i, i), 60) for i in range(3)}
    got, _, _ = _streams(eng, reqs, temperature=1.0, seed=2)
    rows = [events.row_to_dict(r)["fields"] for r in events.drain()[0]
            if events.row_to_dict(r)["name"] == "serve.engine.step"]
    drafted = sum(f.get("drafted", 0) for f in rows)
    accepted = sum(f.get("accepted", 0) for f in rows)
    assert drafted > 60 and 0.25 < accepted / drafted < 0.75
    assert [len(v) for v in got.values()] == [60, 60, 60]


@pytest.mark.parametrize("max_new", [1, 2, 3, 4, 5, 6, 7, 8])
def test_a_slot_that_ends_on_the_first_of_two_tokens_drops_the_second(
        small, max_new):
    """Every budget from one token on: whichever of a step's tokens is the
    last, the stream holds exactly its budget, the undrafted stream's."""
    reqs = {"x": (_tokens(9, 7, 6), max_new)}
    drafted, _, _ = _streams(_engine(small, SMALL), reqs)
    plain, _, _ = _streams(_engine(*_without_mtp(small, SMALL)), reqs)
    assert drafted == plain and len(drafted["x"]) == max_new


def test_preemption_mid_stream_resumes_exactly(small):
    """A pool too small for both sequences: one is preempted, requeued with
    prompt + emitted, prefilled again with its MTP rows and a new first
    draft, and goes on exactly."""
    reqs = {"x": (_tokens(30, 7, 6), 30), "y": (_tokens(27, 8, 6), 30)}
    eng = _engine(small, SMALL, max_slots=2, num_pages=24, page_size=4,
                  max_len=64)
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
        alone, _, _ = _streams(_engine(*_without_mtp(small, SMALL)),
                               {r: (p, n)})
        assert got[r] == alone[r], r
    assert eng._available_pages() == 23


def test_the_engine_holds_one_pool_a_layer_with_attention_and_no_v_pool(
        params):
    eng = _engine(params)
    assert len(eng.pools_k) == CFG.n_layers + 1 and eng.pools_v == []
    assert eng.pools_k[0].shape == (64, 2, 2 * CFG.latent_width)
    assert eng._reach == 2 and eng.drafts.shape == (3,)
    assert eng.draft_q.shape == (3, CFG.vocab_size)
    plain = _engine(*_without_mtp(params, CFG))
    assert plain._reach == 1 and len(plain.pools_k) == CFG.n_layers
    assert not hasattr(plain, "drafts")


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
    eng.submit("req-aaaa-long", _tokens(45, 2), max_new_tokens=12,
               temperature=1.0, seed=1)
    eng.submit("req-bbbb-short", _tokens(9, 3), max_new_tokens=12,
               temperature=1.0, seed=2)
    got = eng.run_to_completion()
    assert [len(v) for v in got.values()] == [12, 12]
    rows = [events.row_to_dict(r) for r in events.drain()[0]]
    by = {}
    for r in rows:
        by.setdefault(r["name"], []).append(r["fields"])
    admits = by["serve.engine.admit"]
    prefill, scatter = by["serve.admit.prefill"], by["serve.admit.scatter"]
    assert [p["chunks"] for p in prefill] == [3, 1]
    assert [p["mtp_rows"] for p in prefill] == [44, 8]
    assert all(s["latent_rows"] == 96 * CFG.n_sublayers
               and s["dispatches"] == 1 for s in scatter)
    assert [d["parent"] for d in by["serve.admit.draft"]] == \
        [a["sid"] for a in admits]
    assert "serve.admit.state" not in by        # the family has none
    steps = by["serve.engine.step"]
    landed = [f for f in steps if "drafted" in f]
    flights = by["serve.step.flight"]
    assert sum(f["landed"] for f in landed) == len(flights)
    # every token but each stream's first came from a step; a flight's row
    # says how many it landed, the dropped ones past a budget among them
    assert sum(f["tokens"] for f in steps) == 24
    committed = sum(f["tokens"] for f in flights)
    assert 22 <= committed <= 24 and all(
        f["active"] <= f["tokens"] <= 2 * f["active"] for f in flights)
    assert sum(f["drafted"] for f in landed) == sum(
        f["active"] for f in flights)
    assert sum(f["accepted"] for f in landed) == committed - sum(
        f["active"] for f in flights)
    # what the step's read gathered a layer: every slot's blocks, whole (a
    # slot's rows round up by less than a block, an idle slot reads one)
    block = 4 * paged_ops.latent_pass_shape(
        3, 24, eng.pools_k[0], 2 * 2 * CFG.n_heads)[0]
    assert block == 12
    for f in landed:
        assert f["latent_positions_read"] % block == 0
        assert f["latent_positions"] <= f["latent_positions_read"] \
            <= f["latent_positions"] + f["landed"] * 3 * block
        assert 0 <= f["experts_hit"] <= f["landed"] * 3 * CFG.experts_held
        assert f["expert_tokens_max"] <= f["landed"] * 2 * f["moe_rows"]
    first = landed[0]       # both slots, two rows each, no token committed
    assert first["latent_positions"] >= 45 + 9 + 4
    assert eng.last_routing.shape == (3, 6, CFG.top_k)
    assert eng.last_logits.shape == (3, 2, CFG.vocab_size)
    assert eng.last_draft_logits.shape == (3, CFG.vocab_size)


def test_greedy_identical_with_recorder_on_and_off(small, _clean_ring):
    reqs = {"x": (_tokens(40, 6, 6), 16)}
    on, _, _ = _streams(_engine(small, SMALL), reqs)
    assert events.pending() > 0
    events.reset()
    events._enabled = False
    off, _, _ = _streams(_engine(small, SMALL), reqs)
    assert on == off and events.pending() == 0


def test_nothing_compiles_for_a_prompt_length_once_one_was_admitted(params):
    """An admission's programs take the prompt padded to whole chunks and its
    length as an operand: after one request of one chunk and one of two,
    other lengths (and other budgets, temperatures and seeds) reach no
    compiler, so nothing compiles inside a measured window."""
    from perfbench.program import CompileCounter

    eng = _engine(params)
    _streams(eng, {"a": (_tokens(9, 1), 5), "b": (_tokens(30, 2), 5)},
             temperature=1.0, seed=1)
    counter = CompileCounter()
    got, _, _ = _streams(eng, {"c": (_tokens(5, 3), 7), "d": (_tokens(23, 4), 4),
                               "e": (_tokens(16, 5), 6), "f": (_tokens(31, 6), 3)},
                         temperature=0.7, seed=9)
    assert [len(v) for v in got.values()] == [7, 4, 6, 3]
    assert counter.count == 0
