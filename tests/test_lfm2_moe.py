"""The gated-short-convolution / attention family with every expert held: its
forward pass, the convolution helpers it shares with the Mamba-2 family, its
lane pools (a head narrower than a lane), the expert layer's share, and
``PagedEngine`` serving it through page pools and conv tails, each against the
plain reference (``perfbench/reference/lfm2_moe.py``) at toy sizes: six layers
(two dense conv layers, then attention, three conv), pages of 4, chunks of 16,
so a prompt of a few dozen tokens crosses chunk boundaries."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import lfm2_moe as ref
from ray_tpu.models import lfm2_moe as lm
from ray_tpu.models import paged, paged_ops
from ray_tpu.models.paged import PagedEngine
from ray_tpu.ops import ssm
from ray_tpu.parallel import moe
from ray_tpu.util import events

CFG = lm.LFM2_MOE_DEBUG     # conv conv attn conv conv conv; chunk 16
K = CFG.conv_kernel


def shape_of(cfg):
    """The reference's ``shape`` keys, as a configuration file names them."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        layer_types=list(cfg.layer_types), num_hidden_layers=cfg.n_layers,
        num_dense_layers=cfg.n_dense_layers, conv_L_cache=cfg.conv_kernel,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.top_k, norm_topk_prob=cfg.norm_topk,
        routed_scaling_factor=cfg.routed_scale)


def _init(cfg):
    """One jitted call, as ``perfbench.program.init_weights`` makes it (the
    calibration's 2048-token pass is slow eagerly)."""
    return jax.jit(lambda k: lm.init_params(cfg, k))(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def params():
    return _init(CFG)


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


# ------------------------------------------------------------------- shapes
@pytest.mark.parametrize("active, count", [(False, 23_843_661_440),
                                           (True, 2_326_881_920)],
                         ids=["held", "active"])
def test_param_count_is_the_published_24_billion_and_2_3_active(active,
                                                                count):
    """Published shape: 23.84 B parameters, 2.3 B of them read by a token."""
    cfg = lm.Lfm2MoeConfig()
    assert cfg.param_count(active) == count
    assert cfg.kinds.count(lm.FULL) == 10 and cfg.kinds.count(lm.CONV) == 30
    assert [i for i, k in enumerate(cfg.kinds) if k == lm.FULL] == list(
        range(2, 40, 4))
    # the benchmark's cut: layers 0-9, every expert and vocabulary row
    cut = lm.Lfm2MoeConfig(n_layers=10)
    assert cut.kinds == ("conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention", "conv", "conv", "conv")
    assert cut.param_count() == 5_267_090_176
    assert (cut.n_attn_layers, cut.n_conv_layers, cut.n_moe_layers) == (
        2, 8, 8)


def test_param_count_counts_the_tree(params):
    assert CFG.param_count() == sum(a.size for a in jax.tree.leaves(params))
    held = dataclasses.replace(CFG, experts_held=4, expert_offset=8)
    share = lm.expert_share(params, 8, 4)
    assert held.param_count() == sum(a.size for a in jax.tree.leaves(share))
    assert "moe" not in params["layers"][0] and "moe" in params["layers"][2]


def test_config_refuses_sizes_the_programs_cannot_cut():
    for kw in ({"layer_types": ("conv", "mamba")}, {"n_layers": 41},
               {"experts_held": 60, "expert_offset": 8},
               {"prefill_chunk": 100}, {"n_heads": 30}):
        with pytest.raises(ValueError):
            lm.Lfm2MoeConfig(**kw)


# -------------------------------------------------------------- convolution
def test_a_chunks_left_edge_is_the_tail_of_the_chunk_before():
    """``causal_conv`` and ``conv_tail`` with ``left``: a sequence cut in
    chunks gives what it gives whole, and the tail handed on is the last
    ``K - 1`` VALID inputs (from ``left`` where the chunk holds fewer)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(23, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, 8)), jnp.float32)
    whole = np.asarray(ssm.causal_conv(x, w))
    left = jnp.zeros((K - 1, 8))
    got = []
    for a in range(0, 23, 5):
        chunk = x[a:a + 5]
        got.append(np.asarray(ssm.causal_conv(chunk, w, None, left)))
        left = ssm.conv_tail(chunk, 23 - a, K, left)
    np.testing.assert_allclose(np.concatenate(got), whole, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(left), np.asarray(x[-2:]))
    # a chunk with one valid input keeps one row of its left edge
    old = jnp.asarray(rng.normal(size=(K - 1, 8)), jnp.float32)
    tail = np.asarray(ssm.conv_tail(x[:5], 1, K, old))
    np.testing.assert_array_equal(tail, np.stack([old[1], x[0]]))
    # and no bias is a zero bias; the one-token form continues the tail
    np.testing.assert_allclose(np.asarray(ssm.causal_conv(x, w)), np.asarray(
        ssm.causal_conv(x, w, jnp.zeros((8,)))), atol=0)
    out, new = ssm.conv_step(jnp.stack([x[20:22]]), x[22][None], w)
    np.testing.assert_allclose(np.asarray(out[0]), whole[22], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(x[21:23]))


def test_the_short_convolution_is_gated_before_and_after(params):
    """``Op = W_out (C * conv(B * X))`` with ``[B, C, X]`` in that order: a
    layer's operator against the reference's, and against a wrong order."""
    layer = params["layers"][3]
    u = jax.random.normal(jax.random.PRNGKey(3), (19, CFG.d_model))
    z, c = lm._split_in(layer, u)
    got = np.asarray(lm.mm(
        c * ssm.causal_conv(z, layer["conv_w"]), layer["w_out"]))
    want = np.asarray(ref.short_conv(u, layer["w_in"], layer["conv_w"],
                                     layer["w_out"]))
    np.testing.assert_allclose(got, want, atol=3e-5)
    b, cc, x = np.split(np.asarray(u @ layer["w_in"]), 3, axis=-1)
    np.testing.assert_allclose(np.asarray(z), b * x, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c), cc, atol=1e-6)


# ------------------------------------------------------------------ forward
@pytest.mark.parametrize("L", [1, 17, 50])
def test_forward_is_the_reference(params, L):
    tokens = _tokens(L, seed=L)
    got = np.asarray(lm.forward(params, jnp.asarray(tokens, jnp.int32), CFG))
    want = np.asarray(_reference(params, tokens)["logits"])
    assert got.shape == (L, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_forward_in_bfloat16_stays_near_the_reference():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    p16 = _init(cfg)
    tokens = _tokens(30, seed=5)
    got = np.asarray(lm.forward(p16, jnp.asarray(tokens, jnp.int32), cfg
                                ).astype(jnp.float32))
    out = _reference(p16, tokens, cfg)
    # under the program's own routing: a near tie swaps a whole expert
    routing = lm.prefill(p16, tokens, 96, cfg, keep_routing=True)[3]
    want = np.asarray(_reference(p16, tokens, cfg, routing=routing)["logits"])
    assert np.sqrt(np.mean((got - want) ** 2)) < 0.05 * want.std()
    assert np.asarray(out["under"]).max() < 0.2


def test_q_and_k_are_normed_over_the_head_before_the_rotary(params):
    """The rotary embedding is half-split over the whole head and comes AFTER
    the per-head RMSNorm: position 0 is not rotated, so its q is the norm's."""
    layer = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(4), (5, CFG.d_model))
    q, k, v = lm._qkv(layer, h, jnp.arange(5), CFG)
    raw = (h @ layer["wq"]).reshape(5, CFG.n_heads, CFG.head_dim)
    normed = raw * jax.lax.rsqrt(jnp.mean(raw * raw, -1, keepdims=True)
                                 + CFG.norm_eps) * (1.0 + layer["q_norm"])
    np.testing.assert_allclose(np.asarray(q[0]), np.asarray(normed[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(q[3]), np.asarray(
        ref._rotary(normed, CFG.rope_theta)[3]), atol=1e-5)
    # a rotation keeps each pair's length: dims i and i + d/2
    half = CFG.head_dim // 2
    np.testing.assert_allclose(
        np.asarray(q[3, :, :half] ** 2 + q[3, :, half:] ** 2),
        np.asarray(normed[3, :, :half] ** 2 + normed[3, :, half:] ** 2),
        atol=1e-5)
    assert v.shape == (5, CFG.n_kv_heads, CFG.head_dim)


# ------------------------------------------------------------ expert layer
def test_sigmoid_gates_with_the_familys_eps_are_the_references_router(params):
    moe_ = params["layers"][4]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(6), (33, CFG.d_model))
    vals, idx = moe.sigmoid_gates(h, moe_["w_router"], moe_["router_bias"],
                                  CFG.top_k, 1.0, True, lm.GATE_EPS)
    gates, chosen, own, under = ref._route(
        h, moe_["w_router"], moe_["router_bias"],
        jnp.zeros((33, CFG.top_k), jnp.int32), 0, top_k=CFG.top_k, scale=1.0,
        norm=True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(own))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(gates), atol=1e-6)
    assert float(np.asarray(under).max()) == 0.0
    # the constant is the family's, and the default is what it was
    s = np.asarray(jax.nn.sigmoid(h @ moe_["w_router"]))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(vals), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        atol=1e-6)
    plain, _ = moe.sigmoid_gates(h, moe_["w_router"], moe_["router_bias"],
                                 CFG.top_k, 1.0)
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, atol=1e-6)
    assert np.abs(np.asarray(vals).sum(-1) - 1.0).min() > 1e-8


def test_the_calibrated_bias_spreads_the_picks(params):
    """``balanced_bias`` (shared with the hybrid and the latent family): every
    expert's ``1 - k / E`` quantile of score + bias is alike, so over the
    sample each expert is picked about ``T k / E`` times."""
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(7),
                                              (4096, 16))
                            + jnp.linspace(-2.0, 2.0, 16)[None, :])
    bias = moe.balanced_bias(scores, 3)
    _, idx = jax.lax.top_k(scores + bias, 3)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=16)
    assert counts.min() > 0.7 * 4096 * 3 / 16
    _, raw = jax.lax.top_k(scores, 3)
    assert np.bincount(np.asarray(raw).ravel(), minlength=16).min() \
        < 0.2 * 4096 * 3 / 16
    assert all(float(jnp.abs(lyr["moe"]["router_bias"]).max()) > 0
               for lyr in params["layers"][CFG.n_dense_layers:])


def test_the_shares_at_eight_offsets_sum_to_the_uncut_layer(params):
    """model-configs section 4's test: the parts of an expert layer that eight
    chips' shares give (two experts each of the toy's sixteen; this family
    has no shared expert to count once) add up to the uncut reference's
    layer, which is what the benchmark's configuration holds whole."""
    layer = params["layers"][3]
    h = jax.random.normal(jax.random.PRNGKey(10), (25, CFG.d_model))
    zeros = jnp.zeros((25, CFG.top_k), jnp.int32)
    whole = np.asarray(ref.experts(
        h, ref.from_program_tree(params)["layers"][3]["moe"], shape_of(CFG),
        zeros, 0)[0])
    everyone = jnp.ones((25,), bool)
    total, hit = np.zeros_like(whole), 0
    for chip in range(8):
        cfg = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * chip)
        share = lm.expert_share(params, 2 * chip, 2)["layers"][3]
        assert share["moe"]["w_up"].shape[0] == 2
        assert share["moe"]["w_router"].shape == (CFG.d_model, 16)
        out, idx, counts = lm._ffn(share, h, everyone, cfg)
        total += np.asarray(out)
        hit += int(counts[0])
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert hit == len(np.unique(np.asarray(idx)))   # every picked expert once
    assert np.abs(np.asarray(out) - whole).max() > 1e-2  # a share is not it
    # the layer with all its experts is the whole layer: Eh = E, offset 0
    all_held = np.asarray(lm._ffn(layer, h, everyone, CFG)[0])
    np.testing.assert_allclose(all_held, whole, atol=2e-5)


def test_both_expert_forms_give_the_layer(params, monkeypatch):
    """The form follows the row count (``GROUPED_FROM_ROWS``); both give the
    reference's layer, with every pair held and room for all of them in the
    grouped product (its default room is for a share of the pairs)."""
    layer = params["layers"][4]
    h = jax.random.normal(jax.random.PRNGKey(11), (40, CFG.d_model))
    mask = jnp.arange(40) < 37
    zeros = jnp.zeros((40, CFG.top_k), jnp.int32)
    want = np.asarray(ref.experts(
        h, ref.from_program_tree(params)["layers"][4]["moe"], shape_of(CFG),
        zeros, 0)[0])
    assert 64 < lm.GROUPED_FROM_ROWS <= 2048    # a step shares, a chunk groups
    outs = {}
    for rows in (0, 10 ** 9):
        monkeypatch.setattr(lm, "GROUPED_FROM_ROWS", rows)
        out, idx, counts = lm._ffn(layer, h, mask, CFG)
        np.testing.assert_allclose(np.asarray(out[:37]), want[:37], atol=3e-5)
        assert float(jnp.abs(out[37:]).max()) == 0.0    # a masked lane
        outs[rows] = np.asarray(counts)
    np.testing.assert_array_equal(outs[0], outs[10 ** 9])


# ------------------------------------------------------------ the lane pools
def _pool_case(seed, lengths, page=4, P=8, pages=40, kvh=2, d=16, rep=4):
    rng = np.random.default_rng(seed)
    S = len(lengths)
    pool_k = jnp.asarray(rng.normal(size=(pages, page, kvh, d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(pages, page, kvh, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[:S * P]
                         .reshape(S, P), jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, 1, kvh * rep, d)), jnp.float32)
    return q, pool_k, pool_v, tables, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("block_pages", [1, 3])
@pytest.mark.parametrize("seed, lengths", [(0, [5, 23, 0]), (1, [31, 8, 11])])
def test_the_blocked_read_of_a_lane_pool_is_the_4d_pools(seed, lengths,
                                                         block_pages):
    """A pool kept as ``[pages, page, kvh * d]`` is read as the same pool
    ``[pages, page, kvh, d]`` is, and written at the same rows."""
    q, pool_k, pool_v, tables, lens = _pool_case(seed, lengths)
    lane = paged_ops.lane_pool_shape(40, 4, 2, 16)
    assert lane == (40, 4, 32)
    want = paged_ops.attend_pages_blocked(q, pool_k, pool_v, tables, lens,
                                          block_pages)
    got = paged_ops.attend_pages_blocked(
        q, pool_k.reshape(lane), pool_v.reshape(lane), tables, lens,
        block_pages)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        paged_ops.attend_pages(q, pool_k, pool_v, None, None, tables, lens,
                               False, jnp.float32)), atol=2e-5)
    k = jnp.asarray(np.random.default_rng(9).normal(size=(3, 1, 2, 16)),
                    jnp.float32)
    page_idx, offs = tables[:, 1], jnp.asarray([0, 3, 2])
    a = paged_ops.write_kv(k, -k, pool_k, pool_v, None, None, page_idx, offs,
                           False)
    b = paged_ops.write_kv(k, -k, pool_k.reshape(lane), pool_v.reshape(lane),
                           None, None, page_idx, offs, False)
    for x, y in zip(a[:2], b[:2]):
        assert y.shape == lane
        np.testing.assert_array_equal(np.asarray(x.reshape(lane)),
                                      np.asarray(y))


# ------------------------------------------------------- prefill and decode
def test_a_prompt_admitted_in_chunks_equals_the_same_prompt_in_one(params):
    """41 tokens: three chunks of 16 against ONE chunk of 48 and against the
    reference; the carry is the attention layer's K/V rows and the five conv
    layers' tails AT the prompt's end."""
    prompt = _tokens(41, seed=13)
    first, bufs, tails, routing = lm.prefill(params, prompt, 96, CFG,
                                             keep_routing=True)
    want = _reference(params, prompt)
    np.testing.assert_allclose(np.asarray(first),
                               np.asarray(want["logits"])[-1], atol=3e-5)
    np.testing.assert_array_equal(np.sort(routing, -1), np.sort(
        np.asarray(want["own_routing"]), -1))
    assert routing.shape == (CFG.n_moe_layers, 41, CFG.top_k)
    assert len(bufs) == 1 and bufs[0][0].shape == (
        96, CFG.n_kv_heads * CFG.head_dim)
    assert len(tails) == 5 and tails[0].shape == (K - 1, CFG.d_model)
    one_first, one_bufs, one_tails = lm.prefill(
        params, prompt, 96, dataclasses.replace(CFG, prefill_chunk=48,
                                                key_block=16))
    np.testing.assert_allclose(np.asarray(first), np.asarray(one_first),
                               atol=3e-5)
    for (k, v), (k1, v1) in zip(bufs, one_bufs):
        np.testing.assert_allclose(np.asarray(k[:41]), np.asarray(k1[:41]),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(v[:41]), np.asarray(v1[:41]),
                                   atol=2e-5)
    for t, t1 in zip(tails, one_tails):
        np.testing.assert_allclose(np.asarray(t), np.asarray(t1), atol=2e-5)
    # the tail is the gated input's last two rows, not a padded position's
    layer = params["layers"][0]
    x = params["embedding"][jnp.asarray(prompt)]
    z, _ = lm._split_in(layer, lm.rms_norm(x, layer["op_norm"], CFG.norm_eps))
    np.testing.assert_allclose(np.asarray(tails[0]), np.asarray(z[-2:]),
                               atol=2e-5)


@pytest.mark.parametrize("n", [1, 16, 17])
def test_a_prompt_that_ends_at_a_chunks_edge_hands_on_the_right_tail(params,
                                                                     n):
    prompt = _tokens(n, seed=40 + n)
    first, _, tails = lm.prefill(params, prompt, 96, CFG)
    one = lm.prefill(params, prompt, 96, dataclasses.replace(
        CFG, prefill_chunk=48, key_block=16))
    np.testing.assert_allclose(np.asarray(first), np.asarray(one[0]),
                               atol=3e-5)
    for t, t1 in zip(tails, one[2]):
        np.testing.assert_allclose(np.asarray(t), np.asarray(t1), atol=2e-5)
    if n == 1:      # one valid input: a zero row before it
        assert float(jnp.abs(tails[0][0]).max()) == 0.0


_LOGITS = jax.jit(lm._decode_logits, static_argnames=("cfg", "page"))


def _decode_rows(params, prompt, n):
    """The engine's decode logits row by row: ``_decode_logits`` over the
    engine's own pools and tails before each step it dispatches. The engine
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
            row = np.asarray(_LOGITS(
                eng.params, eng.pools_k, eng.pools_v, eng.conv,
                jnp.asarray(tables), last, jnp.asarray(lengths), cfg=CFG,
                page=eng.page)[0][0])
        toks += [t for _, t in eng.step() if t is not None]
        if row is not None and eng.slots[0] is slot \
                and slot.length == at + 1:      # the call dispatched a step
            rows.append(row)
    return toks, rows


@pytest.mark.parametrize("n_prompt, new", [(5, 20), (41, 16)],
                         ids=["one-chunk", "three-chunks"])
def test_engine_decode_logits_are_the_references_rows(params, n_prompt, new):
    """Prefill in chunks, then decode through the engine's pages and conv
    tails: every decode row (logits, not tokens) against the reference's full
    forward pass; pages of 4 and read blocks of 3 pages are crossed
    throughout."""
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


def test_the_engine_holds_lane_pools_for_the_attention_layers_and_tails(
        params):
    eng = _engine(params)
    assert eng.family is paged._FAMILIES[lm.Lfm2MoeConfig]
    assert eng.n_kv == CFG.n_attn_layers == 1
    assert [p.shape for p in eng.pools_k + eng.pools_v] == \
        [(64, 4, CFG.n_kv_heads * CFG.head_dim)] * 2
    assert [c.shape for c in eng.conv] == [(3, K - 1, CFG.d_model)] * 5
    # a convolution tail and nothing else
    assert eng._prefill_buckets == () and not hasattr(eng, "ssm")
    assert eng._read_block == 4 * paged_ops.block_pages_of(
        3, 24, 4, CFG.n_kv_heads, CFG.head_dim, CFG.dtype)


def test_a_slot_freed_and_reused_starts_from_a_zero_tail(params):
    """Pages come back to the allocator, tables to the scratch page; a tail
    has no allocator: the next admission to the slot rewrites it whole from a
    prefill that began at a ZERO left edge, so a request streams what it
    streams alone whatever the slot held before."""
    eng = _engine(params, max_slots=1)
    prompt = _tokens(23, seed=20)
    eng.submit("first", _tokens(44, seed=21), max_new_tokens=9)
    eng.run_to_completion()
    assert eng._available_pages() == 63 and not eng.tables.any()
    assert float(jnp.abs(eng.conv[0][0]).sum()) > 0     # the stale tail
    eng.submit("second", prompt, max_new_tokens=20)
    got = eng.run_to_completion()["second"]
    assert got == _alone(params, prompt, 20)
    # a one-token prompt: its tail's older row is the zero left of position 0
    eng.submit("third", prompt[:1], max_new_tokens=1)
    eng.step()
    assert float(jnp.abs(eng.conv[0][0, 0]).max()) == 0.0
    assert float(jnp.abs(eng.conv[0][0, 1]).max()) > 0.0
    eng.run_to_completion()
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


def test_running_ahead_streams_what_the_synchronous_loop_streams(
        params, slow_device):
    reqs = {"long": (_tokens(40, 1), 19), "short": (_tokens(9, 3), 13)}

    def streams(**more):
        eng = _engine(params, max_slots=2)  # every slot held: the full depth
        for r, (prompt, n) in reqs.items():
            eng.submit(r, prompt, max_new_tokens=n, **more)
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
    ({"enable_prefix_cache": True}, "two-row tail at page boundaries"),
    ({"kv_dtype": "int8"}, "lane pools in the model's dtype"),
    ({"max_len": 88}, "whole chunks")],
    ids=["prefix-cache", "int8-pages", "max_len"])
def test_what_the_engine_refuses_for_this_family(params, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(params, **kw)


def test_llm_server_builds_the_engine_from_the_config_and_streams(params):
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(lambda: (params, CFG), max_slots=2, num_pages=64,
                       page_size=4, max_len=96)
    assert isinstance(server.engine, PagedEngine)
    assert server.engine.family is paged._FAMILIES[lm.Lfm2MoeConfig]
    prompt = _tokens(19, seed=30)
    server.engine.submit("r", prompt, max_new_tokens=6)
    assert server.engine.run_to_completion()["r"] == _alone(params, prompt, 6)


def test_the_engines_own_code_names_no_family():
    """PR 51's rule: what differs by family is a row of ``_FAMILIES``; the
    class asks for none by name."""
    import inspect

    body = inspect.getsource(PagedEngine)
    for word in ("lfm2", "Lfm2", "granite", "Granite", "deepseek",
                 "Deepseek", "cohere", "longcat", "sala", "nemotron",
                 "llama"):
        assert word not in body, word
    assert len(paged._FAMILIES) == 8


@pytest.fixture
def _clean_ring():
    events.reset()
    yield
    events._enabled = True
    events.reset()


def test_greedy_identical_with_recorder_on_and_off(params, _clean_ring):
    prompt = _tokens(40, 6)
    on = _alone(params, prompt, 6)
    assert events.pending() > 0
    events.reset()
    events._enabled = False
    off = _alone(params, prompt, 6)
    assert on == off and events.pending() == 0
