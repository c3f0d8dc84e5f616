"""The Granite-MoE-hybrid family: its forward pass, the Mamba-2 mixer it
shares with the Nemotron-H family (and the chunk form that mixer gained), its
multipliers, the expert layer's two shares, and ``PagedEngine`` serving it
through page pools, SSM state and convolution tails with prompts admitted in
chunks, each against the plain reference
(``perfbench/reference/granite_moe_hybrid.py``) at toy sizes: one whole period
of ten layers (five mamba, attention, four mamba), pages of 4, prompt chunks
of 16 over SSD chunks of 8, so a prompt of a few dozen tokens crosses chunk
boundaries."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import granite_moe_hybrid as ref
from ray_tpu.models import granite_moe_hybrid as gm
from ray_tpu.models import nemotron_h, paged, paged_ops
from ray_tpu.models.paged import PagedEngine
from ray_tpu.ops import ssm

CFG = gm.GRANITE_MOE_HYBRID_DEBUG   # 5 mamba, attention, 4 mamba; chunk 16
K = CFG.conv_kernel
#: layers 4 and 5 of the period alone (mamba, attention), for what needs no
#: depth: a program of two layers compiles in a fifth of the time
PAIR = dataclasses.replace(CFG, n_layers=2,
                           layer_types=(gm.MAMBA, gm.ATTENTION))


def _pair(params):
    return {**params, "layers": params["layers"][4:6]}


def shape_of(cfg):
    """The reference's ``shape`` keys, as a configuration file names them."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        layer_types=list(cfg.layer_types), num_hidden_layers=cfg.n_layers,
        mamba_n_heads=cfg.mamba_heads, mamba_d_head=cfg.mamba_head_dim,
        mamba_d_state=cfg.ssm_state, mamba_n_groups=cfg.n_groups,
        mamba_d_conv=cfg.conv_kernel, rms_norm_eps=cfg.norm_eps,
        num_local_experts=cfg.experts_held, router_width=cfg.n_experts,
        expert_offset=cfg.expert_offset, num_experts_per_tok=cfg.top_k,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling)


def _init(cfg):
    """One jitted call, as ``perfbench.program.init_weights`` makes it."""
    return jax.jit(lambda k: gm.init_params(cfg, k))(jax.random.PRNGKey(0))


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
    eng = _engine(params, max_slots=1)
    eng.submit("alone", prompt, max_new_tokens=n)
    return eng.run_to_completion()["alone"]


# ------------------------------------------------------------ configuration
@pytest.mark.parametrize("active, count", [(False, 32_207_337_984),
                                           (True, 8_803_121_664)],
                         ids=["held", "active"])
def test_param_count_is_the_published_32_billion_and_9_active(active, count):
    whole = gm.GraniteMoeHybridConfig()
    assert whole.param_count(active) == count
    assert (whole.n_mamba_layers, whole.n_attn_layers) == (36, 4)
    assert [i for i, t in enumerate(whole.layer_types)
            if t == gm.ATTENTION] == [5, 15, 25, 35]
    # the benchmark's cut: one period, half the experts, half the vocabulary
    cut = dataclasses.replace(whole, n_layers=10, experts_held=36,
                              vocab_size=50176)
    assert cut.param_count() == 4_757_211_776
    assert cut.kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert cut.slot_state_bytes == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)


def test_param_count_counts_the_tree(params):
    held = sum(a.size for a in jax.tree.leaves(params))
    assert held == CFG.param_count()
    assert params["layers"][0]["moe"]["w_router"].dtype == jnp.float32
    assert params["layers"][0]["A_log"].dtype == jnp.float32
    assert "wq" in params["layers"][5] and "w_in" not in params["layers"][5]
    assert "lm_head" not in params       # the head is the embedding


def test_config_refuses_sizes_the_programs_cannot_cut():
    for bad in ({"layer_types": ("mamba", "conv")}, {"n_layers": 41},
                {"experts_held": 40, "expert_offset": 40},
                {"prefill_chunk": 100}, {"n_groups": 3},
                {"n_heads": 6, "n_kv_heads": 4}):
        with pytest.raises(ValueError):
            dataclasses.replace(gm.GraniteMoeHybridConfig(), **bad)


# --------------------------------------------------------- the shared mixer
def test_the_mamba_mixer_is_nemotron_hs_not_a_copy():
    assert gm._mamba_prompt is nemotron_h._mamba_prompt
    assert gm._mamba_token is nemotron_h._mamba_token
    assert gm._write_state is nemotron_h._write_state
    assert gm.seeded_mamba is nemotron_h.seeded_mamba
    assert paged._write_state is nemotron_h._write_state
    import inspect

    body = inspect.getsource(gm)
    assert "ssd_chunked" not in body and "conv_step" not in body


_MIXER = jax.jit(nemotron_h._mamba_prompt, static_argnames=("cfg",))


@pytest.mark.parametrize("family, cut", [
    ("granite", 8), ("granite", 13), ("granite", 16), ("granite", 29),
    ("nemotron", 13)],
    ids=["an-ssd-chunk", "inside-an-ssd-chunk", "two-ssd-chunks", "odd",
         "the-older-family"])
def test_a_mixers_chunk_goes_on_from_the_state_and_tail_before(params, cut,
                                                               family):
    """The chunk form the shared mixer gained: the sequence in two calls, the
    second from the first's SSM state (``h0``) and tail (the convolution's
    left edge), equals the sequence in one, whether or not the boundary is a
    multiple of the SSD chunk (8), and for the older family's config (two B/C
    groups) too."""
    if family == "granite":
        cfg, layer = CFG, params["layers"][0]
    else:
        cfg = nemotron_h.NEMOTRON_H_DEBUG
        layer = nemotron_h.seeded_mamba(
            cfg, jax.random.split(jax.random.PRNGKey(1), 9))
    u = jax.random.normal(jax.random.PRNGKey(3), (37, cfg.d_model))
    zero = (jnp.zeros((cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)),
            jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim)))
    whole, state, tail = _MIXER(layer, u, 33, cfg, *zero)
    plain = nemotron_h._mamba_prompt(layer, u, 33, cfg)  # as Nemotron calls it
    for a, b in zip((whole, state, tail), plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    a, s1, t1 = _MIXER(layer, u[:cut], 33, cfg, *zero)
    b, s2, t2 = _MIXER(layer, u[cut:], 33 - cut, cfg, s1, t1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([a, b]))[:33],
                               np.asarray(whole)[:33], atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(state), atol=2e-5)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(tail), atol=1e-6)
    # a chunk that is all padding hands on what it was given
    _, s3, t3 = _MIXER(layer, u[cut:], 0, cfg, s2, t2)
    np.testing.assert_allclose(np.asarray(s3), np.asarray(s2), atol=1e-7)
    np.testing.assert_array_equal(np.asarray(t3), np.asarray(t2))


# ------------------------------------------------------------------ forward
@pytest.mark.parametrize("L", [1, 17, 50])
def test_forward_is_the_reference(params, L):
    toks = _tokens(L, seed=L)
    got = np.asarray(gm.forward(params, jnp.asarray(toks, jnp.int32), CFG))
    want = np.asarray(_reference(params, toks)["logits"])
    assert want.std() > 1e-3
    np.testing.assert_allclose(got, want, atol=3e-5 * want.std() + 1e-7,
                               rtol=0)


def test_forward_in_bfloat16_stays_near_the_reference():
    cfg = dataclasses.replace(PAIR, dtype=jnp.bfloat16)
    p16 = _init(cfg)
    toks = _tokens(40, seed=2)
    got = np.asarray(gm.forward(p16, jnp.asarray(toks, jnp.int32), cfg)
                     .astype(jnp.float32))
    want = np.asarray(_reference(p16, toks, cfg)["logits"])
    assert np.sqrt(np.mean((got - want) ** 2)) < 0.15 * want.std()


@pytest.mark.parametrize("field, value", [
    ("embedding_multiplier", 6.0), ("residual_multiplier", 0.5),
    ("attention_multiplier", 0.25), ("logits_scaling", 4.0)])
def test_each_multiplier_is_where_the_reference_has_it(params, field, value):
    """Each of the four scalars moves the logits, and moves them as the
    reference's: a multiplier on the wrong branch, or ``head_dim ** -0.5``
    left in the scores, would part the two."""
    cfg = dataclasses.replace(PAIR, **{field: value})
    two = _pair(params)
    toks = _tokens(30, seed=5)
    got = np.asarray(gm.forward(two, jnp.asarray(toks, jnp.int32), cfg))
    want = np.asarray(_reference(two, toks, cfg)["logits"])
    np.testing.assert_allclose(got, want, atol=3e-5 * want.std() + 1e-7,
                               rtol=0)
    base = np.asarray(gm.forward(two, jnp.asarray(toks, jnp.int32), PAIR))
    assert np.abs(got - base).max() > 0.05 * base.std()


def test_the_router_is_a_softmax_over_the_chosen_logits_with_no_bias(params):
    layer = params["layers"][2]
    assert set(layer["moe"]) == {"w_router", "w_gate", "w_up", "w_down"}
    u = jax.random.normal(jax.random.PRNGKey(4), (19, CFG.d_model))
    vals, idx = gm.top_k_gates(gm.router_probs(u, layer["moe"]["w_router"]),
                               CFG.top_k)
    logit = np.asarray(u, np.float64) @ np.asarray(layer["moe"]["w_router"],
                                                   np.float64)
    top = np.sort(logit, -1)[:, -CFG.top_k:]
    np.testing.assert_array_equal(np.sort(idx, -1),
                                  np.sort(np.argsort(logit, -1)
                                          [:, -CFG.top_k:], -1))
    want = np.exp(top - top.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.sort(vals, -1), np.sort(want, -1),
                               atol=1e-6)


def test_the_balanced_routers_spread_the_picks():
    """Seeded routers send a batch to a few experts (every token's normed
    input shares a direction); with that direction taken out of the router's
    columns the picks of a batch spread. No bias is added."""
    cfg = dataclasses.replace(PAIR, n_experts=24, experts_held=24, top_k=4)
    raw = jax.jit(lambda k: gm._seeded_params(cfg, k))(jax.random.PRNGKey(0))
    bal = jax.jit(lambda k: gm.balance_routers(raw, cfg, k))(
        jax.random.PRNGKey(9))
    toks = jnp.asarray(_tokens(64, seed=6), jnp.int32)

    def most(p):
        r = np.asarray(gm._run_chunk(p, toks, jnp.int32(0), jnp.int32(64),
                                     *gm._empty_carry(cfg, 64), cfg)[3])
        return np.mean([np.bincount(layer.ravel(), minlength=24).max()
                        for layer in r])

    # 64 tokens x 4 picks over 24 experts: 10.7 a mean expert
    assert most(bal) < most(raw) and most(bal) < 24
    for a, b in zip(raw["layers"], bal["layers"]):
        assert set(a["moe"]) == set(b["moe"])
        np.testing.assert_array_equal(a["moe"]["w_up"], b["moe"]["w_up"])
        assert float(jnp.abs(a["moe"]["w_router"]
                             - b["moe"]["w_router"]).max()) > 0


# ------------------------------------------------------- the expert layer
def test_the_two_shares_sum_to_the_uncut_layer(params):
    """model-configs section 4's test: the parts of an expert layer that the
    deployment's two chips give (experts 0-5 and 6-11 of the toy's twelve),
    with the shared expert, which both compute, counted once, add up to the
    uncut reference's layer."""
    layer = params["layers"][3]
    u = jax.random.normal(jax.random.PRNGKey(10), (25, CFG.d_model))
    zeros = jnp.zeros((25, CFG.top_k), jnp.int32)
    w = ref.from_program_tree(params)["layers"][3]
    routed = np.asarray(ref.experts(u, w["moe"], shape_of(CFG), zeros, 0)[0])
    sh = w["shared"]
    shared = np.asarray(ref._swiglu(u, jnp.ones((25,)), sh["w_gate"],
                                    sh["w_up"], sh["w_down"]))
    everyone = jnp.ones((25,), bool)
    total, hit = np.zeros_like(routed), 0
    for chip in range(2):
        cfg = dataclasses.replace(CFG, experts_held=6, expert_offset=6 * chip)
        share = gm.expert_share(params, 6 * chip, 6)["layers"][3]
        assert share["moe"]["w_up"].shape[0] == 6
        assert share["moe"]["w_router"].shape == (CFG.d_model, 12)
        out, idx, counts = gm._ffn(share, u, everyone, cfg)
        total += np.asarray(out)
        hit += int(counts[0])
    np.testing.assert_allclose(total - shared, routed + shared, atol=2e-5)
    assert hit == len(np.unique(np.asarray(idx)))   # every picked expert once
    assert np.abs(np.asarray(out) - routed - shared).max() > 1e-2
    # the layer with all its experts is the whole layer: Eh = E, offset 0
    all_held = np.asarray(gm._ffn(layer, u, everyone, CFG)[0])
    np.testing.assert_allclose(all_held, routed + shared, atol=2e-5)


def test_both_expert_forms_give_the_layer(params, monkeypatch):
    layer = params["layers"][4]
    u = jax.random.normal(jax.random.PRNGKey(11), (40, CFG.d_model))
    mask = jnp.arange(40) < 37
    zeros = jnp.zeros((40, CFG.top_k), jnp.int32)
    w = ref.from_program_tree(params)["layers"][4]
    sh = w["shared"]
    want = np.asarray(ref.experts(u, w["moe"], shape_of(CFG), zeros, 0)[0]
                      + ref._swiglu(u, jnp.ones((40,)), sh["w_gate"],
                                    sh["w_up"], sh["w_down"]))
    assert 64 < gm.GROUPED_FROM_ROWS <= 2048    # a step shares, a chunk groups
    outs = {}
    for rows in (0, 10 ** 9):
        monkeypatch.setattr(gm, "GROUPED_FROM_ROWS", rows)
        out, idx, counts = gm._ffn(layer, u, mask, CFG)
        np.testing.assert_allclose(np.asarray(out[:37]), want[:37], atol=3e-5)
        outs[rows] = np.asarray(counts)
    np.testing.assert_array_equal(outs[0], outs[10 ** 9])


# ------------------------------------------------- admission in chunks
@pytest.mark.parametrize("n, chunk, calls", [(41, 16, 3), (41, 24, 2),
                                             (30, 20, 2), (16, 16, 1),
                                             (17, 16, 2), (1, 16, 1)],
                         ids=["three-chunks", "two-chunks",
                              "no-multiple-of-the-ssd-chunk", "at-the-edge",
                              "one-over-the-edge", "one-token"])
def test_a_prompt_admitted_in_chunks_equals_the_same_prompt_in_one(
        params, n, chunk, calls):
    """The carry is the attention layer's K/V rows and the nine Mamba layers'
    SSM states and tails AT the prompt's end; a chunk of 20 or 24 over SSD
    chunks of 8 puts the boundary inside one."""
    cfg = dataclasses.replace(CFG, prefill_chunk=chunk,
                              key_block=chunk // 2 if chunk % 16 else 8)
    total = 96 if chunk == 16 else 120 if chunk == 24 else 100
    prompt = _tokens(n, seed=13 + n)
    first, bufs, states, routing = gm.prefill(params, prompt, total, cfg,
                                              keep_routing=True)
    assert -(-n // chunk) == calls
    want = _reference(params, prompt)
    sigma = float(np.asarray(want["logits"]).std())
    np.testing.assert_allclose(np.asarray(first),
                               np.asarray(want["logits"])[-1],
                               atol=3e-5 * sigma + 1e-7, rtol=0)
    np.testing.assert_array_equal(np.sort(routing, -1), np.sort(
        np.asarray(want["own_routing"]), -1))
    assert routing.shape == (CFG.n_layers, n, CFG.top_k)
    assert len(bufs) == 1 and bufs[0][0].shape == (
        total, CFG.n_kv_heads, CFG.head_dim)
    assert len(states) == 9
    assert states[0][0].shape == (CFG.mamba_heads, CFG.mamba_head_dim,
                                  CFG.ssm_state)
    assert states[0][0].dtype == jnp.float32
    assert states[0][1].shape == (K - 1, CFG.conv_dim)
    if n == 1:      # one valid input: zero rows before it
        assert float(jnp.abs(states[0][1][:2]).max()) == 0.0
        assert float(jnp.abs(states[0][1][2]).max()) > 0.0
    if calls == 1:
        return
    # the carry at the prompt's end against the same prompt as ONE chunk
    pad = -(-n // 16) * 16
    one = dataclasses.replace(CFG, prefill_chunk=pad, key_block=16)
    one_first, one_bufs, one_states = gm.prefill(params, prompt, 2 * pad, one)
    np.testing.assert_allclose(np.asarray(first), np.asarray(one_first),
                               atol=3e-5 * sigma + 1e-7, rtol=0)
    for (k, v), (k1, v1) in zip(bufs, one_bufs):
        np.testing.assert_allclose(np.asarray(k[:n]), np.asarray(k1[:n]),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(v[:n]), np.asarray(v1[:n]),
                                   atol=2e-5)
    for (s, t), (s1, t1) in zip(states, one_states):
        np.testing.assert_allclose(np.asarray(s), np.asarray(s1), atol=2e-5)
        np.testing.assert_allclose(np.asarray(t), np.asarray(t1), atol=2e-5)


def test_n_valid_inside_a_chunk_masks_the_padded_tail(params):
    """The chunk program pads with token 0 past ``n_valid``: the state and
    the tails handed on are those AT ``n_valid``, whatever the padding holds,
    and the padded rows are routed to no expert."""
    prompt = _tokens(21, seed=3)
    padded = np.zeros(32, np.int32)
    padded[:21] = prompt
    noisy = padded.copy()
    noisy[21:] = _tokens(11, seed=4)
    outs = []
    for toks in (padded, noisy):
        carry = gm.prefill_carry(CFG, 96)
        for c in range(2):
            first, *carry, routing = gm._granite_prefill_chunk(
                params, toks[16 * c:16 * c + 16], np.int32(16 * c),
                np.int32(21), *carry, CFG)
        outs.append((first, carry[1]))
    np.testing.assert_allclose(np.asarray(outs[0][0]), np.asarray(outs[1][0]),
                               atol=1e-7)
    for (s, t), (s1, t1) in zip(outs[0][1], outs[1][1]):
        np.testing.assert_allclose(np.asarray(s), np.asarray(s1), atol=1e-7)
        np.testing.assert_array_equal(np.asarray(t), np.asarray(t1))


_LOGITS = jax.jit(gm._decode_logits, static_argnames=("cfg", "page"))


def _decode_rows(params, prompt, n):
    """The engine's decode logits row by row: ``_decode_logits`` over the
    engine's own pools, states and tails before each step it dispatches. The
    engine runs ahead, so the token a step takes is the one the last step
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
                eng.params, eng.pools_k, eng.pools_v, eng.ssm, eng.conv,
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
    """Prefill in chunks, then decode through the engine's pages, SSM state
    and tails: every decode row (logits, not tokens) against the reference's
    full forward pass from a zero state."""
    prompt = _tokens(n_prompt, seed=8 + n_prompt)
    toks, rows = _decode_rows(params, prompt, new)
    # the first step() admits AND decodes: the rows begin at the second
    assert len(toks) == new and len(rows) == new - 2
    seq = prompt + toks
    want = np.asarray(_reference(params, seq[:-1])["logits"])
    sigma, n = float(want.std()), n_prompt
    assert toks[:2] == want[n - 1:n + 1].argmax(-1).tolist()
    for i, row in enumerate(rows):
        np.testing.assert_allclose(row, want[n + 1 + i],
                                   atol=5e-5 * sigma + 1e-7, rtol=0)
        assert toks[i + 2] == int(want[n + 1 + i].argmax())


def test_the_engine_holds_pools_for_the_attention_layer_and_state(params):
    eng = _engine(params)
    assert eng.family is paged._FAMILIES[gm.GraniteMoeHybridConfig]
    assert eng.n_kv == CFG.n_attn_layers == 1
    assert [p.shape for p in eng.pools_k + eng.pools_v] == \
        [(64, 4, CFG.n_kv_heads, CFG.head_dim)] * 2
    assert [s.shape for s in eng.ssm] == [
        (3, CFG.mamba_heads, CFG.mamba_head_dim, CFG.ssm_state)] * 9
    assert all(s.dtype == jnp.float32 for s in eng.ssm)
    assert [c.shape for c in eng.conv] == [(3, K - 1, CFG.conv_dim)] * 9
    assert eng._prefill_buckets == ()
    assert eng._read_block == 4 * paged_ops.block_pages_of(
        3, 24, 4, CFG.n_kv_heads, CFG.head_dim, CFG.dtype)


def test_an_empty_slots_state_stands_still(params, prompt_device):
    """A slot of length 0 flows through the step (static shapes) with ``dt``
    0: its SSM state is multiplied by 1 and nothing is added, whatever a
    request left there."""
    eng = _engine(params, max_slots=2)
    eng.submit("gone", _tokens(20, seed=1), max_new_tokens=5)
    eng.run_to_completion()
    stale = [np.asarray(s[0]) for s in eng.ssm]
    assert np.abs(stale[0]).sum() > 0
    eng.submit("r", _tokens(9, seed=2), max_new_tokens=12)
    eng.step()
    at = eng.slots.index(next(s for s in eng.slots if s is not None))
    other = 1 - at
    before = [np.asarray(s[other]) for s in eng.ssm]
    while eng.has_work():
        eng.step()
    for b, s in zip(before, eng.ssm):
        np.testing.assert_array_equal(b, np.asarray(s[other]))


def test_a_slot_freed_and_reused_starts_from_a_zero_state(params):
    eng = _engine(params, max_slots=1)
    prompt = _tokens(23, seed=20)
    eng.submit("first", _tokens(44, seed=21), max_new_tokens=9)
    eng.run_to_completion()
    assert eng._available_pages() == 63 and not eng.tables.any()
    eng.submit("second", prompt, max_new_tokens=20)
    assert eng.run_to_completion()["second"] == _alone(params, prompt, 20)
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
    ({"enable_prefix_cache": True}, "SSM state and tail at page boundaries"),
    ({"kv_dtype": "int8"}, "float32 SSM state beside int8 pages"),
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
    assert server.engine.family is paged._FAMILIES[gm.GraniteMoeHybridConfig]
    prompt = _tokens(19, seed=30)
    server.engine.submit("r", prompt, max_new_tokens=6)
    assert server.engine.run_to_completion()["r"] == _alone(params, prompt, 6)


def test_the_ssm_helpers_the_chunk_form_passes_are_the_oracles():
    """``ssd_chunked`` from ``h0`` against the recurrence over time from the
    same state: what nothing with an SSM state passed before this family."""
    rng = np.random.default_rng(0)
    L, H, P, N = 21, 4, 3, 5
    x = jnp.asarray(rng.normal(size=(L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 4, size=(H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(L, 1, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(L, 1, N)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(H, P, N)), jnp.float32)
    y, h = ssm.ssd_chunked(x, dt, A, B, C, 8, h0)
    y1, h1 = ssm.ssm_sequential(x, dt, A, B, C, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h1), atol=2e-5)
