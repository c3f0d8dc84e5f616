"""The Granite-MoE-hybrid family's benchmark files: its configuration against
the catalog row, its plain reference through the harness's own path, its
reference check (sound, the int8 control, an SSM state, a convolution tail or
K/V lost at the chunk boundary, an altered token), its byte counts and
readers, and a rehearsal run of ``serve-granite-rag-decode`` end to end. Toy
widths, CPU."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import granite_bytes, program, traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.reference import (REF_NEW, REF_PROMPT,
                                 granite_moe_hybrid as ref,
                                 granite_moe_hybrid_check as chk,
                                 granite_moe_hybrid_control as ctl)
from perfbench.runners import serve as serve_runner

MAN = Manifest(ROOT)
NAME = "granite-4.0-h-small-serve1"
CELL = "serve-granite-rag-decode"
CONFIG = MAN.config(NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "mamba_n_heads", "mamba_d_head",
          "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
          "mamba_n_groups", "mamba_expand")
REDUCED = ["num_hidden_layers", "num_local_experts", "vocab_size"]


# ------------------------------------------------------------ configuration
def test_reduced_is_depth_experts_held_and_vocabulary_and_no_width():
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 40, "num_local_experts": 72,
        "vocab_size": 100352, "parameters": 32_207_337_984,
        "active_parameters": 8_803_121_664}
    # one whole period, half the experts (the floor is 8), half the
    # vocabulary (the floor is an eighth)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"],
            CONFIG["router_width"], CONFIG["num_experts_per_tok"],
            CONFIG["vocab_size"], CONFIG["expert_offset"]) == (
        10, 36, 72, 10, 50176, 0)
    assert len(CONFIG["layer_types"]) == 40     # the published list, whole
    assert CONFIG["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    for said in ("four pipeline stages of ten layers", "two chips a stage",
                 "36 of the 72 routed experts", "half the vocabulary",
                 "8.9 tokens an expert", "17.8", "the last stage does"):
        assert said in CONFIG["deployment"], said
    for key in ("head_dim", "positions", "norm", "multipliers", "mamba",
                "experts", "router", "router balancing", "weights",
                "tied head", "ssm_state_dtype", "kv_cache_dtype", "eos",
                "page_size", "max_slots", "num_pages", "max_len", "prefill",
                "memory"):
        assert key in CONFIG["assumed"], key
    assert "gate then up" in CONFIG["assumed"]["experts"]
    assert "no selection bias" in CONFIG["assumed"]["router"]
    assert "float32 SSM state" in CONFIG["assumed"]["ssm_state_dtype"]
    assert CONFIG["engine"] == {
        "kv_cache": "paged", "max_slots": 64, "page_size": 16,
        "num_pages": 24576, "max_len": 10240, "enable_prefix_cache": False,
        "kv_dtype": "model"}
    assert CONFIG["engine"]["max_len"] == 5 * CONFIG["prefill_chunk"]
    assert CONFIG["programs"]["decode"] == "jit__granite_step"
    assert CONFIG["probe"] == {"prompt_len": 2048 + 200, "new_tokens": 8}


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_published_key_is_at_its_published_value():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CONFIG["source"])
    assert row["name"] == "granite-4.0-h-small"
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["head_dim"] * row["num_attention_heads"] == \
        row["hidden_size"]
    assert CONFIG["mamba_n_heads"] * CONFIG["mamba_d_head"] == \
        CONFIG["mamba_expand"] * CONFIG["hidden_size"]
    assert CONFIG["attention_multiplier"] == 1 / 128
    assert CONFIG["position_embedding_type"] == "nope"


def test_the_config_class_is_built_from_the_file():
    import dataclasses

    cfg = program.model_config(CONFIG, program.shape_of(CONFIG, False))
    assert (cfg.n_layers, cfg.n_attn_layers, cfg.n_mamba_layers,
            cfg.experts_held, cfg.n_experts, cfg.top_k, cfg.expert_offset) \
        == (10, 1, 9, 36, 72, 10, 0)
    assert (cfg.d_model, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
            cfg.n_groups, cfg.conv_kernel, cfg.chunk_size, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.expert_d_ff, cfg.shared_d_ff,
            cfg.norm_eps, cfg.vocab_size) == (
        4096, 128, 64, 128, 1, 4, 256, 32, 8, 128, 768, 1536, 1e-5, 50176)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        12, 0.22, 0.0078125, 16)
    assert cfg.dtype == jnp.bfloat16 and cfg.prefill_chunk == 2048
    assert cfg.param_count() == 4_757_211_776
    whole = dataclasses.replace(cfg, n_layers=40, experts_held=72,
                                vocab_size=100352)
    assert whole.param_count() == CONFIG["published"]["parameters"]
    assert whole.param_count(active=True) == \
        CONFIG["published"]["active_parameters"]
    toy = program.model_config(CONFIG, program.shape_of(CONFIG, True))
    assert (toy.n_layers, toy.kinds, toy.experts_held, toy.n_experts) == (
        2, ("mamba", "attention"), 4, 8)


def test_byte_counts_agree_with_the_program_tree():
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape)
    from ray_tpu.models.granite_moe_hybrid import init_params
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert granite_bytes.weight_bytes(shape) == held
    assert granite_bytes.slot_state_bytes(shape) == cfg.slot_state_bytes
    real = program.shape_of(CONFIG, False)
    assert granite_bytes.weight_bytes(real) == pytest.approx(9.520e9,
                                                             rel=0.0005)
    assert granite_bytes.expert_bytes(real) == 18_874_368       # 18.87 MB
    assert granite_bytes.kv_row_bytes(real) == 4096
    assert granite_bytes.slot_state_bytes(real) == 38_204_928   # 38.2 MB
    bare = granite_bytes.decode_min_bytes(real, 0, 0, 0)
    assert bare == granite_bytes.outside_experts_bytes(real) == \
        pytest.approx(2.726e9, rel=0.002)
    # the tied table is counted once: as the head
    assert bare - 2 * 50176 * 4096 < 2.32e9
    every = granite_bytes.decode_min_bytes(real, 10 * 36, 0, 0)
    assert every == granite_bytes.weight_bytes(real)
    # the experts a step HIT, not the 36 held: fewer hit, fewer bytes
    need = granite_bytes.decode_min_bytes(real, 350, 200_000, 64)
    assert need - bare == pytest.approx(
        350 * 18_874_368 + 2 * 64 * 38_204_928 + 4096 * (200_000 + 64)
        + 2 * 64 * 4096, rel=1e-9)
    assert 15.0e9 < need < 15.2e9
    assert 2 * 64 * 38_204_928 / need == pytest.approx(0.324, abs=0.003)
    e = CONFIG["engine"]
    assert e["num_pages"] * e["page_size"] * 4096 == \
        pytest.approx(1.611e9, rel=0.001)
    assert e["max_slots"] * 38_204_928 == pytest.approx(2.445e9, rel=0.001)


# ---------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    """The rehearsal widths through the harness's own path, in float32: the
    routing then agrees with the reference to the last tie, so a sound engine
    reads ~0 everywhere and what a fault moves is the fault's alone."""
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape, dtype=jnp.float32)
    return shape, cfg, program.init_weights(CONFIG, cfg, 3_400_000_033)


def test_reference_parity_through_the_harness_path(toy):
    from ray_tpu.models.granite_moe_hybrid import forward

    shape, cfg, params = toy
    toks = tg.prompt_tokens(5, 1, 600, shape["vocab_size"])
    got = np.asarray(forward(params, jnp.asarray(toks, jnp.int32), cfg))
    out = ref.forward(ref.from_program_tree(params), toks, shape)
    want = np.asarray(out["logits"])
    assert want.std() > 1e-3
    np.testing.assert_allclose(got, want, atol=3e-4 * want.std(), rtol=0)
    own = np.asarray(out["own_routing"])
    assert own.shape == (2, 600, 2)     # every layer of the toy has experts
    assert float(np.asarray(out["under"]).max()) == 0.0    # nothing imposed
    np.testing.assert_array_equal(
        ref.logits(ref.from_program_tree(params), toks[:50], shape),
        ref.forward(ref.from_program_tree(params), toks[:50], shape,
                    rows=np.arange(50))["logits"])
    import inspect

    assert "ray_tpu" not in inspect.getsource(ref).replace(
        "``ray_tpu.models``", "")


# -------------------------------------------------------------------- check
def _engine(toy):
    from ray_tpu.models.paged import PagedEngine

    _, cfg, params = toy
    kw = {k: v for k, v in program.section(CONFIG, "engine", True).items()
          if k != "kv_cache"}
    return PagedEngine(params, cfg, **{**kw, "num_pages": 512,
                                       "max_slots": 2})


def _prompt(shape, seed=9):
    return tg.prompt_tokens(seed, 10**6 + 99, REF_PROMPT, shape["vocab_size"])


def _by_name(result):
    return {r["name"]: r for r in result["readings"]}


def test_sound_engine_passes_and_an_altered_token_does_not(toy):
    shape = toy[0]
    eng, prompt = _engine(toy), _prompt(toy[0])
    emitted = ctl._generate(eng, prompt)
    r = chk.check(eng, prompt, emitted, CONFIG, shape)
    assert r["ok"] and r["finite"] and len(emitted) == REF_NEW
    by = _by_name(r)
    assert set(by) == {
        "routing_far_disagreements", "rerun_token_mismatches",
        "probe_tokens_missing", "prefill_max_abs_err", "prefill_rms_err",
        "max_margin", "probe_prefill_max_abs_err", "probe_prefill_rms_err",
        "probe_max_margin"}
    assert by["routing_far_disagreements"]["limit"] == 0.0
    for rehearse in (True, False):      # the probe is the cell's own
        n, m = chk.probe_sizes(CONFIG, program.shape_of(CONFIG, rehearse))
        assert (n, m) == (2248, 8)
    assert r["notes"]["probe_len"] == 2248
    # the ONE reading more than the contract's request crosses the chunk
    # boundary by the contract's own length
    assert n == CONFIG["prefill_chunk"] + REF_PROMPT
    # every position of both requests' prefills and decode steps, two layers
    assert r["notes"]["routing_decisions"] == \
        2 * (REF_PROMPT + REF_NEW - 1 + n + m - 1)
    assert r["notes"]["routing_disagreements"] <= 4         # float32
    wrong = list(emitted)
    wrong[7] = (wrong[7] + 1) % shape["vocab_size"]
    bad = chk.check(eng, prompt, wrong, CONFIG, shape)
    assert not bad["ok"]
    assert _by_name(bad)["rerun_token_mismatches"]["value"] == 1
    assert _by_name(bad)["max_margin"]["value"] > \
        _by_name(bad)["max_margin"]["limit"]


@pytest.fixture(scope="module")
def stateful_toy():
    """The rehearsal widths with the published state of 128 and 16 Mamba
    heads where the rehearsal has 8 and 4: with a state of 8 the recurrence
    adds a thirtieth of what ``D x`` does, and a second chunk that starts
    from a ZERO state moves a routing logit by a hundredth of the experts'
    spread (the check passes it); at the published state it is a sixth, as
    at the cell's size."""
    import copy

    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["shape"].update(mamba_d_state=128, mamba_n_heads=16)
    shape = program.shape_of(config, True)
    cfg = program.model_config(config, shape, dtype=jnp.float32)
    return shape, cfg, program.init_weights(config, cfg, 3_400_000_033)


@pytest.mark.parametrize("fault", ["zero_state", "zero_tail",
                                   "kv_lost_at_the_edge"])
def test_what_is_lost_at_the_chunk_boundary_fails_the_probe(
        stateful_toy, monkeypatch, fault):
    """The contract's 200-token request stays inside one chunk and passes;
    only the probe crosses position 2048: a second chunk whose recurrence
    starts from a zero SSM state, whose convolution starts from zeros in
    place of the first chunk's tail, or whose attention no longer finds the
    first chunk's K/V, moves the probe's first-token row or the routing of
    the positions after the edge, which is held to the reference's too."""
    from ray_tpu.models import granite_moe_hybrid as gm
    from ray_tpu.ops import ssm

    scan, conv, attn = ssm.ssd_chunked, ssm.causal_conv, gm._prompt_attention

    def from_zero(x, dt, A, B, C, chunk, h0=None):
        return scan(x, dt, A, B, C, chunk,
                    None if h0 is None else jnp.zeros_like(h0))

    def zero_left(x, w, b=None, left=None):
        return conv(x, w, b, None if left is None else jnp.zeros_like(left))

    def lost(q, buf_k, buf_v, start, window, cfg):
        keep = (jnp.arange(buf_k.shape[0]) >= start)[:, None, None]
        return attn(q, jnp.where(keep, buf_k, 0), jnp.where(keep, buf_v, 0),
                    start, window, cfg)

    patched = {"zero_state": (ssm, "ssd_chunked", from_zero),
               "zero_tail": (ssm, "causal_conv", zero_left),
               "kv_lost_at_the_edge": (gm, "_prompt_attention", lost)}
    toy = stateful_toy
    monkeypatch.setattr(*patched[fault])
    gm._granite_prefill_chunk.clear_cache()
    try:
        eng, prompt = _engine(toy), _prompt(toy[0], seed=11)
        r = chk.check(eng, prompt, ctl._generate(eng, prompt), CONFIG,
                      toy[0])
    finally:
        monkeypatch.undo()
        gm._granite_prefill_chunk.clear_cache()
    by = _by_name(r)
    assert not r["ok"]
    # the request inside one chunk reads sound
    for name in ("prefill_max_abs_err", "prefill_rms_err", "max_margin"):
        assert by[name]["value"] <= by[name]["limit"], name
    if fault == "kv_lost_at_the_edge":
        assert by["probe_prefill_rms_err"]["value"] > \
            by["probe_prefill_rms_err"]["limit"]
    else:
        # the state decays and three positions' convolutions of 2248 are
        # wrong: the last row, 200 positions on, moves little, but the
        # routing of the positions after the edge is held to the
        # reference's too
        assert by["routing_far_disagreements"]["value"] > 0


def test_int8_weights_read_well_over_the_sound_engine():
    """At toy widths the limits (set on the chip at the cell's size) need not
    separate the two; the control's readings must still stand clear of the
    sound ones, as they do there. The second control (a bfloat16 SSM state)
    runs through the same path."""
    r = ctl.one_seed(CONFIG, 41, True, state="bfloat16")
    assert set(r) == {"sound", "state16", "w8"}
    sound, w8 = [], []
    for name in ("prefill_rms_err", "probe_prefill_rms_err"):
        sound.append(_by_name(r["sound"])[name]["value"])
        w8.append(_by_name(r["w8"])[name]["value"])
    assert np.mean(w8) > 1.4 * np.mean(sound)
    assert "w_in" in ctl.QUANT_KEYS and "conv_w" not in ctl.QUANT_KEYS
    # the state's rounding reaches only what is decoded: the prefill rows of
    # the two engines are the same programs on the same weights
    for name in ("prefill_rms_err", "probe_prefill_rms_err"):
        assert _by_name(r["state16"])[name]["value"] == pytest.approx(
            _by_name(r["sound"])[name]["value"], rel=1e-6)


# ------------------------------------------------------------------ readers
def _ctx(steps, admits=()):
    return {"shape": program.shape_of(CONFIG, False),
            "run": {"t_open": 1.0, "t_close": 2.0},
            "spans": {}, "config": CONFIG,
            "_program_spans": {"serve.engine.step": steps,
                               "serve.engine.admit": list(admits)}}


def test_counter_and_span_readers():
    state = 2 * 64 * 38_204_928
    row = {"dur_ns": 1, "active": 64, "experts_hit": 358,
           "expert_tokens_max": 17, "moe_rows": 64,
           "context_positions": 200_000, "ssm_state_bytes": state,
           "landed": 1}
    steps = [{**row, "t0_ns": 1.1e9},
             # a call that landed two steps sums them
             {**row, "t0_ns": 1.2e9, "experts_hit": 720, "moe_rows": 128,
              "expert_tokens_max": 33, "context_positions": 400_128,
              "ssm_state_bytes": 2 * state, "landed": 2},
             {"t0_ns": 1.3e9, "dur_ns": 1, "active": 64},   # nothing landed
             {**row, "t0_ns": 2.5e9, "experts_hit": 1}]     # past the window
    ctx = _ctx(steps,
               [{"t0_ns": 1.4e9, "dur_ns": 0.1e9, "sid": 7,
                 "prompt_len": 1500},
                {"t0_ns": 1.6e9, "dur_ns": 0.25e9, "sid": 8,
                 "prompt_len": 3500},
                {"t0_ns": 0.4e9, "dur_ns": 5e9, "sid": 3, "prompt_len": 999}])
    assert MAN.reader("moe_experts_hit_per_layer.granite")(ctx) == \
        pytest.approx(1078 / 3 / 10)
    assert MAN.reader("moe_expert_tokens_max.granite")(ctx) == \
        pytest.approx(50 / 3)
    assert MAN.reader("admit_ms_per_prompt_token.granite")(ctx) == \
        pytest.approx(350 / 5000)
    need = granite_bytes.decode_min_bytes(ctx["shape"], 1078 / 3,
                                          600_128 / 3, 64)
    assert MAN.reader("ssm_state_bytes_share_pct.granite")(ctx) == \
        pytest.approx(100 * state / need)
    assert 31 < 100 * state / need < 34
    # a program without the counters or the spans (the parent): nothing
    bare = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 16}])
    for name in ("moe_experts_hit_per_layer.granite",
                 "moe_expert_tokens_max.granite",
                 "ssm_state_bytes_share_pct.granite",
                 "granite_decode_hbm_roofline_pct",
                 "admit_ms_per_prompt_token.granite"):
        assert MAN.reader(name)(bare) is None
    ctx.update({"peaks": {"hbm_bytes_per_s": 819e9,
                          "bf16_flops_per_s": 197e12},
                "trace": {"modules": {"jit__granite_step": [0.025, 0.025]}}})
    got = MAN.reader("granite_decode_hbm_roofline_pct")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.025)
    assert 70 < got < 78
    # it counts the experts HIT: with half of them hit it reads lower
    half = _ctx([{**row, "t0_ns": 1.1e9, "experts_hit": 180}])
    half.update({"peaks": ctx["peaks"], "trace": ctx["trace"]})
    assert MAN.reader("granite_decode_hbm_roofline_pct")(half) < got - 15
    ctx["summary"] = {"gaps_ms": [25.0] * 98 + [300.0, 400.0]}
    assert MAN.reader("itl_p99_ms.granite")(ctx) > 25.0


def test_the_cell_lists_what_its_readers_find():
    names = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert names == {
        "granite_decode_hbm_roofline_pct",
        "ssm_state_bytes_share_pct.granite",
        "moe_experts_hit_per_layer.granite", "moe_expert_tokens_max.granite",
        "admit_ms_per_prompt_token.granite", "itl_p99_ms.granite",
        "fill_ms_per_prompt_token", "batch_occupancy",
        "decode_step_device_ms", "device_idle_pct.decode", "setup_weights_s",
        "setup_programs_s"}
    e2e = {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")}
    assert e2e == {"out_tokens_per_s", "setup_s"}
    for m in MAN.metrics_for(CELL, "per_layer"):
        assert m["moves"] in e2e and os.path.isfile(MAN.reader_path(m["name"]))
    for m in MAN.doc["per_layer"]:      # each new reader lists this cell alone
        if m["name"].endswith(".granite") or m["name"].startswith("granite_"):
            assert m["workloads"] == [CELL]
    cell = MAN.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "rag-decode-64"
    assert len(cell["why"]) <= 200
    for said in ("9 recurrences", "2.45 GB of state", "36 held experts",
                 "8.9 tokens", "deployment 17.8", "10 of 40 layers",
                 "carried state"):
        assert said in cell["why"], said
    # nine cells, one of them on four chips
    assert len(MAN.doc["workloads"]) == 9
    assert [w["name"] for w in MAN.doc["workloads"] if w["chips"] == 4] == \
        ["train-fsdp2-tp2"]
    mix = MAN.traffic(cell["traffic"])
    assert (mix["clients"], mix["cycle"], mix["loop"], mix["lead_in_s"]) == (
        64, 128, "closed", 0.0)
    assert CONFIG["engine"]["max_slots"] == mix["clients"]
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.7, "min": 512, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.5, "min": 512, "max": 2048}
    assert "start_why" in mix and 0 <= mix["start"] < mix["cycle"]
    cycle = tg.closed_loop_requests(mix)
    # the ranges' ends do not meet in one request: the cycle's longest fits
    assert max(r.prompt_len + r.output_len for r in cycle) + 1 == 9352 <= \
        CONFIG["engine"]["max_len"]
    # one to four chunks an admission, 1.72 on the mean
    chunks = [-(-r.prompt_len // CONFIG["prefill_chunk"]) for r in cycle]
    assert set(chunks) == {1, 2, 3, 4} and sum(chunks) == 220
    # the 64 longest prompts with the 64 longest outputs at their ends fit
    # the pool: no order of this cycle preempts
    page = CONFIG["engine"]["page_size"]
    worst = sum(-(-(p + o + 1) // page) for p, o in zip(
        sorted((r.prompt_len for r in cycle))[-64:],
        sorted((r.output_len for r in cycle))[-64:]))
    assert worst == 21399 < CONFIG["engine"]["num_pages"] - 1


# ---------------------------------------------------------------- rehearsal
def test_rehearsal_run_of_the_cell_end_to_end(capfd, tmp_path):
    # a root of its own: the runner keeps the replica's trace under
    # <root>/chiprun_out/perfbench_trace and clears that directory when a
    # trace starts, so two traced rehearsals of one root that overlap (xdist
    # workers) take each other's trace away (the driver's run of PR 56 lost
    # this one so)
    for name in ("BENCHMARK.json", "perfbench"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    man = Manifest(str(tmp_path))
    args = argparse.Namespace(seed=3_400_000_011, seconds=3.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(man, man.cell(CELL), args, time.time())
    out, err = capfd.readouterr()
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert m["batch_occupancy"]["value"] > 63
    assert 0 < m["moe_experts_hit_per_layer.granite"]["value"] <= 4
    # 64 slots x 2 picks over 8 experts: 16 a mean expert, the straggler more
    assert 16 <= m["moe_expert_tokens_max.granite"]["value"] <= 64
    assert 0 < m["ssm_state_bytes_share_pct.granite"]["value"] < 100
    assert m["fill_ms_per_prompt_token"]["value"] > 0
    assert m["itl_p99_ms.granite"]["value"] > 0
    assert "compared: routing_far_disagreements 0.00000 (limit 0.00000)" \
        in err
    assert "compared: rerun_token_mismatches 0.00000 (limit 0.00000)" in err
    assert "compared: probe_tokens_missing 0.00000 (limit 0.00000)" in err
    assert "probe_max_margin" in out and "probe_prefill_rms_err" in out
    assert "probe_len 2248" in out
    assert "warm-up of prompts [8192]" in out
    assert "compilations inside the window 0" in out
