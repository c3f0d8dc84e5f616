"""The DeepSeek-V3 family's benchmark files: its configuration against the
catalog row, its plain reference through the harness's own path, its
reference check (sound, the int8 control, a step's row, a draft's row or a
position handed on wrongly, a token outside the slice), its byte counts and
readers, and a rehearsal run of ``serve-gigachat-reasoning-mtp`` end to end.
Toy widths, CPU."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import gigachat_bytes as gb, program, traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.reference import (REF_NEW, REF_PROMPT, deepseek_v3 as ref,
                                 deepseek_v3_check as chk,
                                 deepseek_v3_control as ctl)
from perfbench.runners import serve as serve_runner

MAN = Manifest(ROOT)
NAME = "gigachat3.1-702b-a36b-serve1"
CELL = "serve-gigachat-reasoning-mtp"
CONFIG = MAN.config(NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
          "q_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
          "v_head_dim", "num_experts_per_tok")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]
NEW = ("gigachat_decode_hbm_roofline_pct", "latent_attn_mxu_pct.gigachat",
       "mtp_accept_rate_pct.gigachat", "moe_experts_hit_per_layer.gigachat",
       "moe_expert_tokens_max.gigachat", "admit_ms_per_prompt_token.gigachat",
       "itl_p99_ms.gigachat")
#: the run-ahead's readers, under this cell's names (the accepted ones' lists
#: are held to their two cells by ``test_flight_spans.py``)
FLIGHT = ("step_host_slack_ms.gigachat", "step_flights_ahead.gigachat")


# ------------------------------------------------------------ configuration
def test_reduced_is_depth_dense_layers_experts_held_and_vocabulary():
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 64, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 128256,
        "parameters": 702e9, "active_parameters": 36e9}
    # the leading dense layers once, four of the layers behind them, 16 of
    # the experts (the floor is 8), an eighth of the vocabulary (the floor)
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["router_width"],
            CONFIG["vocab_size"], CONFIG["expert_offset"],
            CONFIG["num_nextn_predict_layers"]) == (5, 1, 16, 256, 16032, 0,
                                                    1)
    assert CONFIG["vocab_size"] * 8 == 128256
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    for said in ("16 TPU v5e chips", "half of routing group 0",
                 "the vocabulary eight ways", "One of the three leading",
                 "four of the 61", "The MTP module", "4 rows an expert",
                 "deployment's 16 chips send 64", "16 times its share",
                 "a sixth of the depth", "~13 times"):
        assert said in CONFIG["deployment"], said
    for key in ("default temperature", "selection bias", "mtp input order",
                "mtp hidden", "mtp positions", "rotary", "head", "weights",
                "kv_cache_dtype", "eos", "page_size", "max_slots",
                "num_pages", "max_len", "prefill", "memory"):
        assert key in CONFIG["assumed"], key
    assert "pairs 0-8 keep" in CONFIG["assumed"]["rotary"]
    assert "0.14468" in CONFIG["assumed"]["rotary"]
    assert CONFIG["engine"] == {
        "kv_cache": "paged", "max_slots": 64, "page_size": 64,
        "num_pages": 6656, "max_len": 14336, "enable_prefix_cache": False,
        "kv_dtype": "model", "generation_defaults": {"temperature": 1.0}}
    assert CONFIG["engine"]["max_len"] == 7 * CONFIG["prefill_chunk"]
    assert CONFIG["programs"] == {
        "decode": "jit__deepseek_step",
        "prefill": "jit__deepseek_prefill_chunk",
        "scatter": "jit__scatter_latent"}
    # the probe steps with every other slot live: the run-ahead's cap is 10
    assert CONFIG["probe"] == {"prompt_len": 4500, "new_tokens": 16,
                               "beside": CONFIG["engine"]["max_slots"] - 1}


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_published_key_is_at_its_published_value():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CONFIG["source"])
    assert row["name"] == "GigaChat3.1-702B-A36B"
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    sc = CONFIG["rope_scaling"]     # whole, and repeated flat for the class
    assert CONFIG["yarn"] == [sc["factor"],
                              sc["original_max_position_embeddings"],
                              sc["beta_fast"], sc["beta_slow"], sc["mscale"],
                              sc["mscale_all_dim"]]
    assert CONFIG["published_depth"] == row["layers"]
    assert CONFIG["router_width"] == row["config"]["n_routed_experts"]


def test_the_config_class_is_built_from_the_file():
    import dataclasses

    cfg = program.model_config(CONFIG, program.shape_of(CONFIG, False))
    assert (cfg.n_layers, cfg.n_dense, cfg.n_nextn, cfg.n_sublayers,
            cfg.experts_held, cfg.n_experts, cfg.n_group, cfg.topk_group,
            cfg.top_k, cfg.n_shared, cfg.expert_offset) == (
        5, 1, 1, 6, 16, 256, 8, 4, 8, 1, 0)
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.d_ff, cfg.expert_d_ff, cfg.norm_eps, cfg.vocab_size,
            cfg.rope_theta, cfg.routed_scale) == (
        7168, 64, 1536, 512, 128, 64, 192, 18432, 2048, 1e-6, 16032, 100000,
        2.5)
    assert cfg.yarn == (64, 4096, 32, 1, 1, 1)
    assert cfg.attn_scale == pytest.approx(0.14468, abs=1e-5)
    assert cfg.latent_width == 576 and cfg.q_scale == cfg.kv_scale == 1.0
    assert cfg.dtype == jnp.bfloat16 and cfg.prefill_chunk == 2048
    assert cfg.param_count() == pytest.approx(5.277e9, rel=2e-4)
    whole = dataclasses.replace(cfg, n_layers=64, n_dense=3, experts_held=256,
                                vocab_size=128256, n_nextn=0)
    assert whole.param_count() == pytest.approx(
        CONFIG["published"]["parameters"], rel=1e-3)
    toy = program.model_config(CONFIG, program.shape_of(CONFIG, True))
    assert (toy.n_layers, toy.n_dense, toy.n_nextn, toy.experts_held,
            toy.n_experts, toy.n_group, toy.topk_group, toy.top_k) == (
        2, 1, 1, 4, 16, 4, 2, 3)
    assert toy.v_head_dim > toy.qk_nope_head_dim       # as published


def test_byte_counts_agree_with_the_program_tree():
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape)
    from ray_tpu.models.deepseek_v3 import init_params
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert gb.weight_bytes(shape) == held
    real = program.shape_of(CONFIG, False)
    assert gb.weight_bytes(real) == pytest.approx(10.573e9, rel=0.0005)
    assert gb.expert_bytes(real) == 88_080_384          # 88.08 MB
    assert gb.latent_row_bytes(real) == 1152
    assert (gb.sublayers(real), gb.expert_layers(real)) == (6, 5)
    bare = gb.decode_min_bytes(real, 0, 0, 0)
    # everything a step reads whatever it routes: attention six times, the
    # dense MLP, five shared experts and routers, eh_proj, the head twice
    assert bare == pytest.approx(3.526e9, rel=0.002)
    every = gb.decode_min_bytes(real, 0, 0, 5 * 16)
    # ... and all 80 held experts: the weights but the embedding table, and
    # the head a second time
    assert every == gb.weight_bytes(real, embedding=False) \
        + 2 * 16032 * 7168
    # the experts a step HIT and the rows that are LIVE, not what is held
    need = gb.decode_min_bytes(real, 300_000, 64, 78.5)
    assert need - bare == pytest.approx(
        78.5 * 88_080_384 + 6 * 300_000 * 1152
        + 64 * 2 * (6 * 1152 + 4 * 7168), rel=1e-9)
    assert 12.4e9 < need < 12.6e9
    assert gb.latent_attn_flops(real, 300_000) == pytest.approx(
        2 * 2 * 64 * 1088 * 300_000 * 6)
    e = CONFIG["engine"]
    assert e["num_pages"] * e["page_size"] * 6 * 1152 == \
        pytest.approx(2.944e9, rel=0.001)


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
    from ray_tpu.models.deepseek_v3 import forward

    shape, cfg, params = toy
    toks = tg.prompt_tokens(5, 1, 600, shape["vocab_size"])
    got, mtp = forward(params, jnp.asarray(toks, jnp.int32), cfg)
    out = ref.forward(ref.from_program_tree(params), toks, shape)
    want = np.asarray(out["logits"])
    assert want.std() > 1e-3
    np.testing.assert_allclose(got, want, atol=3e-4 * want.std(), rtol=0)
    np.testing.assert_allclose(np.asarray(mtp)[:-1], out["mtp_logits"],
                               atol=3e-4 * want.std(), rtol=0)
    assert [np.asarray(o).shape for o in out["own_routing"]] == [
        (600, 3), (599, 3)]             # the one expert layer, the MTP's
    assert max(float(np.asarray(u).max()) for u in out["under"]) == 0.0
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
          if k not in ("kv_cache", "generation_defaults")}
    return PagedEngine(params, cfg, **{**kw, "num_pages": 512,
                                       "max_slots": 2})


def _prompt(shape, seed=9):
    return tg.prompt_tokens(seed, 10**6 + 99, REF_PROMPT, shape["vocab_size"])


def _by_name(result):
    return {r["name"]: r for r in result["readings"]}


@pytest.fixture(scope="module")
def sound(toy):
    eng, prompt = _engine(toy), _prompt(toy[0])
    emitted = ctl._generate(eng, prompt, {"temperature": 1.0})
    return eng, prompt, emitted, chk.program_out(eng, prompt, emitted, CONFIG,
                                                 toy[0])


def test_sound_engine_passes_every_reading(toy, sound):
    shape = toy[0]
    eng, prompt, emitted, got = sound
    r = chk.compare(got, prompt, emitted, eng.params, CONFIG, shape)
    assert r["ok"] and r["finite"] and len(emitted) == REF_NEW
    by = _by_name(r)
    names = ("prefill_max_abs_err", "prefill_rms_err", "step_max_abs_err",
             "step_rms_err", "mtp_max_abs_err", "mtp_rms_err")
    assert set(by) == {
        "routing_far_disagreements", "bad_or_missing_tokens",
        "runahead_token_mismatches", *names,
        *("probe_" + n for n in names)}
    for name in ("routing_far_disagreements", "bad_or_missing_tokens",
                 "runahead_token_mismatches"):
        assert by[name]["limit"] == 0.0 and by[name]["value"] == 0.0
    for name in names:      # float32: nothing but rounding
        assert by[name]["value"] < 1e-4 and by["probe_" + name]["value"] < 1e-4
    for rehearse, want in ((True, (2304, 16)), (False, (4500, 16))):
        assert chk.probe_sizes(
            CONFIG, program.shape_of(CONFIG, rehearse)) == want
    n = r["notes"]
    assert n["probe_len"] == 2304       # it crosses a chunk boundary
    # drafts accepted AND refused among the steps, and both kinds compared
    assert 0 < n["drafts_accepted"] < n["steps"]
    assert n["step_rows_compared"] > n["steps"] - 2
    assert n["mtp_rows_compared"] >= n["steps"] - 2
    # every position of both requests' prefills and steps, the one expert
    # layer and the MTP block's (whose last row has no follower)
    assert n["routing_decisions"] >= 2 * (REF_PROMPT + REF_NEW - 2 + 2304
                                          + 16 - 2) - 2
    assert n["routing_disagreements"] <= 4         # float32
    assert chk.CHECK_TEMPERATURE == 1.0


def test_the_runs_own_tokens_are_held_to_the_slice(toy, sound):
    eng, prompt, emitted, got = sound
    wrong = list(emitted)
    wrong[7] = toy[0]["vocab_size"]         # outside the vocabulary slice
    bad = chk.compare(got, prompt, wrong, eng.params, CONFIG, toy[0])
    assert not bad["ok"]
    assert _by_name(bad)["bad_or_missing_tokens"]["value"] == 1
    short = chk.compare(got, prompt, emitted[:-2], eng.params, CONFIG, toy[0])
    assert _by_name(short)["bad_or_missing_tokens"]["value"] == 2


@pytest.mark.parametrize("fault, reading", [
    ("step_row", "probe_step_rms_err"), ("draft_row", "probe_mtp_rms_err"),
    ("admission_row", "prefill_rms_err"),
    ("handed_on", "runahead_token_mismatches"),
    ("committed_token", "probe_step_rms_err"),
    ("far_expert", "routing_far_disagreements")])
def test_what_a_fault_moves_fails_its_reading(toy, sound, fault, reading):
    """A step's logits from another position, a draft's logits from another
    pair, an admission's row, a stream that ran ahead on other positions, a
    committed token the engine did not compute with, or experts of groups the
    router did not keep: each fails the reading that reads it."""
    import copy

    eng, prompt, emitted, got = sound
    got = copy.deepcopy(got)
    probed = got["probed"]
    if fault == "step_row":
        logits, acc, q, routing = probed["steps"][3]
        probed["steps"][3] = (np.roll(logits, 1, axis=-1), acc, q, routing)
    elif fault == "draft_row":
        logits, acc, q, routing = probed["steps"][3]
        probed["steps"][3] = (logits, acc, probed["steps"][2][2], routing)
    elif fault == "admission_row":
        got["request"]["row"] = got["request"]["row"][::-1].copy()
    elif fault == "handed_on":
        probed["rerun"] = probed["rerun"][:5] + probed["rerun"][4:-1]
    elif fault == "committed_token":
        probed["tokens"][2] = (probed["tokens"][2] + 1) % toy[0]["vocab_size"]
    else:       # the other half of the router's width: two other groups
        probed["routing"] = np.array(probed["routing"])
        probed["routing"][0, 100:110] = (probed["routing"][0, 100:110] + 8) % 16
    r = chk.compare(got, prompt, emitted, eng.params, CONFIG, toy[0])
    by = _by_name(r)
    assert not r["ok"] and by[reading]["value"] > by[reading]["limit"]
    assert by[reading]["value"] > (0.3 if reading.endswith("_err") else 0.5)


def test_int8_weights_read_well_over_the_sound_engine():
    """At toy widths the limits (set on the chip at the cell's size) need not
    separate the two; the control's readings must still stand clear of the
    sound ones, on the admission's row, the steps' rows and the MTP block's
    rows, as they do there."""
    r = ctl.one_seed(CONFIG, 41, True)
    assert set(r) == {"sound", "w8"}
    for kind in ("prefill", "step", "mtp"):
        sound, w8 = [], []
        for name in (kind + "_rms_err", "probe_" + kind + "_rms_err"):
            sound.append(_by_name(r["sound"])[name]["value"])
            w8.append(_by_name(r["w8"])[name]["value"])
        assert np.mean(w8) > 1.4 * np.mean(sound), kind
    assert "eh_proj" in ctl.QUANT_KEYS and "w_router" not in ctl.QUANT_KEYS


# ------------------------------------------------------------------ readers
def _ctx(steps, admits=()):
    return {"shape": program.shape_of(CONFIG, False),
            "run": {"t_open": 1.0, "t_close": 2.0},
            "spans": {}, "config": CONFIG,
            "_program_spans": {"serve.engine.step": steps,
                               "serve.engine.admit": list(admits)}}


def test_counter_and_span_readers():
    row = {"dur_ns": 1, "active": 64, "experts_hit": 78,
           "expert_tokens_max": 9, "moe_rows": 64,
           "latent_positions": 300_000, "latent_positions_read": 917_504,
           "drafted": 64, "accepted": 30, "landed": 1}
    steps = [{**row, "t0_ns": 1.1e9},
             # a call that landed two steps sums them
             {**row, "t0_ns": 1.2e9, "experts_hit": 158, "moe_rows": 128,
              "expert_tokens_max": 21, "latent_positions": 600_300,
              "drafted": 128, "accepted": 66, "landed": 2},
             {"t0_ns": 1.3e9, "dur_ns": 1, "active": 64},   # nothing landed
             {**row, "t0_ns": 2.5e9, "accepted": 0}]        # past the window
    ctx = _ctx(steps,
               [{"t0_ns": 1.4e9, "dur_ns": 0.1e9, "sid": 7,
                 "prompt_len": 1500},
                {"t0_ns": 1.6e9, "dur_ns": 0.25e9, "sid": 8,
                 "prompt_len": 3500},
                {"t0_ns": 0.4e9, "dur_ns": 5e9, "sid": 3, "prompt_len": 999}])
    assert MAN.reader("mtp_accept_rate_pct.gigachat")(ctx) == \
        pytest.approx(100 * 96 / 192)
    assert MAN.reader("moe_experts_hit_per_layer.gigachat")(ctx) == \
        pytest.approx(236 / 3 / 5)
    assert MAN.reader("moe_expert_tokens_max.gigachat")(ctx) == \
        pytest.approx(30 / 3)
    assert MAN.reader("admit_ms_per_prompt_token.gigachat")(ctx) == \
        pytest.approx(350 / 5000)
    # a program without the counters or the spans (the parent): nothing
    bare = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 16,
                  "latent_positions": 5, "landed": 1}])
    for name in NEW[:-1] + FLIGHT:
        assert MAN.reader(name)(bare) is None, name
    # the run-ahead's two, over flights that landed up to two tokens a slot
    flight = {"dur_ns": 60e6, "depth": 10, "active": 64, "admitted": 0,
              "tokens": 95, "wait_ns": 40e6, "call": 1, "landed_by": 2}
    bare["_program_spans"]["serve.step.flight"] = [
        {**flight, "step": i, "t0_ns": 1.0e9 + i * 50e6} for i in range(10)
    ] + [{**flight, "step": 10, "t0_ns": 1.5e9, "dur_ns": 400e6,
          "admitted": 1, "depth": 0, "wait_ns": 350e6}]
    assert [MAN.reader(name)(bare) for name in FLIGHT] == [
        pytest.approx(40.0), 10.0]
    ctx.update({"peaks": {"hbm_bytes_per_s": 819e9,
                          "bf16_flops_per_s": 197e12},
                "trace": {"modules": {"jit__deepseek_step": [0.05, 0.05]}}})
    need = gb.decode_min_bytes(ctx["shape"], 900_300 / 3, 64, 236 / 3)
    got = MAN.reader("gigachat_decode_hbm_roofline_pct")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.05)
    assert 28 < got < 34
    # it counts the experts HIT: with half of them hit it reads lower
    half = _ctx([{**row, "t0_ns": 1.1e9, "experts_hit": 40}])
    half.update({"peaks": ctx["peaks"], "trace": ctx["trace"]})
    assert MAN.reader("gigachat_decode_hbm_roofline_pct")(half) < got - 5
    mxu = MAN.reader("latent_attn_mxu_pct.gigachat")(ctx)
    assert mxu == pytest.approx(
        100 * gb.latent_attn_flops(ctx["shape"], 900_300 / 3) / 197e12 / 0.05)
    assert 4 < mxu < 6
    ctx["summary"] = {"gaps_ms": [25.0] * 98 + [300.0, 400.0]}
    assert MAN.reader("itl_p99_ms.gigachat")(ctx) > 25.0


def test_the_cell_lists_what_its_readers_find():
    """Containment only: what a later PR appends (a metric, a cell, another
    cell on four chips) leaves this standing."""
    listed = MAN.metrics_for(CELL, "per_layer")
    names = {m["name"] for m in listed}
    assert {*NEW, *FLIGHT, "fill_ms_per_prompt_token",
            "decode_step_device_ms", "device_idle_pct.decode"} <= names
    # ``batch_occupancy``'s reader counts the TOKENS a step call emitted, up
    # to two a slot here; ``step_host_ms`` is a call's wall less the device's
    # step, the host's part only where a call waits for the step it sent; a
    # traced window's admissions all fall inside the profiler's stop call
    # here (the first stream ends at its 34th second), which the stall's
    # reader takes off every interval: it read 0.0
    assert not {"batch_occupancy", "step_host_ms",
                "admit_stall_share_pct"} & names
    e2e = {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")}
    assert {"out_tokens_per_s", "setup_s"} <= e2e
    for m in listed:
        assert m["moves"] in e2e and os.path.isfile(MAN.reader_path(m["name"]))
    for m in MAN.doc["per_layer"]:
        if m["name"] in NEW + FLIGHT:
            assert CELL in m["workloads"]
    cell = MAN.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reasoning-mtp-64"
    assert cell["config"] == NAME and len(cell["why"]) <= 200
    for said in ("64 slots", "MTP drafts", "1-2 tokens a slot", "seeded ~48",
                 "trained 85-90", "4 rows an expert", "deployed 64",
                 "attention 16x", "MTP 1/6 depth", "host 13x"):
        assert said in cell["why"], said
    mix = MAN.traffic(cell["traffic"])
    assert (mix["clients"], mix["cycle"], mix["loop"], mix["lead_in_s"]) == (
        64, 128, "closed", 0.0)
    assert CONFIG["engine"]["max_slots"] == mix["clients"]
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.6, "min": 1024, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.5, "min": 1024, "max": 4096}
    assert "start_why" in mix and 0 <= mix["start"] < mix["cycle"]
    assert "temperature 1.0" in mix["sampling"]
    cycle = tg.closed_loop_requests(mix)
    # the cycle's longest request fits, a step's second row at the bound too
    assert max(r.prompt_len + r.output_len for r in cycle) + 2 <= \
        CONFIG["engine"]["max_len"]
    chunks = [-(-r.prompt_len // CONFIG["prefill_chunk"]) for r in cycle]
    assert set(chunks) == {1, 2, 3, 4}
    # the 64 longest prompts with the 64 longest outputs at their ends fit
    # the pool: no order of this cycle preempts
    page = CONFIG["engine"]["page_size"]
    worst = sum(-(-(p + o + 2) // page) for p, o in zip(
        sorted((r.prompt_len for r in cycle))[-64:],
        sorted((r.output_len for r in cycle))[-64:]))
    assert worst == 6564 < CONFIG["engine"]["num_pages"] - 1


# ---------------------------------------------------------------- rehearsal
def test_rehearsal_run_of_the_cell_end_to_end(capfd, tmp_path):
    # a root of its own: the runner keeps the replica's trace under
    # <root>/chiprun_out/perfbench_trace and clears that directory when a
    # trace starts, so two traced rehearsals of one root that overlap (xdist
    # workers) take each other's trace away
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
    assert "batch_occupancy" not in m       # tokens a call here, not slots
    # the run-ahead's readers find their rows where a flight lands two tokens
    assert 0 <= m["step_host_slack_ms.gigachat"]["value"] < 60_000
    assert 0 <= m["step_flights_ahead.gigachat"]["value"] <= 10
    assert 35 < m["mtp_accept_rate_pct.gigachat"]["value"] < 65
    assert 0 < m["moe_experts_hit_per_layer.gigachat"]["value"] <= 4
    assert 8 <= m["moe_expert_tokens_max.gigachat"]["value"] <= 128
    assert m["fill_ms_per_prompt_token"]["value"] > 0
    assert m["itl_p99_ms.gigachat"]["value"] > 0
    assert "compared: bad_or_missing_tokens 0.00000 (limit 0.00000)" in err
    assert "compared: runahead_token_mismatches 0.00000 (limit 0.00000)" \
        in err
    assert "compared: failed requests 0 (must be 0)" in err
    assert "compared: requests with a bad token 0 (must be 0)" in err
    assert "probe_step_rms_err" in out and "probe_mtp_rms_err" in out
    assert "probe_len 2304" in out and "drafts_accepted" in out
    assert "warm-up of prompts [8192]" in out
    assert "compilations inside the window 0" in out
