"""The gated-short-convolution / attention family's benchmark files: its
configuration against the catalog row, its plain reference through the
harness's own path, its reference check (sound, the int8 control, a conv tail
or K/V lost at the chunk boundary, an altered token), its byte counts and
readers, and a rehearsal run of ``serve-lfm2-moe-decode`` end to end. Toy
widths, CPU."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import lfm2_bytes, program, traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.reference import (REF_NEW, REF_PROMPT, lfm2_moe as ref,
                                 lfm2_moe_check as chk,
                                 lfm2_moe_control as ctl)
from perfbench.runners import serve as serve_runner

MAN = Manifest(ROOT)
NAME = "lfm2-24b-a2b-serve1"
CELL = "serve-lfm2-moe-decode"
CONFIG = MAN.config(NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "conv_L_cache")


# ------------------------------------------------------------ configuration
def test_reduced_is_the_depth_and_nothing_else():
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 40, "parameters": 23_843_661_440,
        "active_parameters": 2_326_881_920}
    # every expert and every vocabulary row; two whole periods after the two
    # leading dense layers (the floor is one period and four layers)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"], CONFIG["num_dense_layers"]) == (
        10, 64, 65536, 2)
    assert len(CONFIG["layer_types"]) == 40     # the published list, whole
    assert CONFIG["layer_types"][:10] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    assert "four pipeline stages of ten layers" in CONFIG["deployment"]
    assert "4 tokens an expert" in CONFIG["deployment"]
    for key in ("head_dim", "tied head", "norm", "positions", "short conv",
                "router", "selection bias", "weights", "conv state",
                "kv_cache_dtype", "eos", "page_size", "max_slots",
                "num_pages", "max_len", "prefill", "memory"):
        assert key in CONFIG["assumed"], key
    assert "1e-6" in CONFIG["assumed"]["router"]
    assert "B, C, X" in CONFIG["assumed"]["short conv"]
    assert CONFIG["engine"] == {
        "kv_cache": "paged", "max_slots": 64, "page_size": 16,
        "num_pages": 24576, "max_len": 6144, "enable_prefix_cache": False,
        "kv_dtype": "model"}
    assert CONFIG["engine"]["max_len"] % CONFIG["prefill_chunk"] == 0
    assert CONFIG["programs"]["decode"] == "jit__lfm2_step"
    assert CONFIG["probe"] == {"prompt_len": 2048 + 200, "new_tokens": 8}


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_published_key_is_at_its_published_value():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CONFIG["source"])
    assert row["name"] == "LFM2-24B-A2B"
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    assert CONFIG["rope_theta"] == row["config"]["rope_parameters"][
        "rope_theta"]
    assert CONFIG["head_dim"] * row["num_attention_heads"] == \
        row["hidden_size"]


def test_the_config_class_is_built_from_the_file():
    import dataclasses

    cfg = program.model_config(CONFIG, program.shape_of(CONFIG, False))
    assert (cfg.n_layers, cfg.n_attn_layers, cfg.n_conv_layers,
            cfg.n_moe_layers, cfg.n_dense_layers, cfg.experts_held,
            cfg.n_experts, cfg.top_k, cfg.expert_offset) == (
        10, 2, 8, 8, 2, 64, 64, 4, 0)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.expert_d_ff, cfg.conv_kernel, cfg.rope_theta, cfg.norm_eps,
            cfg.vocab_size) == (2048, 32, 8, 64, 11776, 1536, 3, 1e6, 1e-5,
                                65536)
    assert cfg.dtype == jnp.bfloat16 and cfg.prefill_chunk == 2048
    assert cfg.norm_topk is True and cfg.routed_scale == 1
    assert cfg.param_count() == 5_267_090_176
    whole = dataclasses.replace(cfg, n_layers=40)
    assert whole.param_count() == CONFIG["published"]["parameters"]
    assert whole.param_count(active=True) == \
        CONFIG["published"]["active_parameters"]
    toy = program.model_config(CONFIG, program.shape_of(CONFIG, True))
    assert (toy.n_layers, toy.kinds, toy.experts_held, toy.n_experts) == (
        4, ("conv", "conv", "full_attention", "conv"), 8, 8)


def test_byte_counts_agree_with_the_program_tree():
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape)
    from ray_tpu.models.lfm2_moe import init_params
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert lfm2_bytes.weight_bytes(shape) == held
    real = program.shape_of(CONFIG, False)
    assert lfm2_bytes.weight_bytes(real) == pytest.approx(10.536e9,
                                                          rel=0.0005)
    assert lfm2_bytes.expert_bytes(real) == 18_874_368         # 18.87 MB
    assert lfm2_bytes.kv_row_bytes(real) == 2048
    assert lfm2_bytes.n_expert_layers(real) == 8
    assert lfm2_bytes.conv_tail_bytes(real, 64) == 8 * 64 * 2 * 4096
    bare = lfm2_bytes.decode_min_bytes(real, 0, 0, 0)
    assert bare == lfm2_bytes.outside_experts_bytes(real) == pytest.approx(
        0.873e9, rel=0.002)
    # the tied table is counted once: as the head
    assert bare - 2 * 65536 * 2048 < 0.61e9
    every = lfm2_bytes.decode_min_bytes(real, 8 * 64, 0, 0)
    assert every == lfm2_bytes.weight_bytes(real)
    need = lfm2_bytes.decode_min_bytes(real, 500, 120_000, 64)
    assert need - bare == pytest.approx(
        500 * 18_874_368 + 4096 * (120_000 + 64) + 2 * 8 * 64 * 2 * 4096,
        rel=1e-9)
    assert 10.7e9 < need < 10.9e9
    e = CONFIG["engine"]
    assert e["num_pages"] * e["page_size"] * 2 * 2048 == \
        pytest.approx(1.611e9, rel=0.001)
    assert e["num_pages"] * e["page_size"] == e["max_slots"] * e["max_len"]


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
    from ray_tpu.models.lfm2_moe import forward

    shape, cfg, params = toy
    toks = tg.prompt_tokens(5, 1, 600, shape["vocab_size"])
    got = np.asarray(forward(params, jnp.asarray(toks, jnp.int32), cfg))
    out = ref.forward(ref.from_program_tree(params), toks, shape)
    want = np.asarray(out["logits"])
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert want.std() > 0.3
    own = np.asarray(out["own_routing"])
    assert own.shape == (2, 600, 2)     # the two expert layers of the toy
    assert float(np.asarray(out["under"]).max()) == 0.0    # nothing imposed
    np.testing.assert_array_equal(
        ref.logits(ref.from_program_tree(params), toks[:50], shape),
        ref.forward(ref.from_program_tree(params), toks[:50], shape,
                    rows=np.arange(50))["logits"])


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


@pytest.mark.parametrize("fault", ["zero_left_edge", "kv_lost_at_the_edge",
                                   "tail_of_the_padded_end"])
def test_what_is_lost_at_the_chunk_boundary_fails_the_probe(toy, monkeypatch,
                                                            fault):
    """The contract's 200-token request stays inside one chunk and passes;
    only the probe crosses position 2048: a second chunk whose attention no
    longer finds the first chunk's K/V moves its first-token row; one whose
    convolutions start from zeros instead of the first chunk's tail moves the
    routing of the positions at the edge (and the last row by little: two
    positions of 2248); a tail taken at the chunk's padded end instead of the
    prompt's moves every token decoded after it."""
    from ray_tpu.models import lfm2_moe as lm
    from ray_tpu.ops import ssm

    conv, tail, attn = ssm.causal_conv, ssm.conv_tail, lm._prompt_attention

    def zero_left(x, w, b=None, left=None):
        return conv(x, w, b, None if left is None else jnp.zeros_like(left))

    def lost(q, buf_k, buf_v, start, window, cfg):
        keep = (jnp.arange(buf_k.shape[0]) >= start)[:, None, None]
        return attn(q, jnp.where(keep, buf_k, 0), jnp.where(keep, buf_v, 0),
                    start, window, cfg)

    def padded_end(x, n_valid, K, left=None):
        return tail(x, x.shape[0], K, left)

    patched = {"zero_left_edge": (ssm, "causal_conv", zero_left),
               "kv_lost_at_the_edge": (lm, "_prompt_attention", lost),
               "tail_of_the_padded_end": (ssm, "conv_tail", padded_end)}
    monkeypatch.setattr(*patched[fault])
    lm._lfm2_prefill_chunk.clear_cache()
    try:
        eng, prompt = _engine(toy), _prompt(toy[0], seed=11)
        r = chk.check(eng, prompt, ctl._generate(eng, prompt), CONFIG,
                      toy[0])
    finally:
        monkeypatch.undo()
        lm._lfm2_prefill_chunk.clear_cache()
    by = _by_name(r)
    assert not r["ok"]
    if fault == "tail_of_the_padded_end":   # both prefill rows are sound
        assert by["probe_max_margin"]["value"] > \
            by["probe_max_margin"]["limit"] \
            or by["max_margin"]["value"] > by["max_margin"]["limit"] \
            or by["routing_far_disagreements"]["value"] > 0
        return
    for name in ("prefill_max_abs_err", "prefill_rms_err", "max_margin"):
        assert by[name]["value"] <= by[name]["limit"], name
    if fault == "zero_left_edge":
        # two positions' convolutions of 2248 are wrong: the last row, 200
        # positions on, hardly moves, but the routing of the positions at the
        # edge is held to the reference's too
        assert by["routing_far_disagreements"]["value"] > 0
    else:
        assert by["probe_prefill_rms_err"]["value"] > \
            by["probe_prefill_rms_err"]["limit"]


def test_int8_weights_read_well_over_the_sound_engine():
    """At toy widths the limits (set on the chip at the cell's size) need not
    separate the two; the control's readings must still stand clear of the
    sound ones, as they do there."""
    sound, w8 = [], []
    for seed in (41,):
        r = ctl.one_seed(CONFIG, seed, True)
        for name in ("prefill_rms_err", "probe_prefill_rms_err"):
            sound.append(_by_name(r["sound"])[name]["value"])
            w8.append(_by_name(r["w8"])[name]["value"])
    assert np.mean(w8) > 1.4 * np.mean(sound)
    assert "w_in" in ctl.QUANT_KEYS and "conv_w" not in ctl.QUANT_KEYS


# ------------------------------------------------------------------ readers
def _ctx(steps, admits=()):
    return {"shape": program.shape_of(CONFIG, False),
            "run": {"t_open": 1.0, "t_close": 2.0},
            "spans": {}, "config": CONFIG,
            "_program_spans": {"serve.engine.step": steps,
                               "serve.engine.admit": list(admits)}}


def test_counter_and_span_readers():
    row = {"dur_ns": 1, "active": 64, "experts_hit": 504,
           "expert_tokens_max": 11, "moe_rows": 64,
           "context_positions": 120_000, "landed": 1}
    steps = [{**row, "t0_ns": 1.1e9},
             # a call that landed two steps sums them
             {**row, "t0_ns": 1.2e9, "experts_hit": 1000, "moe_rows": 128,
              "expert_tokens_max": 25, "context_positions": 240_128,
              "landed": 2},
             {"t0_ns": 1.3e9, "dur_ns": 1, "active": 64},   # nothing landed
             {**row, "t0_ns": 2.5e9, "experts_hit": 1}]     # past the window
    ctx = _ctx(steps,
               [{"t0_ns": 1.4e9, "dur_ns": 0.05e9, "sid": 7,
                 "prompt_len": 1500},
                {"t0_ns": 1.6e9, "dur_ns": 0.1e9, "sid": 8,
                 "prompt_len": 3500},
                {"t0_ns": 0.4e9, "dur_ns": 5e9, "sid": 3, "prompt_len": 999}])
    assert MAN.reader("moe_experts_hit_per_layer.lfm2")(ctx) == \
        pytest.approx(1504 / 3 / 8)
    assert MAN.reader("moe_expert_tokens_max.lfm2")(ctx) == \
        pytest.approx(36 / 3)
    assert MAN.reader("admit_ms_per_prompt_token.lfm2")(ctx) == \
        pytest.approx(150 / 5000)
    # a program without the counters or the spans (the parent): nothing
    bare = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 16}])
    for name in ("moe_experts_hit_per_layer.lfm2",
                 "moe_expert_tokens_max.lfm2",
                 "lfm2_decode_hbm_roofline_pct",
                 "admit_ms_per_prompt_token.lfm2"):
        assert MAN.reader(name)(bare) is None
    ctx.update({"peaks": {"hbm_bytes_per_s": 819e9,
                          "bf16_flops_per_s": 197e12},
                "trace": {"modules": {"jit__lfm2_step": [0.017, 0.017]}}})
    need = lfm2_bytes.decode_min_bytes(ctx["shape"], 1504 / 3, 360_128 / 3,
                                       64)
    got = MAN.reader("lfm2_decode_hbm_roofline_pct")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.017)
    assert 70 < got < 85
    ctx["summary"] = {"gaps_ms": [17.0] * 99 + [90.0]}
    assert MAN.reader("itl_p99_ms.lfm2")(ctx) > 17.0


def test_the_cell_lists_what_its_readers_find():
    names = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert names == {
        "lfm2_decode_hbm_roofline_pct", "moe_experts_hit_per_layer.lfm2",
        "moe_expert_tokens_max.lfm2", "admit_ms_per_prompt_token.lfm2",
        "itl_p99_ms.lfm2", "fill_ms_per_prompt_token", "batch_occupancy",
        "decode_step_device_ms", "device_idle_pct.decode", "setup_weights_s",
        "setup_programs_s"}
    e2e = {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")}
    assert e2e == {"out_tokens_per_s", "setup_s"}
    for m in MAN.metrics_for(CELL, "per_layer"):
        assert m["moves"] in e2e and os.path.isfile(MAN.reader_path(m["name"]))
    for m in MAN.doc["per_layer"]:      # each new reader lists this cell alone
        if m["name"].endswith(".lfm2") or m["name"].startswith("lfm2_"):
            assert m["workloads"] == [CELL]
    cell = MAN.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "moe-decode-64"
    assert len(cell["why"]) <= 200
    for said in ("all 64 experts", "4 tokens an expert", "10 of 40 layers"):
        assert said in cell["why"]
    mix = MAN.traffic(cell["traffic"])
    assert (mix["clients"], mix["cycle"], mix["loop"]) == (64, 128, "closed")
    assert CONFIG["engine"]["max_slots"] == mix["clients"]
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.7, "min": 256, "max": 4096}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.5, "min": 512, "max": 2048}
    assert "start_why" in mix and 0 <= mix["start"] < mix["cycle"]
    cycle = tg.closed_loop_requests(mix)
    # the ranges' ends do not meet in one request: the cycle's longest fits
    assert max(r.prompt_len + r.output_len for r in cycle) + 1 <= \
        CONFIG["engine"]["max_len"]
    # sixty-four of the cycle's longest requests at their ends fit the pool:
    # no order of this cycle preempts
    ends = sorted(-(-(r.prompt_len + r.output_len + 1)
                    // CONFIG["engine"]["page_size"]) for r in cycle)
    assert sum(ends[-64:]) < CONFIG["engine"]["num_pages"] - 1


# ---------------------------------------------------------------- rehearsal
def test_rehearsal_run_of_the_cell_end_to_end(capfd):
    cell = MAN.cell(CELL)
    args = argparse.Namespace(seed=3_400_000_011, seconds=3.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(MAN, cell, args, time.time())
    out, err = capfd.readouterr()
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert m["batch_occupancy"]["value"] > 63
    assert 0 < m["moe_experts_hit_per_layer.lfm2"]["value"] <= 8
    # 64 slots x 2 picks over 8 experts: 16 a mean expert, the straggler more
    assert 16 <= m["moe_expert_tokens_max.lfm2"]["value"] <= 64
    assert m["fill_ms_per_prompt_token"]["value"] > 0
    assert m["itl_p99_ms.lfm2"]["value"] > 0
    assert "compared: routing_far_disagreements 0.00000 (limit 0.00000)" \
        in err
    assert "compared: rerun_token_mismatches 0.00000 (limit 0.00000)" in err
    assert "compared: probe_tokens_missing 0.00000 (limit 0.00000)" in err
    assert "probe_max_margin" in out and "probe_prefill_rms_err" in out
    assert "probe_len 2248" in out
    assert "warm-up of prompts [4096]" in out
    assert "compilations inside the window 0" in out
