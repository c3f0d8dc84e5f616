"""The latent-attention / zero-expert family's benchmark files: its
configuration against the catalog row, its plain reference through the
harness's own path, its reference check (sound, the int8 control, a corrupted
latent write, an altered token), its byte counts and readers, and a rehearsal
run of ``serve-longcat-agent-decode`` end to end. Toy widths, CPU."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import longcat_bytes, program, traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.reference import (REF_NEW, REF_PROMPT, longcat_flash as ref,
                                 longcat_flash_check as chk,
                                 longcat_flash_control as ctl)
from perfbench.runners import serve as serve_runner

MAN = Manifest(ROOT)
NAME = "longcat-flash-omni-serve1"
CELL = "serve-longcat-agent-decode"
CONFIG = MAN.config(NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
          "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
          "qk_nope_head_dim", "num_attention_heads", "moe_topk",
          "zero_expert_num", "routed_scaling_factor")


# ------------------------------------------------------------ configuration
def test_reduced_is_the_depth_the_experts_held_and_the_vocabulary():
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {
        "num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072,
        "parameters": 5.607e11}
    assert (CONFIG["num_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (4, 16, 16384)
    # the floors: four layers, eight experts, an eighth of the vocabulary
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert CONFIG["router_width"] == 512 + CONFIG["zero_expert_num"] == 768
    assert CONFIG["expert_offset"] == 0 and CONFIG["published_depth"] == 28
    assert "32 TPU v5e chips share every layer" in CONFIG["deployment"]
    for key in ("encoders", "rotary", "latent scales", "selection bias",
                "head", "weights", "kv_cache_dtype", "eos", "page_size",
                "max_slots", "num_pages", "max_len", "prefill", "memory"):
        assert key in CONFIG["assumed"], key
    assert CONFIG["engine"]["max_len"] % CONFIG["prefill_chunk"] == 0
    assert CONFIG["programs"]["decode"] == "jit__longcat_step"


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_published_key_is_at_its_published_value():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CONFIG["source"])
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert not set(CONFIG["reduced"]) & set(WIDTHS)


def test_the_config_class_is_built_from_the_file():
    import dataclasses

    cfg = program.model_config(CONFIG, program.shape_of(CONFIG, False))
    assert (cfg.n_layers, cfg.n_sublayers, cfg.experts_held, cfg.n_real,
            cfg.router_width, cfg.top_k) == (4, 8, 16, 512, 768, 12)
    assert (cfg.latent_width, cfg.q_scale) == (576, 2.0)
    assert cfg.dtype == jnp.bfloat16 and cfg.prefill_chunk == 2048
    assert cfg.param_count() == pytest.approx(5.173e9, rel=0.0005)
    whole = dataclasses.replace(cfg, n_layers=28, experts_held=512,
                                vocab_size=131072)
    assert whole.param_count() == pytest.approx(560.7e9, rel=0.0005)
    toy = program.model_config(CONFIG, program.shape_of(CONFIG, True))
    assert (toy.n_layers, toy.experts_held, toy.n_real, toy.top_k) == \
        (1, 4, 24, 3)


def test_byte_and_operation_counts_agree_with_the_program_tree():
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape)
    from ray_tpu.models.longcat_flash import init_params
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert longcat_bytes.weight_bytes(shape) == held
    real = program.shape_of(CONFIG, False)
    assert longcat_bytes.weight_bytes(real) == pytest.approx(10.38e9,
                                                             rel=0.002)
    assert longcat_bytes.latent_row_bytes(real) == 1152
    bare = longcat_bytes.decode_min_bytes(real, 0, 0)
    assert bare == longcat_bytes.weight_bytes(real, embedding=False)
    need = longcat_bytes.decode_min_bytes(real, 170_000, 16)
    assert need - bare == pytest.approx(8 * 170_000 * 1152, rel=0.001)
    assert 11.6e9 < need < 11.9e9
    # 2 x 64 x (576 + 512) operations a position a sublayer
    assert longcat_bytes.latent_attn_flops(real, 1) == 8 * 2 * 64 * 1088
    e = CONFIG["engine"]
    assert e["num_pages"] * e["page_size"] * 8 * 1152 == \
        pytest.approx(2.416e9, rel=0.001)


# ---------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    """The rehearsal widths through the harness's own path, in float32: the
    routing then agrees with the reference to the last tie, so a sound
    engine reads ~0 everywhere and what a fault moves is the fault's alone."""
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape, dtype=jnp.float32)
    return shape, cfg, program.init_weights(CONFIG, cfg, 3_400_000_033)


def test_reference_parity_through_the_harness_path(toy):
    from ray_tpu.models.longcat_flash import forward

    shape, cfg, params = toy
    toks = tg.prompt_tokens(5, 1, 300, shape["vocab_size"])
    got = np.asarray(forward(params, jnp.asarray(toks, jnp.int32), cfg))
    out = ref.forward(ref.from_program_tree(params), toks, shape)
    want = np.asarray(out["logits"])
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert want.std() > 0.3
    own = np.asarray(out["own_routing"])
    assert own.shape == (1, 300, 3)
    assert 0.2 < (own >= 24).mean() < 0.5           # zero picks
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
    return PagedEngine(params, cfg, **{**kw, "num_pages": 64, "max_slots": 2,
                                       "max_len": 4096})


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
    n, m = chk.probe_sizes(CONFIG, shape)
    assert (n, m) == (2304, 8) and r["notes"]["probe_len"] == 2304
    assert n > CONFIG["prefill_chunk"]      # the probe crosses a chunk
    # every position of both requests' prefills and decode steps, one layer
    assert r["notes"]["routing_decisions"] == \
        REF_PROMPT + REF_NEW - 1 + n + m - 1
    assert r["notes"]["routing_disagreements"] <= 2         # float32
    assert chk.probe_sizes(CONFIG, program.shape_of(CONFIG, False)) == \
        (8192, 8)
    wrong = list(emitted)
    wrong[7] = (wrong[7] + 1) % shape["vocab_size"]
    bad = chk.check(eng, prompt, wrong, CONFIG, shape)
    assert not bad["ok"]
    assert _by_name(bad)["rerun_token_mismatches"]["value"] == 1
    assert _by_name(bad)["max_margin"]["value"] > \
        _by_name(bad)["max_margin"]["limit"]


@pytest.mark.parametrize("fault", ["unscaled", "wrong_page", "no_rotary_key"])
def test_a_corrupted_latent_write_fails_the_check(toy, monkeypatch, fault):
    """The prefill is sound, so its rows pass; what was decoded in the
    absorbed form over the rows the admission scattered into the slot's
    pages shows the fault: an admission that scatters the latent without its
    scale or onto the neighbouring page, or an absorbed form that leaves the
    shared rotary key out of its scores. (A step that misplaces its ONE new
    row among hundreds moves no margin: ``tests/test_longcat_flash.py`` holds
    the step's write and its logits row by row.)"""
    from ray_tpu.models import longcat_flash as lc
    from ray_tpu.models import paged_ops

    _, cfg, _ = toy
    scatter, attend = lc._scatter_latent, paged_ops.attend_latent

    def bad_scatter(pools, lats, page_ids):
        if fault == "unscaled":
            C = cfg.kv_lora_rank
            lats = [jnp.concatenate([r[:, :C] / cfg.kv_scale, r[:, C:]], -1)
                    for r in lats]
        elif fault == "wrong_page":
            page_ids = np.roll(np.asarray(page_ids), 1)
        return scatter(pools, lats, page_ids)

    def bad_attend(q_nope, q_rope, *rest):
        return attend(q_nope, jnp.zeros_like(q_rope), *rest)

    if fault == "no_rotary_key":
        monkeypatch.setattr(lc, "attend_latent", bad_attend)
        lc._longcat_step.clear_cache()      # the step is traced again
    else:
        monkeypatch.setattr(lc, "_scatter_latent", bad_scatter)
    try:
        eng, prompt = _engine(toy), _prompt(toy[0], seed=11)
        r = chk.check(eng, prompt, ctl._generate(eng, prompt), CONFIG,
                      toy[0])
    finally:
        monkeypatch.undo()
        lc._longcat_step.clear_cache()
    by = _by_name(r)
    assert not r["ok"]
    assert by["probe_max_margin"]["value"] > by["probe_max_margin"]["limit"] \
        or by["max_margin"]["value"] > by["max_margin"]["limit"] \
        or by["routing_far_disagreements"]["value"] > 0
    for name in ("prefill_max_abs_err", "prefill_rms_err",
                 "probe_prefill_max_abs_err", "probe_prefill_rms_err"):
        assert by[name]["value"] <= by[name]["limit"], name


def test_int8_weights_read_well_over_the_sound_engine():
    """At toy widths the limits (set on the chip at the cell's size) need not
    separate the two; the control's readings must still stand clear of the
    sound ones, as they do there."""
    sound, w8 = [], []
    for seed in (41, 42, 43):
        r = ctl.one_seed(CONFIG, seed, True)
        for name in ("prefill_rms_err", "probe_prefill_rms_err"):
            sound.append(_by_name(r["sound"])[name]["value"])
            w8.append(_by_name(r["w8"])[name]["value"])
    assert np.mean(w8) > 1.4 * np.mean(sound)


# ------------------------------------------------------------------ readers
def _ctx(steps, admits=()):
    return {"shape": program.shape_of(CONFIG, False),
            "run": {"t_open": 1.0, "t_close": 2.0},
            "spans": {}, "config": CONFIG,
            "_program_spans": {"serve.engine.step": steps,
                               "serve.engine.admit": list(admits)}}


def test_counter_and_span_readers():
    row = {"dur_ns": 1, "active": 16, "experts_hit": 14,
           "expert_tokens_max": 2, "zero_picks": 250,
           "latent_positions": 160_000, "moe_rows": 16, "landed": 1}
    steps = [{**row, "t0_ns": 1.1e9},
             # a call that landed two steps sums them
             {**row, "t0_ns": 1.2e9, "experts_hit": 30, "zero_picks": 518,
              "latent_positions": 320_032, "moe_rows": 32, "landed": 2},
             {"t0_ns": 1.3e9, "dur_ns": 1, "active": 16},   # nothing landed
             {**row, "t0_ns": 2.5e9, "zero_picks": 1}]      # past the window
    ctx = _ctx(steps,
               [{"t0_ns": 1.4e9, "dur_ns": 1.0e9, "sid": 7,
                 "prompt_len": 8000},
                {"t0_ns": 1.6e9, "dur_ns": 0.5e9, "sid": 8,
                 "prompt_len": 4500},
                {"t0_ns": 0.4e9, "dur_ns": 5e9, "sid": 3, "prompt_len": 999}])
    assert MAN.reader("moe_zero_pick_share_pct")(ctx) == pytest.approx(
        100 * 768 / (48 * 12 * 4))
    assert MAN.reader("moe_experts_hit_per_layer.longcat")(ctx) == \
        pytest.approx(44 / 3 / 4)
    assert MAN.reader("admit_ms_per_prompt_token.longcat")(ctx) == \
        pytest.approx(1500 / 12500)
    # a program without the counters or the spans (the parent): nothing
    bare = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 16}])
    for name in ("moe_zero_pick_share_pct", "latent_attn_mxu_pct",
                 "moe_experts_hit_per_layer.longcat",
                 "longcat_decode_hbm_roofline_pct",
                 "admit_ms_per_prompt_token.longcat"):
        assert MAN.reader(name)(bare) is None
    ctx.update({"peaks": {"hbm_bytes_per_s": 819e9,
                          "bf16_flops_per_s": 197e12},
                "trace": {"modules": {"jit__longcat_step": [0.03, 0.03]}}})
    positions = 480_032 / 3
    need = longcat_bytes.decode_min_bytes(ctx["shape"], positions, 16)
    got = MAN.reader("longcat_decode_hbm_roofline_pct")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.03)
    assert 40 < got < 55
    mxu = MAN.reader("latent_attn_mxu_pct")(ctx)
    assert mxu == pytest.approx(
        100 * 8 * 2 * 64 * 1088 * positions / 197e12 / 0.03)
    assert 1 < mxu < 10
    ctx["summary"] = {"gaps_ms": [30.0] * 99 + [900.0]}
    assert MAN.reader("itl_p99_ms.longcat")(ctx) > 30.0


def test_the_cell_lists_what_its_readers_find():
    names = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert names == {
        "longcat_decode_hbm_roofline_pct", "latent_attn_mxu_pct",
        "moe_zero_pick_share_pct", "moe_experts_hit_per_layer.longcat",
        "admit_ms_per_prompt_token.longcat", "itl_p99_ms.longcat",
        "fill_ms_per_prompt_token", "batch_occupancy",
        "decode_step_device_ms", "device_idle_pct.decode", "setup_weights_s",
        "setup_programs_s"}
    e2e = {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")}
    assert e2e == {"out_tokens_per_s", "setup_s"}
    for m in MAN.metrics_for(CELL, "per_layer"):
        assert m["moves"] in e2e and os.path.isfile(MAN.reader_path(m["name"]))
    cell = MAN.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "agent-decode-16"
    assert len(cell["why"]) <= 200
    mix = MAN.traffic(cell["traffic"])
    assert (mix["clients"], mix["cycle"], mix["loop"], mix["start"]) == \
        (16, 32, "closed", 10)
    assert CONFIG["engine"]["max_slots"] == mix["clients"]
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.35, "min": 4096, "max": 12288}
    assert mix["output_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.5, "min": 1024, "max": 4096}
    first = tg.closed_loop_requests(mix)[:16]
    assert sum(r.output_len < 1400 for r in first) >= 3
    hi = tg.length_range(mix["prompt_len"])[1] + \
        tg.length_range(mix["output_len"])[1]
    assert hi + 1 <= CONFIG["engine"]["max_len"]
    # the lengths' expected sum is two thirds of the pool
    cycle = tg.closed_loop_requests(mix)
    mean = sum(r.prompt_len + r.output_len for r in cycle) / len(cycle)
    pool = CONFIG["engine"]["num_pages"] * CONFIG["engine"]["page_size"]
    assert 0.55 * pool < 16 * mean < 0.75 * pool


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
    assert m["batch_occupancy"]["value"] > 15
    assert 15 < m["moe_zero_pick_share_pct"]["value"] < 60
    assert 0 < m["moe_experts_hit_per_layer.longcat"]["value"] <= 4
    assert m["fill_ms_per_prompt_token"]["value"] > 0
    assert m["itl_p99_ms.longcat"]["value"] > 0
    assert "compared: routing_far_disagreements 0.00000 (limit 0.00000)" \
        in err
    assert "probe_max_margin" in out and "probe_prefill_rms_err" in out
    assert "warm-up of prompts [12288]" in out
