"""The window / full attention family's benchmark files: its configuration
against the catalog row, its plain reference through the harness's own path,
its reference check (sound, the int8 control, a corrupted ring or window, an
altered token), its byte counts and readers, and a rehearsal run of
``serve-commanda-mixed-ctx-decode`` end to end. Toy widths, CPU."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import commanda_bytes, program, traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.reference import (REF_NEW, REF_PROMPT, cohere2_moe as ref,
                                 cohere2_moe_check as chk,
                                 cohere2_moe_control as ctl)
from perfbench.runners import serve as serve_runner

MAN = Manifest(ROOT)
NAME = "command-a-plus-serve1"
CELL = "serve-commanda-mixed-ctx-decode"
CONFIG = MAN.config(NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "num_shared_experts", "sliding_window")


# ------------------------------------------------------------ configuration
def test_reduced_is_the_depth_the_experts_held_and_the_vocabulary():
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144,
        "parameters": 218_254_938_112, "active_parameters": 24_981_409_792}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 16, 32768)
    # the floors: a whole period and four layers, eight experts, an eighth
    # of the vocabulary
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert CONFIG["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"] and len(CONFIG["layer_types"]) == 32
    assert CONFIG["router_width"] == 128 and CONFIG["expert_offset"] == 0
    assert "eight TPU v5e chips share every layer" in CONFIG["deployment"]
    for key in ("vision tower", "shared experts", "router", "expert width",
                "window", "positions", "norm", "weights", "kv_cache_dtype",
                "eos", "page_size", "max_slots", "num_pages", "rings",
                "max_len", "prefill", "memory"):
        assert key in CONFIG["assumed"], key
    assert "average" in CONFIG["assumed"]["shared experts"]
    assert "no selection bias" in CONFIG["assumed"]["router"]
    assert CONFIG["engine"]["max_len"] % CONFIG["prefill_chunk"] == 0
    assert CONFIG["programs"]["decode"] == "jit__cohere_step"


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
    assert (cfg.n_layers, cfg.n_full_layers, cfg.n_window_layers,
            cfg.experts_held, cfg.router_width, cfg.top_k, cfg.n_shared) == \
        (4, 1, 3, 16, 128, 8, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.sliding_window,
            cfg.expert_d_ff, cfg.rope_theta) == (128, 8, 128, 4096, 4096,
                                                 50000)
    assert cfg.dtype == jnp.bfloat16 and cfg.prefill_chunk == 2048
    assert cfg.param_count() == 4_733_292_544
    whole = dataclasses.replace(cfg, n_layers=32, experts_held=128,
                                vocab_size=262144)
    assert whole.param_count() == CONFIG["published"]["parameters"]
    toy = program.model_config(CONFIG, program.shape_of(CONFIG, True))
    assert (toy.n_layers, toy.kinds, toy.experts_held, toy.router_width,
            toy.sliding_window) == (
        2, ("sliding_attention", "full_attention"), 4, 16, 256)


def test_byte_counts_agree_with_the_program_tree():
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape)
    from ray_tpu.models.cohere2_moe import init_params
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert commanda_bytes.weight_bytes(shape) == held
    real = program.shape_of(CONFIG, False)
    assert commanda_bytes.weight_bytes(real) == pytest.approx(9.471e9,
                                                              rel=0.0005)
    assert commanda_bytes.kv_row_bytes(real) == 4096
    assert commanda_bytes.kinds(real).count("full_attention") == 1
    bare = commanda_bytes.decode_min_bytes(real, 0, 0, 0)
    assert bare == commanda_bytes.weight_bytes(real)
    need = commanda_bytes.decode_min_bytes(real, 320_000, 110_000, 32)
    assert need - bare == pytest.approx(
        4096 * (320_000 + 3 * 110_000 + 4 * 32), rel=1e-9)
    assert 12.0e9 < need < 12.2e9
    e = CONFIG["engine"]
    assert e["num_pages"] * e["page_size"] * 4096 == \
        pytest.approx(1.879e9, rel=0.001)
    assert 3 * e["max_slots"] * real["sliding_window"] * 4096 == \
        pytest.approx(1.611e9, rel=0.001)


# ---------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    """The rehearsal widths, with two query heads over the K/V head where the
    rehearsal has one (one head of 8 moves a row too little for a corrupted
    ring to show), through the harness's own path, in float32: the routing
    then agrees with the reference to the last tie, so a sound engine reads
    ~0 everywhere and what a fault moves is the fault's alone."""
    shape = {**program.shape_of(CONFIG, True), "num_attention_heads": 2}
    cfg = program.model_config(CONFIG, shape, dtype=jnp.float32)
    return shape, cfg, program.init_weights(CONFIG, cfg, 3_400_000_033)


def test_reference_parity_through_the_harness_path(toy):
    from ray_tpu.models.cohere2_moe import forward

    shape, cfg, params = toy
    toks = tg.prompt_tokens(5, 1, 600, shape["vocab_size"])
    got = np.asarray(forward(params, jnp.asarray(toks, jnp.int32), cfg))
    out = ref.forward(ref.from_program_tree(params), toks, shape)
    want = np.asarray(out["logits"])
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert want.std() > 0.3
    own = np.asarray(out["own_routing"])
    assert own.shape == (2, 600, 2)
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
    return PagedEngine(params, cfg, **{**kw, "num_pages": 128, "max_slots": 2,
                                       "max_len": 6144})


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
    assert (n, m) == (4352, 8) and r["notes"]["probe_len"] == 4352
    # the probe crosses two chunk boundaries and passes the (toy) window
    assert n > 2 * CONFIG["prefill_chunk"] and n > shape["sliding_window"]
    # every position of both requests' prefills and decode steps, two layers
    assert r["notes"]["routing_decisions"] == \
        2 * (REF_PROMPT + REF_NEW - 1 + n + m - 1)
    assert r["notes"]["routing_disagreements"] <= 4         # float32
    real = program.shape_of(CONFIG, False)
    n, m = chk.probe_sizes(CONFIG, real)
    assert (n, m) == (6144, 8)
    # ... at the cell's size: two chunk boundaries, the window passed by 2048
    assert n == 3 * CONFIG["prefill_chunk"] == real["sliding_window"] + 2048
    wrong = list(emitted)
    wrong[7] = (wrong[7] + 1) % shape["vocab_size"]
    bad = chk.check(eng, prompt, wrong, CONFIG, shape)
    assert not bad["ok"]
    assert _by_name(bad)["rerun_token_mismatches"]["value"] == 1
    assert _by_name(bad)["max_margin"]["value"] > \
        _by_name(bad)["max_margin"]["limit"]


@pytest.mark.parametrize("fault", ["ring_unwrapped", "ring_reads_unwritten",
                                   "step_rotates_late", "full_block_dropped"])
def test_a_corrupted_ring_or_read_fails_the_check(toy, monkeypatch, fault):
    """The prefill is sound, so its rows pass; what was decoded over the ring
    and the pages the admission wrote shows the fault: a ring written from
    the prompt's FIRST positions (no wrap), a ring read that masks nothing
    (indices no position has reached), a step that rotates a window layer's query and
    key by the wrong position, a full read that stops one block early."""
    from ray_tpu.models import cohere2_moe as cm
    from ray_tpu.models import paged_ops

    write, ring, rows, blocked = (cm._write_rings, paged_ops.attend_ring,
                                  cm.rope_rows, cm.attend_pages_blocked)

    def unwrapped(rings_k, rings_v, bufs, n, slot):
        return write(rings_k, rings_v, bufs,
                     np.int32(min(int(n), rings_k[0].shape[2])), slot)

    def reads_unwritten(q, ring_k, ring_v, lengths):
        return ring(q, ring_k, ring_v, lengths + ring_k.shape[2])

    def late(positions, *a):
        if positions.shape[0] == 2:     # the step's two slots, not a chunk
            positions = positions + 3
        return rows(positions, *a)

    def dropped(q, pool_k, pool_v, tables, lengths, block_pages):
        short = jnp.maximum(lengths - block_pages * pool_k.shape[1], 0)
        return blocked(q, pool_k, pool_v, tables, short, block_pages)

    if fault == "ring_unwrapped":
        monkeypatch.setattr(cm, "_write_rings", unwrapped)
    elif fault == "ring_reads_unwritten":
        monkeypatch.setattr(cm, "attend_ring", reads_unwritten)
    elif fault == "step_rotates_late":
        monkeypatch.setattr(cm, "rope_rows", late)   # the step's two slots
    else:
        monkeypatch.setattr(cm, "attend_pages_blocked", dropped)
    cm._cohere_step.clear_cache()           # the step is traced again
    try:
        eng, prompt = _engine(toy), _prompt(toy[0], seed=11)
        r = chk.check(eng, prompt, ctl._generate(eng, prompt), CONFIG,
                      toy[0])
    finally:
        monkeypatch.undo()
        cm._cohere_step.clear_cache()
    by = _by_name(r)
    assert not r["ok"]
    assert by["probe_max_margin"]["value"] > by["probe_max_margin"]["limit"] \
        or by["max_margin"]["value"] > by["max_margin"]["limit"] \
        or by["routing_far_disagreements"]["value"] > 0
    for name in ("prefill_max_abs_err", "prefill_rms_err",
                 "probe_prefill_max_abs_err", "probe_prefill_rms_err"):
        assert by[name]["value"] <= by[name]["limit"], name


def test_a_prefill_without_the_windows_second_bound_fails_the_probe(
        toy, monkeypatch):
    """Only the probe is longer than the window: a prompt attention that is
    merely causal in a window layer passes the contract's 200-token request
    and fails the probe's prefill row."""
    from ray_tpu.models import cohere2_moe as cm

    attention = cm._prompt_attention
    monkeypatch.setattr(cm, "_prompt_attention",
                        lambda q, k, v, start, window, cfg: attention(
                            q, k, v, start, 0, cfg))
    cm._cohere_prefill_chunk.clear_cache()
    try:
        eng, prompt = _engine(toy), _prompt(toy[0], seed=12)
        r = chk.check(eng, prompt, ctl._generate(eng, prompt), CONFIG,
                      toy[0])
    finally:
        monkeypatch.undo()
        cm._cohere_prefill_chunk.clear_cache()
    by = _by_name(r)
    assert not r["ok"]
    for name in ("prefill_max_abs_err", "prefill_rms_err", "max_margin"):
        assert by[name]["value"] <= by[name]["limit"], name
    assert by["probe_prefill_rms_err"]["value"] > \
        by["probe_prefill_rms_err"]["limit"]


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
    row = {"dur_ns": 1, "active": 32, "moe_hit": 56, "moe_max": 5,
           "moe_rows": 32, "context_positions": 320_000,
           "window_positions": 110_000, "landed": 1}
    steps = [{**row, "t0_ns": 1.1e9},
             # a call that landed two steps sums them
             {**row, "t0_ns": 1.2e9, "moe_hit": 110, "moe_rows": 64,
              "context_positions": 640_064, "window_positions": 220_010,
              "landed": 2},
             {"t0_ns": 1.3e9, "dur_ns": 1, "active": 32},   # nothing landed
             {**row, "t0_ns": 2.5e9, "moe_hit": 1}]         # past the window
    ctx = _ctx(steps,
               [{"t0_ns": 1.4e9, "dur_ns": 1.0e9, "sid": 7,
                 "prompt_len": 8000},
                {"t0_ns": 1.6e9, "dur_ns": 0.5e9, "sid": 8,
                 "prompt_len": 4500},
                {"t0_ns": 0.4e9, "dur_ns": 5e9, "sid": 3, "prompt_len": 999}])
    assert MAN.reader("window_kv_read_share_pct")(ctx) == pytest.approx(
        100 * 330_010 / 960_064)
    assert MAN.reader("moe_experts_hit_per_layer.commanda")(ctx) == \
        pytest.approx(166 / 3 / 4)
    assert MAN.reader("admit_ms_per_prompt_token.commanda")(ctx) == \
        pytest.approx(1500 / 12500)
    # a program without the counters or the spans (the parent): nothing
    bare = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 16}])
    for name in ("window_kv_read_share_pct",
                 "moe_experts_hit_per_layer.commanda",
                 "commanda_decode_hbm_roofline_pct",
                 "admit_ms_per_prompt_token.commanda"):
        assert MAN.reader(name)(bare) is None
    ctx.update({"peaks": {"hbm_bytes_per_s": 819e9,
                          "bf16_flops_per_s": 197e12},
                "trace": {"modules": {"jit__cohere_step": [0.02, 0.02]}}})
    need = commanda_bytes.decode_min_bytes(ctx["shape"], 960_064 / 3,
                                           330_010 / 3, 32)
    got = MAN.reader("commanda_decode_hbm_roofline_pct")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.02)
    assert 65 < got < 80
    ctx["summary"] = {"gaps_ms": [20.0] * 99 + [900.0]}
    assert MAN.reader("itl_p99_ms.commanda")(ctx) > 20.0


def test_the_cell_lists_what_its_readers_find():
    names = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert names == {
        "commanda_decode_hbm_roofline_pct", "window_kv_read_share_pct",
        "moe_experts_hit_per_layer.commanda",
        "admit_ms_per_prompt_token.commanda", "itl_p99_ms.commanda",
        "fill_ms_per_prompt_token", "batch_occupancy",
        "decode_step_device_ms", "device_idle_pct.decode", "setup_weights_s",
        "setup_programs_s"}
    e2e = {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")}
    assert e2e == {"out_tokens_per_s", "setup_s"}
    for m in MAN.metrics_for(CELL, "per_layer"):
        assert m["moves"] in e2e and os.path.isfile(MAN.reader_path(m["name"]))
    cell = MAN.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "mixed-ctx-decode-32"
    assert len(cell["why"]) <= 200
    for said in ("2 tokens an expert (deployment: 16)", "attention 8x",
                 "4 of 32 layers"):
        assert said in cell["why"]
    mix = MAN.traffic(cell["traffic"])
    assert (mix["clients"], mix["cycle"], mix["loop"]) == (32, 64, "closed")
    assert CONFIG["engine"]["max_slots"] == mix["clients"]
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                 "sigma": 0.9, "min": 512, "max": 26624}
    assert mix["output_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.5, "min": 1024, "max": 4096}
    cycle = tg.closed_loop_requests(mix)
    under = sum(r.prompt_len < CONFIG["sliding_window"] for r in cycle)
    assert 0.28 < under / len(cycle) < 0.38         # about a third
    assert sum(r.prompt_len > 20000 for r in cycle) >= 5
    hi = tg.length_range(mix["prompt_len"])[1] + \
        tg.length_range(mix["output_len"])[1]
    assert hi + 1 <= CONFIG["engine"]["max_len"]
    # the lengths' expected sum is under three quarters of the pool
    mean = sum(r.prompt_len + r.output_len for r in cycle) / len(cycle)
    pool = CONFIG["engine"]["num_pages"] * CONFIG["engine"]["page_size"]
    assert 0.6 * pool < 32 * mean < 0.8 * pool


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
    assert m["batch_occupancy"]["value"] > 31
    # 32 contexts of 0.7k-27k positions against a toy window of 256
    assert 1 < m["window_kv_read_share_pct"]["value"] < 10
    assert 0 < m["moe_experts_hit_per_layer.commanda"]["value"] <= 4
    assert m["fill_ms_per_prompt_token"]["value"] > 0
    assert m["itl_p99_ms.commanda"]["value"] > 0
    assert "compared: routing_far_disagreements 0.00000 (limit 0.00000)" \
        in err
    assert "probe_max_margin" in out and "probe_prefill_rms_err" in out
    assert "warm-up of prompts [26624]" in out
