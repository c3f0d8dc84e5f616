"""The hybrid family's benchmark files: its configuration against the catalog
row, its plain reference through the harness's own path, its reference check
(sound, the int8 control, a corrupted state write, an altered token), its
byte counts and readers, and a rehearsal run of ``serve-nemotron-decode``
end to end. Toy widths, CPU."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import nemotron_bytes, program, traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.reference import (REF_NEW, REF_PROMPT, nemotron_h as ref,
                                 nemotron_h_check as chk,
                                 nemotron_h_control as ctl)
from perfbench.runners import serve as serve_runner

MAN = Manifest(ROOT)
NAME = "nemotron-3-nano-30b-a3b-serve1"
CELL = "serve-nemotron-decode"
CONFIG = MAN.config(NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
          "moe_intermediate_size", "moe_shared_expert_intermediate_size",
          "intermediate_size", "num_experts_per_tok", "expand")


# ------------------------------------------------------------ configuration
def test_reduced_is_the_share_and_no_width():
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == ["n_routed_experts",
                                                     "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"]["n_routed_experts"] == 128 == \
        CONFIG["router_width"]
    assert CONFIG["published"]["vocab_size"] == 131072
    assert CONFIG["n_routed_experts"] == 16 and CONFIG["vocab_size"] == 16384
    # the guide's floors: >= 8 experts a layer, >= an eighth of the vocabulary
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["published"]["vocab_size"]
    assert len(CONFIG["hybrid_override_pattern"]) == 52 == \
        CONFIG["num_hidden_layers"]
    assert "eight" in CONFIG["deployment"]
    assert any("positional" in k for k in CONFIG["assumed"])


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
    cfg = program.model_config(CONFIG, program.shape_of(CONFIG, False))
    assert (cfg.n_layers, cfg.n_mamba_layers, cfg.n_moe_layers,
            cfg.n_attn_layers) == (52, 23, 23, 6)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset) == (128, 16, 0)
    assert (cfg.d_inner, cfg.conv_dim) == (4096, 6144)
    assert cfg.dtype == jnp.bfloat16
    # what this chip holds, and the whole model from the same arithmetic
    assert cfg.param_count() * 2 == pytest.approx(10.5e9, rel=0.01)
    import dataclasses
    whole = dataclasses.replace(cfg, experts_held=128, vocab_size=131072)
    assert whole.param_count() == pytest.approx(31.6e9, rel=0.005)


def test_byte_counts_agree_with_the_program_tree():
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape)
    from ray_tpu.models.nemotron_h import init_params, init_state
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert nemotron_bytes.weight_bytes(shape) == held
    state = jax.eval_shape(lambda: init_state(cfg, 3))
    assert nemotron_bytes.slot_state_bytes(shape) * 3 == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    real = program.shape_of(CONFIG, False)
    assert nemotron_bytes.weight_bytes(real) == pytest.approx(10.53e9,
                                                              rel=0.005)
    assert nemotron_bytes.slot_state_bytes(real) == 23 * (
        64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert nemotron_bytes.kv_bytes_per_position(real) == 6144
    full = nemotron_bytes.decode_min_bytes(real, 32 * 400, 32, 23 * 12)
    none = nemotron_bytes.decode_min_bytes(real, 32 * 400, 32, 0)
    assert full - none == 23 * 12 * nemotron_bytes.expert_bytes(real)
    assert 11e9 < full < 13e9 and full < nemotron_bytes.weight_bytes(real) + \
        2 * 32 * nemotron_bytes.slot_state_bytes(real) + 32 * 400 * 6144 + 1e6


# ---------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    """The rehearsal widths (a share of 4 of 16 experts) through the
    harness's own path, in float32: routing then agrees with the reference
    to the last tie, so a sound engine reads ~0 everywhere and what a fault
    moves is the fault's alone. (In bfloat16 one near-tie swap of an expert
    moves a toy model's logits by 0.2-0.4 sigma; at the cell's widths it is
    a small share of the residual stream: PERF.md section 6, PR 28.)"""
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape, dtype=jnp.float32)
    return shape, cfg, program.init_weights(CONFIG, cfg, 2_800_000_033)


def test_reference_parity_through_the_harness_path(toy):
    from ray_tpu.models.nemotron_h import forward

    shape, cfg, params = toy
    toks = tg.prompt_tokens(5, 1, 40, shape["vocab_size"])
    got = np.asarray(forward(params, jnp.asarray(toks, jnp.int32), cfg))
    out = ref.forward(ref.from_program_tree(params), toks, shape)
    want = np.asarray(out["logits"])
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert want.std() > 0.5
    assert out["own_routing"].shape == (2, 40, 3)
    assert float(out["under"].max()) == 0.0       # nothing imposed


def test_imposed_routing_is_used_and_measured(toy):
    shape, _, params = toy
    w = ref.from_program_tree(params)
    toks = tg.prompt_tokens(6, 2, 12, shape["vocab_size"])
    free = ref.forward(w, toks, shape)
    own = np.asarray(free["own_routing"])
    same = ref.forward(w, toks, shape, routing=own)
    np.testing.assert_array_equal(same["logits"], free["logits"])
    other = (own + 1) % shape["router_width"]     # another set everywhere
    forced = ref.forward(w, toks, shape, routing=other[:, :8])
    assert float(forced["under"][:, :8].min()) > 0.0
    assert float(forced["under"][:, 8:].max()) == 0.0   # free past the prompt
    assert not np.allclose(forced["logits"], free["logits"], atol=1e-3)
    np.testing.assert_array_equal(ref.logits(w, toks, shape, other[:, :8]),
                                  forced["logits"])


# -------------------------------------------------------------------- check
def _engine(toy):
    from ray_tpu.models.paged import PagedEngine

    _, cfg, params = toy
    kw = {k: v for k, v in program.section(CONFIG, "engine", True).items()
          if k != "kv_cache"}
    return PagedEngine(params, cfg, **kw)


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
    assert set(by) == {"routing_far_disagreements", "prefill_max_abs_err",
                       "prefill_rms_err", "rerun_token_mismatches",
                       "max_margin"}
    assert by["rerun_token_mismatches"]["value"] == 0.0
    assert by["routing_far_disagreements"]["limit"] == 0.0
    assert r["notes"]["routing_decisions"] == 2 * (REF_PROMPT + REF_NEW - 1)
    wrong = list(emitted)
    wrong[7] = (wrong[7] + 1) % shape["vocab_size"]
    bad = chk.check(eng, prompt, wrong, CONFIG, shape)
    assert not bad["ok"]
    for name in ("rerun_token_mismatches", "max_margin"):
        assert _by_name(bad)[name]["value"] > _by_name(bad)[name]["limit"]


@pytest.mark.parametrize("fault", ["zero_state", "stale_tail"])
def test_a_corrupted_state_write_fails_the_check(toy, monkeypatch, fault):
    """The prefill is sound and the engine repeats itself, so (b) and (c)
    pass; what was decoded over the state the admission wrote into the slot
    shows it: the decoded positions' routing lies far from the reference's
    own choice, and where a token flips, its margin."""
    from ray_tpu.models import paged

    write = paged._write_state

    def corrupted(ssm_states, conv_tails, new, slot):
        if fault == "zero_state":
            new = [(jnp.zeros_like(s), t) for s, t in new]
        else:       # the tail as a padded prefill would leave it: zeros
            new = [(s, jnp.zeros_like(t)) for s, t in new]
        return write(ssm_states, conv_tails, new, slot)

    monkeypatch.setattr(paged, "_write_state", corrupted)
    # a fault shows in tokens only where it flips one: this prompt's do
    eng, prompt = _engine(toy), _prompt(toy[0], seed=11)
    r = chk.check(eng, prompt, ctl._generate(eng, prompt), CONFIG, toy[0])
    by = _by_name(r)
    assert not r["ok"]
    assert by["routing_far_disagreements"]["value"] >= 2
    if fault == "stale_tail":
        assert by["max_margin"]["value"] > by["max_margin"]["limit"]
    for name in ("prefill_max_abs_err", "prefill_rms_err",
                 "rerun_token_mismatches"):
        assert by[name]["value"] <= by[name]["limit"], name


def test_int8_weights_read_well_over_the_sound_engine():
    """At toy widths the limits (set on the chip at the cell's size) need not
    separate the two; the control's readings must still stand clear of the
    sound ones, as they do there."""
    sound, w8 = [], []
    for seed in (41, 42, 43):
        r = ctl.one_seed(CONFIG, seed, True)
        sound.append(_by_name(r["sound"])["prefill_rms_err"]["value"]
                     / r["sound"]["notes"]["ref_logit_std"])
        w8.append(_by_name(r["w8"])["prefill_rms_err"]["value"]
                  / r["w8"]["notes"]["ref_logit_std"])
    assert min(w8) > 1.5 * max(sound) or np.mean(w8) > 2.0 * np.mean(sound)


# ------------------------------------------------------------------ readers
def _ctx(steps, admits_state=()):
    return {"shape": program.shape_of(CONFIG, False),
            "run": {"t_open": 1.0, "t_close": 2.0},
            "spans": {}, "config": CONFIG,
            "_program_spans": {"serve.engine.step": steps,
                               "serve.admit.state": list(admits_state)}}


def test_expert_load_readers():
    steps = [{"t0_ns": 1.1e9, "dur_ns": 1, "active": 32, "experts_hit": 276},
             {"t0_ns": 1.2e9, "dur_ns": 1, "active": 31, "experts_hit": 230},
             {"t0_ns": 1.3e9, "dur_ns": 1, "active": 0},
             {"t0_ns": 2.5e9, "dur_ns": 1, "active": 32, "experts_hit": 1}]
    ctx = _ctx(steps, [{"t0_ns": 1.4e9, "dur_ns": 600_000},
                       {"t0_ns": 1.5e9, "dur_ns": 800_000},
                       {"t0_ns": 0.5e9, "dur_ns": 9_000_000}])
    assert MAN.reader("moe_experts_hit_per_layer")(ctx) == pytest.approx(
        (276 + 230) / 2 / 23)
    assert MAN.reader("admit_state_write_ms")(ctx) == pytest.approx(0.7)
    # a program without the counter or the span (the parent): nothing to read
    bare = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 16}])
    for name in ("moe_experts_hit_per_layer", "admit_state_write_ms",
                 "nemotron_decode_hbm_roofline_pct"):
        assert MAN.reader(name)(bare) is None


def test_roofline_reader_counts_the_hit_experts():
    ctx = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 32,
                 "experts_hit": 276}])
    ctx.update({
        "config": CONFIG, "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"modules": {"jit__hybrid_step": [0.06, 0.06]}},
        "spans": {"steps": [(1.1e9, 1.2e9, 0, 32, True, 32 * 400)]}})
    from perfbench import xplane
    if not xplane.module_times(ctx["trace"], "jit__hybrid_step"):
        pytest.skip("the reduced trace keeps module times under another key")
    need = nemotron_bytes.decode_min_bytes(ctx["shape"], 32 * 400, 32, 276)
    got = MAN.reader("nemotron_decode_hbm_roofline_pct")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.06)
    assert 20 < got < 30


def test_the_cell_lists_what_its_readers_find():
    names = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert {"nemotron_decode_hbm_roofline_pct", "moe_experts_hit_per_layer",
            "admit_state_write_ms", "step_host_ms", "batch_occupancy",
            "decode_step_device_ms", "device_idle_pct.decode",
            "pump_ms_per_token", "setup_weights_s",
            "setup_programs_s"} <= names
    # ``test_program_spans.py`` holds these three to the decode cell alone
    assert not {"step_prepare_ms", "step_fetch_ms",
                "pump_handoff_ms"} & names
    assert "decode_hbm_roofline_pct" not in names   # counts a dense decoder
    e2e = {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")}
    assert e2e == {"out_tokens_per_s", "itl_p99_ms", "setup_s"}
    for m in MAN.metrics_for(CELL, "per_layer"):
        assert m["moves"] in e2e and os.path.isfile(MAN.reader_path(m["name"]))
    mix = MAN.traffic(MAN.cell(CELL)["traffic"])
    assert (mix["clients"], mix["cycle"], mix["loop"]) == (64, 128, "closed")
    assert CONFIG["engine"]["max_slots"] * 2 == mix["clients"]
    hi = tg.length_range(mix["prompt_len"])[1] + \
        tg.length_range(mix["output_len"])[1]
    assert hi + 1 <= CONFIG["engine"]["max_len"]


# ---------------------------------------------------------------- rehearsal
def test_rehearsal_run_of_the_cell_end_to_end(capfd):
    cell = MAN.cell(CELL)
    args = argparse.Namespace(seed=2_800_000_039, seconds=3.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(MAN, cell, args, time.time())
    out, err = capfd.readouterr()
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    m = line["metrics"]
    assert 0 < m["moe_experts_hit_per_layer"]["value"] <= 4
    assert m["admit_state_write_ms"]["value"] > 0
    assert m["batch_occupancy"]["value"] > 3
    assert "compared: routing_far_disagreements 0.00000 (limit 0.00000)" in err
    assert "max_margin" in out and "prefill_max_abs_err" in out
