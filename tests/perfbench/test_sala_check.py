"""The lightning / block-sparse family's benchmark files: its configuration
against the catalog row, its plain reference through the harness's own path,
its reference check (sound, the int8 control, a corrupted state write, an
altered token), its byte counts and readers, and a rehearsal run of
``serve-sala-longctx-decode`` end to end. Toy widths, CPU."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import program, sala_bytes, traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.reference import (REF_NEW, REF_PROMPT, minicpm_sala as ref,
                                 minicpm_sala_check as chk,
                                 minicpm_sala_control as ctl)
from perfbench.runners import serve as serve_runner

MAN = Manifest(ROOT)
NAME = "minicpm-sala-9b-serve1"
CELL = "serve-sala-longctx-decode"
CONFIG = MAN.config(NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "head_dim", "lightning_head_dim",
          "intermediate_size", "num_attention_heads", "num_key_value_heads",
          "lightning_nh", "lightning_nkv", "vocab_size", "dim_model_base")


# ------------------------------------------------------------ configuration
def test_reduced_is_the_depth_and_no_width():
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers",
                                                     "mixer_types"]
    assert entry["source"] == CONFIG["source"]
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 32 == len(pub["mixer_types"])
    assert CONFIG["num_hidden_layers"] == 16 == len(CONFIG["mixer_types"])
    assert CONFIG["mixer_types"] == pub["mixer_types"][8:24]
    assert CONFIG["layer_offset"] == 8 and CONFIG["published_depth"] == 32
    kinds = CONFIG["mixer_types"]
    assert (kinds.count("lightning-attn"), kinds.count("minicpm4")) == (12, 4)
    # the published 3 : 1, and at least four of the driver's 4-layer periods
    assert pub["mixer_types"].count("minicpm4") * 3 == \
        pub["mixer_types"].count("lightning-attn")
    assert "two" in CONFIG["deployment"]
    for key in ("decay", "sparse sizes", "norms", "weights", "state_dtype",
                "kv_cache_dtype", "eos", "page_size", "max_slots",
                "num_pages", "max_len", "prefill", "memory"):
        assert key in CONFIG["assumed"], key
    assert CONFIG["engine"]["page_size"] == CONFIG["sparse_block_size"]
    assert CONFIG["engine"]["max_len"] % CONFIG["prefill_chunk"] == 0


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
    assert (cfg.n_layers, cfg.n_lightning_layers, cfg.n_sparse_layers) == \
        (16, 12, 4)
    assert (cfg.layer_offset, cfg.n_layers_published) == (8, 32)
    assert (cfg.kernel, cfg.stride, cfg.block, cfg.topk, cfg.init_blocks,
            cfg.window, cfg.dense_len) == (32, 16, 64, 64, 1, 2048, 8192)
    assert cfg.dtype == jnp.bfloat16 and cfg.prefill_chunk == 2048
    assert cfg.param_count() * 2 == pytest.approx(10.08e9, rel=0.005)
    import dataclasses
    whole = dataclasses.replace(
        cfg, mixer_types=CONFIG["published"]["mixer_types"], layer_offset=0)
    assert whole.param_count() == pytest.approx(9.48e9, rel=0.002)
    toy = program.model_config(CONFIG, program.shape_of(CONFIG, True))
    assert toy.n_layers == 4 and toy.block == 64 and toy.dense_len == 128


def test_byte_counts_agree_with_the_program_tree():
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape)
    from ray_tpu.models.minicpm_sala import init_params, init_state
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert sala_bytes.weight_bytes(shape) == held
    state = jax.eval_shape(lambda: init_state(cfg, 3))
    assert sala_bytes.slot_state_bytes(shape) * 3 == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    real = program.shape_of(CONFIG, False)
    assert sala_bytes.weight_bytes(real) == pytest.approx(10.08e9, rel=0.005)
    assert sala_bytes.slot_state_bytes(real) == 12 * 32 * 128 * 128 * 4
    assert sala_bytes.page_head_bytes(real) == 32768
    assert sala_bytes.ckey_page_head_bytes(real) == 1024
    # 8 slots of ~400 pages: 64 of them read by each K/V head and layer
    read, live = 8 * 4 * 2 * 64, 8 * 4 * 2 * 400
    need = sala_bytes.decode_min_bytes(real, 8, read, live)
    bare = sala_bytes.decode_min_bytes(real, 0, 0, 0)
    assert bare == sala_bytes.weight_bytes(real, embedding=False)
    assert need - bare == pytest.approx(
        2 * 8 * 25.2e6 + read * 32768 + live * 1024, rel=0.01)
    assert 10.0e9 < need < 10.3e9


# ---------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    """The rehearsal widths through the harness's own path, in float32: the
    selection then agrees with the reference to the last tie, so a sound
    engine reads ~0 everywhere and what a fault moves is the fault's alone."""
    shape = program.shape_of(CONFIG, True)
    cfg = program.model_config(CONFIG, shape, dtype=jnp.float32)
    return shape, cfg, program.init_weights(CONFIG, cfg, 3_200_000_033)


def test_reference_parity_through_the_harness_path(toy):
    from ray_tpu.models.minicpm_sala import forward

    shape, cfg, params = toy
    toks = tg.prompt_tokens(5, 1, 400, shape["vocab_size"])
    got = np.asarray(forward(params, jnp.asarray(toks, jnp.int32), cfg))
    out = ref.forward(ref.from_program_tree(params), toks, shape)
    want = np.asarray(out["logits"])
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert want.std() > 0.3
    own = np.asarray(out["own_selection"])
    assert own.shape == (1, 400, 1, 6)
    assert (own[0, :128] == -1).all() and (own[0, 128:] >= 0).all()
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
    return PagedEngine(params, cfg, **{**kw, "num_pages": 64,
                                       "max_len": 2048})


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
        "selection_far_disagreements", "probe_tokens_missing",
        "prefill_max_abs_err", "prefill_rms_err", "max_margin",
        "probe_prefill_max_abs_err", "probe_prefill_rms_err",
        "probe_max_margin"}
    assert by["selection_far_disagreements"]["limit"] == 0.0
    n, m = chk.probe_sizes(CONFIG, shape)
    assert (n, m) == (640, 8) and r["notes"]["probe_len"] == 640
    # every position past dense_len of the prefill and of 7 decode steps
    assert r["notes"]["selection_decisions"] == n + m - 1 - 128
    assert r["notes"]["selection_disagreements"] == 0      # float32
    assert chk.probe_sizes(CONFIG, program.shape_of(CONFIG, False)) == \
        (12288, 8)
    wrong = list(emitted)
    wrong[7] = (wrong[7] + 1) % shape["vocab_size"]
    bad = chk.check(eng, prompt, wrong, CONFIG, shape)
    assert not bad["ok"]
    assert _by_name(bad)["max_margin"]["value"] > \
        _by_name(bad)["max_margin"]["limit"]


@pytest.mark.parametrize("fault", ["zero_state", "other_slots_state"])
def test_a_corrupted_state_write_fails_the_check(toy, monkeypatch, fault):
    """The prefill is sound, so its rows pass; what was decoded over the
    state the admission wrote into the slot shows the fault: the streamed
    tokens' margins against the reference, on the contract's request (dense
    regime) and on the probe."""
    from ray_tpu.models import paged

    write = paged._write_state

    def corrupted(ssm_states, conv_tails, new, slot):
        if fault == "zero_state":
            new = [(jnp.zeros_like(s[0]),) for s in new]
        else:       # the state lands in the slot beside the request's
            slot = (slot + 1) % 2
        return write(ssm_states, conv_tails, new, slot)

    monkeypatch.setattr(paged, "_write_state", corrupted)
    eng, prompt = _engine(toy), _prompt(toy[0], seed=11)
    r = chk.check(eng, prompt, ctl._generate(eng, prompt), CONFIG, toy[0])
    by = _by_name(r)
    assert not r["ok"]
    assert by["max_margin"]["value"] > by["max_margin"]["limit"]
    assert by["probe_max_margin"]["value"] > by["probe_max_margin"]["limit"]
    for name in ("prefill_max_abs_err", "prefill_rms_err",
                 "probe_prefill_max_abs_err", "probe_prefill_rms_err"):
        assert by[name]["value"] <= by[name]["limit"], name


def test_a_wrong_selection_is_far_from_the_references(toy, monkeypatch):
    """The engine's decode steps choose the LOWEST-scored blocks: the prefill
    is sound, the decode steps' choices lie far under the reference's
    cut-off."""
    from ray_tpu.models import paged_ops

    choose = paged_ops.choose_blocks

    def worst(logits, n, sizes):
        idx, B = choose(logits, n, sizes)
        flipped = jnp.where(jnp.isfinite(B), -B, B)
        return jax.lax.top_k(flipped, sizes.topk)[1].astype(jnp.int32), B

    eng, prompt = _engine(toy), _prompt(toy[0])
    emitted = ctl._generate(eng, prompt)
    monkeypatch.setattr(paged_ops, "choose_blocks", worst)
    from ray_tpu.models import minicpm_sala as ms
    ms._sala_step.clear_cache()           # the step is traced again
    try:
        r = chk.check(eng, prompt, emitted, CONFIG, toy[0])
    finally:
        monkeypatch.undo()
        ms._sala_step.clear_cache()
    by = _by_name(r)
    assert not r["ok"]
    assert by["selection_far_disagreements"]["value"] >= 1
    assert r["notes"]["selection_worst_under"] > chk.SELECT_TIE_TOL


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
def _ctx(steps, admits=(), prefills=()):
    return {"shape": program.shape_of(CONFIG, False),
            "run": {"t_open": 1.0, "t_close": 2.0},
            "spans": {}, "config": CONFIG,
            "_program_spans": {"serve.engine.step": steps,
                               "serve.engine.admit": list(admits),
                               "serve.admit.prefill": list(prefills)}}


def test_counter_and_span_readers():
    steps = [{"t0_ns": 1.1e9, "dur_ns": 1, "active": 8,
              "sparse_pages_read": 4096, "sparse_pages_live": 25600,
              "sparse_slots": 8},
             {"t0_ns": 1.2e9, "dur_ns": 1, "active": 7,
              "sparse_pages_read": 3584, "sparse_pages_live": 12800,
              "sparse_slots": 7},
             {"t0_ns": 1.3e9, "dur_ns": 1, "active": 0},
             {"t0_ns": 2.5e9, "dur_ns": 1, "active": 8,
              "sparse_pages_read": 1, "sparse_pages_live": 1}]
    ctx = _ctx(steps,
               [{"t0_ns": 1.4e9, "dur_ns": 5e9, "sid": 7, "prompt_len": 20000},
                {"t0_ns": 0.4e9, "dur_ns": 5e9, "sid": 3, "prompt_len": 999}],
               [{"t0_ns": 1.4e9, "dur_ns": 3e9, "parent": 7},
                {"t0_ns": 0.4e9, "dur_ns": 9e9, "parent": 3}])
    assert MAN.reader("sparse_pages_read_share_pct")(ctx) == pytest.approx(
        100 * 7680 / 38400)
    assert MAN.reader("admit_prefill_ms_per_prompt_token.sala")(ctx) == \
        pytest.approx(3000 / 20000)
    # a program without the counters or the spans (the parent): nothing
    bare = _ctx([{"t0_ns": 1.1e9, "dur_ns": 1, "active": 8}])
    for name in ("sparse_pages_read_share_pct",
                 "sala_decode_hbm_roofline_pct",
                 "admit_prefill_ms_per_prompt_token.sala"):
        assert MAN.reader(name)(bare) is None
    ctx.update({"peaks": {"hbm_bytes_per_s": 819e9},
                "trace": {"modules": {"jit__sala_step": [0.03, 0.03]}}})
    need = sala_bytes.decode_min_bytes(ctx["shape"], 7.5, 3840, 19200)
    got = MAN.reader("sala_decode_hbm_roofline_pct")(ctx)
    assert got == pytest.approx(100 * need / 819e9 / 0.03)
    assert 35 < got < 50


def test_fill_reader():
    Req = argparse.Namespace
    sent = [Req(req=Req(prompt_len=20000), times=[5.0, 6.0]),
            Req(req=Req(prompt_len=30000), times=[9.5]),
            Req(req=Req(prompt_len=40000), times=[12.0]),      # a successor
            Req(req=Req(prompt_len=12345), times=[])]
    ctx = {"run": {"fill_s": 8.0, "t_open": 10.0, "sent": sent}}
    assert MAN.reader("fill_ms_per_prompt_token")(ctx) == pytest.approx(
        8000.0 / 50000)
    assert MAN.reader("fill_ms_per_prompt_token")(
        {"run": {"sent": sent, "t_open": 10.0}}) is None      # an open loop


def test_the_cell_lists_what_its_readers_find():
    names = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert names == {
        "sala_decode_hbm_roofline_pct", "sparse_pages_read_share_pct",
        "fill_ms_per_prompt_token", "admit_prefill_ms_per_prompt_token.sala",
        "itl_p99_ms.sala", "batch_occupancy",
        "decode_step_device_ms", "device_idle_pct.decode", "setup_weights_s",
        "setup_programs_s"}
    # ``test_program_spans.py`` holds these three to the decode cell alone;
    # the two rooflines count other families' bytes; ``step_host_ms`` is a
    # call's wall minus the device time, the host's part of a step only
    # where the call waits for the step it dispatched: this family's waits
    # for the one before (it read -0.63 ms on the chip, PERF.md PR 32)
    assert not {"step_prepare_ms", "step_fetch_ms", "pump_handoff_ms",
                "step_host_ms", "pump_ms_per_token",
                "decode_hbm_roofline_pct",
                "nemotron_decode_hbm_roofline_pct"} & names
    e2e = {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")}
    # the tail is per layer (``itl_p99_ms.sala`` says why), and with it goes
    # ``pump_ms_per_token``, which moves the end-to-end tail
    assert e2e == {"out_tokens_per_s", "setup_s"}
    for m in MAN.metrics_for(CELL, "per_layer"):
        assert m["moves"] in e2e and os.path.isfile(MAN.reader_path(m["name"]))
    cell = MAN.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longctx-decode-8"
    mix = MAN.traffic(cell["traffic"])
    assert (mix["clients"], mix["cycle"], mix["loop"], mix["start"]) == \
        (8, 16, "closed", 5)
    assert CONFIG["engine"]["max_slots"] == mix["clients"]
    first = tg.closed_loop_requests(mix)[:8]
    assert sum(r.output_len < 1000 for r in first) >= 2
    assert all(r.prompt_len > CONFIG["sparse_dense_len"] for r in first)
    assert sum(r.prompt_len for r in first) == 183124
    hi = tg.length_range(mix["prompt_len"])[1] + \
        tg.length_range(mix["output_len"])[1]
    assert hi + 1 <= CONFIG["engine"]["max_len"]
    # the eight longest of the cycle fit the pool together
    worst = sorted((r.prompt_len + r.output_len + 1
                    for r in tg.closed_loop_requests(mix)), reverse=True)[:8]
    assert sum(-(-n // 64) for n in worst) < CONFIG["engine"]["num_pages"]


# ---------------------------------------------------------------- rehearsal
def test_rehearsal_run_of_the_cell_end_to_end(capfd):
    cell = MAN.cell(CELL)
    args = argparse.Namespace(seed=3_200_000_011, seconds=3.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(MAN, cell, args, time.time())
    out, err = capfd.readouterr()
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert m["batch_occupancy"]["value"] > 7
    # 6 of ~200-650 pages a slot
    assert 0 < m["sparse_pages_read_share_pct"]["value"] < 5
    assert m["fill_ms_per_prompt_token"]["value"] > 0
    assert m["itl_p99_ms.sala"]["value"] > 0
    assert "compared: selection_far_disagreements 0.00000 (limit 0.00000)" \
        in err
    assert "probe_max_margin" in out and "probe_prefill_rms_err" in out
    assert "warm-up of prompts [40960]" in out
