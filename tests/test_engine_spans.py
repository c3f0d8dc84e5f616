"""The flight recorder inside the engine and the pump (ISSUE 24).

``events.span`` rows from ``PagedEngine`` and
``LLMServer._engine_loop`` on the monotonic clock, their nesting, what they
cost a disabled recorder (nothing, not even a clock), the profiler
annotations they double as, and the spill file that lets the rows outlive
the GCS (``drain`` -> ``spill`` -> ``read_spill``).
"""

import asyncio
import dataclasses
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models import LlamaConfig, init_params
from ray_tpu.models.paged import PagedEngine
from ray_tpu.util import events

STEP_PHASES = ["serve.step.prepare", "serve.step.dispatch",
               "serve.step.fetch", "serve.step.emit"]
ADMIT_PHASES = ["serve.admit.prefill", "serve.admit.scatter",
                "serve.admit.sample"]
REQS = {"req-aaaa-long-id": ([1, 2, 3, 4], 6), "req-bbbb": ([7, 8], 3),
        "req-cccc": ([10, 11, 12, 13, 14, 15, 16, 17, 18], 5)}


@pytest.fixture(autouse=True)
def _clean_ring(prompt_device):
    # ``prompt_device``: each call fetches the step it dispatched, so a
    # ``serve.engine.step`` row here is one step's, with its four phases
    events.reset()
    yield
    events._enabled = True
    events.reset()


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(model):
    cfg, params = model
    return PagedEngine(params, cfg, max_slots=2, num_pages=24,
                       page_size=8, max_len=64)


def _drive(eng):
    """-> (results, calls of step(), tokens emitted)."""
    for rid, (prompt, n) in REQS.items():
        eng.submit(rid, prompt, max_new_tokens=n)
    calls = tokens = 0
    results = {rid: [] for rid in REQS}
    while eng.has_work():
        calls += 1
        for rid, tok in eng.step():
            if tok is not None:
                tokens += 1
                results[rid].append(tok)
    return results, calls, tokens


def _rows():
    rows, _ = events.drain()
    return [events.row_to_dict(r) for r in rows]


def _by_name(rows):
    by = {}
    for r in rows:
        by.setdefault(r["name"], []).append(r["fields"])
    return by


def _inside(child, parent):
    c, p = child["fields"], parent["fields"]
    return (p["t0_ns"] <= c["t0_ns"]
            and c["t0_ns"] + c["dur_ns"] <= p["t0_ns"] + p["dur_ns"])


# ----------------------------------------------------- the span primitive

def test_span_nests_and_stamps_one_monotonic_clock():
    before = time.perf_counter_ns()
    with events.span("serve.engine.step", "serve", k=7) as outer:
        with events.span("serve.step.prepare", "serve") as inner:
            pass
        outer.set(tokens=3)
    events.span_done("serve.pump.deliver", "serve", before, tokens=2)
    after = time.perf_counter_ns()
    prepare, step, deliver = _rows()
    assert [r["name"] for r in (prepare, step, deliver)] == [
        "serve.step.prepare", "serve.engine.step", "serve.pump.deliver"]
    assert step["fields"]["k"] == 7 and step["fields"]["tokens"] == 3
    assert step["fields"]["sid"] == outer.sid != inner.sid
    assert prepare["fields"]["parent"] == outer.sid
    assert step["fields"]["parent"] == 0 == deliver["fields"]["parent"]
    assert _inside(prepare, step)
    for r in (prepare, step, deliver):
        f = r["fields"]
        assert isinstance(f["t0_ns"], int) and isinstance(f["dur_ns"], int)
        assert before <= f["t0_ns"] <= f["t0_ns"] + f["dur_ns"] <= after
        assert r["dur"] == pytest.approx(f["dur_ns"] / 1e9)   # wall fields
        assert abs(r["ts"] - time.time()) < 60                 # stay wall
    assert deliver["fields"]["t0_ns"] == before


def test_disabled_span_reads_no_clock_and_records_nothing():
    events._enabled = False
    with events.span("serve.engine.step", "serve", k=0) as sp:
        sp.set(tokens=1)
        with events.span("serve.step.prepare", "serve") as inner:
            pass
    events.span_done("serve.pump.deliver", "serve", 1, tokens=1)
    assert sp.sid == sp.t0_ns == inner.t0_ns == 0
    assert events._current_span.get() == 0
    events._enabled = True
    assert _rows() == []


# -------------------------------------------------------- the engine's rows

def test_step_rows_count_calls_and_tokens(model):
    _, calls, tokens = _drive(_engine(model))
    rows = _rows()
    steps = [r for r in rows if r["name"] == "serve.engine.step"]
    assert len(steps) == calls
    assert [s["fields"]["k"] for s in steps] == list(range(calls))
    assert sum(s["fields"]["tokens"] for s in steps) == tokens \
        == sum(n for _, n in REQS.values())
    assert sum(s["fields"]["admitted"] for s in steps) == len(REQS)
    assert all(0 <= s["fields"]["active"] <= 2 for s in steps)
    admits = [r for r in rows if r["name"] == "serve.engine.admit"]
    assert [a["fields"]["rid"] for a in admits] == [r[:8] for r in REQS]
    for a, (prompt, _) in zip(admits, REQS.values()):
        f = a["fields"]
        assert f["waited_ns"] >= 0 and f["prompt_len"] == len(prompt)
        assert f["bucket"] == 16
        parent = next(s for s in steps if s["fields"]["sid"] == f["parent"])
        assert _inside(a, parent)


def test_step_rows_count_the_slots_that_sample(model):
    """``sampling`` on the step row is the active slots with a
    temperature above 0: 0 means the step program took the argmax side of
    ``_pick_tokens``. The slot a finished sampling request leaves EMPTY
    keeps its temperature (nothing clears it) and must not count. The
    tokens are the parent commit's for this seed: the keys are split as
    before the ``cond``."""
    eng = _engine(model)
    eng.submit("req-samp", [1, 2, 3, 4], max_new_tokens=4, temperature=0.8,
               top_k=10, top_p=0.9, seed=5)
    eng.submit("req-greedy", [7, 8], max_new_tokens=9)
    got = eng.run_to_completion()
    assert got == {"req-samp": [63, 78, 19, 78],
                   "req-greedy": [57, 32, 85, 85, 85, 85, 85, 85, 85]}
    steps = [r["fields"] for r in _rows() if r["name"] == "serve.engine.step"]
    assert [f["sampling"] for f in steps] == [1, 1, 1, 0, 0, 0, 0, 0]
    assert [f["active"] for f in steps] == [2, 2, 2, 1, 1, 1, 1, 1]
    assert eng.slots == [None, None] and eng.temps[0] > 0   # left stale


def test_step_rows_count_the_positions_the_read_gathers(model):
    """``kv_positions_read`` / ``kv_positions_live`` on the step row: what
    ``paged_attention`` gathers of the pools (each active slot's positions
    before its query's own, in whole blocks of table columns) beside what of
    it is live. One request crosses a block's edge while it decodes, a short
    one beside it leaves: read >= live on every row, read is whole blocks and
    at most the active slots' (padded) tables, and it grows by one block on
    the row whose query is the first to see a block's worth of positions
    before it, and by no other."""
    cfg, params = model
    cfg = type(cfg)(**{**cfg.__dict__, "max_seq_len": 512})
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=80, page_size=16,
                      max_len=512)
    block = eng._read_block
    assert block < eng.max_len
    eng.submit("req-long", [1 + i % 95 for i in range(block - 2)],
               max_new_tokens=6)
    eng.submit("req-short", [7, 8, 9], max_new_tokens=2)
    eng.run_to_completion()
    steps = [r["fields"] for r in _rows() if r["name"] == "serve.engine.step"]
    decoded = [f for f in steps if f["active"]]
    assert len(decoded) == 5 and all(
        "kv_positions_read" not in f for f in steps if not f["active"])
    width = -(-eng.max_len // block) * block
    for f in decoded:
        assert f["kv_positions_live"] <= f["kv_positions_read"] \
            <= width * f["active"]
        assert f["kv_positions_read"] % block == 0
    # the long request's positions before its query: block - 2 .. block + 2;
    # the short one's 3 beside it on the first row (its second token ends it)
    assert [f["kv_positions_live"] for f in decoded] == [
        block - 2 + 3, block - 1, block, block + 1, block + 2]
    assert [f["kv_positions_read"] for f in decoded] == [
        2 * block, block, block, 2 * block, 2 * block]


@pytest.fixture(scope="module")
def conv_model():
    from ray_tpu.models import lfm2_moe as lm

    cfg = lm.LFM2_MOE_DEBUG     # conv conv attn conv conv conv; chunk 16
    return cfg, jax.jit(lambda k: lm.init_params(cfg, k))(
        jax.random.PRNGKey(0))


def _conv_rows(conv_model, reqs):
    cfg, params = conv_model
    eng = PagedEngine(params, cfg, max_slots=3, num_pages=64, page_size=4,
                      max_len=96)
    for rid, (prompt, n) in reqs.items():
        eng.submit(rid, prompt, max_new_tokens=n)
    eng.run_to_completion()
    return eng, _by_name(_rows())


def test_conv_family_step_rows_carry_the_expert_and_context_counters(
        conv_model):
    """The sixth family's fields of the step row ride with the tokens in the
    one transfer: ``experts_hit`` (summed over the expert layers),
    ``expert_tokens_max``, ``moe_rows`` (the active rows), ``context_
    positions`` (what the active slots' queries attend in an attention
    layer) and ``landed``; a row on which no step landed has none."""
    cfg = conv_model[0]
    eng, by = _conv_rows(conv_model, {
        "req-aaaa-long": ([1 + i % 90 for i in range(45)], 6),
        "req-bbbb-short": ([7, 8, 9, 10, 11, 12, 13, 14, 15], 6)})
    steps = by["serve.engine.step"]
    landed = [f for f in steps if "context_positions" in f]
    assert len(landed) == 5 == len([f for f in steps if f["active"]])
    assert steps[0]["admitted"] == 2 and all(
        "experts_hit" not in f for f in steps if not f["active"])
    for k, f in enumerate(landed):
        assert f["landed"] == 1 and f["moe_rows"] == f["active"] == 2
        # positions 45 + k and 9 + k, and the row the step wrote
        assert f["context_positions"] == 45 + 9 + 2 * (k + 1)
        # two rows x top-3 over sixteen experts, four expert layers
        assert 3 * cfg.n_moe_layers <= f["experts_hit"] \
            <= 6 * cfg.n_moe_layers
        assert 1 <= f["expert_tokens_max"] <= 2
        # the dense family's count of what the blocked read gathers stands
        # beside them: this family's step reads through the same read
        assert f["kv_positions_live"] == 45 + 9 + 2 * k
    assert eng.last_routing.shape == (cfg.n_moe_layers, 3, cfg.top_k)


def test_conv_family_admit_rows_count_chunks_and_conv_rows(conv_model):
    """``serve.admit.prefill`` says ``chunks`` (the prompt over the chunk of
    16, rounded up), ``serve.admit.state`` the conv layers written and their
    ``conv_rows`` (two rows a layer), each one dispatch, all inside their
    admission."""
    cfg = conv_model[0]
    _, by = _conv_rows(conv_model, {
        "req-aaaa-long": ([1 + i % 90 for i in range(45)], 3),
        "req-bbbb-short": ([7, 8, 9], 3), "req-cccc": ([5] * 16, 3)})
    admits, prefill = by["serve.engine.admit"], by["serve.admit.prefill"]
    assert [p["chunks"] for p in prefill] == [3, 1, 1]
    # a chunk of 16 rows is under the family's GROUPED_FROM_ROWS: no row is
    # grouped by expert, so there is no form of the grouped products to name
    assert not any("experts_form" in p for p in prefill)
    assert [a["bucket"] for a in admits] == [96] * 3    # no bucket: max_len
    state = by["serve.admit.state"]
    assert [(s["layers"], s["conv_rows"], s["dispatches"]) for s in state] \
        == [(cfg.n_conv_layers, 2 * cfg.n_conv_layers, 1)] * 3
    assert cfg.n_conv_layers == 5
    scatter = by["serve.admit.scatter"]
    assert [s["pages"] for s in scatter] == [12, 1, 5]
    assert [p["parent"] for p in prefill] == [a["sid"] for a in admits] \
        == [s["parent"] for s in state] == [s["parent"] for s in scatter]


@pytest.fixture(scope="module")
def mamba_moe_model():
    from ray_tpu.models import granite_moe_hybrid as gm

    # layers 3-6 of the period (mamba, mamba, attention, mamba); chunk 16
    cfg = dataclasses.replace(gm.GRANITE_MOE_HYBRID_DEBUG, n_layers=4,
                              layer_types=("mamba", "mamba", "attention",
                                           "mamba"))
    return cfg, jax.jit(lambda k: gm.init_params(cfg, k))(
        jax.random.PRNGKey(0))


def test_mamba_moe_family_rows_carry_state_bytes_chunks_and_state_rows(
        mamba_moe_model):
    """The seventh family's fields: on the step row ``experts_hit`` (summed
    over the layers: every layer has experts), ``expert_tokens_max``,
    ``moe_rows``, ``context_positions``, ``ssm_state_bytes`` (every active
    row's recurrent state read and written, from shapes, on the host: at the
    benchmark's size it is past an int32) and ``landed``; on the admission
    ``chunks``, and on its state span the Mamba layers written and their
    ``state_rows``."""
    cfg = mamba_moe_model[0]
    eng, by = _conv_rows(mamba_moe_model, {
        "req-aaaa-long": ([1 + i % 90 for i in range(45)], 6),
        "req-bbbb-short": ([7, 8, 9, 10, 11, 12, 13, 14, 15], 6)})
    steps = by["serve.engine.step"]
    landed = [f for f in steps if "ssm_state_bytes" in f]
    assert len(landed) == 5 == len([f for f in steps if f["active"]])
    per_slot = 3 * (4 * cfg.d_inner * cfg.ssm_state
                    + 4 * (cfg.conv_kernel - 1) * cfg.conv_dim)  # float32 toy
    assert cfg.slot_state_bytes == per_slot
    for k, f in enumerate(landed):
        assert f["landed"] == 1 and f["moe_rows"] == f["active"] == 2
        assert f["ssm_state_bytes"] == 2 * 2 * per_slot
        assert f["context_positions"] == 45 + 9 + 2 * (k + 1)
        # two rows x top-3 over twelve held experts, four layers
        assert 3 * cfg.n_layers <= f["experts_hit"] <= 6 * cfg.n_layers
        assert 1 <= f["expert_tokens_max"] <= 2
        assert f["kv_positions_live"] == 45 + 9 + 2 * k
    assert eng.last_routing.shape == (cfg.n_layers, 3, cfg.top_k)
    assert [(p["chunks"], p.get("experts_form"))
            for p in by["serve.admit.prefill"]] == [(3, None), (1, None)]
    assert [(s["layers"], s["state_rows"], s["dispatches"])
            for s in by["serve.admit.state"]] == [
        (3, 3 * (cfg.d_inner + cfg.conv_kernel - 1), 1)] * 2
    assert [s["parent"] for s in by["serve.admit.state"]] == \
        [a["sid"] for a in by["serve.engine.admit"]]


@pytest.mark.parametrize("family, config, from_rows, on_tpu, form", [
    ("lfm2_moe", "Lfm2MoeConfig", None, True, "kernel"),
    ("lfm2_moe", "Lfm2MoeConfig", None, False, "ragged"),
    ("granite_moe_hybrid", "GraniteMoeHybridConfig", None, True, "kernel"),
    ("cohere2_moe", "Cohere2MoeConfig", None, True, "kernel"),
    ("longcat_flash", "LongcatFlashConfig", None, True, "kernel"),
    ("longcat_flash", "LongcatFlashConfig", None, False, "ragged"),
    ("granite_moe_hybrid", "GraniteMoeHybridConfig", 4096, True, None)])
def test_a_chunks_experts_form_follows_widths_and_platform(
        family, config, from_rows, on_tpu, form, monkeypatch):
    """``_Family.experts_form`` of the four families whose chunks group their
    rows by expert, at their published widths (each config class's own
    defaults: a chunk of 2048 rows): the kernel on a TPU, ``ragged`` off it,
    none where a chunk has fewer rows than ``GROUPED_FROM_ROWS``. It is what
    the engine asks once and every ``serve.admit.prefill`` row says."""
    import importlib

    from ray_tpu.models import paged
    from ray_tpu.ops import attention

    mod = importlib.import_module(f"ray_tpu.models.{family}")
    cfg = getattr(mod, config)()
    assert cfg.prefill_chunk == 2048 and cfg.dtype == jnp.bfloat16
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    if from_rows:
        monkeypatch.setattr(mod, "GROUPED_FROM_ROWS", from_rows)
    assert paged._FAMILIES[type(cfg)].experts_form(cfg) == form


def test_the_prefill_row_names_the_form_only_where_experts_are_grouped(
        model, conv_model, monkeypatch):
    """The field is the family's answer at the engine's own configuration
    (toy rows grouped by ``lax.ragged_dot`` once ``GROUPED_FROM_ROWS`` lets
    a chunk of 16 group), and a family without routed experts has none."""
    from ray_tpu.models import lfm2_moe as lm, paged

    monkeypatch.setattr(lm, "GROUPED_FROM_ROWS", 16)
    _, by = _conv_rows(conv_model, {"req-aaaa": ([1 + i for i in range(20)],
                                                 2)})
    assert [p["experts_form"] for p in by["serve.admit.prefill"]] \
        == ["ragged"] == [paged._FAMILIES[lm.Lfm2MoeConfig].experts_form(
            conv_model[0])]
    events.reset()
    _drive(_engine(model))
    assert all("experts_form" not in r["fields"] and "chunks"
               not in r["fields"] for r in _rows()
               if r["name"] == "serve.admit.prefill")


def test_a_chunked_admissions_prefill_row_names_its_attentions_form(
        conv_model, mamba_moe_model, model):
    """``serve.admit.prefill`` of a family whose chunk calls ``cohere2_moe.
    _prompt_attention`` says ``prompt_attn_form``, the answer of the function
    that picks the form (``ops.attention.chunk_attention_form``) at the
    engine's chunk and ``max_len``: ``loop`` on the CPU, for every chunk of
    the admission's row; the dense family's row has no such field."""
    from ray_tpu.models import paged

    for family in (conv_model, mamba_moe_model):
        events.reset()
        eng, by = _conv_rows(family, {
            "req-aaaa-long": ([1 + i % 90 for i in range(45)], 2),
            "req-bbbb": ([7, 8, 9], 2)})
        assert [(p["chunks"], p["prompt_attn_form"])
                for p in by["serve.admit.prefill"]] \
            == [(3, "loop"), (1, "loop")]
        assert paged._prompt_attn_form(family[0], eng.max_len) == "loop"
    events.reset()
    _drive(_engine(model))
    assert not any("prompt_attn_form" in r["fields"] for r in _rows())


def test_paged_admit_phases_nest_in_order_and_carry_the_rid(model):
    _drive(_engine(model))
    rows = _rows()
    admits = [r for r in rows if r["name"] == "serve.engine.admit"]
    assert len(admits) == len(REQS)
    for a in admits:
        kids = [r for r in rows
                if r["fields"].get("parent") == a["fields"]["sid"]]
        assert [k["name"] for k in kids] == ADMIT_PHASES
        assert all(k["fields"]["rid"] == a["fields"]["rid"] for k in kids)
        assert all(_inside(k, a) for k in kids)
        starts = [k["fields"]["t0_ns"] for k in kids]
        ends = [k["fields"]["t0_ns"] + k["fields"]["dur_ns"] for k in kids]
        assert starts == sorted(starts) and all(
            e <= s for e, s in zip(ends, starts[1:]))
        assert kids[1]["fields"]["pages"] == a["fields"]["own_pages"] >= 1
        # one program per admission, however many pages (ISSUE 26)
        assert kids[1]["fields"]["dispatches"] == 1
        assert a["fields"]["shared_pages"] == 0
    for s in (r for r in rows if r["name"] == "serve.engine.step"):
        kids = [r["name"] for r in rows
                if r["fields"].get("parent") == s["fields"]["sid"]
                and r["name"] in STEP_PHASES]
        # a step that decoded has the four phases, in order; one that only
        # reaped has its prepare alone
        assert kids == (STEP_PHASES if s["fields"]["active"]
                        else STEP_PHASES[:1])
        assert "free_pages" in s["fields"] and "preempted" in s["fields"]


def test_phases_of_a_call_with_steps_in_flight(model, slow_device):
    """Ahead of the device a call that decodes has its prepare and its
    dispatch, and the fetch and the emit of the OLDEST step once enough are
    in flight; a call that only lands (a request waits beside a free slot)
    has a fetch and an emit alone; ``active`` is what the call dispatched,
    ``tokens`` what it landed."""
    eng = _engine(model)
    eng.submit("req-a", [1, 2, 3, 4], max_new_tokens=9)
    for _ in range(3):
        eng.step()
    eng.submit("req-b", [7, 8], max_new_tokens=3)
    eng.step()
    rows = _rows()
    steps = [r for r in rows if r["name"] == "serve.engine.step"]
    kids = [[r["name"] for r in rows
             if r["fields"].get("parent") == s["fields"]["sid"]
             and r["name"] in STEP_PHASES] for s in steps]
    assert kids == [STEP_PHASES[:2], STEP_PHASES[:2], STEP_PHASES,
                    STEP_PHASES[2:]]
    assert [(s["fields"]["active"], s["fields"]["tokens"])
            for s in steps] == [(1, 1), (1, 0), (1, 1), (0, 1)]
    while eng.has_work():
        eng.step()


def test_preemption_is_counted_on_the_step_row(model):
    cfg, params = model
    eng = PagedEngine(params, cfg, max_slots=2, num_pages=6, page_size=4,
                      max_len=32)
    eng.submit("p1", [1, 2, 3], max_new_tokens=9)
    eng.submit("p2", [4, 5, 6], max_new_tokens=9)
    got = eng.run_to_completion()
    assert len(got["p1"]) == len(got["p2"]) == 9
    steps = [r for r in _rows() if r["name"] == "serve.engine.step"]
    assert sum(s["fields"]["preempted"] for s in steps) >= 1
    # a preempted request is admitted again: one admit row more each time
    assert sum(s["fields"]["admitted"] for s in steps) == 2 + sum(
        s["fields"]["preempted"] for s in steps)


def test_greedy_identical_with_recorder_on_and_off(model):
    on, _, _ = _drive(_engine(model))
    assert _rows()
    events._enabled = False
    off, _, _ = _drive(_engine(model))
    events._enabled = True
    assert _rows() == []        # off yields no rows
    assert on == off


def test_spans_are_profiler_annotations_under_a_trace(model, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(model)
    with jax.profiler.trace(str(tmp_path)):
        _drive(eng)
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("ray_tpu/")}
    assert "ray_tpu/serve.engine.step" in names
    assert {"ray_tpu/" + n for n in STEP_PHASES + ADMIT_PHASES} <= names
    assert "ray_tpu/serve.engine.admit" in names


# ------------------------------------------- a dispatched step's own row

def _two_held_slots(model, third=True):
    """Two streams hold both slots (the cap is ``_STEPS_AHEAD``), a third
    waits for the first to end: -> (engine, flights left after each call)."""
    eng = _engine(model)
    eng.submit("req-a", [1, 2, 3, 4], max_new_tokens=16)
    eng.submit("req-b", [7, 8], max_new_tokens=40)
    if third:
        eng.submit("req-c", [5, 6, 9], max_new_tokens=6)
    left = []
    while eng.has_work():
        eng.step()
        left.append(len(eng._flights))
    return eng, left


def test_the_flights_of_a_run_join_dispatch_to_landing(model, slow_device):
    """One ``serve.step.flight`` row a dispatched step: ``step`` runs from 0
    without a hole, each has one dispatch, one fetch and one emit row of its
    ``step``, begins where its dispatch span began and ends inside its fetch
    span; ``call`` and ``landed_by`` are ``serve.engine.step`` rows, the one
    that holds its dispatch and the one that holds its fetch."""
    eng, _ = _two_held_slots(model)
    by = _by_name(_rows())
    flights = by["serve.step.flight"]
    assert [f["step"] for f in flights] == list(range(eng._dispatched))
    assert eng._dispatched == 39     # req-b's 40 tokens less its first
    calls = {f["sid"]: f for f in by["serve.engine.step"]}
    for name in ("serve.step.dispatch", "serve.step.fetch",
                 "serve.step.emit"):
        assert sorted(f["step"] for f in by[name]) \
            == list(range(eng._dispatched))
    dispatch = {f["step"]: f for f in by["serve.step.dispatch"]}
    fetch = {f["step"]: f for f in by["serve.step.fetch"]}
    for f in flights:
        d, got = dispatch[f["step"]], fetch[f["step"]]
        assert f["call"] in calls and f["landed_by"] in calls
        assert d["parent"] == f["call"] and got["parent"] == f["landed_by"]
        assert f["parent"] == got["sid"]    # written inside its fetch span
        assert f["t0_ns"] == d["t0_ns"] and f["depth"] == d["depth"]
        landing = f["t0_ns"] + f["dur_ns"]
        assert got["t0_ns"] + f["wait_ns"] <= landing \
            <= got["t0_ns"] + got["dur_ns"]
        assert 0 <= f["wait_ns"] <= got["dur_ns"] and f["active"] in (1, 2)
    # steps stay in flight: from the second on, another call lands a step
    # than the one that dispatched it
    assert flights[0]["landed_by"] != flights[0]["call"]
    assert all(f["landed_by"] > f["call"] for f in flights)
    landings = [f["t0_ns"] + f["dur_ns"] for f in flights]
    assert landings == sorted(landings)


def test_a_flight_row_says_how_many_tokens_it_landed(model, slow_device):
    """One a slot from a step that commits one token a slot; up to two from a
    family whose model drafts (``tests/test_deepseek_v3.py`` holds the
    counters that ride with them)."""
    import dataclasses

    from ray_tpu.models import deepseek_v3 as ds

    _two_held_slots(model)
    flights = _by_name(_rows())["serve.step.flight"]
    assert flights and all(f["tokens"] == f["active"] for f in flights)
    cfg = dataclasses.replace(ds.DEEPSEEK_V3_DEBUG, vocab_size=6)
    eng = PagedEngine(ds.init_params(cfg, jax.random.PRNGKey(1)), cfg,
                      max_slots=2, num_pages=64, page_size=4, max_len=96)
    for rid, n in (("a", 9), ("b", 30)):
        eng.submit(rid, [1, 2, 3, 4, 5], max_new_tokens=n, temperature=1.0,
                   seed=n)
    got = eng.run_to_completion()
    assert [len(v) for v in got.values()] == [9, 30]
    by = _by_name(_rows())
    flights = by["serve.step.flight"]
    assert all(f["active"] <= f["tokens"] <= 2 * f["active"]
               for f in flights)
    assert any(f["tokens"] > f["active"] for f in flights)
    emits = {f["step"]: f["tokens"] for f in by["serve.step.emit"]}
    assert all(emits[f["step"]] == f["tokens"] for f in flights)
    # what the calls' rows count is what reached the streams: a token past a
    # stream's budget is landed and dropped
    streamed = sum(f["tokens"] for f in by["serve.engine.step"])
    assert streamed == 9 + 30
    assert streamed - 2 <= sum(f["tokens"] for f in flights) <= streamed


def test_depth_climbs_to_the_cap_and_falls_to_0_after_an_admission(
        model, slow_device):
    from ray_tpu.models import paged

    _two_held_slots(model)
    flights = _by_name(_rows())["serve.step.flight"]
    cap = paged._STEPS_AHEAD
    depths = [f["depth"] for f in flights]
    assert depths[:cap + 3] == list(range(cap + 1)) + [cap, cap]
    assert max(depths) == cap
    assert flights[0]["admitted"] == 2      # both before the first dispatch
    later = [f for f in flights[1:] if f["admitted"]]
    # req-c is admitted when req-a has ended, with nothing in flight
    assert [(f["admitted"], f["depth"], f["active"]) for f in later] \
        == [(1, 0, 2)]
    at = flights.index(later[0])
    assert depths[at - 1] > 0 and depths[at:at + 4] == [0, 1, 2, 3]
    assert sum(f["admitted"] for f in flights) == 3


def test_the_step_row_counts_the_flights_a_call_leaves(model, slow_device):
    eng, left = _two_held_slots(model, third=False)
    steps = _by_name(_rows())["serve.engine.step"]
    assert [f["flights"] for f in steps] == left
    assert max(left) == 10 and left[-1] == 0 == len(eng._flights)


def test_a_step_that_has_ended_lands_in_the_call_that_dispatched_it(model):
    """``prompt_device`` (this file's default): nothing stays in flight."""
    _drive(_engine(model))
    by = _by_name(_rows())
    flights = by["serve.step.flight"]
    assert flights and len(flights) == len(by["serve.step.dispatch"])
    assert all(f["depth"] == 0 and f["landed_by"] == f["call"]
               for f in flights)
    assert all(f["flights"] == 0 for f in by["serve.engine.step"])


def test_a_stream_with_an_eos_id_is_stepped_with_nothing_in_flight(
        model, slow_device):
    eng = _engine(model)
    eng.submit("req-eos", [1, 2, 3, 4], max_new_tokens=7, eos_id=10**6)
    eng.run_to_completion()
    flights = _by_name(_rows())["serve.step.flight"]
    assert [f["step"] for f in flights] == list(range(6))
    assert all(f["depth"] == 0 and f["landed_by"] == f["call"]
               and f["active"] == 1 for f in flights)
    assert [f["admitted"] for f in flights] == [1, 0, 0, 0, 0, 0]


def test_the_recorder_off_writes_no_flight_row_and_reads_no_clock(
        model, slow_device, monkeypatch):
    from ray_tpu.models import paged

    events._enabled = False
    eng = _engine(model)
    eng.submit("req-a", [1, 2, 3, 4], max_new_tokens=9)   # stamps its arrival
    eng.submit("req-b", [7, 8], max_new_tokens=9)
    reads = []
    clock = time.perf_counter_ns
    monkeypatch.setattr(paged.time, "perf_counter_ns",
                        lambda: reads.append(1) or clock())
    for _ in range(4):
        eng.step()
    assert len(eng._flights) == 4 and not reads
    assert all(f.t0_ns == 0 == f.call for f in eng._flights)
    assert [f.step for f in eng._flights] == [0, 1, 2, 3]
    # switched on with steps in flight: those have no dispatch instant and
    # write no row; the steps dispatched from here on do
    events._enabled = True
    while eng.has_work():
        eng.step()
    flights = _by_name(_rows())["serve.step.flight"]
    assert [f["step"] for f in flights] == list(range(4, eng._dispatched))
    assert reads


def test_greedy_identical_with_recorder_on_and_off_ahead_of_the_device(
        model, slow_device):
    on, calls_on, _ = _drive(_engine(model))
    assert any(r["name"] == "serve.step.flight" for r in _rows())
    events._enabled = False
    off, calls_off, _ = _drive(_engine(model))
    events._enabled = True
    assert _rows() == [] and on == off and calls_on == calls_off


# ------------------------------------------------------------ the pump

def test_pump_and_request_rows_share_the_engine_clock(model):
    from ray_tpu.serve.llm import LLMServer

    cfg, params = model
    server = LLMServer(lambda: (params, cfg), max_slots=2, max_len=64,
                       num_pages=24, page_size=8)

    async def run():
        async def stream():
            return [t async for t in server._stream(
                {"prompt": [5, 6, 7], "max_new_tokens": 4, "stream": True})]

        return await asyncio.gather(
            server({"prompt": [1, 2, 3, 4], "max_new_tokens": 5}), stream())

    t_before = time.perf_counter_ns()
    unary, streamed = asyncio.run(run())
    assert unary["num_tokens"] == 5 and len(streamed) == 4
    rows = _rows()
    names = [r["name"] for r in rows]
    assert not any(n.endswith(".tokens_done") for n in names)  # unread: gone
    queued = [r for r in rows if r["name"] == "serve.req.queue"]
    first = [r for r in rows if r["name"] == "serve.req.first_token"]
    admits = [r for r in rows if r["name"] == "serve.engine.admit"]
    assert len(queued) == len(first) == len(admits) == 2
    # one identifier from the handler to the engine's phases
    assert {r["fields"]["rid"] for r in queued} \
        == {r["fields"]["rid"] for r in admits} \
        == {r["fields"]["rid"] for r in first}
    for q in queued:
        a = next(r for r in admits
                 if r["fields"]["rid"] == q["fields"]["rid"])
        f = next(r for r in first
                 if r["fields"]["rid"] == q["fields"]["rid"])
        assert t_before <= f["fields"]["t0_ns"] <= q["fields"]["t0_ns"] \
            <= a["fields"]["t0_ns"]
        assert f["fields"]["t0_ns"] + f["fields"]["dur_ns"] \
            >= a["fields"]["t0_ns"] + a["fields"]["dur_ns"]
    delivers = [r for r in rows if r["name"] == "serve.pump.deliver"]
    steps = [r for r in rows if r["name"] == "serve.engine.step"]
    assert sum(d["fields"]["tokens"] for d in delivers) == 9 \
        == sum(s["fields"]["tokens"] for s in steps)
    for d in delivers:
        assert d["fields"]["lock_wait_ns"] >= 0 and d["fields"]["dur_ns"] >= 0
        # the hand-off starts where a step() ended (stamped just after it,
        # in the executor thread)
        assert any(0 <= d["fields"]["t0_ns"]
                   - (s["fields"]["t0_ns"] + s["fields"]["dur_ns"]) < 5e7
                   for s in steps)


def test_one_admission_and_one_step_emit_all_eleven_span_names(model):
    """The names the benchmark's per-layer metrics read. Two new tokens:
    the first comes from the admission, the second from the one step
    that decodes (a single token would show no step phase), whose own
    row, ``serve.step.flight``, is the eleventh name."""
    from ray_tpu.serve.llm import LLMServer

    cfg, params = model
    server = LLMServer(lambda: (params, cfg), max_slots=2, max_len=64,
                       num_pages=24, page_size=8)
    out = asyncio.run(server({"prompt": list(range(1, 20)),
                              "max_new_tokens": 2}))
    assert out["num_tokens"] == 2
    rows = _rows()
    assert {r["name"] for r in rows if r["name"].startswith(
        ("serve.engine.", "serve.step.", "serve.admit.", "serve.pump."))} \
        == {"serve.engine.step", "serve.engine.admit", "serve.pump.deliver",
            "serve.step.flight", *STEP_PHASES, *ADMIT_PHASES}
    admit, = [r for r in rows if r["name"] == "serve.engine.admit"]
    scatter, = [r for r in rows if r["name"] == "serve.admit.scatter"]
    assert scatter["fields"]["parent"] == admit["fields"]["sid"]
    assert scatter["fields"]["rid"] == admit["fields"]["rid"]
    assert scatter["fields"]["dispatches"] == 1
    assert scatter["fields"]["pages"] == admit["fields"]["own_pages"] == 3
    assert sum(r["name"] == "serve.step.dispatch" for r in rows) == 1


# ------------------------------------------------------------- the spill

def _some_rows(n=3):
    for i in range(n):
        with events.span("serve.engine.step", "serve", k=i):
            pass
    events.emit("serve.req.queue", plane="serve", rid="ab", oid=b"\x01\x02")
    rows, _ = events.drain()
    return rows


def test_spill_round_trip(tmp_path):
    rows = _some_rows()
    wrote = events.spill(rows, str(tmp_path), pid=4242)
    path = events.spill_path(str(tmp_path), 4242)
    assert path.endswith(os.path.join("logs", "events", "plane-4242.jsonl"))
    assert wrote == os.path.getsize(path) > 0
    back = events.read_spill(pid=4242, session_dir=str(tmp_path))
    assert [r["name"] for r in back] == [r[1] for r in rows]
    assert [r["fields"].get("k") for r in back] == [0, 1, 2, None]
    assert back[0]["fields"]["t0_ns"] == rows[0][6]["t0_ns"]
    assert back[0]["pid"] == 4242 and back[0]["plane"] == "serve"
    assert back[3]["fields"]["oid"] == "0102"       # bytes leave as hex
    # a second batch appends; another pid's file is another list
    events.spill(_some_rows(1), str(tmp_path), pid=4242)
    events.spill(_some_rows(1), str(tmp_path), pid=77)
    assert len(events.read_spill(pid=4242, session_dir=str(tmp_path))) == 6
    assert len(events.read_spill(pid=77, session_dir=str(tmp_path))) == 2
    assert len(events.read_spill(session_dir=str(tmp_path))) == 8
    assert events.read_spill(pid=5, session_dir=str(tmp_path)) == []
    assert events.read_spill(session_dir=str(tmp_path / "nowhere")) == []
    # a torn last line (the process was killed mid-write) is skipped
    with open(path, "a") as f:
        f.write('[1.0,"serve.engine.step","ser')
    assert len(events.read_spill(pid=4242, session_dir=str(tmp_path))) == 6


def test_spill_is_bounded_by_its_cap(tmp_path):
    cap = events._spill_cap
    events._spill_cap = 4096
    try:
        total = 0
        for _ in range(40):
            total += events.spill(_some_rows(4), str(tmp_path), pid=9)
        folder = os.path.dirname(events.spill_path(str(tmp_path), 9))
        on_disk = sum(os.path.getsize(os.path.join(folder, f))
                      for f in os.listdir(folder))
        one_batch = total // 40
        assert total > 4 * 4096          # far more was written than is kept
        assert on_disk <= 4096 + one_batch
        # two segments at most: the newer, and the older it replaced
        assert "plane-9.jsonl.1" in os.listdir(folder)
        assert set(os.listdir(folder)) <= {"plane-9.jsonl",
                                           "plane-9.jsonl.1"}
        kept = events.read_spill(pid=9, session_dir=str(tmp_path))
        assert 0 < len(kept) < 40 * 5
        # what is kept is the newest, oldest first
        stamps = [r["fields"]["t0_ns"] for r in kept if "t0_ns" in r["fields"]]
        assert stamps == sorted(stamps)
        events._spill_cap = 0            # 0 turns the file off
        assert events.spill(_some_rows(1), str(tmp_path), pid=10) == 0
        assert not os.path.exists(events.spill_path(str(tmp_path), 10))
    finally:
        events._spill_cap = cap


@pytest.mark.parametrize("obstacle", ["read_only", "file_in_the_way"])
def test_spill_raises_nothing_where_it_cannot_write(tmp_path, obstacle):
    session = tmp_path / "session"
    session.mkdir()
    if obstacle == "read_only":
        (session / "logs" / "events").mkdir(parents=True)
        os.chmod(session / "logs" / "events", 0o555)
        if os.access(session / "logs" / "events", os.W_OK):   # root
            os.chmod(session / "logs" / "events", 0o755)
            target = events.spill_path(str(session), 3)
            os.mkdir(target)        # appending to a directory fails too
    else:
        (session / "logs").write_text("not a directory")
    assert events.spill(_some_rows(), str(session), pid=3) == 0
    assert events.read_spill(pid=3, session_dir=str(session)) == []
    assert events.spill(_some_rows(), None) == 0      # no session known


def test_worker_rows_reach_the_session_spill_file():
    """A worker's flush tick appends what it pushes to the GCS; the driver
    reads it back by pid, from its own session and after shutdown too."""
    ray_tpu.init(num_cpus=2, probe_tpu=False)
    try:
        @ray_tpu.remote
        def work():
            from ray_tpu.util import events as ev

            with ev.span("serve.engine.step", "serve", k=41):
                pass
            return os.getpid()

        pid = ray_tpu.get(work.remote())
        assert pid != os.getpid()
        deadline = time.time() + 20
        mine = []
        while time.time() < deadline and not mine:
            mine = [r for r in events.read_spill(pid=pid)
                    if r["name"] == "serve.engine.step"]
            time.sleep(0.1)
        assert mine and mine[0]["fields"]["k"] == 41 and mine[0]["pid"] == pid
        session_dir = ray_tpu._private.worker.global_worker().session_dir
        assert os.path.isfile(events.spill_path(session_dir, pid))
        # the same rows went to the GCS table, as before
        from ray_tpu.util import state

        deadline = time.time() + 20
        while time.time() < deadline and not any(
                e["name"] == "serve.engine.step"
                for e in state.list_plane_events()):
            time.sleep(0.1)
        assert any(e["name"] == "serve.engine.step"
                   and e["fields"]["k"] == 41
                   for e in state.list_plane_events())
    finally:
        ray_tpu.shutdown()
    again = events.read_spill(pid=pid)      # the last session, by default
    assert any(r["fields"].get("k") == 41 for r in again)
    with open(events.spill_path(session_dir, pid)) as f:
        assert json.loads(f.readline())[2] in events.PLANES


# ---------------------------------------------------------- the name gate

def test_event_check_registers_span_names(tmp_path):
    from ray_tpu.analysis.event_check import check_event_paths

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "emit.py").write_text(
        "from ray_tpu.util import events\n"
        "def f(t):\n"
        "    with events.span('serve.engine.step', 'serve'):\n"
        "        pass\n"
        "    events.span_done('serve.pump.deliver', 'serve', t)\n")
    ref = tmp_path / "ref"
    ref.mkdir()
    (ref / "test_x.py").write_text(
        "A = 'serve.engine.step'\nB = 'serve.pump.deliver'\n"
        "C = 'serve.engine.stepp'\n")
    findings = check_event_paths([str(pkg)], [str(ref)])
    assert [(f.line, "engine.stepp" in f.message)
            for f in findings] == [(3, True)]
