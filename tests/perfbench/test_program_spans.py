"""The readers of the program's own spans (``perfbench/program_spans.py`` and
the nine per-layer metrics that use it): each against a synthetic ``ctx`` and
a small spill file in the recorder's row format, the expected value worked
out by hand beside it; None where the program wrote no such rows; a span name
that NO row of the run carries (the program does not write it: its metric is
left out of the line, by name) told apart from rows that exist and a traced
stretch that held none (an empty reading: no result), and from a share of a
roofline, which is never left out; and one rehearsal of
``serve-decode-heavy`` whose traced line holds the new names."""

import argparse
import json
import os
import shutil
import time

import pytest

from perfbench import program_spans as ps
from perfbench.manifest import BENCH_DIR, ROOT, Manifest, layer_values
from perfbench.runners.common import every_listed_metric

MAN = Manifest(ROOT)
PID = 555
S = 10**9          # ns
OFF = -50 * S      # trace clock = perf_counter_ns + OFF


def _row(name, t0, dur, **fields):
    """One line of ``plane-<pid>.jsonl``: the recorder's positional row."""
    fields.update(t0_ns=int(t0), dur_ns=int(dur))
    return [1790000000.0 + t0 / S, name, "serve", "", "", dur / S, fields]


def _admission(sid, t0, prompt_len, waited_ms, prefill_ms, scatter_at,
               scatter_ms, sample_at, sample_ms):
    ms = 10**6
    return [
        _row(ps.PREFILL, t0 + ms, prefill_ms * ms, sid=sid + 1, parent=sid,
             rid="r%d" % sid),
        _row(ps.SCATTER, scatter_at, scatter_ms * ms, sid=sid + 2,
             parent=sid, rid="r%d" % sid, pages=prompt_len // 16 + 1),
        _row(ps.SAMPLE, sample_at, sample_ms * ms, sid=sid + 3, parent=sid,
             rid="r%d" % sid),
        _row(ps.ADMIT, t0, sample_at + sample_ms * ms - t0, sid=sid,
             parent=1, rid="r%d" % sid, prompt_len=prompt_len, bucket=256,
             shared_pages=0, own_pages=prompt_len // 16 + 1,
             waited_ns=waited_ms * ms),
    ]


# The window is [100 s, 110 s) on perf_counter; the trace covers
# [102 s, 108 s) of it (on the trace's clock, OFF away).
ROWS = (
    # before the window opens: counted nowhere
    _admission(30, 99 * S, 999, 500, 80, 99.5 * S, 300, 99.9 * S, 70)
    # A1: in the window, before the trace
    + _admission(10, 101 * S, 100, 4, 20, 101.03 * S, 600, 101.65 * S, 50)
    # A2: in the window and in the trace
    + _admission(20, 105 * S, 50, 10, 10, 105.02 * S, 300, 105.33 * S, 30)
    # A4: begins in the window; its scatter and sample begin after the close
    + _admission(40, 109.9 * S, 10, 6, 5, 110.05 * S, 40, 110.1 * S, 20)
    + [
        # scatters that straddle the trace's end and its start (their
        # admissions are not in the file): 0.1 s and 0.2 s of them are inside
        _row(ps.SCATTER, 107.9 * S, 0.4 * S, sid=90, parent=999, rid="x",
             pages=3),
        _row(ps.SCATTER, 101.9 * S, 0.3 * S, sid=91, parent=998, rid="y",
             pages=2),
        _row(ps.PREPARE, 99.0 * S, 100e6, sid=50, parent=2),    # outside
        _row(ps.PREPARE, 103.0 * S, 1e6, sid=51, parent=3),
        _row(ps.PREPARE, 104.0 * S, 9e6, sid=52, parent=4),
        _row(ps.PREPARE, 106.0 * S, 2e6, sid=53, parent=5),
        _row(ps.FETCH, 103.1 * S, 70e6, sid=54, parent=3),
        _row(ps.FETCH, 104.1 * S, 80e6, sid=55, parent=4),
        _row(ps.FETCH, 111.0 * S, 500e6, sid=56, parent=6),     # outside
        _row(ps.DELIVER, 103.2 * S, 0.2e6, sid=57, parent=0, tokens=16,
             lock_wait_ns=900),
        _row(ps.DELIVER, 104.2 * S, 0.6e6, sid=58, parent=0, tokens=16,
             lock_wait_ns=800),
        _row(ps.DELIVER, 106.2 * S, 0.4e6, sid=59, parent=0, tokens=15,
             lock_wait_ns=700),
        # rows that are not spans ride the same file
        [1790000101.0, "proto.send.frame", "proto", "", "", 0.0,
         {"key": "actor_call", "n": 12, "bytes": 3400, "agg": 1}],
        [1790000102.0, "serve.req.done", "serve", "t", "", 0.25,
         {"deployment": "d"}],
    ])

EXPECTED = {
    # median of the waits of A1, A2, A4: 4, 10, 6 ms
    "engine_queue_wait_ms": 6.0,
    # (20 + 10 + 5) ms over 100 + 50 + 10 prompt tokens
    "admit_prefill_ms_per_prompt_token": 35.0 / 160,
    # (600 + 300 + 40) ms over the same tokens: a phase belongs to its
    # admission, wherever it began
    "admit_scatter_ms_per_prompt_token": 940.0 / 160,
    # samples that BEGAN in the window: A1's 50 and A2's 30
    "admit_sample_ms": 40.0,
    # scatter inside the trace: A2's 0.30 s, 0.10 s and 0.20 s of the two
    # straddlers; the device is busy for 0.03 + 0.10 + 0.02 s of the first and
    # idle in the others: 1 - 0.15 / 0.60
    "admit_scatter_device_idle_pct": 75.0,
    # two executions of the prefill program, 4 + 6 ms, over A2's 50 tokens
    "prefill_device_ms_per_prompt_token": 0.2,
    "step_prepare_ms": 2.0,        # median of 1, 9, 2
    "step_fetch_ms": 75.0,         # median of 70, 80
    "pump_handoff_ms": 0.4,        # median of 0.2, 0.6, 0.4
}


def _ctx(session_dir, traced=True):
    ctx = {"device": {"pid": PID}, "session_dir": str(session_dir),
           "run": {"t_open": 100.0, "t_close": 110.0},
           "trace": None, "host": None}
    if traced:
        ctx["host"] = {"offset_ns": OFF,
                       "window_ns": (102 * S + OFF, 108 * S + OFF)}
        ctx["trace"] = {
            "busy_intervals": [(105.00 * S + OFF, 105.05 * S + OFF),
                               (105.10 * S + OFF, 105.20 * S + OFF),
                               (105.30 * S + OFF, 105.40 * S + OFF)],
            "modules": {"jit__prefill_one(1234)": [0.004, 0.006],
                        "jit__paged_step(99)": [0.08, 0.08]}}
    return ctx


@pytest.fixture()
def session(tmp_path):
    folder = tmp_path / "logs" / "events"
    folder.mkdir(parents=True)
    with open(folder / f"plane-{PID}.jsonl", "w") as f:
        for row in ROWS:
            f.write(json.dumps(row) + "\n")
    # another process's rows are never read
    with open(folder / "plane-556.jsonl", "w") as f:
        f.write(json.dumps(_row(ps.PREPARE, 105 * S, 900e6, sid=1,
                                parent=0)) + "\n")
    return tmp_path


def test_the_new_entries_are_the_nine_and_only_appended():
    names = [m["name"] for m in MAN.doc["per_layer"]]
    at = names.index(list(EXPECTED)[0])   # later PRs append after them
    assert names[at:at + len(EXPECTED)] == list(EXPECTED)
    by_name = {m["name"]: m for m in MAN.doc["per_layer"]}
    chat, decode = ["serve-chat-steady"], ["serve-decode-heavy"]
    for name in list(EXPECTED)[:6]:
        assert by_name[name]["workloads"] == chat
        assert by_name[name]["moves"] == "latency_ms_per_out_token"
    for name in list(EXPECTED)[6:]:
        assert by_name[name]["workloads"] == decode
    assert by_name["pump_handoff_ms"]["layer"] == "replica pump"
    assert {by_name[n]["source"] for n in EXPECTED} == {"program_span",
                                                        "device_trace"}


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_against_a_recorded_spill(metric, session):
    value = MAN.reader(metric)(_ctx(session))
    assert value == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_finds_nothing_without_a_spill(metric, tmp_path, monkeypatch):
    read = MAN.reader(metric)
    assert read(_ctx(tmp_path)) is None               # no file
    from ray_tpu.util import events

    # a program from before the spans: no read_spill at all
    monkeypatch.delattr(events, "read_spill")
    assert read(_ctx(tmp_path)) is None


def test_trace_readers_need_a_trace_and_span_readers_do_not(session):
    ctx = _ctx(session, traced=False)
    for metric in ("admit_scatter_device_idle_pct",
                   "prefill_device_ms_per_prompt_token"):
        assert MAN.reader(metric)(ctx) is None
    assert MAN.reader("step_fetch_ms")(ctx) == pytest.approx(75.0)
    # a trace with no device plane (a rehearsal): still nothing
    ctx = _ctx(session)
    ctx["trace"] = {"annotations": [], "modules": {}}
    assert MAN.reader("admit_scatter_device_idle_pct")(ctx) is None
    assert MAN.reader("prefill_device_ms_per_prompt_token")(ctx) is None


def test_spans_are_read_once_and_cut_on_both_clocks(session):
    ctx = _ctx(session)
    by_name = ps.spans(ctx)
    assert ps.spans(ctx) is by_name
    assert "proto.send.frame" not in by_name and "serve.req.done" not in by_name
    assert [f["sid"] for f in ps.in_window(ctx, ps.ADMIT)] == [10, 20, 40]
    in_trace = ps.in_trace(ctx, ps.ADMIT)
    assert [f["sid"] for _, _, f in in_trace] == [20]
    assert in_trace[0][0] == 105 * S + OFF
    assert ps.in_trace(_ctx(session, traced=False), ps.ADMIT) == []
    # overlap, not containment: both straddlers count, whole
    assert [f["sid"] for _, _, f in ps.in_trace(ctx, ps.SCATTER)] \
        == [22, 90, 91]
    # a trace that opens inside A2 (after its prefill ran): its scatter is
    # still read, its prefill has nothing to stand on
    late = _ctx(session)
    late["host"]["window_ns"] = (105.1 * S + OFF, 108 * S + OFF)
    assert [f["sid"] for _, _, f in ps.in_trace(late, ps.ADMIT)] == [20]
    assert MAN.reader("prefill_device_ms_per_prompt_token")(late) is None
    # 0.22 s of A2's scatter (busy 0.10 + 0.02) and 0.10 s of a straddler
    assert MAN.reader("admit_scatter_device_idle_pct")(late) \
        == pytest.approx(100 * (1 - 0.12 / 0.32))
    assert ps.median_ms(ctx, "no.such_span") is None


# ------------------------- a span the program lacks, and an empty reading
#: what a later PR brings beside the spans it adds to the program: readers
#: of span names that no row of ROWS carries (their first segment is no
#: plane of the recorder's, so the repo's event-name lint passes them by)
NEW_READERS = {
    "step_newphase_ms": ("ms", "ps.median_ms(ctx, 'later.step.newphase')"),
    "admit_newphase_ms_per_prompt_token": (
        "ms", "ps.ms_per_prompt_token(ctx, 'later.admit.newphase')"),
    "newkernel_roofline_pct": (
        "%", "ps.median_ms(ctx, 'later.kernel.new', 'share')"),
}


def _tree_with(root, readers, cell="serve-chat-steady"):
    """The benchmark's files with the nine program-span readers (which a
    synthetic ``ctx`` can feed) and ``readers`` appended for ``cell``."""
    shutil.copytree(os.path.join(BENCH_DIR, "layer_metrics"),
                    os.path.join(root, "perfbench", "layer_metrics"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"] = [m for m in doc["per_layer"] if m["name"] in EXPECTED]
    for name in readers:
        unit, expr = NEW_READERS[name]
        with open(os.path.join(root, "perfbench", "layer_metrics",
                               name + ".py"), "w") as f:
            f.write("from perfbench import program_spans as ps\n\n\n"
                    f"def read(ctx):\n    return {expr}\n")
        doc["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "engine",
            "moves": "latency_ms_per_out_token", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return Manifest(root)


def test_a_span_the_program_lacks_leaves_its_metric_out_and_the_run_goes_on(
        session, tmp_path_factory, capsys):
    """The parent under a PR's new readers: no row of the run is named
    ``later.step.newphase`` or ``later.admit.newphase``. Both metrics are
    left out, each named on standard error with the span it lacks; every
    other reader reads what it read before; nothing is raised (exit 0)."""
    cell = "serve-chat-steady"
    man = _tree_with(str(tmp_path_factory.mktemp("tree")),
                     ["step_newphase_ms",
                      "admit_newphase_ms_per_prompt_token"])
    ctx = _ctx(session)
    values = layer_values(man, cell, ctx)
    assert ctx["program_lacks"] == {
        "step_newphase_ms": ["later.step.newphase"],
        "admit_newphase_ms_per_prompt_token": ["later.admit.newphase"]}
    for name in list(EXPECTED)[:6]:
        assert values[name]["value"] == pytest.approx(EXPECTED[name])
    line = dict(values)
    every_listed_metric(man, cell, line, ctx["program_lacks"])
    err = capsys.readouterr().err
    for name, span in (("step_newphase_ms", "later.step.newphase"),
                       ("admit_newphase_ms_per_prompt_token",
                        "later.admit.newphase")):
        assert name not in line
        assert f"{name} is left out of the line" in err and span in err
    # without the harness's say-so the line is refused as before
    with pytest.raises(SystemExit) as e:
        every_listed_metric(man, cell, line)
    assert "step_newphase_ms" in str(e.value)


def test_rows_outside_the_traced_stretch_are_an_empty_reading_no_result(
        session, tmp_path_factory, capsys):
    """``serve.engine.admit`` rows exist and none began inside a trace that
    opens after A2: the program writes the span, the stretch held none. That
    ends the run by name, beside a lacking span that alone would not."""
    cell = "serve-chat-steady"
    man = _tree_with(str(tmp_path_factory.mktemp("tree")),
                     ["step_newphase_ms"])
    ctx = _ctx(session)
    ctx["host"]["window_ns"] = (106 * S + OFF, 108 * S + OFF)
    values = layer_values(man, cell, ctx)
    assert "prefill_device_ms_per_prompt_token" not in values
    assert ctx["program_lacks"] == {
        "step_newphase_ms": ["later.step.newphase"]}
    with pytest.raises(SystemExit) as e:
        every_listed_metric(man, cell, values, ctx["program_lacks"])
    assert e.value.code not in (0, None)
    assert "prefill_device_ms_per_prompt_token" in str(e.value)
    assert "step_newphase_ms" not in str(e.value)
    assert "step_newphase_ms is left out" in capsys.readouterr().err


def test_a_share_of_a_roofline_is_never_left_out(session, tmp_path_factory,
                                                 capsys):
    cell = "serve-chat-steady"
    man = _tree_with(str(tmp_path_factory.mktemp("tree")),
                     ["newkernel_roofline_pct"])
    ctx = _ctx(session)
    values = layer_values(man, cell, ctx)
    assert ctx["program_lacks"] == {
        "newkernel_roofline_pct": ["later.kernel.new"]}
    with pytest.raises(SystemExit) as e:
        every_listed_metric(man, cell, values, ctx["program_lacks"])
    assert "newkernel_roofline_pct" in str(e.value)
    assert "left out" not in capsys.readouterr().err


def test_what_a_reader_asked_for_in_vain_is_collected_per_reader(session):
    ctx = _ctx(session)
    value, names = ps.asked_in_vain(
        ctx, lambda c: ps.median_ms(c, "no.such_span"))
    assert value is None and names == ["no.such_span"]
    # rows exist, none in the window: nothing is noted, None means "empty"
    far = {**_ctx(session), "run": {"t_open": 500.0, "t_close": 510.0}}
    assert ps.asked_in_vain(far, lambda c: ps.median_ms(c, ps.PREPARE)) \
        == (None, [])
    # one reader's note is not the next one's
    assert ps.asked_in_vain(ctx, lambda c: ps.median_ms(c, ps.PREPARE)) \
        == (pytest.approx(2.0), [])
    # a reader that is called outside the harness notes and harms nothing
    assert ps.median_ms(_ctx(session), "no.such_span") is None


def test_rehearsed_decode_cell_reports_the_program_span_metrics():
    """The whole path at toy size on the CPU: replica -> recorder -> flush
    tick -> spill file -> ``read_spill`` in the driver after the cluster has
    stopped -> the traced line."""
    cell = MAN.cell("serve-decode-heavy")
    args = argparse.Namespace(seed=3_000_000_019, seconds=3.0, trace=1,
                              rehearse=True)
    from perfbench.runners import serve as serve_runner

    line = serve_runner.run(MAN, cell, args, time.time())
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    new = {"step_prepare_ms", "step_fetch_ms", "pump_handoff_ms"}
    assert new <= {m["name"] for m in MAN.metrics_for(cell["name"],
                                                      "per_layer")
                   if m["source"] == "program_span"}
    assert new <= set(line["metrics"])
    for name in new:
        assert line["metrics"][name]["unit"] == "ms"
        assert 0 < line["metrics"][name]["value"] < 5_000
    # the hop inside the replica is a part of the client's view of it
    assert line["metrics"]["pump_handoff_ms"]["value"] \
        <= line["metrics"]["pump_ms_per_token"]["value"]
