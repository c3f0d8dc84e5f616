"""The readers of ``serve.step.flight`` (``perfbench/flight_spans.py`` and the
four per-layer metrics on it): each against a small spill file in the
recorder's row format with the value worked out by hand beside it (an
admission between two steps, a dropped row, a profiler call, both edges of
the window); a spill with no such row at all, the parent's case, where every
reader returns None AND has the name noted in vain before any early return,
so that the harness leaves the four out of the line and the run goes on; a
hand-made reduced trace for the one that needs a device plane; and one traced
rehearsal of ``serve-decode-heavy`` from a root of its own."""

import argparse
import json
import os
import shutil
import time

import pytest

from perfbench import flight_spans as fs, program_spans as ps
from perfbench.manifest import BENCH_DIR, ROOT, Manifest, layer_values
from perfbench.runners.common import every_listed_metric

MAN = Manifest(ROOT)
PID = 777
S, MS = 10**9, 10**6
OFF = -40 * S       # trace clock = perf_counter_ns + OFF
THE_FOUR = ("step_host_slack_ms", "step_flights_ahead",
            "admit_stall_share_pct", "device_idle_restart_pct")
#: the two closed-loop cells whose own tests do not hold their list of
#: per-layer metrics to an exact set (the five later families' do, in files
#: of the benchmark that no PR of this kind may edit: PERF.md section 7)
LISTED = ["serve-decode-heavy", "serve-nemotron-decode"]


def _row(name, t0, dur, **fields):
    fields.update(t0_ns=int(t0), dur_ns=int(dur))
    return [1790000000.0 + t0 / S, name, "serve", "", "", dur / S, fields]


def _flight(step, landing_s, depth, admitted, wait_ms, in_flight_ms=30):
    """The row of step ``step``, which landed at ``landing_s``; its call's
    ``sid`` is 200 + step, the call that landed it 300 + step."""
    landing = landing_s * S
    return _row(fs.FLIGHT, landing - in_flight_ms * MS, in_flight_ms * MS,
                sid=1000 + step, parent=2000 + step, step=step, depth=depth,
                active=16, admitted=admitted, wait_ns=int(wait_ms * MS),
                call=200 + step, landed_by=300 + step)


# The window is [100 s, 110 s) on perf_counter; the profiler's two calls take
# [103.0, 103.1) and [106.0, 107.0) of it; the trace covers [102 s, 108 s).
ROWS = [
    _flight(4, 99.990, 2, 0, 9),        # landed before the window opened
    _flight(5, 100.010, 3, 0, 2),       # the first inside: no pair with 4
    _flight(6, 100.020, 4, 0, 4),       # 10 ms after 5
    _flight(7, 100.032, 5, 0, 6),       # 12 ms
    _flight(8, 100.132, 0, 1, 1),       # 100 ms, after one admission
    _flight(9, 100.143, 1, 0, 0.5),     # 11 ms
    # step 10's row was dropped (a full ring): 9 -> 11 is no step's pace
    _flight(11, 100.170, 2, 0, 8),
    # 3030 ms after two admissions, 100 ms of it inside start_trace()
    _flight(12, 103.200, 3, 2, 5),
    _flight(13, 103.213, 4, 0, 3),      # 13 ms
    _flight(14, 103.263, 0, 2, 7),      # 50 ms, after two admissions
    _flight(15, 106.500, 1, 0, 9),      # landed inside stop_trace(): cut
    _flight(16, 110.500, 2, 0, 9),      # landed after the close
    # the calls that dispatched steps 13 and 14, and their phases
    _row(ps.STEP, 103.2005 * S, 12 * MS, sid=213, parent=0, k=90),
    _row(ps.PREPARE, 103.201 * S, 1 * MS, sid=2131, parent=213),
    _row(ps.DISPATCH, 103.202 * S, 8 * MS, sid=2132, parent=213, step=13,
         depth=4),
    _row(ps.STEP, 103.2145 * S, 11 * MS, sid=214, parent=0, k=91),
    _row(ps.PREPARE, 103.215 * S, 2 * MS, sid=2141, parent=214),
    _row(ps.DISPATCH, 103.217 * S, 8 * MS, sid=2142, parent=214, step=14,
         depth=0),
    # a restart outside the traced stretch: step 8's
    _row(ps.PREPARE, 100.033 * S, 2 * MS, sid=2081, parent=208),
    _row(ps.DISPATCH, 100.035 * S, 8 * MS, sid=2082, parent=208, step=8,
         depth=0),
]

EXPECTED = {
    # waits of steps 5-9 and 11-15: 2, 4, 6, 1, 0.5, 8, 5, 3, 7, 9
    "step_host_slack_ms": 4.5,
    # depths after no admission, steps 5, 6, 7, 9, 11, 13, 15:
    # 3, 4, 5, 1, 2, 4, 1
    "step_flights_ahead": 3.0,
    # pairs 10, 12, 100*, 11, 2930* (3030 less the 100 inside start_trace()),
    # 13, 50* ms (* after an admission) and 14 -> 15, cut by stop_trace(),
    # which sets no baseline; 9 -> 11 skipped; baseline the median of 10, 12,
    # 11, 13 = 11.5; lost 88.5 + 2918.5 + 38.5 ms of the 10 s less the
    # profiler's 0.1 + 1.0 s
    "admit_stall_share_pct": 100 * 3.0455 / 8.9,
    # step 14 alone was dispatched with nothing in flight inside the trace:
    # its call's prepare [103.215, 103.217) and dispatch [103.217, 103.225)
    # hold the idle [103.216, 103.222); the idle 2 ms in step 13's dispatch
    # are no restart's; over the traced 6 s
    "device_idle_restart_pct": 100 * 0.006 / 6.0,
}


def _ctx(session_dir, traced=True, window=True):
    ctx = {"device": {"pid": PID}, "session_dir": str(session_dir),
           "config": {"programs": {"decode": "jit__paged_step"}},
           "trace": None, "host": None}
    if window:
        ctx["run"] = {"t_open": 100.0, "t_close": 110.0,
                      "trace_ctl": [(103.0, 103.1), (106.0, 107.0)]}
    if traced:
        ctx["host"] = {"offset_ns": OFF,
                       "window_ns": (102 * S + OFF, 108 * S + OFF)}
        ctx["trace"] = {
            "busy_intervals": [(101.5 * S + OFF, 103.204 * S + OFF),
                               (103.206 * S + OFF, 103.216 * S + OFF),
                               (103.222 * S + OFF, 108.5 * S + OFF)],
            "modules": {"jit__paged_step(99)": [0.010, 0.012],
                        "jit__prefill_one(12)": [0.030],
                        "jit__scatter_pages(3)": [0.001],
                        "jit_convert_element_type(1)": [0.002]}}
    return ctx


def _spill(folder, rows):
    folder = folder / "logs" / "events"
    folder.mkdir(parents=True)
    with open(folder / f"plane-{PID}.jsonl", "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


@pytest.fixture()
def session(tmp_path):
    _spill(tmp_path, ROWS)
    return tmp_path


@pytest.fixture()
def parent_session(tmp_path):
    """What the parent writes: every row but the flights."""
    _spill(tmp_path / "parent", [r for r in ROWS if r[1] != fs.FLIGHT])
    return tmp_path / "parent"


def test_the_new_entries_are_the_four_and_only_appended():
    entries = MAN.doc["per_layer"]
    at = [m["name"] for m in entries].index(THE_FOUR[0])
    assert at >= 58     # what the accepted benchmark had stands before them
    assert tuple(m["name"] for m in entries[at:at + 4]) == THE_FOUR
    for m in entries[at:at + 4]:
        assert m["workloads"] == LISTED
        assert m["moves"] == "out_tokens_per_s"
        assert os.path.isfile(MAN.reader_path(m["name"]))
    assert [(m["unit"], m["better"], m["source"], m["layer"])
            for m in entries[at:at + 4]] == [
        ("ms", "higher", "program_span", "engine host loop"),
        ("steps", "higher", "program_counter", "engine host loop"),
        ("%", "lower", "program_span", "engine host loop"),
        ("%", "lower", "device_trace", "device")]


@pytest.mark.parametrize("metric", THE_FOUR)
def test_reader_against_a_recorded_spill(metric, session):
    assert MAN.reader(metric)(_ctx(session)) == pytest.approx(
        EXPECTED[metric], rel=1e-9)


def test_the_windows_flights_in_step_order_on_both_clocks(session):
    ctx = _ctx(session)
    landed = fs.flights(ctx)
    assert [f["step"] for _, f in landed] == [5, 6, 7, 8, 9, 11, 12, 13, 14,
                                              15]
    assert landed[0][0] == 100.010 * S
    in_trace = fs.flights_in_trace(ctx)
    assert [f["step"] for _, _, f in in_trace] == [12, 13, 14, 15]
    assert in_trace[0][1] == 103.200 * S + OFF
    assert fs.flights_in_trace(_ctx(session, traced=False)) == []
    pairs, skipped = fs.landing_pairs(ctx)
    assert [(round(d / MS), cut, f["step"]) for d, cut, f in pairs] == [
        (10, False, 6), (12, False, 7), (100, False, 8), (11, False, 9),
        (2930, True, 12), (13, False, 13), (50, False, 14),
        (2737, True, 15)]
    assert skipped == 1


def test_the_stall_readers_earlier_line_holds_its_counts(session, capsys):
    fs.admit_stall_share_pct(_ctx(session))
    out = capsys.readouterr().out
    assert "8 pairs of consecutive steps, 3 of them after an admission " \
           "(5 admissions), 2 cut by a profiler call" in out
    assert "1 skipped" in out and "baseline 11.500 ms (median of 4" in out
    assert "hold 3.080s, 3.046s of it over" in out
    assert "of 8.900s" in out


def test_no_admission_in_the_window_is_a_value_and_no_plain_pair_is_not(
        tmp_path):
    _spill(tmp_path, [_flight(n, 100 + n * 0.012, 10, 0, 1)
                      for n in range(5)])
    assert fs.admit_stall_share_pct(_ctx(tmp_path)) == 0.0
    assert fs.device_idle_restart_pct(_ctx(tmp_path)) == 0.0
    other = tmp_path / "other"
    _spill(other, [_flight(0, 100.1, 0, 1, 1), _flight(1, 100.2, 0, 1, 1)])
    assert fs.admit_stall_share_pct(_ctx(other)) is None
    assert fs.step_flights_ahead(_ctx(other)) is None
    assert fs.step_host_slack_ms(_ctx(other)) == 1.0


def test_idle_by_phase_and_busy_by_program(session, capsys):
    ctx = _ctx(session)
    gaps = fs.idle_by_phase(ctx, ctx["trace"], ctx["host"]["window_ns"])
    # [103.204, 103.206) in step 13's dispatch; [103.216, 103.217) in step
    # 14's prepare and [103.217, 103.222) in its dispatch
    assert gaps == {ps.PREPARE: pytest.approx(0.001),
                    ps.DISPATCH: pytest.approx(0.007)}
    assert fs.busy_by_program(ctx, ctx["trace"]) == {
        "decode": (pytest.approx(0.022), 2), "prefill": (0.030, 1),
        "scatter": (0.001, 1), "other": (0.002, 1)}
    fs.device_idle_restart_pct(ctx)
    out = capsys.readouterr().out
    assert "device idle by program phase, 0.0080s of the traced 6.000s: " \
           "serve.step.dispatch 0.0070; serve.step.prepare 0.0010" in out
    assert "prefill 0.0300 (1 runs); decode 0.0220 (2 runs)" in out
    # an instant inside a call and outside its phases is the call's rest
    ctx["trace"]["busy_intervals"][0] = (101.5 * S + OFF, 103.2007 * S + OFF)
    ctx["trace"]["busy_intervals"].insert(
        1, (103.2009 * S + OFF, 103.204 * S + OFF))
    gaps = fs.idle_by_phase(ctx, ctx["trace"], ctx["host"]["window_ns"])
    assert gaps["serve.engine.step, rest"] == pytest.approx(0.0002)


def test_the_restart_reader_needs_a_device_plane(session):
    assert fs.device_idle_restart_pct(_ctx(session, traced=False)) is None
    ctx = _ctx(session)
    ctx["trace"] = {"annotations": [], "modules": {}}     # a rehearsal's
    value, in_vain = ps.asked_in_vain(ctx, fs.device_idle_restart_pct)
    assert value is None and in_vain == []      # the rows exist: empty


# ------------------------------- the parent: no row of the name at all
@pytest.mark.parametrize("metric", THE_FOUR)
@pytest.mark.parametrize("traced,window", [(True, True), (False, True),
                                           (False, False)])
def test_without_a_flight_row_the_name_is_noted_in_vain_before_any_return(
        metric, traced, window, parent_session):
    ctx = _ctx(parent_session, traced=traced, window=window)
    value, in_vain = ps.asked_in_vain(ctx, MAN.reader(metric))
    assert value is None and in_vain == [fs.FLIGHT]


def _tree_of_the_four(root):
    shutil.copytree(os.path.join(BENCH_DIR, "layer_metrics"),
                    os.path.join(root, "perfbench", "layer_metrics"))
    doc = dict(MAN.doc)
    doc["per_layer"] = [m for m in doc["per_layer"] if m["name"] in THE_FOUR]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return Manifest(root)


def test_on_the_parents_run_the_four_are_left_out_by_name_and_it_goes_on(
        parent_session, session, tmp_path_factory, capsys):
    cell = "serve-decode-heavy"
    man = _tree_of_the_four(str(tmp_path_factory.mktemp("tree")))
    ctx = _ctx(parent_session)
    values = layer_values(man, cell, ctx)
    assert values == {}
    assert ctx["program_lacks"] == {m: [fs.FLIGHT] for m in THE_FOUR}
    every_listed_metric(man, cell, values, ctx["program_lacks"])   # exit 0
    err = capsys.readouterr().err
    for metric in THE_FOUR:
        assert f"{cell}: {metric} is left out of the line" in err
    assert f"no row of this run carries ['{fs.FLIGHT}']" in err
    # the change's run: all four on the line, nothing lacking
    ctx = _ctx(session)
    values = layer_values(man, cell, ctx)
    assert {m: v["value"] for m, v in values.items()} == {
        m: pytest.approx(EXPECTED[m]) for m in THE_FOUR}
    assert ctx["program_lacks"] == {}
    every_listed_metric(man, cell, values, ctx["program_lacks"])
    # rows that exist and a stretch that held none is an empty reading still
    far = {**_ctx(session), "run": {"t_open": 500.0, "t_close": 510.0}}
    values = layer_values(man, cell, far)
    assert far["program_lacks"] == {}
    with pytest.raises(SystemExit) as e:
        every_listed_metric(man, cell, values, far["program_lacks"])
    assert "step_host_slack_ms" in str(e.value)


# ---------------------------------------------------------------- rehearsal
def test_rehearsed_decode_cell_reports_the_three_that_read_rows_alone(
        tmp_path):
    """The whole path at toy size on the CPU, from a root of its own (the
    trace directory is the root's: two rehearsals of one checkout take each
    other's trace away): engine -> recorder -> spill -> the traced line. A
    rehearsal's trace has no device plane, so the fourth has nothing to
    stand on."""
    from perfbench.runners import serve as serve_runner

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(BENCH_DIR, tmp_path / "perfbench")
    man = Manifest(str(tmp_path))
    cell = man.cell("serve-decode-heavy")
    args = argparse.Namespace(seed=3_000_000_029, seconds=3.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(man, cell, args, time.time())
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    got = line["metrics"]
    assert set(THE_FOUR[:3]) <= set(got) and THE_FOUR[3] not in got
    assert got["step_host_slack_ms"]["unit"] == "ms"
    assert 0 <= got["step_host_slack_ms"]["value"] < 5_000
    assert 0 <= got["step_flights_ahead"]["value"] <= 10
    assert 0 <= got["admit_stall_share_pct"]["value"] <= 100
