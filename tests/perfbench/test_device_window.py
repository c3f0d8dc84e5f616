"""The device line of a traced run is read inside its own window: the helper
that clips one device's busy intervals against hand-worked cases and as a
property of any interval list and any window, the line on the two recorded
samples with a window cut inside their span, the train worker's summary, and
one rehearsal of a serving cell whose (planted) device line runs past both
ends of the traced window, as an engine with steps in flight leaves it."""

import argparse
import json
import os
import random
import time

import pytest

from perfbench import serve_spans, stats, train_spans, xplane
from perfbench.manifest import ROOT, Manifest
from perfbench.runners.common import device_line

HERE = os.path.dirname(os.path.abspath(__file__))
MAN = Manifest(ROOT)
DEVICE = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
          "peak_bytes_in_use": [7, 9]}
WINDOW = (100.0, 200.0)


def _holds_the_drivers_words(device: dict):
    """'a number above 0 and at most device.window_s'."""
    assert 0 < device["busy_s"] <= device["window_s"]


@pytest.mark.parametrize("intervals,want_ns", [
    ([(50, 150)], 50),                      # starts before the window
    ([(150, 250)], 50),                     # ends after it
    ([(50, 250)], 100),                     # straddles both ends
    ([(0, 100), (200, 300)], 0),            # wholly outside, touching it
    ([(0, 40), (260, 300)], 0),             # wholly outside
    ([], 0),
    ([(90, 110), (120, 130), (190, 400)], 10 + 10 + 10),
    ([(100, 200)], 100),                    # the window itself
])
def test_busy_inside_a_window_by_hand(intervals, want_ns):
    assert xplane.busy_inside_s(intervals, WINDOW) * 1e9 \
        == pytest.approx(want_ns)


@pytest.mark.parametrize("seed", range(8))
def test_busy_is_never_over_its_window_nor_under_nought(seed):
    """For any merged interval list and any window, on the nanosecond grid
    the profiler writes: 0 <= busy_s <= window_s, and the clipped figure is
    never over the unclipped one."""
    rng = random.Random(seed)
    for _ in range(200):
        base = rng.choice([0, 4_000_000_000, 1_790_000_000 * 10**9])
        marks = sorted(rng.sample(range(0, 10_000), 2 * rng.randint(0, 40)))
        merged = stats.merge((float(base + a), float(base + b))
                             for a, b in zip(marks[::2], marks[1::2]))
        a = base + rng.randint(-500, 10_500)
        window = (float(a), float(a + rng.randint(1, 6_000)))
        red = {"n_devices": 1, "busy_intervals": merged,
               "busy_s": [sum(y - x for x, y in merged) / 1e9]}
        busy_s = xplane.busy_inside_s(merged, window)
        assert 0.0 <= busy_s <= (window[1] - window[0]) / 1e9
        if not merged:
            continue
        line = device_line(DEVICE, xplane.device_window(red, window))
        assert line["busy_s"] == busy_s <= line["busy_trace_s"]
        assert line["window_s"] == (window[1] - window[0]) / 1e9


def test_the_line_carries_the_three_figures_and_averages_the_devices():
    red = {"n_devices": 2, "busy_s": [150e-9, 300e-9],
           "busy_intervals": [(50.0, 200.0)],
           "all_busy_intervals": [[(50.0, 200.0)], [(0.0, 300.0)]]}
    line = device_line(DEVICE, xplane.device_window(red, WINDOW))
    assert line == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                    "memory_peak_bytes": 9,
                    "busy_s": pytest.approx(100e-9),
                    "window_s": pytest.approx(100e-9),
                    "busy_trace_s": pytest.approx(225e-9)}
    # one chip's list comes once (the replica drops the copy) ...
    one = {"n_devices": 1, "busy_s": [150e-9],
           "busy_intervals": [(50.0, 200.0)]}
    assert xplane.device_window(one, WINDOW)["busy_s"] \
        == pytest.approx(100e-9)
    # ... several devices' never: the first one's is not the others'
    with pytest.raises(ValueError):
        xplane.device_window({**red, "all_busy_intervals": None}, WINDOW)


def test_an_untraced_line_and_a_trace_without_a_device_have_no_busy_time():
    plain = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
             "memory_peak_bytes": 9}
    assert device_line(DEVICE) == plain
    assert xplane.device_window(None, WINDOW) is None
    # a rehearsal's trace: host annotations, no device plane
    assert xplane.device_window({"n_devices": 0, "annotations": []},
                                WINDOW) is None
    assert device_line(DEVICE, None) == plain


def test_a_window_in_which_nothing_ran_reads_nought():
    """What is true of it; the driver refuses such a line, and should."""
    red = {"n_devices": 1, "busy_s": [40e-9], "busy_intervals": [(0.0, 40.0)]}
    line = device_line(DEVICE, xplane.device_window(red, WINDOW))
    assert line["busy_s"] == 0.0 and line["busy_trace_s"] > 0


def _sample(name):
    with open(os.path.join(HERE, name)) as f:
        return xplane.reduce(json.load(f))


@pytest.mark.parametrize("name", ["trace_sample_serve.json",
                                  "trace_sample_train.json"])
def test_recorded_sample_with_a_window_cut_inside_its_span(name):
    """The window is half as long as the sample's unclipped busy time and
    lies in the middle of its span: the union of the whole file exceeds the
    window, as it did in the runs the driver refused, and the printed
    ``busy_s`` does not."""
    red = _sample(name)
    whole = sum(red["busy_s"]) / len(red["busy_s"])
    mid = (red["span_ns"][0] + red["span_ns"][1]) / 2
    window = (mid - whole * 1e9 / 4, mid + whole * 1e9 / 4)
    line = device_line(DEVICE, xplane.device_window(red, window))
    assert line["busy_trace_s"] == pytest.approx(whole)
    assert line["busy_trace_s"] > line["window_s"]
    _holds_the_drivers_words(line)
    # the readers' own arithmetic over the same window gives the same
    clipped = [sum(b - a for a, b in stats.intersect(iv, [window])) / 1e9
               for iv in red["all_busy_intervals"]]
    assert line["busy_s"] == pytest.approx(sum(clipped) / len(clipped))
    # over the file's whole span nothing is cut away
    whole_line = xplane.device_window(red, red["span_ns"])
    assert whole_line["busy_s"] == pytest.approx(whole)


def test_the_train_summary_sends_its_line_through_the_same_helper():
    """The traced steps' span is the window: the line's ``busy_s`` is the
    mean of ``busy_in_span_s`` (what ``device_idle_pct.train`` reads, its
    value as before), ``window_s`` is ``steps_span_s``."""
    red = _sample("trace_sample_train.json")
    lo, hi = red["span_ns"]
    cut = (lo + (hi - lo) * 0.25, lo + (hi - lo) * 0.5)
    red["annotations"] = [("perfbench/train.step#3", cut[0], cut[1])]
    got = train_spans.summarise(red, "jit_")
    line = device_line(DEVICE, got["device_window"])
    _holds_the_drivers_words(line)
    assert line["window_s"] == pytest.approx(got["steps_span_s"])
    assert line["busy_s"] == pytest.approx(
        sum(got["busy_in_span_s"]) / len(got["busy_in_span_s"]))
    assert line["busy_trace_s"] == pytest.approx(sum(red["busy_s"]) / 4)
    assert line["busy_trace_s"] > line["window_s"]
    idle = MAN.reader("device_idle_pct.train")({"trace": got})
    assert idle == pytest.approx(
        100.0 * (1.0 - line["busy_s"] / line["window_s"]))
    # no device plane (a rehearsal): no line
    assert "device_window" not in train_spans.summarise(
        {"annotations": red["annotations"]}, "jit_")


def test_a_serving_line_whose_device_ran_past_both_ends_of_the_window(
        monkeypatch):
    """A traced rehearsal of ``serve-decode-heavy`` on the CPU, whose trace
    holds the host's annotations and no device plane; one is planted on the
    reduced trace as the replica hands it over: busy from 2 s before the
    first ``engine.step`` annotation to 2 s after the last one, but for a
    millisecond in every hundred. The whole file's busy time then exceeds
    the traced window (PR 45's refusal); the line's does not."""
    from perfbench.runners import serve as serve_runner

    planted = {}
    admin = serve_runner.ServeSession.admin

    def plant(self, op, *a, **kw):
        out = admin(self, op, *a, **kw)
        if op == "bench_collect" and out.get("trace"):
            red = out["trace"]
            steps = [(s, e) for n, s, e in red["annotations"]
                     if "engine.step#" in n]
            lo, hi = min(s for s, _ in steps) - 2e9, max(
                e for _, e in steps) + 2e9
            busy = [(t, t + 99e6) for t in
                    (lo + k * 100e6 for k in range(int((hi - lo) // 100e6)))]
            red.update(n_devices=1, busy_intervals=busy, span_ns=(lo, hi),
                       busy_s=[sum(b - a for a, b in busy) / 1e9],
                       collective_s=[0.0], modules_by_device=[{}])
            planted.update(red=red, collected=out)
        return out

    monkeypatch.setattr(serve_runner.ServeSession, "admin", plant)
    args = argparse.Namespace(seed=3_000_000_023, seconds=3.0, trace=1,
                              rehearse=True)
    line = serve_runner.run(MAN, MAN.cell("serve-decode-heavy"), args,
                            time.time())
    assert line["correct"] is True and planted
    device = line["device"]
    _holds_the_drivers_words(device)
    assert device["busy_trace_s"] > device["window_s"]
    host = serve_spans.align(planted["collected"], planted["red"])
    assert host["from_annotations"]
    assert device["window_s"] == pytest.approx(
        (host["window_ns"][1] - host["window_ns"][0]) / 1e9)
    a, b = planted["collected"]["trace_window_ns"]
    assert device["window_s"] == pytest.approx((b - a) / 1e9)
    # 99 of every 100 ms inside the window, to the planting's own edges
    assert device["busy_s"] / device["window_s"] == pytest.approx(0.99,
                                                                  abs=0.02)
    # the readers cut the same intervals to the same window, as before
    assert line["metrics"]["device_idle_pct.decode"]["value"] >= 0.0
    assert line["breakdown"]["idle_gaps"]
