"""The trace reduction on a small trace whose answers are worked by hand,
and on a piece of a trace recorded on the chip where one is kept here."""

import json
import os

import pytest

from perfbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def _ev(name, start_us, dur_us):
    return [name, start_us * 1e3, dur_us * 1e3]


# two devices, one step program each; device 0:
#   fusion.1 [0,10) ; while.2 [10,30) enclosing body fusion.3 [12,20) and
#   all-reduce.4 [20,28) ; idle [30,40) ; all-gather-done.5 [40,45) ;
#   fusion.1 [45,50)
SMALL = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [_ev("jit_step(1)", 0, 50)]},
        {"name": "XLA Ops", "events": [
            _ev("fusion.1", 0, 10), _ev("while.2", 10, 20),
            _ev("fusion.3", 12, 8), _ev("all-reduce.4", 20, 8),
            _ev("all-gather-done.5", 40, 5), _ev("fusion.1", 45, 5)]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [_ev("jit_step(1)", 0, 48)]},
        {"name": "XLA Ops", "events": [_ev("fusion.1", 0, 48)]}]},
    {"name": "/device:TPU:0 SparseCore 0", "lines": [
        {"name": "XLA Ops", "events": [_ev("sc", 0, 1000)]}]},
    {"name": "/host:CPU", "lines": [{"name": "thread", "events": [
        _ev("perfbench/engine.step#0", 0, 32),
        _ev("perfbench/engine.admit#0", 30, 2),
        _ev("perfbench/engine.step#1", 38, 12),
        _ev("something else", 0, 1)]}]},
]}


def test_busy_union_per_op_self_time_and_exposed_collective():
    r = xplane.reduce(SMALL)
    assert r["n_devices"] == 2            # the SparseCore plane is left out
    assert r["busy_s"] == pytest.approx([40e-6, 48e-6])
    assert r["busy_intervals"] == [(0.0, 30e3), (40e3, 50e3)]
    # while.2 keeps only what its body does not cover: 20 - 8 - 8
    assert r["op_self_s"]["while.2"] == pytest.approx(4e-6)
    assert r["op_self_s"]["fusion.1"] == pytest.approx((10 + 5 + 48) * 1e-6)
    assert r["collective_s"] == pytest.approx([13e-6, 0.0])
    assert r["modules"]["jit_step(1)"] == pytest.approx([50e-6, 48e-6])
    assert xplane.module_times(r, "jit_step") == pytest.approx([50e-6, 48e-6])
    assert [a[0] for a in r["annotations"]] == [
        "perfbench/engine.step#0", "perfbench/engine.admit#0",
        "perfbench/engine.step#1"]
    top = xplane.top_ops(r, 2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(31.5e-6)


def test_idle_gaps_go_to_the_span_that_covers_them():
    r = xplane.reduce(SMALL)
    spans = [("engine.admit", 30e3, 32e3), ("engine.step.host", 0, 32e3),
             ("engine.step.host", 38e3, 50e3), ("pump", 32e3, 38e3)]
    gaps = xplane.idle_gaps_by_span(r, spans, (0, 50e3), "no_work")
    assert gaps == pytest.approx({"engine.admit": 2e-6, "pump": 6e-6,
                                  "engine.step.host": 2e-6})
    assert xplane.idle_gaps_by_span(r, [], (0, 60e3), "train.host") == \
        pytest.approx({"train.host": 20e-6})


@pytest.mark.parametrize("name", ["trace_sample_serve.json",
                                  "trace_sample_train.json"])
def test_recorded_sample(name):
    """A piece of a trace a chip run of this benchmark recorded: the planes
    and lines the reduction relies on are there and reduce to something."""
    with open(os.path.join(HERE, name)) as f:
        trace = json.load(f)
    r = xplane.reduce(trace)
    assert r["n_devices"] >= 1 and min(r["busy_s"]) > 0
    assert r["modules"] and r["op_self_s"]
    assert any(a[0].startswith("perfbench/") for a in r["annotations"])
    span = (r["span_ns"][1] - r["span_ns"][0]) / 1e9
    assert all(b <= span * (1 + 1e-9) for b in r["busy_s"])
