"""Where the traced stretch of an open loop lies: the simulation over every
window position of ``chat-steady``'s cycle. The chat cell's trace readers
(``prefill_device_ms_per_prompt_token``, ``admit_scatter_device_idle_pct``)
need an admission that begins inside the traced stretch; a line without
them is refused."""

import random

import pytest

from perfbench import traffic as tg
from perfbench.manifest import ROOT, Manifest
from perfbench.runners.serve import TRACE_SECONDS

MAN = Manifest(ROOT)
MIX = MAN.traffic("chat-steady")
RUN_SECONDS = float(MAN.doc["run_seconds"])
#: (seconds the profiler's start call takes, seconds from a request's due
#: instant to the start of its admission): none, and both far above what
#: the chip showed (start 0.0-0.1 s; queueing 45 ms median, under 0.3 s)
DELAYS = [(0.0, 0.05), (0.0, 0.3), (0.4, 0.05), (0.4, 0.3)]
#: an admission takes 20-120 ms (PERF.md section 5): it has ended by then
ADMISSION_S = 0.2


def _dues(seed, seconds=RUN_SECONDS):
    return [r.due_s for r in tg.open_loop_schedule(MIX, seconds, seed)
            if r.measured]


def _admissions_inside(dues, start, length, started_s, delay_s):
    a = start + started_s
    return sum(1 for d in dues
               if a <= d + delay_s and d + delay_s + ADMISSION_S <= a + length)


def _positions():
    """One seed for every place the window can open on the cycle."""
    n = len(_dues(0))
    seen = {}
    for seed in range(10_000):
        seen.setdefault(random.Random(seed).randrange(n), seed)
        if len(seen) == n:
            return n, seen
    raise AssertionError("not every window position was reached")


def test_every_window_position_holds_admissions():
    n, seen = _positions()
    assert n == round(MIX["rate_per_s"] * RUN_SECONDS) == 18
    lacking_mid, lacking_planned = [], []
    for position, seed in sorted(seen.items()):
        dues = _dues(seed)
        mid = (RUN_SECONDS - TRACE_SECONDS) / 2
        start = tg.trace_start(dues, RUN_SECONDS, TRACE_SECONDS)
        assert 0.0 <= start <= RUN_SECONDS - TRACE_SECONDS
        for started_s, delay_s in DELAYS:
            if not _admissions_inside(dues, mid, TRACE_SECONDS, started_s,
                                      delay_s):
                lacking_mid.append(position)
            if not _admissions_inside(dues, start, TRACE_SECONDS, started_s,
                                      delay_s):
                lacking_planned.append(position)
        # as planned (no delay beyond a step): two, so one may slip
        assert _admissions_inside(dues, start, TRACE_SECONDS, 0.0, 0.05) >= 2
    assert lacking_mid, "the simulation no longer sees the mid-window hole"
    assert lacking_planned == []


def test_403_seeds_as_perf_md_counted():
    """The count PERF.md gave for the mid-window stretch (about one seed in
    sixteen without an admission) against the planned one: none."""
    mid = (RUN_SECONDS - TRACE_SECONDS) / 2
    lacking_mid = lacking_planned = 0
    for seed in range(403):
        dues = _dues(seed)
        start = tg.trace_start(dues, RUN_SECONDS, TRACE_SECONDS)
        lacking_mid += not _admissions_inside(dues, mid, TRACE_SECONDS,
                                              0.0, 0.05)
        lacking_planned += not all(
            _admissions_inside(dues, start, TRACE_SECONDS, *d)
            for d in DELAYS)
    assert 15 <= lacking_mid <= 40
    assert lacking_planned == 0


def test_the_middle_is_kept_where_it_holds_two():
    dues = [1.0, 23.0, 25.5, 40.0]
    assert tg.trace_start(dues, 50.0, 6.0) == 22.0


def test_moves_no_further_than_it_must():
    dues = [5.0, 6.0, 30.0, 31.0, 44.0]
    start = tg.trace_start(dues, 50.0, 6.0)
    # 30.0 and 31.0 need start + 0.5 <= 30 and 31 <= start + 4.5
    assert start == pytest.approx(26.5, abs=0.051)
    assert start + 0.5 <= 30.0 and 31.0 <= start + 6.0 - 1.5


@pytest.mark.parametrize("seconds", [9.0, 12.0, 20.0, 35.0, 51.0])
def test_any_window_length_gets_a_stretch_inside_it(seconds):
    """Trial runs are shorter than ``run_seconds``: the stretch stays inside
    the window and takes the most requests it can."""
    length = min(TRACE_SECONDS, seconds / 3.0)
    for seed in range(40):
        dues = _dues(seed, seconds)
        start = tg.trace_start(dues, seconds, length)
        assert 0.0 <= start <= seconds - length + 1e-9


def test_no_request_at_all_keeps_the_middle():
    assert tg.trace_start([], 50.0, 6.0) == 22.0


class _FakeSession:
    """Records the admin ops the tracer sends."""

    def __init__(self):
        self.ops = []

    async def admin_async(self, op, **kw):
        self.ops.append(op)
        return True


def _trace(sess, dues, seconds=3.0):
    import asyncio

    from perfbench.runners import serve

    ctl = []
    t_open = serve.now()
    asyncio.run(serve._trace_in_window(sess, t_open, seconds, True, ctl,
                                       dues=dues))
    assert len(ctl) == 2
    return ctl[0][1] - t_open, serve.now() - t_open


def test_an_open_loop_traces_the_planned_stretch_and_asks_nothing_else():
    """Three seconds of trace where the schedule says, whatever the replica
    holds: nothing is searched for on the chip, and the stretch's length
    does not depend on the data."""
    sess = _FakeSession()
    began, took = _trace(sess, [2.0, 2.2], seconds=9.0)
    assert sess.ops == ["bench_trace_start", "bench_trace_stop"]
    # 2.0 and 2.2 need start + 0.5 <= 2.0 and 2.2 <= start + 3 - 1.5: the
    # middle (3.0) does not hold them, 1.5 is the nearest start that does
    assert 1.45 <= began <= 1.8
    assert took - began == pytest.approx(3.0, abs=0.3)


def test_a_closed_loop_keeps_the_middle():
    sess = _FakeSession()
    began, took = _trace(sess, None)
    assert sess.ops == ["bench_trace_start", "bench_trace_stop"]
    assert 0.95 <= began <= 1.4 and took <= 2.6   # (3 - 1) / 2, then 1 s


def test_no_trace_no_op():
    import asyncio

    from perfbench.runners import serve

    sess, ctl = _FakeSession(), []
    asyncio.run(serve._trace_in_window(sess, serve.now(), 3.0, False, ctl,
                                       dues=[1.0]))
    assert sess.ops == [] and ctl == []
