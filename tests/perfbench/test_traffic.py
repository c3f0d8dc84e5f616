"""The traffic generator: every seed offers the same work, in another order."""

import json
import os

import pytest

from perfbench import traffic as tg
from perfbench.manifest import BENCH_DIR


def _mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


CHAT, DECODE = _mix("chat-steady"), _mix("decode-heavy")
BIG = 3_000_000_007      # the driver's seeds pass 2**31


def test_same_seed_same_schedule_and_tokens():
    a = tg.open_loop_schedule(CHAT, 50.0, BIG)
    b = tg.open_loop_schedule(CHAT, 50.0, BIG)
    assert a == b
    assert tg.prompt_tokens(BIG, 3, 40, 32768) == \
        tg.prompt_tokens(BIG, 3, 40, 32768)
    assert tg.prompt_tokens(BIG, 3, 40, 32768) != \
        tg.prompt_tokens(BIG, 4, 40, 32768)
    assert all(0 <= t < 32768 for t in tg.prompt_tokens(BIG, 3, 400, 32768))


@pytest.mark.parametrize("seed", [1, 2, BIG])
def test_every_seed_offers_the_same_work(seed):
    ref = [r for r in tg.open_loop_schedule(CHAT, 50.0, 0) if r.measured]
    got = [r for r in tg.open_loop_schedule(CHAT, 50.0, seed) if r.measured]
    assert len(got) == len(ref) == round(CHAT["rate_per_s"] * 50.0)
    assert sorted(r.prompt_len for r in got) == \
        sorted(r.prompt_len for r in ref)
    assert sorted(r.output_len for r in got) == \
        sorted(r.output_len for r in ref)
    gaps = lambda rs: sorted(round(b.due_s - a.due_s, 6)      # noqa: E731
                             for a, b in zip(rs, rs[1:]))
    # another order of one cycle: all gaps but the one at the seam agree
    assert len(set(gaps(got)) ^ set(gaps(ref))) <= 4


def test_open_loop_window_and_lead_in():
    reqs = tg.open_loop_schedule(CHAT, 50.0, 7)
    measured = [r for r in reqs if r.measured]
    lead = [r for r in reqs if not r.measured]
    assert all(0.0 <= r.due_s < 50.0 for r in measured)
    assert lead and all(-CHAT["lead_in_s"] <= r.due_s < 0.0 for r in lead)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    assert [r.index for r in reqs] == list(range(len(reqs)))
    # the lead-in arrives at the window's own rate
    assert abs(len(lead) / CHAT["lead_in_s"] - CHAT["rate_per_s"]) < 0.2


@pytest.mark.parametrize("mix,key", [(CHAT, "prompt_len"),
                                     (CHAT, "output_len"),
                                     (DECODE, "prompt_len"),
                                     (DECODE, "output_len")])
def test_lengths_are_clipped_quantiles(mix, key):
    d = mix[key]
    xs = tg.quantile_lengths(d, 1000)
    assert d["min"] <= min(xs) and max(xs) <= d["max"]
    tight = tg.quantile_lengths({**d, "min": d["median"] - 1,
                                 "max": d["median"] + 1}, 1000)
    assert min(tight) == d["median"] - 1 and max(tight) == d["median"] + 1
    assert xs == sorted(xs)
    assert abs(xs[500] - d["median"]) <= 1


def test_quantile_gaps_sum_to_the_window_and_look_poisson():
    gaps = tg.quantile_gaps(200, 50.0)
    assert abs(sum(gaps) - 50.0) < 1e-9
    mean = 50.0 / 200
    var = sum((g - mean) ** 2 for g in gaps) / 200
    assert 0.85 < var ** 0.5 / mean < 1.05      # an exponential's CV is 1


def test_closed_loop_cycle():
    a = tg.closed_loop_requests(DECODE)
    assert len(a) == DECODE["cycle"]
    assert all(DECODE["output_len"]["min"] <= r.output_len
               <= DECODE["output_len"]["max"] for r in a)
    assert a == tg.closed_loop_requests(DECODE)
    # another starting point is the same cycle, turned
    b = tg.closed_loop_requests({**DECODE, "start": DECODE["start"] + 5})
    assert [r.output_len for r in b] == [r.output_len for r in a[5:] + a[:5]]
    assert sorted(r.prompt_len for r in a) == sorted(
        tg.quantile_lengths(DECODE["prompt_len"], DECODE["cycle"]))


def test_lateness_is_reported():
    assert tg.lateness([1.0, 2.0, 3.0], [1.0, 2.5, 2.9]) == [0.0, 0.5, 0.0]
