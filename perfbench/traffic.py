"""The one general traffic generator: a mix is a data file of parameters.

Every seed offers the same total work. The lengths of a mix are the evenly
spaced quantiles of its distributions and the gaps between arrivals the
evenly spaced quantiles of the exponential distribution, laid out once in an
order fixed by the mix's ``base_seed``. In an open loop the window holds the
whole cycle: ``--seed`` picks where on it the window starts (and, in the
runner, the token ids and the weights), so two seeds see the same set of
sizes and arrivals in another order, and the lead-in before the window is the
part of the cycle that precedes it. A closed loop's window holds only a part
of the cycle, so there every seed keeps the one order.
"""

from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist
from typing import List


@dataclasses.dataclass(frozen=True)
class Request:
    index: int           # position in this run's order
    due_s: float         # open loop: seconds from the window's opening
    prompt_len: int
    output_len: int
    measured: bool       # due inside the window


def quantile_lengths(dist: dict, n: int) -> List[int]:
    """n evenly spaced quantiles of a clipped lognormal, as whole numbers."""
    if dist["dist"] == "fixed":
        return [int(dist["value"])] * n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    inv = NormalDist().inv_cdf
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * inv((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def length_range(dist: dict):
    """(shortest, longest) a length distribution can give."""
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


def quantile_gaps(n: int, total_s: float) -> List[float]:
    """n evenly spaced quantiles of the exponential distribution, scaled to
    sum to ``total_s``: a Poisson process's gaps (CV about 1) with no luck
    in them."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total_s / sum(raw)
    return [g * scale for g in raw]


def _cycle(mix: dict, n: int, seconds: float):
    """The mix's fixed cycle of n (gap, prompt_len, output_len)."""
    base = random.Random(mix.get("base_seed", 0))
    prompts = quantile_lengths(mix["prompt_len"], n)
    outputs = quantile_lengths(mix["output_len"], n)
    base.shuffle(prompts)
    base.shuffle(outputs)
    if mix["loop"] == "open":
        draw = mix["arrivals"]["draw"]
        if draw == "quantile_gaps":
            gaps = quantile_gaps(n, seconds)
            base.shuffle(gaps)
        else:
            raise ValueError(f"unknown arrival draw {draw!r}")
    else:
        gaps = [0.0] * n
    return gaps, prompts, outputs


def open_loop_schedule(mix: dict, seconds: float, seed: int) -> List[Request]:
    """N = round(rate x seconds) measured requests due inside the window,
    preceded by the lead-in: the same cycle continued backwards."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    gaps, prompts, outputs = _cycle(mix, n, seconds)
    start = random.Random(seed).randrange(n)
    order = [(start + i) % n for i in range(n)]
    dues, t = [], 0.0
    for k in order:
        dues.append(t + gaps[k] / 2.0)   # instants sit mid-gap: none at 0
        t += gaps[k]
    reqs = []
    lead = float(mix.get("lead_in_s", 0.0))
    # walk the cycle backwards from the window's opening
    back, i = [], 0
    while True:
        k = order[(-1 - i) % n]
        due = dues[(-1 - i) % n] - seconds * (1 + i // n)
        if due < -lead:
            break
        back.append((due, prompts[k], outputs[k]))
        i += 1
    for due, p, o in reversed(back):
        reqs.append(Request(len(reqs), due, p, o, False))
    for j, k in enumerate(order):
        reqs.append(Request(len(reqs), dues[j], prompts[k], outputs[k], True))
    return reqs


#: a traced stretch holds TRACE_NEED requests due no less than TRACE_AFTER_S
#: past its start (the profiler's start call, a step in flight) and
#: TRACE_BEFORE_S ahead of its end (queueing, then a 20-120 ms admission)
TRACE_NEED, TRACE_AFTER_S, TRACE_BEFORE_S = 2, 0.5, 1.5


def trace_start(dues, seconds: float, length: float) -> float:
    """Where in an open loop's window (seconds from its opening) a traced
    stretch of ``length`` seconds begins: wholly inside the window, and as
    near its middle as leaves ``TRACE_NEED`` requests due no less than
    ``TRACE_AFTER_S`` past its start and ``TRACE_BEFORE_S`` ahead of its
    end. An admission begins within a step or two of its due instant, so
    such a stretch holds the start, the prefill and the scatter of one:
    the readers that need an admission inside the trace find it whatever
    the seed. The schedule is known before the window opens, so this is
    planned, not searched for on the chip. Where no start holds that many
    (a short trial window), the start that holds most, nearest the middle."""
    room = max(0.0, seconds - length)
    mid, grid = room / 2.0, 0.05
    starts = sorted({mid, *(i * grid for i in range(int(room / grid) + 1))},
                    key=lambda s: (abs(s - mid), s))

    def held(s):
        return sum(1 for d in dues if s + TRACE_AFTER_S <= d
                   <= s + length - TRACE_BEFORE_S)

    for s in starts:
        if held(s) >= TRACE_NEED:
            return s
    return max(starts, key=held)   # the first of the fullest: nearest mid


def closed_loop_requests(mix: dict) -> List[Request]:
    """The cycle a closed loop's clients draw from; they take the next one
    as their last completes, round and round. A window sees only a part of
    the cycle, so the order is the same for every seed (``start`` is where
    on the cycle the first client begins): with another starting point the
    window admits other prompts, and a tail of the gaps then moved by a
    quarter between seeds while two runs of one seed agreed within 3 %
    (my chip run, PR 23). The seed draws the token ids and the weights."""
    n = int(mix["cycle"])
    _, prompts, outputs = _cycle(mix, n, 0.0)
    start = int(mix.get("start", 0)) % n
    return [Request(i, 0.0, prompts[(start + i) % n],
                    outputs[(start + i) % n], True) for i in range(n)]


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> List[int]:
    """Token ids of one prompt: from the seed, distinct per request."""
    rng = random.Random((seed << 20) ^ (index * 2654435761 % (1 << 20)))
    return [rng.randrange(vocab) for _ in range(length)]


def lateness(due_abs, sent_abs):
    """How late the generator sent each request, seconds."""
    return [max(0.0, s - d) for d, s in zip(due_abs, sent_abs)]
