"""The serving replica's host spans put on the device trace's clock, and the
reductions several per-layer readers share."""

from __future__ import annotations

import re

from perfbench import stats, xplane


def align(collected: dict, reduced: dict | None):
    """Host spans (``perf_counter_ns`` in the replica) shifted onto the
    trace's clock by the annotations both carry: ``engine.step#k`` in the
    trace starts when step k's span does. Falls back to the wall clock read
    beside the window's start. None where nothing was traced."""
    window = collected.get("trace_window_ns")
    if not reduced or not window or window[1] is None:
        return None
    steps = collected["steps"]
    offsets = []
    for name, start, _ in reduced.get("annotations", []):
        m = re.match(r"perfbench/engine\.step#(\d+)$", name)
        if m and int(m.group(1)) < len(steps):
            offsets.append(start - steps[int(m.group(1))][0])
    if offsets:
        off = stats.median(offsets)
    else:
        off = collected["trace_window_epoch_ns"] - window[0]
    sh = lambda t: t + off   # noqa: E731
    return {
        "offset_ns": off, "from_annotations": bool(offsets),
        "window_ns": (sh(window[0]), sh(window[1])),
        "steps": [(sh(s[0]), sh(s[1])) + tuple(s[2:]) for s in steps],
        "admits": [(sh(a[0]), sh(a[1])) + tuple(a[2:])
                   for a in collected["admits"]],
    }


def in_window(spans, run: dict):
    """Host spans (ns, the replica's clock) that ended inside the measured
    window (the client's ``perf_counter`` seconds: one host, one clock)."""
    a, b = run["t_open"] * 1e9, run["t_close"] * 1e9
    return [s for s in spans if a <= s[1] < b]


def work_intervals(host: dict):
    """The time the engine had work, inside the traced window: the union of
    its ``engine.step()`` spans."""
    w = [tuple(host["window_ns"])]
    return stats.intersect(stats.merge((s[0], s[1]) for s in host["steps"]), w)


def device_idle_pct(ctx: dict):
    """1 - device busy over the time the engine had work."""
    host, red = ctx.get("host"), ctx.get("trace")
    if not host or not red or not red.get("busy_intervals"):
        return None
    work = work_intervals(host)
    total = sum(b - a for a, b in work)
    if total <= 0:
        return None
    busy = sum(b - a for a, b in stats.intersect(red["busy_intervals"], work))
    return 100.0 * (1.0 - busy / total)


def decode_device_s(ctx: dict):
    """Device seconds per execution of the decode program, in the trace."""
    red = ctx.get("trace")
    if not red:
        return None
    times = xplane.module_times(red, ctx["config"]["programs"]["decode"])
    return sum(times) / len(times) if times else None


def steps_that_decoded(ctx: dict):
    """Steps of the window in which the decode program ran."""
    return [s for s in in_window(ctx["spans"].get("steps") or [], ctx["run"])
            if s[3] > 0]


def decode_steps(ctx: dict):
    """Of those, the steps that admitted nothing."""
    return [s for s in steps_that_decoded(ctx) if s[2] == 0]


def admits(ctx: dict):
    """``_admit`` calls of the window that admitted something."""
    return [a for a in in_window(ctx["spans"].get("admits") or [], ctx["run"])
            if a[2] > 0]


def breakdown(ctx: dict) -> dict:
    host, red = ctx["host"], ctx["trace"]
    spans = [("engine.admit", a[0], a[1]) for a in host["admits"] if a[2] > 0]
    spans += [("engine.step.host", s[0], s[1]) for s in host["steps"]]
    for prev, nxt in zip(host["steps"], host["steps"][1:]):
        spans.append(("pump" if prev[4] else "no_work", prev[1], nxt[0]))
    gaps = xplane.idle_gaps_by_span(red, spans, host["window_ns"], "no_work")
    return {"device_ops": xplane.top_ops(red),
            "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                key=lambda kv: -kv[1])[:10]}
