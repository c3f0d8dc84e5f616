"""A dispatched step's own row, ``serve.step.flight``, as the four readers of
the engine's run-ahead see it.

Since PR 49 a ``step()`` call dispatches one step and lands another, up to
ten older, so no call's wall is a step's. The engine numbers the steps it
dispatches and writes one row a step as its tokens reach the host
(``models/paged.py`` ``_land``): ``t0_ns`` is the start of the step's
``serve.step.dispatch`` span, ``t0_ns + dur_ns`` its LANDING instant, and the
fields say what the call's row cannot: ``step``, ``depth`` (steps already in
flight at its dispatch), ``active``, ``admitted`` (admissions since the
dispatch before it), ``wait_ns`` (how long the host stood blocked for these
tokens), ``call`` / ``landed_by`` (the ``sid`` of the ``serve.engine.step``
row that dispatched it, and of the one that landed it).

Everything here stands on ``program_spans.rows``, and every reader asks it for
the flight rows FIRST, before any early return: a run whose program writes no
such row (the parent of the PR that added it) then has the name noted in vain,
and the harness leaves the metric out of that line by name
(``manifest.layer_values`` -> ``runners/common.every_listed_metric``) where a
plain None would end the run as an empty reading.
"""

from __future__ import annotations

from perfbench import program_spans as ps, stats, xplane
from perfbench.runners.common import say

FLIGHT = "serve.step.flight"
STATE = "serve.admit.state"
#: what the device's idle time is told by, in priority order: the program's
#: spans that enclose no other, in the order of a call, then what covers an
#: instant no leaf does (span name, the line's label)
PHASES = tuple((name, name) for name in (
    ps.PREPARE, ps.DISPATCH, ps.FETCH, ps.EMIT, ps.PREFILL, ps.SCATTER,
    STATE, ps.SAMPLE)) + ((ps.ADMIT, "serve.engine.admit, rest"),
                          (ps.STEP, "serve.engine.step, rest"))
BETWEEN = "between calls"
RESTART = "restart"


def landing_ns(f: dict) -> int:
    return f["t0_ns"] + f["dur_ns"]


def flights(ctx: dict) -> list:
    """``[(landing_ns, fields)]`` of the flights that LANDED inside the
    measured window, in ``step`` order (the order they land in)."""
    found = ps.rows(ctx, FLIGHT)
    run = ctx.get("run")
    if not found or not run:
        return []
    a, b = run["t_open"] * 1e9, run["t_close"] * 1e9
    return sorted(((landing_ns(f), f) for f in found
                   if a <= landing_ns(f) < b), key=lambda lf: lf[1]["step"])


def flights_in_trace(ctx: dict) -> list:
    """``[(dispatch_ns, landing_ns, fields)]`` on the TRACE's clock, of the
    flights that overlap the traced window, in ``step`` order."""
    ps.rows(ctx, FLIGHT)    # asked before ``in_trace`` can return early
    return sorted(ps.in_trace(ctx, FLIGHT), key=lambda s: s[2]["step"])


def profiler_calls_ns(ctx: dict) -> list:
    """The spans of the client's clock in which the profiler was started and
    stopped (the replica stands still in them), cut to the window."""
    run = ctx["run"]
    window = [(run["t_open"] * 1e9, run["t_close"] * 1e9)]
    return stats.intersect(stats.merge(
        (a * 1e9, b * 1e9) for a, b in run.get("trace_ctl") or []), window)


def landing_pairs(ctx: dict):
    """``([(interval_ns, cut, the later flight's fields)], skipped)`` over the
    window's consecutive flights (``step`` n-1, n). A pair with a step
    missing between (a dropped row) is no step's pace: skipped, and counted.
    What an interval has inside one of the profiler's two calls is taken off
    it (``cut``: whether anything was), as it is off the window's length: an
    admission of seconds is not lost to the 50 ms of a ``start_trace()``."""
    landed = flights(ctx)
    still = profiler_calls_ns(ctx) if landed else []
    pairs, skipped = [], 0
    for (t_prev, prev), (t, f) in zip(landed, landed[1:]):
        if f["step"] != prev["step"] + 1:
            skipped += 1
            continue
        inside = sum(b - a for a, b in stats.intersect([(t_prev, t)], still))
        pairs.append((t - t_prev - inside, inside > 0, f))
    return pairs, skipped


# ------------------------------------------------------------- the four
def step_host_slack_ms(ctx: dict):
    """Median ``wait_ns`` of the window's flights: the room the host's call
    has under the device's step. Near the device's step, the device paces
    the engine; at ~0 the host does, and a shorter step gives nothing end to
    end."""
    waits = [f["wait_ns"] for _, f in flights(ctx)]
    return stats.median(waits) / 1e6 if waits else None


def step_flights_ahead(ctx: dict):
    """Median ``depth`` of the window's flights that no admission preceded:
    how many steps stood queued behind the device when one more was sent."""
    depths = [f["depth"] for _, f in flights(ctx) if not f["admitted"]]
    return stats.median(depths) if depths else None


def admit_stall_share_pct(ctx: dict):
    """The share of the window the streams lost to admissions, as they felt
    it: what the landing interval of every step that followed an admission
    had over the median interval of the steps that followed none (those that
    no profiler call cut), summed, over the window's length less the
    profiler's two calls. The counts go on an earlier line."""
    pairs, skipped = landing_pairs(ctx)
    plain = [d for d, cut, f in pairs if not f["admitted"] and not cut]
    if not plain:
        return None
    baseline = stats.median(plain)
    after = [d for d, _, f in pairs if f["admitted"]]
    lost = sum(max(0.0, d - baseline) for d in after)
    run = ctx["run"]
    length = (run["t_close"] - run["t_open"]) * 1e9 - sum(
        b - a for a, b in profiler_calls_ns(ctx))
    say(f"landing intervals of the window: {len(pairs)} pairs of consecutive "
        f"steps, {len(after)} of them after an admission "
        f"({sum(f['admitted'] for _, _, f in pairs)} admissions), "
        f"{sum(cut for _, cut, _ in pairs)} cut by a profiler call (what lies "
        f"outside it counts, none sets the baseline), {skipped} skipped (a "
        f"missing row between); baseline {baseline / 1e6:.3f} ms (median of "
        f"{len(plain)}, mean {sum(plain) / len(plain) / 1e6:.3f}); the "
        f"admissions' pairs hold {sum(after) / 1e9:.3f}s, {lost / 1e9:.3f}s "
        f"of it over the baseline, of {length / 1e9:.3f}s")
    return 100.0 * lost / length if length > 0 else None


def device_idle_restart_pct(ctx: dict):
    """The device's idle time inside the traced window that lies in the
    ``serve.step.prepare`` and ``serve.step.dispatch`` spans of the calls that
    dispatched a step with nothing in flight (``depth`` 0: after an admission
    drained the queue, the device waits for the host's dispatch), over the
    traced window. The idle time by every leaf phase and the busy time by
    program go on earlier lines."""
    found = ps.rows(ctx, FLIGHT)
    red, host = ctx.get("trace") or {}, ctx.get("host")
    if not found or not host or not red.get("busy_intervals"):
        return None
    window = tuple(host["window_ns"])
    restarts = {f["call"] for f in found if f["depth"] == 0}
    spans = [(RESTART, a, b) for name in (ps.PREPARE, ps.DISPATCH)
             for a, b, f in ps.in_trace(ctx, name)
             if f["parent"] in restarts]
    gaps = xplane.idle_gaps_by_span(red, spans, window, BETWEEN)
    say_idle_by_phase(ctx, red, window)
    say_busy_by_program(ctx, red)
    return 100.0 * gaps.get(RESTART, 0.0) / ((window[1] - window[0]) / 1e9)


# ------------------------------------------------------ the earlier lines
def idle_by_phase(ctx: dict, red: dict, window) -> dict:
    """Idle seconds of the first device inside the traced window by the
    program's leaf phase that covered them; what lies in an admission or a
    call outside its phases goes to that span's rest, what no call covers to
    ``between calls`` (the pump, the executor's hand-over, an idle engine)."""
    spans = [(label, a, b) for name, label in PHASES
             for a, b, _ in ps.in_trace(ctx, name)]
    return xplane.idle_gaps_by_span(red, spans, window, BETWEEN)


def say_idle_by_phase(ctx: dict, red: dict, window):
    gaps = idle_by_phase(ctx, red, window)
    say(f"device idle by program phase, {sum(gaps.values()):.4f}s of the "
        f"traced {(window[1] - window[0]) / 1e9:.3f}s: "
        + ("; ".join(f"{name} {s:.4f}" for name, s in sorted(
            gaps.items(), key=lambda kv: -kv[1])) or "none"))


def busy_by_program(ctx: dict, red: dict) -> dict:
    """``{role: (device seconds, executions)}`` over the WHOLE trace file
    (the reduced trace keeps each program's executions as durations, without
    instants), by the role the configuration's ``programs`` gives it
    (``decode``, ``prefill``, ``scatter``, ``state_write``; the dense
    family's file names its step alone, so its admission programs are named
    here); a program with no role goes to ``other``."""
    named = dict((ctx.get("config") or {}).get("programs") or {})
    named.setdefault("prefill", ps.PREFILL_MODULE)
    named.setdefault("scatter", "jit__scatter")
    out = {}
    for module, durations in (red.get("modules") or {}).items():
        role = next((r for r, pattern in named.items() if pattern in module),
                    "other")
        spent, runs = out.get(role, (0.0, 0))
        out[role] = (spent + sum(durations), runs + len(durations))
    return out


def say_busy_by_program(ctx: dict, red: dict):
    busy = busy_by_program(ctx, red)
    say("device seconds by program over the whole trace file: "
        + ("; ".join(f"{role} {s:.4f} ({n} runs)" for role, (s, n) in sorted(
            busy.items(), key=lambda kv: -kv[1][0])) or "none"))
