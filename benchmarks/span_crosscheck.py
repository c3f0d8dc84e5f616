"""One run of a serving cell through perfbench's own entry point, keeping
what its readers saw, to hold the program's spans against the benchmark's
wrapper spans (ISSUE 24's cross-checks).

    python benchmarks/span_crosscheck.py --workload serve-decode-heavy \\
        --seed 7 --trace 1        # perfbench/run.py's arguments

Prints perfbench's result line as always and, before it, one line
``[crosscheck] {...}``; with ``SPAN_CROSSCHECK_OUT`` set, writes the same
object there. Nothing here changes what is measured: ``layer_values`` is
wrapped to look at the ``ctx`` after the readers have run.

- ``admit_cover``: the program's ``serve.engine.admit`` spans over the
  wrapper's ``engine.admit`` spans that enclose them (sums, in the window).
- ``step_cover``: ``prepare + dispatch + fetch + emit`` over the wrapper's
  wall time, on steps that decoded and admitted nothing (ratio of sums, and
  the least and the largest single step).
- the phases' shares, rows a second and spill bytes a second in the window.
- ``flight_metrics``: the four readers of ``serve.step.flight``
  (``perfbench/flight_spans.py``) on the same ``ctx``, whichever of them the
  cell lists: the chat cell lists none (its landing intervals are arrivals,
  not steps), so this is the only way to its readings. Under the run-ahead a
  call dispatches one step and lands an older one: ``step_cover`` still tiles
  a CALL by its four phases, whichever steps they belong to.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


FLIGHT_METRICS = ("step_host_slack_ms", "step_flights_ahead",
                  "admit_stall_share_pct", "device_idle_restart_pct")


def crosscheck(ctx: dict, values: dict, man=None) -> dict:
    from perfbench import program_spans as ps, stats
    from ray_tpu.util import events

    a, b = ctx["run"]["t_open"] * 1e9, ctx["run"]["t_close"] * 1e9
    rows = ps.spans(ctx)
    out = {"window_s": (b - a) / 1e9, "window_ns": [a, b],
           "profiler_calls_s": ctx["run"].get("trace_ctl") or []}

    def kids(parent_sid, name):
        return [f for f in rows.get(name, []) if f["parent"] == parent_sid]

    # 1. admissions: program spans inside each wrapper span
    inside = wrapper = 0
    for t0, t1, taken, _ in ctx["spans"].get("admits") or []:
        if not taken or not a <= t1 < b:
            continue
        wrapper += t1 - t0
        inside += sum(f["dur_ns"] for f in rows.get(ps.ADMIT, [])
                      if t0 <= f["t0_ns"] and f["t0_ns"] + f["dur_ns"] <= t1)
    out["admit_wrapper_s"], out["admit_program_s"] = wrapper / 1e9, inside / 1e9
    out["admit_cover"] = inside / wrapper if wrapper else None
    admits = ps.in_window(ctx, ps.ADMIT)
    total = sum(f["dur_ns"] for f in admits)
    if total:
        sids = {f["sid"] for f in admits}
        out["admit_phase_share"] = {
            name.rsplit(".", 1)[1]: sum(
                f["dur_ns"] for f in rows.get(name, [])
                if f["parent"] in sids) / total
            for name in (ps.PREFILL, ps.SCATTER, ps.SAMPLE)}
        out["admissions"] = len(admits)
        out["prompt_tokens"] = sum(f["prompt_len"] for f in admits)
    # 2. decode steps that admitted nothing: the four phases over the wall
    phases = (ps.PREPARE, ps.DISPATCH, ps.FETCH, ps.EMIT)
    ratios, walls, medians = [], [], {p: [] for p in phases}
    sum_phase = sum_wall = 0
    program_steps = sorted(rows.get(ps.STEP, []), key=lambda f: f["t0_ns"])
    for t0, t1, admitted, decoded, _, _ in ctx["spans"].get("steps") or []:
        if admitted or decoded <= 0 or not a <= t1 < b:
            continue
        own = [f for f in program_steps
               if t0 <= f["t0_ns"] and f["t0_ns"] + f["dur_ns"] <= t1]
        if len(own) != 1:
            continue
        parts = {p: sum(f["dur_ns"] for f in kids(own[0]["sid"], p))
                 for p in phases}
        for p in phases:
            medians[p].append(parts[p])
        sum_phase += sum(parts.values())
        sum_wall += t1 - t0
        ratios.append(sum(parts.values()) / (t1 - t0))
        walls.append(t1 - t0)
    if ratios:
        out["step_cover"] = sum_phase / sum_wall
        out["step_cover_min_max"] = [min(ratios), max(ratios)]
        out["steps"] = len(ratios)
        out["step_wall_ms_median"] = stats.median(walls) / 1e6
        out["step_phase_ms_median"] = {
            p.rsplit(".", 1)[1]: stats.median(v) / 1e6
            for p, v in medians.items()}
    delivers = ps.in_window(ctx, ps.DELIVER)
    if delivers:
        out["lock_wait_us_median"] = stats.median(
            [f["lock_wait_ns"] for f in delivers]) / 1e3
    # 3. what the recorder wrote in the window
    session = ctx.get("session_dir") or events._session_dir
    pid = (ctx.get("device") or {}).get("pid")
    n_rows = n_bytes = 0
    wall0 = wall1 = None
    for suffix in (".1", ""):
        try:
            with open(events.spill_path(session, pid) + suffix) as f:
                for line in f:
                    ts = json.loads(line)[0]
                    wall0 = ts if wall0 is None else min(wall0, ts)
                    wall1 = ts if wall1 is None else max(wall1, ts)
                    n_rows += 1
                    n_bytes += len(line)
        except (OSError, TypeError, ValueError):
            continue
    if n_rows and wall1 > wall0:
        out["spill_rows_per_s"] = n_rows / (wall1 - wall0)
        out["spill_bytes_per_s"] = n_bytes / (wall1 - wall0)
        out["spill_bytes"] = n_bytes
    in_win = sum(len([f for f in fs if a <= f["t0_ns"] < b])
                 for fs in rows.values())
    out["span_rows_per_s_in_window"] = in_win / out["window_s"]
    out["metrics"] = {k: v["value"] for k, v in values.items()}
    if man is not None:
        # a cell that lists one has read it already (and said its lines)
        out["flight_metrics"] = {
            name: out["metrics"][name] if name in out["metrics"]
            else man.reader(name)(ctx) for name in FLIGHT_METRICS}
    return out


def main():
    from perfbench import run
    from perfbench.runners import serve

    readers = serve.layer_values

    def keep(man, cell, ctx):
        values = readers(man, cell, ctx)
        try:
            found = crosscheck(ctx, values, man)
        except Exception as e:  # noqa: BLE001 — never cost the run its line
            found = {"error": repr(e)}
        print("[crosscheck] " + json.dumps(found), flush=True)
        if os.environ.get("SPAN_CROSSCHECK_OUT"):
            os.makedirs(os.path.dirname(os.path.abspath(
                os.environ["SPAN_CROSSCHECK_OUT"])), exist_ok=True)
            with open(os.environ["SPAN_CROSSCHECK_OUT"], "w") as f:
                json.dump(found, f, indent=1)
        return values

    serve.layer_values = keep
    run.main()


if __name__ == "__main__":
    main()
