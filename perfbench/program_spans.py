"""The program's own spans, as the new per-layer readers see them.

The replica's flight recorder (``ray_tpu/util/events.py``) appends the rows
it drains to ``<session_dir>/logs/events/plane-<pid>.jsonl``; the readers run
in the driver after the cluster has stopped and read that file back with
``events.read_spill``. A span row holds ``t0_ns`` / ``dur_ns`` on
``perf_counter_ns``, the clock of the client's timestamps (one host), so the
window is cut directly and ``ctx["host"]["offset_ns"]`` puts a span on the
device trace's clock.

A program without these spans (the parent of the PR that added them) has no
``read_spill`` and writes no such rows: every function here then returns
nothing, and the readers return None. None keeps its meaning, "nothing to
read"; that the run holds NO row of a name at all, which only a program that
does not write the span explains, is noted beside it (``rows`` notes,
``asked_in_vain`` collects), so that the harness can leave such a metric out
of a parent-side line instead of ending the run.
"""

from __future__ import annotations

from perfbench import stats

STEP, ADMIT = "serve.engine.step", "serve.engine.admit"
PREPARE, FETCH = "serve.step.prepare", "serve.step.fetch"
DISPATCH, EMIT = "serve.step.dispatch", "serve.step.emit"
PREFILL, SCATTER = "serve.admit.prefill", "serve.admit.scatter"
SAMPLE, DELIVER = "serve.admit.sample", "serve.pump.deliver"
#: the prefill program's module, as the trace's ``XLA Modules`` line names it
PREFILL_MODULE = "jit__prefill_one"


def spans(ctx: dict) -> dict:
    """``{name: [fields]}`` of the replica's span rows (those that carry
    ``t0_ns``), in the order they were recorded; read once per ``ctx``."""
    if "_program_spans" not in ctx:
        from ray_tpu.util import events

        read = getattr(events, "read_spill", None)
        rows = read(pid=(ctx.get("device") or {}).get("pid"),
                    session_dir=ctx.get("session_dir")) if read else []
        by_name: dict = {}
        for r in rows:
            if "t0_ns" in r["fields"]:
                by_name.setdefault(r["name"], []).append(r["fields"])
        ctx["_program_spans"] = by_name
    return ctx["_program_spans"]


_IN_VAIN = "_spans_asked_in_vain"


def note_in_vain(ctx: dict, *names):
    """A reader asked for these span names and no row of the run has one."""
    ctx.setdefault(_IN_VAIN, set()).update(names)


def asked_in_vain(ctx: dict, read):
    """``(read(ctx), names)``: the reader's value and, sorted, the span names
    it asked for that no row of the run carries."""
    ctx[_IN_VAIN] = set()
    value = read(ctx)
    return value, sorted(ctx.pop(_IN_VAIN))


def rows(ctx: dict, name: str) -> list:
    """The ``name`` span rows of the run, whenever they were recorded. A name
    that no row carries is noted: the program does not write it."""
    by_name = spans(ctx)
    if name not in by_name:
        note_in_vain(ctx, name)
    return by_name.get(name, [])


def in_window(ctx: dict, name: str) -> list:
    """The ``name`` spans that began inside the measured window."""
    a, b = ctx["run"]["t_open"] * 1e9, ctx["run"]["t_close"] * 1e9
    return [f for f in rows(ctx, name) if a <= f["t0_ns"] < b]


def in_trace(ctx: dict, name: str) -> list:
    """``[(start_ns, end_ns, fields)]`` on the TRACE's clock, of the ``name``
    spans that overlap the traced window (whole, not clipped); nothing where
    no trace was taken."""
    host = ctx.get("host")
    if not host:
        return []
    off = host["offset_ns"]
    a, b = host["window_ns"]
    out = [(f["t0_ns"] + off, f["t0_ns"] + f["dur_ns"] + off, f)
           for f in rows(ctx, name)]
    return [s for s in out if s[0] < b and s[1] > a]


def median_ms(ctx: dict, name: str, field: str = "dur_ns"):
    """Median of one field (nanoseconds) over the window, in ms."""
    values = [f[field] for f in in_window(ctx, name) if field in f]
    return stats.median(values) / 1e6 if values else None


def ms_per_prompt_token(ctx: dict, phase: str):
    """Time in one phase of the window's admissions over the prompt tokens
    they admitted (as ``admit_ms_per_prompt_token`` is): the phase's spans
    are those whose ``parent`` is an admission of the window."""
    admitted = {f["sid"]: f["prompt_len"] for f in in_window(ctx, ADMIT)}
    tokens = sum(admitted.values())
    of_phase = rows(ctx, phase)
    if not tokens or not of_phase:    # no row of the phase at all is not 0 ms
        return None
    spent = sum(f["dur_ns"] for f in of_phase if f["parent"] in admitted)
    return spent / 1e6 / tokens
