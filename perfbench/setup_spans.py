"""The set-up path's spans, as the six ``setup_*_s`` readers see them.

Every process of a session appends the flight recorder's rows it drains to
``<session_dir>/logs/events/plane-<pid>.jsonl``; the readers run in the
driver after the cluster has stopped and read ALL of those files back
(``events.read_spill(pid=None)``): the driver's ``gcs.cluster.start``, the
head's ``gcs.node.probe`` / ``lease.actor.place`` / ``lease.worker.spawn``,
the chip-holding worker's ``lease.worker.boot`` / ``lease.actor.load``, its
constructor's span
(``serve.replica.init`` / ``train.worker.setup``), ``jit.program.build`` and
the engine's own spans. All are on ``perf_counter_ns``, one clock for every
process of the host and the clock of a serving run's ``t_open``; a training
run's window opens on the wall clock (``train["t_open_wall"]``), which the
rows' own ``ts`` (wall, taken as a span ends) and ``t0_ns + dur_ns`` put on
the monotonic one.

The six durations overlap (a program is built inside a constructor, the probe
runs beside the driver's registration), so ``phases`` also TILES the stretch
from the start of ``gcs.cluster.start`` to the window's opening: each instant
goes to the first phase of ``PHASES`` that has a span over it, as
``xplane.idle_gaps_by_span`` tiles idle time, and what no span of any process
covers is ``untraced``.

A program without these spans (the parent of the PR that added them) writes
no ``gcs.cluster.start`` row: every function here then returns nothing, and
the readers return None.
"""

from __future__ import annotations

import os

from perfbench import program_spans, stats
from perfbench.runners.common import say

CLUSTER, PROBE = "gcs.cluster.start", "gcs.node.probe"
PLACE, SPAWN, BOOT, LOAD = ("lease.actor.place", "lease.worker.spawn",
                            "lease.worker.boot", "lease.actor.load")
ENTRY = ("serve.app.run", "train.fit.start")
INIT = ("serve.replica.init", "train.worker.setup")
BUILD, BACKEND = "jit.program.build", "backend_compile_duration"

#: the tiling's phases in their order of priority, each with the spans that
#: make it; a span no phase names goes to ``other spans``
PHASES = (
    ("program build or cache load", (BUILD,)),
    ("weights", ("serve.replica.weights",)),
    ("engine construction", ("serve.replica.engine",)),
    ("engine steps and admissions (warm-up, lead-in, fill)",
     ("serve.engine.step", "serve.engine.admit")),
    ("jax import", ("jit.jax.import",)),
    ("actor constructor, rest", INIT),
    ("actor class and arguments load", (LOAD,)),
    ("worker boot", (BOOT,)),
    ("worker spawn", (SPAWN,)),
    ("actor placement", (PLACE,)),
    ("cluster start", (CLUSTER,)),
    ("chip probe", (PROBE,)),
    ("deploy or fit, rest", ENTRY),
)
OTHER, UNTRACED = "other spans", "untraced"


def spans(ctx: dict) -> list:
    """Every span row of every process of the session as
    ``{"name", "pid", "t0", "end", "ts", "f"}`` (ns; ``f`` the row's
    fields), oldest first; read once per ``ctx``. Empty where the program
    wrote no ``gcs.cluster.start``."""
    if "_setup_spans" not in ctx:
        from ray_tpu.util import events

        read = getattr(events, "read_spill", None)
        rows = read(pid=None, session_dir=ctx.get("session_dir")) \
            if read else []
        out = [{"name": r["name"], "pid": r["pid"], "ts": r["ts"],
                "t0": r["fields"]["t0_ns"],
                "end": r["fields"]["t0_ns"] + r["fields"]["dur_ns"],
                "f": r["fields"]}
               for r in rows if "dur_ns" in r["fields"]]
        out.sort(key=lambda s: s["t0"])
        ctx["_setup_spans"] = out if any(
            s["name"] == CLUSTER for s in out) else []
    return ctx["_setup_spans"]


def named(ctx: dict, *names) -> list:
    """The rows of any of ``names``; where the run holds none at all, that
    is noted for the harness (``program_spans.asked_in_vain``): the program
    does not write them, and a parent-side line may lack their metric."""
    found = [s for s in spans(ctx) if s["name"] in names]
    if not found:
        program_spans.note_in_vain(ctx, *names)
    return found


def seconds(span: dict) -> float:
    return (span["end"] - span["t0"]) / 1e9


def t_open_ns(ctx: dict):
    """The window's opening on the spans' clock."""
    if "run" in ctx:
        return ctx["run"]["t_open"] * 1e9
    wall = (ctx.get("train") or {}).get("t_open_wall")
    rows = spans(ctx)
    if wall is None or not rows:
        return None
    # a row's ``ts`` is the wall clock where its span ended
    return wall * 1e9 + stats.median([s["end"] - s["ts"] * 1e9
                                      for s in rows])


def chip_holders(ctx: dict) -> list:
    """The constructor's span of every actor that was granted a chip (its
    ``actor`` has a ``lease.actor.place`` row): the replica, or each train
    worker."""
    placed = {s["f"].get("actor") for s in named(ctx, PLACE)}
    return [s for s in named(ctx, *INIT) if s["f"].get("actor") in placed]


# ------------------------------------------------------------- the six
def setup_cluster_s(ctx):
    """``ray_tpu.init()`` in the driver: head spawned, GCS serving, the
    node agent registered, the driver connected."""
    first = named(ctx, CLUSTER)
    return seconds(first[0]) if first else None


def setup_chip_probe_s(ctx):
    """The node agent's probe: subprocess start -> the node's ``TPU`` count
    sent to the GCS (nothing where the chips were declared)."""
    probes = named(ctx, PROBE)
    return max(map(seconds, probes)) if probes else None


def setup_actor_start_s(ctx):
    """Start of ``serve.run`` / ``JaxTrainer.fit`` -> the chip-holding
    actor's constructor begins (placement, spawn, boot and load are inside
    it); with several workers, the slowest."""
    entry, holders = named(ctx, *ENTRY), chip_holders(ctx)
    if not entry or not holders:
        return None
    return max(h["t0"] - entry[0]["t0"] for h in holders) / 1e9


def setup_actor_init_s(ctx):
    """The chip-holding actor's constructor (``serve.replica.init``), or a
    train worker's set-up until the user's loop is entered; the slowest."""
    holders = chip_holders(ctx)
    if not holders:
        return None
    slowest = max(holders, key=seconds)
    inside = [s for s in spans(ctx) if s["pid"] == slowest["pid"]
              and s["f"].get("parent") == slowest["f"].get("sid")]
    say(f"{slowest['name']} {seconds(slowest):.2f}s"
        + "".join(f"; {s['name']} {seconds(s):.2f}s" for s in inside))
    return seconds(slowest)


def setup_program_build_s(ctx):
    """Seconds, before the window opens, in which a process that holds a
    chip traced, lowered, compiled or loaded a program (the union of its
    ``jit.program.build`` rows: a trace inside a trace counts once)."""
    t_open, pids = t_open_ns(ctx), {h["pid"] for h in chip_holders(ctx)}
    if t_open is None or not pids:
        return None
    rows = [s for s in named(ctx, BUILD)
            if s["pid"] in pids and s["end"] <= t_open]
    if not rows:
        return None
    backend = [s for s in rows if s["f"].get("event") == BACKEND]
    longest = sorted(backend, key=seconds, reverse=True)[:3]
    say(f"programs built before the window: {len(backend)}, "
        f"{sum(1 for s in backend if s['f'].get('cache_hit'))} of them "
        f"from the persistent cache; longest: "
        + ", ".join(f"{s['f'].get('program')} {seconds(s):.2f}s"
                    for s in longest))
    return sum(
        sum(b - a for a, b in stats.merge(
            [(s["t0"], s["end"]) for s in rows if s["pid"] == pid]))
        for pid in pids) / 1e9


def phases(ctx: dict):
    """``(stretch_s, [(phase, seconds)], [(a_s, b_s)])``: the stretch from
    the start of ``gcs.cluster.start`` to the window's opening, tiled by
    ``PHASES`` in their order, then ``other spans`` and ``untraced``; and
    the uncovered intervals, in seconds from the stretch's start."""
    start, t_open = named(ctx, CLUSTER), t_open_ns(ctx)
    if not start or t_open is None or t_open <= start[0]["t0"]:
        return None
    t0 = start[0]["t0"]
    left = [(t0, t_open)]
    rest = {s["name"] for s in spans(ctx)}
    table = []
    for phase, names in PHASES + ((OTHER, None),):
        cover = stats.merge([(s["t0"], s["end"]) for s in spans(ctx)
                             if s["name"] in (rest if names is None
                                              else names)])
        rest -= set(names or ())
        table.append((phase, sum(
            b - a for a, b in stats.intersect(left, cover)) / 1e9))
        left = stats.subtract(left, cover)
    table.append((UNTRACED, sum(b - a for a, b in left) / 1e9))
    return ((t_open - t0) / 1e9, table,
            [((a - t0) / 1e9, (b - t0) / 1e9) for a, b in left])


def setup_untraced_s(ctx):
    """Seconds between the start of ``gcs.cluster.start`` and the window's
    opening that no span of any process covers; the table by phase goes to
    an earlier output line."""
    tiled = phases(ctx)
    if tiled is None:
        return None
    stretch, table, gaps = tiled
    start = named(ctx, CLUSTER)[0]
    if start["pid"] == os.getpid():     # the readers run in the driver
        from ray_tpu.util import events

        say(f"{(start['t0'] - events.process_start_ns()) / 1e9:.1f}s of "
            f"this process (interpreter, imports) lie before {CLUSTER}")
    say(f"set-up by phase, {stretch:.1f}s from the start of {CLUSTER} to "
        f"the window's opening: "
        + "; ".join(f"{phase} {s:.1f}" for phase, s in table if s >= 0.05))
    say("untraced stretches over 0.5s, from-to in seconds: "
        + (", ".join(f"{a:.1f}-{b:.1f}" for a, b in gaps if b - a > 0.5)
           or "none"))
    return table[-1][1]


#: metric -> its layer in PERF.md's list: what a manifest entry says beside
#: ``"unit": "s", "better": "lower", "source": "program_span",
#: "moves": "setup_s"``, and what its file under ``layer_metrics/`` imports
#: from here as ``read``
METRICS = {
    "setup_cluster_s": "scheduler and chip ownership",
    "setup_chip_probe_s": "scheduler and chip ownership",
    "setup_actor_start_s": "scheduler and chip ownership",
    "setup_actor_init_s": "entry points",
    "setup_program_build_s": "entry points",
    "setup_untraced_s": "entry points",
}
