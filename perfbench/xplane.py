"""From a profiler trace to numbers: the one reduction every PR shares.

``load`` turns the profiler's ``.xplane.pb`` into plain lists (so a recorded
sample can live beside the tests as JSON); ``reduce`` turns those into
device busy time, time per operation and per program, exposed collective
time and the benchmark's own host annotations, all on the trace's clock.

A device plane is ``/device:TPU:<n>``. Its ``XLA Ops`` line holds one event
per executed HLO operation (a TensorCore runs them one at a time; an event
may enclose others, e.g. a loop and its body, so times per operation are
SELF times), and its ``XLA Modules`` line one event per executed program.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from perfbench import stats

ANNOTATION_PREFIX = "perfbench/"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: an operation that moves data between chips; while one is the operation
#: the core is executing, the core computes nothing
COLLECTIVE_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)")


def op_name(event_name: str) -> str:
    """The instruction's own name: on the chip an operation's event carries
    its whole HLO text, ``%all-reduce.4 = bf16[...] all-reduce(%fusion.3)``."""
    return event_name.split(" = ", 1)[0]


def short_name(event_name: str) -> str:
    """``%copy.3 bf16[2048,16,8,128]``: the instruction's name and the
    (first) shape it yields, from the whole HLO text."""
    name, _, rest = event_name.partition(" = ")
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return f"{name[:96]} {shape.group(0)}" if shape else name[:100]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]}`` with the device planes whole and,
    of the host planes, only this benchmark's annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(ANNOTATION_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def write_sample(trace: dict, path: str, keep: int = 150):
    """A small piece of a loaded trace, to keep beside the tests."""
    import json

    sample = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": ln["events"][:keep]}
            for ln in p["lines"]]} for p in trace["planes"]]}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(sample, f, separators=(",", ":"))


def self_times(events):
    """``[(name, self_ns)]``: each event's duration less what the events it
    encloses on the same line cover."""
    out = []
    stack = []   # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


def _is_tensorcore(plane_name: str) -> bool:
    return bool(re.match(r"^/device:TPU:\d+$", plane_name))


def reduce(trace: dict) -> dict:
    """All times in seconds, clock in nanoseconds as the trace has it.

    ``busy_s``            per device: length of the union of EVERY operation
                          in the file, clipped to nothing: the profiler
                          records from inside ``start_trace()`` until
                          somewhere inside ``stop_trace()``, so this is not
                          the busy time of any window the host read
                          (``device_window`` gives that)
    ``op_self_s``         name -> self seconds, summed over devices
    ``modules``           name -> per-execution seconds, every device's
    ``modules_by_device`` the same, one dict per device
    ``collective_s``      per device: self time of collective operations
    ``busy_intervals``    of the first device, (start_ns, end_ns)
    ``annotations``       [(name, start_ns, end_ns)] the benchmark's spans
    ``span_ns``           first start and last end of any device operation
    """
    devices = []
    op_self = defaultdict(float)
    modules = defaultdict(list)
    annotations = []
    for plane in trace["planes"]:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if not plane["name"].startswith("/device:"):
            for events in lines.values():
                annotations += [(n, s, s + d) for n, s, d in events
                                if n.startswith(ANNOTATION_PREFIX)]
            continue
        if not _is_tensorcore(plane["name"]):
            continue
        ops = lines.get(OPS_LINE) or []
        if not ops:
            continue
        busy = stats.merge((s, s + d) for _, s, d in ops)
        collective = 0.0
        for name, self_ns in self_times(ops):
            op_self[name] += self_ns / 1e9
            if COLLECTIVE_RE.search(op_name(name)):
                collective += self_ns / 1e9
        own_modules = defaultdict(list)
        for name, _, dur in lines.get(MODULES_LINE) or []:
            modules[name].append(dur / 1e9)
            own_modules[name].append(dur / 1e9)
        devices.append({
            "modules": dict(own_modules),
            "plane": plane["name"],
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "collective_s": collective,
            "busy_intervals": busy,
            "span_ns": (busy[0][0], busy[-1][1]),
        })
    out = {"n_devices": len(devices), "op_self_s": dict(op_self),
           "modules": dict(modules),
           "annotations": sorted(annotations, key=lambda a: a[1])}
    if devices:
        out.update(
            busy_s=[d["busy_s"] for d in devices],
            collective_s=[d["collective_s"] for d in devices],
            modules_by_device=[d["modules"] for d in devices],
            busy_intervals=devices[0]["busy_intervals"],
            all_busy_intervals=[d["busy_intervals"] for d in devices],
            span_ns=(min(d["span_ns"][0] for d in devices),
                     max(d["span_ns"][1] for d in devices)))
    return out


def busy_inside_s(busy_intervals, window_ns) -> float:
    """Seconds of one device's ``busy_intervals`` (sorted and disjoint, as
    ``reduce`` gives them) that lie inside ``window_ns`` = (a_ns, b_ns) on
    the trace's clock: never under 0 and never over the window's own length,
    whatever ran before the window opened or after it closed."""
    window = [tuple(window_ns)]
    return sum(b - a for a, b in stats.intersect(busy_intervals, window)) / 1e9


def device_window(reduced: dict | None, window_ns) -> dict | None:
    """What a traced run's result line says of the device, read on ONE clock
    over ONE stretch: ``busy_s``, the seconds in which an operation ran on
    the device INSIDE ``window_ns`` (the trace's clock), averaged over the
    devices; ``window_s``, that window's length; and ``busy_trace_s``, the
    unclipped union of the whole file. An engine with steps in flight keeps
    the device busy through ``start_trace()`` and ``stop_trace()``, so
    ``busy_trace_s`` may exceed ``window_s``; ``busy_s`` cannot. A window in
    which nothing ran reads 0: that is what is true of it. None where the
    trace holds no device line."""
    if not reduced or not reduced.get("busy_s"):
        return None
    per_device = reduced.get("all_busy_intervals")
    if per_device is None:      # the replica ships one chip's list once
        if reduced["n_devices"] != 1:
            raise ValueError("the reduced trace of several devices came "
                             "without all_busy_intervals")
        per_device = [reduced["busy_intervals"]]
    inside = [busy_inside_s(iv, window_ns) for iv in per_device]
    return {"busy_s": sum(inside) / len(inside),
            "window_s": (window_ns[1] - window_ns[0]) / 1e9,
            "busy_trace_s": sum(reduced["busy_s"]) / len(reduced["busy_s"])}


def top_ops(reduced: dict, k: int = 10):
    """The operations that took most device time, per device on average."""
    n = max(reduced.get("n_devices", 0), 1)
    ranked = sorted(reduced.get("op_self_s", {}).items(),
                    key=lambda kv: -kv[1])[:k]
    return [[short_name(name), secs / n] for name, secs in ranked]


def module_times(reduced: dict, pattern: str):
    """Per-execution seconds of the programs whose name holds ``pattern``."""
    out = []
    for name, durs in reduced.get("modules", {}).items():
        if pattern in name:
            out += durs
    return out


def idle_gaps_by_span(reduced: dict, spans, window_ns, default: str):
    """Idle time of the first device inside ``window_ns``, attributed to the
    host span that covered it. ``spans``: ``[(name, start_ns, end_ns)]`` in
    priority order — an instant covered by two goes to the first listed.
    What no span covers goes to ``default``. -> ``{name: seconds}``."""
    window = [tuple(window_ns)]
    idle = stats.subtract(window, stats.intersect(
        reduced.get("busy_intervals", []), window))
    out = defaultdict(float)
    by_name = defaultdict(list)
    order = []
    for name, a, b in spans:
        if name not in by_name:
            order.append(name)
        by_name[name].append((a, b))
    for name in order:
        cover = stats.merge(by_name[name])
        hit = stats.intersect(idle, cover)
        out[name] += sum(b - a for a, b in hit) / 1e9
        idle = stats.subtract(idle, cover)
    out[default] += sum(b - a for a, b in idle) / 1e9
    return {k: v for k, v in out.items() if v > 0}
