"""Plane-event flight recorder: one clock across every plane.

The task plane has had spans / ``task_events`` / ``timeline()`` since the
seed; every OTHER plane shipped in PRs 3-10 (broadcast, wait groups,
collectives, admission, serving, podracer) was observable only through
its own bench's ad-hoc counters — "concurrent broadcast traffic vs.
rollout egress" interference was undiagnosable because no two planes
shared a timeline. This module is the shared emitter: a cheap
per-process ring buffer stamped at the same plane boundaries the
failpoint registry already marks, flushed over the existing coalesced
``task_events`` push path into a bounded GCS plane-event table, and
surfaced through ``ray_tpu.util.state.timeline(planes=True)`` (one
Chrome-trace lane per (node, plane) — Perfetto shows all planes on one
clock), the metrics path (queue-depth gauges), and ``python -m ray_tpu
timeline --planes``.

Contract (the reason this can sit on hot paths):

* **Never backpressure the emit site.** ``emit`` is a bounded append
  under a tiny lock; a full ring increments the per-plane ``dropped``
  counter and returns — it never blocks, never allocates beyond the
  row, never raises into the caller.
* **Aggregate the per-frame paths.** Protocol send/dispatch run at
  100k+ frames/s; per-frame rows would be all drops. ``count`` folds
  them into per-(name, key) counters drained as ONE aggregate row per
  flush interval — the rate signal without the row storm.
* **Cross-link with spans.** When tracing is live (``RAY_TPU_TRACE`` or
  an adopted remote context), every row carries the active trace id, so
  a Perfetto lane click joins the task-plane span tree.

* **One monotonic clock for spans.** ``span`` / ``span_done`` stamp
  ``time.perf_counter_ns()`` (``CLOCK_MONOTONIC``: one clock for every
  process of a host) into the row's fields beside the wall ``ts``, so a
  client's ``perf_counter`` stamps, a profiler trace (one offset) and
  the rows of any process of the host compare directly.
* **Rows outlive the GCS.** Where rows leave a process, a process that
  knows its session directory also appends them to
  ``<session_dir>/logs/events/plane-<pid>.jsonl`` (``spill`` /
  ``read_spill``): bounded, off the emit path, silent on ``OSError``.

Event names are dotted three-segment literals (``plane.noun.verb``);
``ray_tpu check --events`` cross-checks every name referenced by
benchmarks/tests against the literals registered here-abouts, exactly
like ``--failpoints`` does for chaos sites.
"""

from __future__ import annotations

import asyncio
import contextvars
import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

# The planes a row may be tagged with (the timeline groups lanes by
# these; the --events checker treats the set as the name grammar's
# first-segment alphabet). "slo" rows are the interference detector's
# breach/recovery/sweep journal; "enforce" rows are the reactive
# control plane's action journal — cause (slo.*) and action (enforce.*)
# share one clock with every other plane, which is what lets
# ``timeline --planes`` prove breach -> attribution -> action ->
# recovery on a single trace. "train" and "jit" carry the set-up
# path: ``JaxTrainer.fit`` until the user's loop runs, and every program
# jax traces, lowers, compiles or loads from its cache.
PLANES = ("task", "proto", "gcs", "lease", "wait", "bcast", "coll",
          "serve", "rl", "pipe", "slo", "enforce", "train", "jit")

_lock = threading.Lock()
_ring: List[list] = []
_dropped: Dict[str, int] = {}
# (name, key) -> [n, nbytes] aggregate counters (hot per-frame paths).
_counts: Dict[Tuple[str, str], list] = {}

# Import-time snapshot of the enable flag + ring cap (hot-path reads);
# re-snapshotted on config change so driver-side _system_config lands.
_enabled = True
_cap = 65536
_spill_cap = 64 << 20


def _snapshot_config():
    global _enabled, _cap, _spill_cap
    try:
        from ray_tpu._private.config import config as _cfg

        c = _cfg()
        _enabled = bool(c.plane_events)
        _cap = max(16, int(c.plane_event_ring))
        _spill_cap = max(0, int(c.plane_event_spill_bytes))
    except Exception:  # pragma: no cover - bootstrap import cycles
        pass


def enabled() -> bool:
    return _enabled


def process_tenant() -> str:
    """The tenant (namespace) this process acts for — the connected
    driver/worker's namespace, or "" when no worker is live. Emit sites
    on tenant-less planes (broadcast chunk accounting, podracer
    rollout egress) tag their rows with this so the GCS-side
    interference detector can attribute a plane's traffic to a tenant
    without the emit site threading a namespace through every call."""
    worker_mod = sys.modules.get("ray_tpu._private.worker")
    if worker_mod is None:
        return ""
    w = worker_mod._global_worker
    if w is None:
        return ""
    ns = getattr(w, "namespace", "")
    return "" if ns in ("", "default", None) else str(ns)


def process_actor() -> Dict[str, object]:
    """``{"actor": <id hex>, "worker_pid": <pid>}`` inside an actor's
    constructor or method, else ``{}``: what the set-up rows of one actor
    carry in every process that writes one (the GCS's placement, the
    agent's spawn, the worker's boot and load, the constructor's span),
    so a reader joins them across spill files."""
    ctx_mod = sys.modules.get("ray_tpu._private.runtime_context")
    ctx = ctx_mod._exec_ctx.get() if ctx_mod is not None else None
    if not ctx or not ctx[1]:
        return {}
    return {"actor": ctx[1].hex(), "worker_pid": os.getpid()}


def process_start_ns() -> int:
    """This process's start — its fork, for a zygote's child — on
    ``perf_counter_ns``'s clock, from ``/proc/self/stat`` (the kernel
    counts it in ticks of 10 ms since boot); now, where that cannot be
    read."""
    now = time.perf_counter_ns()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return now - max(0, time.clock_gettime_ns(time.CLOCK_BOOTTIME)
                         - ticks * 10**9 // os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return now


def _trace_id() -> str:
    """Active trace id when the tracing module is live in this process
    (module-presence gate: don't import tracing just to answer no)."""
    tracing = sys.modules.get("ray_tpu.util.tracing")
    if tracing is None:
        return ""
    ctx = tracing._ctx.get()
    return ctx[0] if ctx is not None else ""


def emit(name: str, plane: str, tenant: str = "",
         dur: Optional[float] = None, trace: Optional[str] = None,
         **fields) -> None:
    """Record one discrete plane event. Bounded, non-blocking: a full
    ring drops the row and counts it — emit sites never stall.

    ``dur`` (seconds) makes the row a span in the exported trace
    (``ph="X"``); without it the row is an instant. ``trace`` overrides
    the ambient trace id (cross-process stitch points)."""
    if not _enabled:
        return
    _append([time.time(), name, plane, tenant,
             trace if trace is not None else _trace_id(),
             float(dur) if dur is not None else 0.0,
             fields if fields else None])


def _append(row: list) -> None:
    with _lock:
        if len(_ring) < _cap:
            _ring.append(row)
        else:
            _dropped[row[2]] = _dropped.get(row[2], 0) + 1


# ------------------------------------------------------------- spans

_span_ids = itertools.count(1)          # per process
_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_plane_span", default=0)


def _annotation(name: str):
    """The same interval on the profiler's own clock, when jax is
    already in the process (module-presence gate: the GCS and drivers
    stay jax-free). With no profiler session live this is TraceMe's
    inactive path."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation("ray_tpu/" + name)
    except AttributeError:  # pragma: no cover - jax still importing
        return None


def _record_span(name, plane, tenant, t0_ns, sid, parent, fields):
    end_ns = time.perf_counter_ns()
    fields.update(t0_ns=t0_ns, dur_ns=end_ns - t0_ns, sid=sid,
                  parent=parent)
    _append([time.time(), name, plane, tenant, _trace_id(),
             (end_ns - t0_ns) / 1e9, fields])


def span_done(name: str, plane: str, t0_ns: int, tenant: str = "",
              **fields) -> None:
    """Record as a span an interval that began at ``t0_ns`` (a
    ``time.perf_counter_ns()`` reading, possibly another thread's) and
    ends now: the begin/end spelling for sites a ``with`` cannot
    bracket (no profiler annotation: that cannot be entered late)."""
    if not _enabled:
        return
    _record_span(name, plane, tenant, t0_ns, next(_span_ids),
                 _current_span.get(), fields)


class span:
    """``with events.span("serve.engine.step", "serve", k=3) as sp:`` —
    one row when the block ends, holding the interval on the monotonic
    clock (``t0_ns`` / ``dur_ns``), a per-process span id and the
    enclosing span's (``sid`` / ``parent``). ``sp.set(tokens=n)`` adds
    what is known only at the end. A span brackets what the host does
    today: it adds no synchronisation. With the recorder off it returns
    before reading a clock."""

    __slots__ = ("name", "plane", "tenant", "fields", "sid", "t0_ns",
                 "_parent", "_token", "_ann")

    def __init__(self, name: str, plane: str, tenant: str = "", **fields):
        self.name, self.plane, self.tenant = name, plane, tenant
        self.fields = fields
        self.sid = self.t0_ns = 0

    def set(self, **fields) -> None:
        self.fields.update(fields)

    def __enter__(self):
        if not _enabled:
            return self
        self.sid = next(_span_ids)
        self._parent = _current_span.get()
        self._token = _current_span.set(self.sid)
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if not self.sid:
            return False
        if _enabled:
            _record_span(self.name, self.plane, self.tenant, self.t0_ns,
                         self.sid, self._parent, self.fields)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _current_span.reset(self._token)
        return False


def count(name: str, key: str = "", n: int = 1, nbytes: int = 0,
          plane: str = "proto") -> None:
    """Fold a hot-path occurrence into an aggregate counter. Drained as
    one ``{name, key, n, bytes}`` row per flush — the per-frame planes
    (protocol send/dispatch) ride this, never per-event rows."""
    if not _enabled:
        return
    k = (name, key)
    with _lock:
        c = _counts.get(k)
        if c is None:
            _counts[k] = [n, nbytes, plane]
        else:
            c[0] += n
            c[1] += nbytes


def pending() -> int:
    with _lock:
        return len(_ring) + len(_counts)


def dropped_counts() -> Dict[str, int]:
    """Per-plane rows dropped at THIS process's ring since the last
    drain (drain resets; the GCS table accumulates pushed totals)."""
    with _lock:
        return dict(_dropped)


def drain() -> Tuple[List[list], Dict[str, int]]:
    """Swap out the ring + fold counters into rows; returns
    ``(rows, dropped)``. Counter rows carry ``{"n": .., "bytes": ..}``
    fields and a zero duration. Resets the drop counters — the flusher
    forwards them to the GCS, which accumulates."""
    with _lock:
        rows, _ring[:] = list(_ring), []
        counts, drops = dict(_counts), dict(_dropped)
        _counts.clear()
        _dropped.clear()
    now = time.time()
    for (name, key), (n, nb, plane) in counts.items():
        rows.append([now, name, plane, "", "", 0.0,
                     {"key": key, "n": n, "bytes": nb, "agg": 1}])
    return rows, drops


def reset() -> None:
    """Test hook: drop everything buffered (ring, counters, drops)."""
    with _lock:
        _ring.clear()
        _counts.clear()
        _dropped.clear()


def drain_and_spill(deliver, session_dir: Optional[str],
                    nid: bytes = b"") -> int:
    """The one drain-and-send of a process's ring: the rows go to
    ``deliver`` as a ``plane_events`` frame (a connection's ``send``, or
    the GCS's own table) and to this process's spill file. Every flusher
    calls it: a driver's metrics tick and disconnect (``flush_now``), a
    worker's coalesced ``task_events`` tick, the node agent's reap tick,
    the GCS's fold of its own ring. On an event loop the file append
    goes to an executor (``spilled`` waits for the last one); a
    connection lost under the send costs the frame, never the file."""
    if not _enabled or pending() == 0:
        return 0
    rows, drops = drain()
    if not rows and not drops:
        return 0
    try:
        deliver({"t": "plane_events", "ev": rows, "drops": drops,
                 "nid": nid or b"", "pid": os.getpid()})
    except ConnectionError:
        pass
    global _last_spill
    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:
        spill(rows, session_dir)
    else:
        _last_spill = loop.run_in_executor(None, spill, rows, session_dir)
    return len(rows)


async def spilled(timeout: float = 0.25) -> None:
    """Wait (bounded) for the last spill append an event loop handed to
    its executor: what a process does before a hard exit."""
    if _last_spill is not None:
        await asyncio.wait([_last_spill], timeout=timeout)


def flush_now(worker=None) -> int:
    """Push buffered rows to the GCS plane-event table (no-op when not
    connected). Driver processes flush through the metrics flusher's
    tick (``util/metrics.py``) and once more when they disconnect;
    workers flush through the executor's coalesced ``task_events`` loop.
    Thread-safe: the send marshals onto the worker IO loop."""
    global _session_dir
    if worker is None:
        from ray_tpu._private import worker as worker_mod

        worker = worker_mod._global_worker
    if (worker is None or worker.closed or worker.gcs is None
            or worker.loop is None):
        return 0
    _session_dir = worker.session_dir or _session_dir
    return drain_and_spill(
        lambda frame: worker.loop.call_soon_threadsafe(
            worker._send_gcs, frame),
        worker.session_dir, getattr(worker, "node_id", b""))


# ------------------------------------------------------------- spill
# The GCS table is an in-memory deque: it dies with the GCS, which is
# when a post-mortem wants the rows (Ray's analog:
# ``logs/events/event_*.log``). Two segments per process, the older
# dropped when the newer passes half the cap.

_spill_lock = threading.Lock()
_spill_dirs: set = set()
# the last append handed to an event loop's executor (``spilled``)
_last_spill = None
# the last session this process flushed into (``read_spill``'s default
# once the worker has disconnected)
_session_dir: Optional[str] = None


def spill_path(session_dir: str, pid: int) -> str:
    return os.path.join(session_dir, "logs", "events",
                        f"plane-{pid}.jsonl")


def _plain(value):
    return value.hex() if isinstance(value, (bytes, bytearray)) \
        else str(value)


_encode_row = json.JSONEncoder(separators=(",", ":"),
                               default=_plain).encode


def spill(rows: List[list], session_dir: Optional[str],
          pid: Optional[int] = None) -> int:
    """Append drained rows as JSON lines to this process's spill file;
    returns the bytes written. BLOCKING file I/O, off the emit path:
    ``drain_and_spill`` hands it to an executor where it runs on an
    event loop. Silent on ``OSError`` — a full or read-only disk costs
    the file, never the process."""
    global _session_dir
    if session_dir:
        _session_dir = session_dir
    if not rows or not session_dir or _spill_cap <= 0:
        return 0
    path = spill_path(session_dir, pid or os.getpid())
    data = "".join(_encode_row(r) + "\n" for r in rows)
    try:
        with _spill_lock:
            folder = os.path.dirname(path)
            if folder not in _spill_dirs:
                os.makedirs(folder, exist_ok=True)
                _spill_dirs.add(folder)
            with open(path, "a") as f:
                f.write(data)
                size = f.tell()
            if size > _spill_cap // 2:
                os.replace(path, path + ".1")
    except (OSError, ValueError):
        return 0
    return len(data)


def read_spill(pid: Optional[int] = None,
               session_dir: Optional[str] = None) -> List[dict]:
    """Decoded rows (``row_to_dict`` shape) of one process's spill file,
    or of every process's where ``pid`` is None, oldest first per
    process. ``session_dir`` defaults to the session this process is
    connected to, else the last one it flushed into — never another
    session's, so concurrent clusters do not read each other's."""
    if session_dir is None:
        worker_mod = sys.modules.get("ray_tpu._private.worker")
        w = worker_mod._global_worker if worker_mod else None
        session_dir = getattr(w, "session_dir", None) or _session_dir
    if not session_dir:
        return []
    folder = os.path.dirname(spill_path(session_dir, 0))
    if pid is None:
        try:
            names = sorted(os.listdir(folder))
        except OSError:
            return []
        stems = [n[len("plane-"):-len(".jsonl")] for n in names
                 if n.startswith("plane-") and n.endswith(".jsonl")]
        pids = [int(stem) for stem in stems if stem.isdigit()]
    else:
        pids = [int(pid)]
    out: List[dict] = []
    for p in pids:
        path = spill_path(session_dir, p)
        for segment in (path + ".1", path):
            try:
                with open(segment) as f:
                    lines = f.readlines()
            except OSError:
                continue
            for line in lines:
                try:
                    out.append(row_to_dict(json.loads(line), pid=p))
                except ValueError:
                    continue    # a torn last line of a killed process
    return out


def gauge(name: str, description: str = "",
          tag_keys: Tuple[str, ...] = ()):
    """A recorder-gated queue-depth gauge: returns a ``set(value,
    **tags)`` callable that lazily creates the underlying
    ``metrics.Gauge`` on first use (importing an emitter module never
    starts the metrics flusher) and no-ops while the recorder is
    disabled — the ``--recorder off`` A/B arm silences the telemetry
    gauges with the event rows, in one place."""
    holder: list = []

    def set_value(value, **tags) -> None:
        if not _enabled:
            return
        if not holder:
            from ray_tpu.util.metrics import Gauge

            holder.append(Gauge(name, description,
                                tag_keys=tuple(tag_keys)))
        holder[0].set(value, tags=tags or None)

    return set_value


def row_to_dict(row, nid_hex: str = "", pid: int = 0) -> dict:
    """Decode one stored row (the state API / timeline read side)."""
    ts, name, plane, tenant, trace, dur, fields = row
    return {"ts": ts, "name": name, "plane": plane, "tenant": tenant,
            "trace_id": trace, "dur": dur, "fields": fields or {},
            "node_id": nid_hex, "pid": pid}


def stripe_share(rows) -> Dict[str, dict]:
    """Per-object source-share accounting over broadcast chunk events.

    Input: decoded plane-event rows (``list_plane_events()`` dicts).
    Every completed chunk transfer emits ``bcast.chunk.done`` on the
    PULLER with ``{oid, src, nbytes}`` — summing those per (object,
    source) yields exactly how many delivered bytes each endpoint
    served. The object-plane-v2 target is stated on this output: on a
    cooperative relay no single source (the origin included) serves
    >=50% of an object's delivered bytes. Endgame ``bcast.chunk.steal``
    duplicates are counted so a report can bound the waste.
    """
    out: Dict[str, dict] = {}
    for r in rows:
        name = r.get("name")
        if name not in ("bcast.chunk.done", "bcast.chunk.steal"):
            continue
        f = r.get("fields") or {}
        oid = str(f.get("oid") or "")
        o = out.setdefault(oid, {"bytes": 0, "chunks": 0, "steals": 0,
                                 "sources": {}})
        if name == "bcast.chunk.steal":
            o["steals"] += 1
            continue
        src = str(f.get("src") or "?")
        nb = int(f.get("nbytes") or 0)
        o["bytes"] += nb
        o["chunks"] += 1
        s = o["sources"].setdefault(src, {"chunks": 0, "bytes": 0})
        s["chunks"] += 1
        s["bytes"] += nb
    for o in out.values():
        total = o["bytes"]
        max_src, max_bytes = "", 0
        for src, s in o["sources"].items():
            s["share"] = (s["bytes"] / total) if total else 0.0
            if s["bytes"] > max_bytes:
                max_src, max_bytes = src, s["bytes"]
        o["max_share"] = (max_bytes / total) if total else 0.0
        o["max_src"] = max_src
    return out


_snapshot_config()
try:
    from ray_tpu._private.config import on_config_change

    on_config_change(_snapshot_config)
except Exception:  # pragma: no cover - bootstrap import cycles
    pass
