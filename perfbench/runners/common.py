"""What both runners share in the driver process, which stays off jax."""

from __future__ import annotations

import os
import sys
import time


def say(msg: str):
    print(f"[perfbench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def say_compared(lines):
    """Each number that decided ``correct`` beside its limit, as the run's
    last lines on standard error too (the standard output has them
    earlier): what a record of a run that was not correct keeps."""
    for line in lines:
        print(f"perfbench: compared: {line}", file=sys.stderr, flush=True)


def say_device_window(window: dict, stretch: str, clocks: str):
    """A traced run's device line in words, the unclipped figure beside it:
    ``busy_trace_s`` over ``window_s`` is an overrun seen and not only
    survived."""
    say(f"device line: busy {window['busy_s']:.6f}s inside {stretch} of "
        f"{window['window_s']:.6f}s; the whole trace file holds "
        f"{window['busy_trace_s']:.6f}s of device work ({clocks})")


#: a share of a roofline or of a peak is never left out of a line: a later
#: PR that takes a kernel off the path may not go unbounded for it
NEVER_LEFT_OUT = ("roofline", "mfu")


def every_listed_metric(man, cell: str, metrics: dict,
                        program_lacks: dict | None = None):
    """A traced line of the chip carries every per-layer metric the manifest
    lists for its cell, with one exception, or the run ends here with another
    code than 0 and no result line: a reader that came up empty (a traced
    stretch that held no admission, say) is said aloud, by name.

    The exception is a metric in ``program_lacks`` (``manifest.layer_values``
    fills it: metric -> the span names its reader asked for that NO row of
    the run carries). The program does not write that span at all, as the
    PARENT of the PR that adds the span and its reader does not, and the
    driver runs the parent's traced runs with the PR's benchmark files. Such
    a metric is named on standard error and left out of the line, and the
    run goes on: a parent-side line may lack a metric whose span the program
    cannot write. (On the change side the driver still refuses a line that
    lacks a listed metric.) A span the program does write, with no row in the
    stretch that was read, is an empty reading and ends the run as before; so
    does any ``roofline`` or ``mfu`` share, whatever it lacks."""
    lacking = [m["name"] for m in man.metrics_for(cell, "per_layer")
               if m["name"] not in metrics]
    left_out = [name for name in lacking if name in (program_lacks or {})
                and not any(part in name for part in NEVER_LEFT_OUT)]
    for name in left_out:
        print(f"perfbench: {cell}: {name} is left out of the line: no row of "
              f"this run carries {program_lacks[name]}, so the program does "
              f"not write what its reader reads", file=sys.stderr, flush=True)
    lacking = [name for name in lacking if name not in left_out]
    if lacking:
        raise SystemExit(
            f"perfbench: {cell}: the traced run has nothing to read for "
            f"{lacking}, which BENCHMARK.json lists for this cell: no result")


class NoChip(SystemExit):
    """The measuring path found no accelerator: exit non-zero, no result."""

    def __init__(self, why: str):
        print(f"perfbench: refusing to measure: {why}", file=sys.stderr,
              flush=True)
        super().__init__(3)


#: room for every program of a cell: the four-chip step program alone is
#: 172 MB, its sharded initialiser 25 MB
CACHE_ROOM_BYTES = 4 << 30


def make_room_in_compile_cache():
    """A machine may cap jax's persistent compile cache
    (``JAX_COMPILATION_CACHE_MAX_SIZE``; 192 MiB on the chip machines). Under
    a cap smaller than one cell's programs every run evicts what the next
    one needs, and every run compiles (measured: the four-chip cell set up in
    370 s warm and cold alike). The benchmark's processes ask for room; where
    the cache lives stays the program's choice
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``)."""
    cap = os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE")
    if cap is not None and 0 <= int(cap) < CACHE_ROOM_BYTES:
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(CACHE_ROOM_BYTES)


def start_cluster(chips: int, rehearse: bool):
    """``ray_tpu.init`` and, on the chip, the node's probe. Rehearsing, the
    chips are declared (there are none to probe) and the workers inherit
    the platform this shell is held to."""
    import ray_tpu
    from ray_tpu._private.node import session_pinned_off_tpu

    make_room_in_compile_cache()   # before anything is spawned: inherited
    if rehearse:
        ray_tpu.init(num_tpus=chips)
        return
    if session_pinned_off_tpu():
        raise NoChip("this environment pins the session off the TPU "
                     f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, "
                     f"RAY_TPU_JAX_PLATFORM="
                     f"{os.environ.get('RAY_TPU_JAX_PLATFORM')!r}); "
                     "--rehearse runs the paths on the CPU")
    ray_tpu.init()
    t0 = time.perf_counter()
    found = 0
    while time.perf_counter() - t0 < 180:
        found = ray_tpu.cluster_resources().get("TPU", 0)
        if found >= 1:
            break
        time.sleep(0.25)
    say(f"chip probe: TPU={found} after {time.perf_counter() - t0:.1f}s")
    if found != chips:
        ray_tpu.shutdown()
        raise NoChip(f"the cell needs {chips} chip(s), the node reports "
                     f"{found}")


def _parent_if_alive(pid):
    """The parent's pid, or None where ``pid`` has ended (a zombie has, but
    for the reaping)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
    except (OSError, IndexError, ValueError):
        return None   # exited while we looked
    return None if state == "Z" else int(ppid)


def _alive_descendants() -> set:
    parent = {int(pid): _parent_if_alive(pid)
              for pid in filter(str.isdigit, os.listdir("/proc"))}
    tree, grew = {os.getpid()}, True
    while grew:
        more = {p for p, pp in parent.items() if pp in tree} - tree
        tree |= more
        grew = bool(more)
    return tree - {os.getpid()}


def stop_cluster(with_serve: bool = False, limit_s: float = 90.0):
    """Shut the session down and WAIT until every process it started has
    ended (a replica holding gigabytes on the chip takes seconds to exit):
    a run leaves nothing behind."""
    import signal

    import ray_tpu

    started = _alive_descendants()
    try:
        if with_serve:
            from ray_tpu import serve

            serve.shutdown()
    finally:
        ray_tpu.shutdown()
    t0 = time.perf_counter()
    left = started
    while left and time.perf_counter() - t0 < limit_s:
        time.sleep(0.1)
        left = {pid for pid in left if _parent_if_alive(pid) is not None}
    for pid in left:
        say(f"process {pid} outlived the shutdown by {limit_s:.0f}s: killed")
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    say(f"every process of the session has ended "
        f"({time.perf_counter() - t0:.1f}s after the shutdown)")


def trace_sample_path():
    """Where a traced run leaves a small piece of its trace (the recorded
    samples beside the tests were made so); unset in every driver run."""
    p = os.environ.get("PERFBENCH_TRACE_SAMPLE")
    return os.path.abspath(p) if p else None


def check_device(device: dict | None, chips: int, rehearse: bool):
    if rehearse:
        return
    if (not device or device["platform"] != "tpu"
            or device["device_count"] != chips):
        raise NoChip(f"the process that computes reports {device}, the cell "
                     f"needs {chips} TPU chip(s)")


def device_line(device: dict, window: dict | None = None) -> dict:
    """The result line's ``device``. ``window`` is a traced run's
    ``xplane.device_window``: ``busy_s``, the seconds in which an operation
    ran on the device INSIDE the traced window (averaged over the chips),
    ``window_s``, that window's length on the same clock, so that
    0 <= ``busy_s`` <= ``window_s`` by construction, and ``busy_trace_s``, the
    unclipped union of the whole trace file, which the driver ignores: over
    ``window_s`` it shows an engine that ran ahead of the device through the
    profiler's start or stop."""
    peaks = [p for p in device.get("peak_bytes_in_use") or [] if p]
    out = {"platform": device["platform"], "kind": device["device_kind"],
           "count": device["device_count"],
           "memory_peak_bytes": max(peaks) if peaks else 0}
    out.update(window or {})
    return out
