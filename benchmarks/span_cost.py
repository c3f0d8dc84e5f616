"""What the flight recorder's spans cost the host (ISSUE 24's cost budget).

    python benchmarks/span_cost.py            # prints one JSON object

Nanoseconds per ``events.span`` with the recorder on, off, and on under a
live ``jax.profiler`` session; and per ``step()``-shaped group (one outer
span, four phases inside it, the step's own ``serve.step.flight`` row through
``span_done`` with its seven fields and the clock read behind its ``wait_ns``,
and the pump's ``span_done``: the seven rows a decode step writes since
PR 58, six before). Host code only: pin the process to the CPU backend
(``JAX_PLATFORMS=cpu``) so it takes no chip.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.util import events  # noqa: E402

N = 20_000


def one_span():
    with events.span("serve.step.prepare", "serve"):
        pass


def one_step():
    """The rows of a call that dispatches one step and lands an older one,
    with the fields ``PagedEngine.step`` / ``_step`` / ``_land`` give them."""
    with events.span("serve.engine.step", "serve", k=1) as sp:
        with events.span("serve.step.prepare", "serve"):
            pass
        with events.span("serve.step.dispatch", "serve", step=11,
                         depth=10) as sent:
            pass
        with events.span("serve.step.fetch", "serve", step=1) as got:
            if got.sid and sent.t0_ns:
                events.span_done(
                    "serve.step.flight", "serve", sent.t0_ns, step=1, depth=1,
                    active=16, admitted=0,
                    wait_ns=time.perf_counter_ns() - got.t0_ns, call=sp.sid,
                    landed_by=sp.sid)
        with events.span("serve.step.emit", "serve", tokens=16, step=1):
            pass
        sp.set(active=16, admitted=0, tokens=16, pending=0, free_pages=1500,
               preempted=0, flights=10)
    events.span_done("serve.pump.deliver", "serve", sp.t0_ns, tokens=16,
                     lock_wait_ns=1)


def ns_per_call(fn, n=N):
    """Median over five batches; the ring is drained between batches (as the
    0.5 s flush tick does) so no batch measures the drop path."""
    out = []
    for _ in range(5):
        events.drain()
        t0 = time.perf_counter_ns()
        for _ in range(n):
            fn()
        out.append((time.perf_counter_ns() - t0) / n)
    events.drain()
    return sorted(out)[2]


def main():
    result = {"n": N, "jax_imported": "jax" in sys.modules}
    result["span_ns_on_without_jax"] = ns_per_call(one_span)
    import jax

    jax.devices()
    result["platform"] = jax.devices()[0].platform
    result["span_ns_on"] = ns_per_call(one_span)
    result["step_group_ns_on"] = ns_per_call(one_step, N // 5)
    events._enabled = False
    result["span_ns_off"] = ns_per_call(one_span)
    result["step_group_ns_off"] = ns_per_call(one_step, N // 5)
    events._enabled = True
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            result["span_ns_on_live_profiler"] = ns_per_call(one_span, 2000)
            result["step_group_ns_on_live_profiler"] = ns_per_call(
                one_step, 400)
        finally:
            jax.profiler.stop_trace()
    # the spill: bytes and seconds per flush tick of a decode replica's rows
    # (~35 rows a 0.5 s tick)
    for _ in range(6):
        one_step()
    rows, _ = events.drain()
    with tempfile.TemporaryDirectory() as d:
        events.spill(rows, d)           # makes the directory
        t0 = time.perf_counter_ns()
        wrote = sum(events.spill(rows, d) for _ in range(200))
        result["spill_us_per_tick"] = (time.perf_counter_ns() - t0) / 200e3
        result["spill_bytes_per_row"] = wrote / 200 / len(rows)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
