"""Serving cells: ``serve.run`` -> ``LLMServer`` -> ``PagedEngine``, loaded
from this one process by an asyncio generator, every request streamed.

The driver stays off jax: the chip goes to the replica, which is also the
only process that can trace it.
"""

from __future__ import annotations

import asyncio
import math
import os
import sys
import time

from perfbench import serve_spans, stats, traffic as tg, xplane
from perfbench.manifest import Manifest, layer_values
from perfbench.program import Factory, section, shape_of
from perfbench.reference import REF_NEW, REF_PROMPT
from perfbench.runners.common import (check_device, device_line,
                                      every_listed_metric, say, say_compared,
                                      say_device_window, start_cluster,
                                      stop_cluster, trace_sample_path)

now = time.perf_counter
TRACE_SECONDS = 6.0


class ServeSession:
    """One deployed replica and the admin ops to it."""

    def __init__(self, man: Manifest, cell: dict, seed: int, rehearse: bool):
        self.man, self.cell, self.seed, self.rehearse = man, cell, seed, rehearse
        self.config = man.config(cell["config"])
        self.shape = shape_of(self.config, rehearse)
        self.engine = section(self.config, "engine", rehearse)
        self.vocab = self.shape["vocab_size"]
        self.handle = None

    def admin(self, op: str, timeout: float = 900, **kw):
        return self.handle.remote({"_admin": op, **kw}).result(timeout=timeout)

    async def admin_async(self, op: str, **kw):
        return await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.admin(op, **kw))

    def deploy(self):
        from ray_tpu import serve

        from perfbench.replica import BenchLLMServer

        start_cluster(self.cell["chips"], self.rehearse)
        t0 = now()
        trace_dir = os.path.join(self.man.root, "chiprun_out",
                                 "perfbench_trace")
        app = serve.deployment(
            BenchLLMServer, ray_actor_options={"num_tpus": 1}).bind(
            Factory(self.config, self.seed, self.rehearse),
            trace_dir=trace_dir, **self.engine)
        self.handle = serve.run(app, name="perfbench", route_prefix=None)
        self.info = self.admin("bench_info")
        self.deploy_s = now() - t0
        check_device(self.info["device"], self.cell["chips"], self.rehearse)
        d = self.info["device"]
        say(f"replica pid {d['pid']} on {d['platform']} ({d['device_kind']} "
            f"x{d['device_count']}); weights {self.info['weights_s']:.1f}s, "
            f"replica up in {self.deploy_s:.1f}s")

    def stream_all(self, bodies):
        async def one(body):
            return [t async for t in self.handle.stream(
                {**body, "stream": True})]

        async def run():
            return await asyncio.gather(*(one(b) for b in bodies))

        return asyncio.run(run())

    def warm_up(self, mix: dict):
        """Every shape this mix's traffic uses, and no others: per prefill
        bucket the mix can reach, its LONGEST prompt there (the admission's
        page slices compile per page offset, so the longest covers the
        rest), each decoded a few steps."""
        t0 = now()
        lo, hi = tg.length_range(mix["prompt_len"])
        lengths, floor = [], 0
        for b in self.info["prefill_buckets"]:
            top = min(b, hi)
            if top > floor and top >= lo:
                lengths.append(top)
            floor = b
        for i, n in enumerate(lengths):   # one at a time: each compiles
            self.stream_all([{"prompt": tg.prompt_tokens(self.seed, 10**6 + i,
                                                         n, self.vocab),
                              "max_new_tokens": 4}])
        self.programs_s = now() - t0 + self.info["init_s"] \
            - self.info["weights_s"]
        say(f"warm-up of prompts {lengths} and the decode step: "
            f"{now() - t0:.1f}s")

    def reference_check(self) -> bool:
        """One seeded request through the engine, then the configuration's
        own check of it (``perfbench/reference/__init__.py`` has the
        contract). The runner's own conditions: every token asked for was
        emitted, everything is finite, no reading is over its limit."""
        t0 = now()
        prompt = tg.prompt_tokens(self.seed, 10**6 + 99, REF_PROMPT, self.vocab)
        out = self.stream_all([{"prompt": prompt, "max_new_tokens": REF_NEW}])[0]
        r = self.admin("bench_reference", prompt=prompt, tokens=out)
        within = all(math.isfinite(x["value"]) and x["value"] <= x["limit"]
                     for x in r["readings"])
        ok = bool(r["ok"] and r["finite"] and within and len(out) == REF_NEW)
        self.compared = [
            f"{x['name']} {x['value']:.5f} (limit {x['limit']:.5f})"
            for x in r["readings"]] + [
            f"tokens emitted {len(out)} (must be {REF_NEW})",
            f"finite {r['finite']}"]
        notes = "; ".join(f"{k} {v:.4f}" if isinstance(v, float)
                          else f"{k} {v}" for k, v in
                          (r.get("notes") or {}).items())
        say(f"reference check ({now() - t0:.1f}s): "
            + "; ".join(self.compared) + (f"; {notes}" if notes else "")
            + f" -> {'ok' if ok else 'FAILED'}")
        self.reference = r
        return ok

    def close(self):
        stop_cluster(with_serve=True)


class Sent:
    """One request as the client saw it."""

    __slots__ = ("req", "due", "sent", "times", "tokens", "error", "done")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = None
        self.times, self.tokens = [], []
        self.error, self.done = None, False

    def ok(self, vocab: int) -> bool:
        return (self.done and self.error is None
                and len(self.tokens) == self.req.output_len
                and all(isinstance(t, int) and 0 <= t < vocab
                        for t in self.tokens))


async def _stream(sess: ServeSession, s: Sent, prompt):
    s.sent = now()
    try:
        async for tok in sess.handle.stream({
                "prompt": prompt, "max_new_tokens": s.req.output_len,
                "stream": True, "bench_id": s.req.index}):
            s.times.append(now())
            s.tokens.append(tok)
        s.done = True
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        s.error = repr(e)


async def _trace_in_window(sess, t_open, seconds, trace, ctl, dues=None):
    """Trace a stretch of the window; ``ctl`` takes the two spans of the
    client's clock in which the profiler was started and stopped (the
    replica stands still in them). A closed loop (no ``dues``) admits all
    through its window and is traced in the middle. An open loop's stretch
    is planned from its schedule (``traffic.trace_start``) so that it holds
    admissions: the chat cell's trace readers need one, and a line without
    them is refused (``every_listed_metric``)."""
    if not trace:
        return
    length = min(TRACE_SECONDS, seconds / 3.0)
    start = ((seconds - length) / 2 if dues is None
             else tg.trace_start(dues, seconds, length))
    await asyncio.sleep(max(0.0, t_open + start - now()))
    t0 = now()
    await sess.admin_async("bench_trace_start")
    t_begun = now()
    ctl.append((t0, t_begun))
    say(f"trace from {t_begun - t_open:.2f}s into the window (its middle: "
        f"{(seconds - length) / 2:.2f}s) for {length:.1f}s")
    await asyncio.sleep(length)
    t0 = now()
    await sess.admin_async("bench_trace_stop")
    ctl.append((t0, now()))


async def open_loop(sess: ServeSession, mix: dict, seconds: float, seed: int,
                    trace: bool):
    reqs = tg.open_loop_schedule(mix, seconds, seed)
    prompts = [tg.prompt_tokens(seed, r.index, r.prompt_len, sess.vocab)
               for r in reqs]
    t_open = now() + float(mix.get("lead_in_s", 0.0)) + 0.5
    t_close = t_open + seconds
    sent = [Sent(r, t_open + r.due_s) for r in reqs]

    async def one(s, prompt):
        await asyncio.sleep(max(0.0, s.due - now()))
        await _stream(sess, s, prompt)

    tasks = [asyncio.ensure_future(one(s, p)) for s, p in zip(sent, prompts)]
    ctl = []
    tracer = asyncio.ensure_future(_trace_in_window(
        sess, t_open, seconds, trace, ctl,
        dues=[r.due_s for r in reqs if r.measured]))
    await asyncio.sleep(max(0.0, t_open - now()))
    backlog0 = sum(1 for s in sent if s.due < t_open and not s.times)
    c0 = (await sess.admin_async("bench_info"))["compiles"]
    await asyncio.sleep(max(0.0, t_close - now()))
    c1 = (await sess.admin_async("bench_info"))["compiles"]
    backlog = sum(1 for s in sent if s.due < t_close and not s.times)
    measured = [t for t, s in zip(tasks, sent) if s.req.measured]
    _, pending = await asyncio.wait(
        measured, timeout=float(mix.get("drain_limit_s", 60.0)))
    drained = now() - t_close
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, tracer, return_exceptions=True)
    return {"sent": sent, "t_open": t_open, "t_close": t_close,
            "trace_ctl": ctl, "compiles_in_window": c1 - c0,
            "backlog_at_open": backlog0,
            "backlog_at_close": backlog, "drain_s": drained,
            "unfinished": len(pending)}


async def closed_loop(sess: ServeSession, mix: dict, seconds: float,
                      seed: int, trace: bool):
    cycle = tg.closed_loop_requests(mix)
    slots = min(sess.info["max_slots"], mix["clients"])
    sent, state = [], {"next": 0, "open": None, "close": None}

    def decoding():
        return sum(1 for s in sent if s.times and not s.done and not s.error)

    async def client():
        while state["close"] is None or now() < state["close"]:
            i = state["next"]
            state["next"] += 1
            base = cycle[i % len(cycle)]
            req = tg.Request(i, 0.0, base.prompt_len, base.output_len, True)
            s = Sent(req, now())
            sent.append(s)
            await _stream(sess, s, tg.prompt_tokens(
                seed, i, req.prompt_len, sess.vocab))

    tasks = [asyncio.ensure_future(client()) for _ in range(mix["clients"])]
    t0 = now()
    while decoding() < slots:      # the window opens on a full batch
        if now() - t0 > 600 or all(t.done() for t in tasks):
            break
        await asyncio.sleep(0.02)
    await asyncio.sleep(float(mix.get("lead_in_s", 0.0)))
    t_open = now()
    t_close = state["close"] = t_open + seconds
    ctl = []
    tracer = asyncio.ensure_future(
        _trace_in_window(sess, t_open, seconds, trace, ctl))
    c0 = (await sess.admin_async("bench_info"))["compiles"]
    await asyncio.sleep(max(0.0, t_close - now()))
    c1 = (await sess.admin_async("bench_info"))["compiles"]
    for t in tasks:        # what is in flight at the close is censored
        t.cancel()
    await asyncio.gather(*tasks, tracer, return_exceptions=True)
    return {"sent": sent, "t_open": t_open, "t_close": t_close,
            "trace_ctl": ctl, "compiles_in_window": c1 - c0,
            "backlog_at_close": 0,
            "drain_s": 0.0, "unfinished": 0, "fill_s": t_open - t0}


def summarise(sess: ServeSession, mix: dict, run: dict) -> dict:
    """Client-side numbers of one window."""
    sent, t_open, t_close = run["sent"], run["t_open"], run["t_close"]
    if mix["loop"] == "open":
        judged = [s for s in sent if s.req.measured]
    else:   # completed or failed inside the window; in flight is censored
        judged = [s for s in sent if s.error or (
            s.done and t_open <= s.times[-1] < t_close)]
    failed = [s for s in judged if not s.ok(sess.vocab)]
    partial_bad = [s for s in sent if any(
        not (isinstance(t, int) and 0 <= t < sess.vocab) for t in s.tokens)]
    ttft = [(s.times[0] - s.due) * 1e3 for s in judged
            if s.times and mix["loop"] == "open"]
    served = [s for s in judged if s.times]
    streams = [s.times for s in sent]
    gaps = [g * 1e3 for g in stats.pooled_gaps(streams, t_open, t_close)]
    out_tokens = stats.tokens_in_window(streams, t_open, t_close)
    late = tg.lateness([s.due for s in sent if s.sent is not None],
                       [s.sent for s in sent if s.sent is not None]) \
        if mix["loop"] == "open" else [0.0]
    return {"attempted": len(judged), "failed": len(failed),
            "bad_tokens": len(partial_bad), "ttft_ms": ttft, "gaps_ms": gaps,
            "latency_ms": [(s.times[-1] - s.due) * 1e3 for s in served],
            "tokens_got": sum(len(s.times) for s in served),
            "out_tokens": out_tokens, "seconds": t_close - t_open,
            "late_max_ms": max(late, default=0.0) * 1e3,
            "errors": [s.error for s in sent if s.error][:3]}


def end_to_end(summary: dict) -> dict:
    """Every end-to-end number this traffic yields, by metric name; the
    manifest says which of them the cell reports."""
    out = {}
    if summary["ttft_ms"]:
        out["ttft_p95_ms"] = stats.percentile(summary["ttft_ms"], 95)
    if summary["gaps_ms"]:
        out["itl_p99_ms"] = stats.percentile(summary["gaps_ms"], 99)
    if summary["tokens_got"]:
        # all the time the judged requests' clients waited, due instant to
        # last token (queueing, admission, first token and every gap), over
        # all the tokens they got: Orca's and vLLM's normalised latency,
        # weighted by tokens so that it is one rate over the whole window
        out["latency_ms_per_out_token"] = (sum(summary["latency_ms"])
                                           / summary["tokens_got"])
    out["out_tokens_per_s"] = summary["out_tokens"] / summary["seconds"]
    return out


def run(man: Manifest, cell: dict, args, t_start: float) -> dict:
    rehearse, trace = args.rehearse, bool(args.trace)
    mix = man.traffic(cell["traffic"])
    sess = ServeSession(man, cell, args.seed, rehearse)
    try:
        sess.deploy()
        sess.warm_up(mix)
        ref_ok = sess.reference_check()
        if trace:
            sess.admin("bench_spans", on=True)
        loop_fn = open_loop if mix["loop"] == "open" else closed_loop
        run_ = asyncio.run(loop_fn(sess, mix, args.seconds, args.seed, trace))
        setup_s = (time.time() - t_start) - (now() - run_["t_open"])
        info = sess.admin("bench_info")
        collected = sess.admin(
            "bench_collect", sample_to=trace_sample_path()) if trace else {}
    finally:
        sess.close()
    if "jax" in sys.modules and not rehearse:
        raise SystemExit("perfbench: the driver imported jax")
    s = summarise(sess, mix, run_)
    say(f"requests judged {s['attempted']}, failed {s['failed']}, unfinished "
        f"at the drain limit {run_['unfinished']}, backlog at close "
        f"{run_['backlog_at_close']}, drained in {run_['drain_s']:.1f}s; "
        f"generator at most {s['late_max_ms']:.1f} ms late; "
        f"compilations inside the window {run_['compiles_in_window']}")
    if s["ttft_ms"]:
        say(f"ttft ms: n {len(s['ttft_ms'])}, median "
            f"{stats.median(s['ttft_ms']):.1f}, p95 "
            f"{stats.percentile(s['ttft_ms'], 95):.1f}; due instant to "
            f"last token, summed: {sum(s['latency_ms']) / 1e3:.3f}s for "
            f"{s['tokens_got']} tokens")
    if s["gaps_ms"]:
        say(f"gap ms: n {len(s['gaps_ms'])}, median "
            f"{stats.median(s['gaps_ms']):.2f}, mean "
            f"{sum(s['gaps_ms']) / len(s['gaps_ms']):.2f}, p99 "
            f"{stats.percentile(s['gaps_ms'], 99):.1f}; output tokens in "
            f"window {s['out_tokens']}")
    if run_["trace_ctl"]:
        say("profiler start and stop calls took "
            + " and ".join(f"{b - a:.2f}s" for a, b in run_["trace_ctl"]))
    if s["errors"]:
        say(f"errors: {s['errors']}")
    correct = (ref_ok and s["failed"] == 0 and s["bad_tokens"] == 0
               and run_["unfinished"] == 0 and run_["compiles_in_window"] == 0)
    say_compared(sess.compared + [
        f"{name} {value} (must be 0)" for name, value in (
            ("failed requests", s["failed"]),
            ("requests with a bad token", s["bad_tokens"]),
            ("unfinished at the drain limit", run_["unfinished"]),
            ("compilations inside the window", run_["compiles_in_window"]))])
    e2e = {**end_to_end(s), "setup_s": setup_s}
    red = collected.get("trace")
    ctx = {"cell": cell, "config": sess.config, "shape": sess.shape,
           "mix": mix, "engine": sess.engine, "run": run_, "summary": s,
           "spans": collected, "trace": red, "device": info["device"],
           "setup": {"weights_s": sess.info["weights_s"],
                     "programs_s": sess.programs_s},
           "peaks": None if rehearse else man.peaks(
               info["device"]["device_kind"]),
           "rehearse": rehearse}
    # the traced window on the TRACE's clock: the device line and every
    # trace reader are cut to it (None where nothing was traced)
    host = ctx["host"] = serve_spans.align(collected, red)
    window = xplane.device_window(red, host["window_ns"]) if host else None
    if window:
        say_device_window(window, "the traced window", "aligned by " + (
            "the step annotations" if host["from_annotations"]
            else "the wall clock"))
    line = {"correct": bool(correct), "attempted": s["attempted"],
            "failed": s["failed"],
            "device": device_line(info["device"], window)}
    if trace:
        line["metrics"] = layer_values(man, cell["name"], ctx)
        if not rehearse:
            every_listed_metric(man, cell["name"], line["metrics"],
                                ctx["program_lacks"])
        if window:
            line["breakdown"] = serve_spans.breakdown(ctx)
    else:
        line["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in man.metrics_for(cell["name"], "end_to_end")}
    return line
