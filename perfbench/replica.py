"""What runs in the replica: ``LLMServer`` with the benchmark's admin ops.

The subclass changes no behaviour. It times ``engine.step()`` and
``engine._admit()`` by wrapping the engine's bound methods (only while a
traced run asks for spans), counts compilations, starts and stops
``jax.profiler`` on an admin op, and calls the configuration's reference
check where the weights and the chip are.

What it reads of the engine object, which a later family inside
``PagedEngine`` keeps until the wrapper spans are retired: ``step``,
``_admit``, ``has_work``, ``pending`` rows as ``(rid, prompt, ...)``,
``slots[i].length``, ``_prefill_buckets``, ``max_len``, ``S``.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import shutil
import time

from ray_tpu.serve.llm import LLMServer

from perfbench import program

_ns = time.perf_counter_ns


class BenchLLMServer(LLMServer):

    def __init__(self, model_factory, *, trace_dir: str, **engine):
        t0 = time.perf_counter()
        self._compiles = program.CompileCounter()
        super().__init__(model_factory, **engine)
        self._bench = {
            "config": model_factory.config, "seed": model_factory.seed,
            "rehearse": model_factory.rehearse, "trace_dir": trace_dir,
            "spans": False, "tracing": False,
            "steps": [], "admits": [], "ids": {},
            "token_step_end_ns": {}, "admit_start_ns": {},
            "init_s": time.perf_counter() - t0,
        }
        self._wrap_engine()

    # ------------------------------------------------------------ hooks
    def _wrap_engine(self):
        eng, b = self.engine, self._bench
        step, admit = eng.step, eng._admit

        def annotate(name):
            if not b["tracing"]:
                return contextlib.nullcontext()
            import jax

            return jax.profiler.TraceAnnotation("perfbench/" + name)

        def timed_admit():
            if not b["spans"]:
                return admit()
            before = [(p[0], len(p[1])) for p in eng.pending]
            k = len(b["admits"])
            t0 = _ns()
            with annotate(f"engine.admit#{k}"):
                admit()
            t1 = _ns()
            left = {p[0] for p in eng.pending}
            took = [(rid, n) for rid, n in before if rid not in left]
            for rid, _ in took:
                b["admit_start_ns"][b["ids"].get(rid, rid)] = t0
            b["admits"].append((t0, t1, len(took), sum(n for _, n in took)))

        def timed_step():
            if not b["spans"]:
                return step()
            k = len(b["steps"])
            n_admits = len(b["admits"])
            t0 = _ns()
            with annotate(f"engine.step#{k}"):
                events = step()
            t1 = _ns()
            admitted = sum(a[2] for a in b["admits"][n_admits:])
            tokens = 0
            for rid, tok in events:
                if tok is not None:
                    tokens += 1
                    b["token_step_end_ns"].setdefault(
                        b["ids"].get(rid, rid), []).append(t1)
            live = sum(s.length for s in eng.slots if s is not None)
            b["steps"].append((t0, t1, admitted, tokens - admitted,
                               eng.has_work(), live))
            return events

        eng._admit, eng.step = timed_admit, timed_step

    def _submit(self, body: dict) -> str:
        rid = super()._submit(body)
        if self._bench["spans"] and "bench_id" in body:
            self._bench["ids"][rid] = body["bench_id"]
        return rid

    # -------------------------------------------------------- admin ops
    async def __call__(self, request):
        body = self._body(request)
        op = body.get("_admin")
        if isinstance(op, str) and op.startswith("bench_"):
            # off the event loop: some of these compile or write a trace
            return await asyncio.get_running_loop().run_in_executor(
                None, getattr(self, "_" + op), body)
        return await super().__call__(request)

    def _bench_info(self, body):
        from ray_tpu._private.jax_platform import device_report

        eng = self.engine
        return {"device": device_report(), "compiles": self._compiles.count,
                "weights_s": program.SETUP.get("weights_s"),
                "init_s": self._bench["init_s"],
                "shape": program.SETUP.get("shape"),
                "prefill_buckets": list(eng._prefill_buckets) + [eng.max_len],
                "max_len": eng.max_len, "max_slots": eng.S,
                "active_slots": sum(s is not None for s in eng.slots),
                "pending": len(eng.pending)}

    def _bench_spans(self, body):
        self._bench["spans"] = bool(body["on"])
        return True

    def _bench_trace_start(self, body):
        import jax

        d = self._bench["trace_dir"]
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        self._bench["tracing"] = True
        self._bench["trace_window_ns"] = [_ns(), None]
        self._bench["trace_window_epoch_ns"] = time.time_ns()
        return True

    def _bench_trace_stop(self, body):
        import jax

        self._bench["trace_window_ns"][1] = _ns()
        self._bench["tracing"] = False
        jax.profiler.stop_trace()
        return True

    def _bench_collect(self, body):
        """Spans and the reduced trace, after the window."""
        from perfbench import xplane

        b = self._bench
        out = {k: b[k] for k in ("steps", "admits", "token_step_end_ns",
                                 "admit_start_ns")}
        out["trace_window_ns"] = b.get("trace_window_ns")
        out["trace_window_epoch_ns"] = b.get("trace_window_epoch_ns")
        if b.get("trace_window_ns"):
            trace = xplane.load(xplane.find_xplane(b["trace_dir"]))
            if body.get("sample_to"):
                xplane.write_sample(trace, body["sample_to"])
            red = xplane.reduce(trace)
            if red["n_devices"] < 2:   # the same list as ``busy_intervals``
                red.pop("all_busy_intervals", None)
            out["trace"] = red
            shutil.rmtree(b["trace_dir"], ignore_errors=True)
        return out

    def _bench_reference(self, body):
        """The configuration's own reference check (``program.reference_check``
        in its file; the contract is ``perfbench/reference/__init__.py``'s),
        run where the weights and the chip are, with the engine held still."""
        from perfbench.manifest import resolve

        config = self._bench["config"]
        check = resolve(config["program"]["reference_check"])
        with self._engine_lock:
            return check(self.engine, body["prompt"], body["tokens"], config,
                         program.SETUP["shape"])
