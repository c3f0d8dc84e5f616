"""Training cells: ``JaxTrainer.fit`` -> one worker that holds the chips ->
the sharded AdamW step. The driver stays off jax; the loop in
``perfbench.trainloop`` measures, traces and checks where the chips are."""

from __future__ import annotations

import math
import os
import sys

from perfbench.manifest import Manifest, layer_values
from perfbench.program import shape_of
from perfbench.runners.common import (NoChip, check_device, device_line,
                                      every_listed_metric, say, say_compared,
                                      say_device_window, start_cluster,
                                      stop_cluster, trace_sample_path)

TRACE_STEPS = 3


#: the most the window's lowest loss, and the mean of its last WINDOW_END_K
#: losses, may be, as shares of the loss at the seeded weights. Readings (my
#: chip runs, PR 27, six seeds; `perfbench/train_readings.py` reads them, four
#: seeds a call, and reproduces the benchmark's own run of 3197000303 to the
#: digit): the seeded weights read 10.87-10.90; on one repeated batch at a
#: constant 3e-4 the loss JUMPS (single steps back up to 0.92-0.93 of the
#: seeded loss in three of four seeds, 10.09 after 8.33) and is not monotone
#: (3197000303: 7.5084 -> 7.5299 over its window's 25 steps). Lowest loss of
#: the window: 0.617, under 0.286, 0.548, 0.379, 0.500, 0.381; mean of its
#: last five: 0.626, 0.607, 0.609, 0.515 (four seeds; 3197000303's last is
#: 0.691). A state that goes nowhere useful stays at 1.0, a model fallen back
#: to a uniform guess reads ln(32768) = 0.955. So 0.8 for the lowest (1.3
#: times the sound runs' largest) and 0.9 for the end's mean (1.3 times the
#: largest; four of the five would have to be spikes to fail it). A dozen
#: seeds would be better and cost a chip-minute each: PERF.md section 7.
WINDOW_LOW, WINDOW_END, WINDOW_END_K = 0.8, 0.9, 5


def window_moved(losses, window) -> list:
    """What the window's own steps have to show, as ``(text, ok)`` pairs;
    ``losses`` are all the steps from the seeded weights on, ``window`` the
    last of them, the timed ones. Every step runs the same batch, so a step
    that hands its state back unchanged repeats the loss before it exactly:
    no window step may. The window's lowest loss lies under ``WINDOW_LOW``
    of the loss at the seeded weights, and the mean of its last
    ``WINDOW_END_K`` under ``WINDOW_END`` of it (a window that climbs back
    to where it began, and stays, fails; one spike does not). Not "the
    window's last under the window's first": a sound run read 7.5084 ->
    7.5299."""
    seeded = losses[0]
    tail = losses[len(losses) - len(window) - 1:]   # the step before it, too
    repeats = sum(1 for a, b in zip(tail, tail[1:]) if a == b)
    finite = all(math.isfinite(x) for x in losses)
    lowest = min(window, default=math.inf)
    end = window[-WINDOW_END_K:]
    end_mean = sum(end) / len(end) if end else math.inf
    return [
        (f"losses finite {finite} (must be True)", finite),
        (f"window steps that repeat the loss before them {repeats} of "
         f"{len(window)} (must be 0: a state handed back unchanged)",
         repeats == 0 and len(window) > 0),
        (f"window's lowest loss {lowest:.4f} (limit {WINDOW_LOW * seeded:.4f}"
         f" = {WINDOW_LOW} x the seeded weights' {seeded:.4f})",
         lowest <= WINDOW_LOW * seeded),
        (f"mean of the window's last {len(end)} losses {end_mean:.4f} (limit "
         f"{WINDOW_END * seeded:.4f} = {WINDOW_END} x the seeded weights')",
         end_mean <= WINDOW_END * seeded)]


def run(man: Manifest, cell: dict, args, t_start: float) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from perfbench.trainloop import LOSS_TOL, train_loop

    rehearse, chips = args.rehearse, cell["chips"]
    config = man.config(cell["config"])
    mix = man.traffic(cell["traffic"])
    if rehearse:
        mix = {**mix, **mix.get("rehearsal", {})}
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    start_cluster(chips, rehearse)
    try:
        trainer = JaxTrainer(
            train_loop,
            train_loop_config={
                "config": config, "mix": mix, "seed": args.seed,
                "seconds": args.seconds, "trace": bool(args.trace),
                "trace_steps": TRACE_STEPS, "rehearse": rehearse,
                "chips": chips, "root": man.root,
                "sample_to": trace_sample_path()},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=chips),
            run_config=RunConfig(name=cell["name"], storage_path=os.path.join(
                man.root, "chiprun_out", "perfbench_train")))
        result = trainer.fit()
    finally:
        stop_cluster()
    if result.error is not None:
        raise SystemExit(f"perfbench: {cell['name']}: {result.error}")
    if "jax" in sys.modules and not rehearse:
        raise SystemExit("perfbench: the driver imported jax")
    m = result.metrics
    if "no_chip" in m:
        raise NoChip(m["no_chip"])
    device = m["device"]
    check_device(device, chips, rehearse)
    device["peak_bytes_in_use"] = m["peak_bytes_in_use"]
    losses = m["window_losses"]
    ref = m["reference"]
    seeded_loss = m["losses"][0]
    say(f"worker pid {device['pid']} on {device['platform']} "
        f"({device['device_kind']} x{device['device_count']}); "
        f"{m['params'] / 1e9:.3f} B parameters; weights {m['weights_s']:.1f}s,"
        f" programs {m['programs_s']:.1f}s, reference check "
        f"{m['reference_s']:.1f}s")
    say(f"reference check at depth 2: sharded step's first loss "
        f"{ref['step_loss']:.5f}, float32 reference {ref['reference_loss']:.5f}"
        f" -> {'ok' if ref['ok'] else 'FAILED'}")
    say(f"{m['steps']} whole steps of {m['tokens_per_step']} tokens in "
        f"{m['window_s']:.3f}s; losses {seeded_loss:.4f} at the seeded weights, "
        f"{losses[0]:.4f} -> {losses[-1]:.4f} in the window (lowest "
        f"{min(losses):.4f}); "
        f"compilations inside the window {m['compiles_in_window']}; flash "
        f"kernel in the step: {m['has_tpu_custom_call']}; bytes in use "
        f"{m['bytes_in_use']}")
    moved = window_moved(m["losses"], losses)
    correct = (ref["ok"] and all(ok for _, ok in moved)
               and m["compiles_in_window"] == 0
               and (rehearse or m["has_tpu_custom_call"]))
    say_compared([
        f"|first loss - reference| at depth 2 "
        f"{abs(ref['step_loss'] - ref['reference_loss']):.5f} "
        f"(limit {LOSS_TOL})",
        *(text for text, _ in moved),
        f"compilations inside the window {m['compiles_in_window']} "
        f"(must be 0)",
        f"flash kernel in the step {m['has_tpu_custom_call']} (must be True "
        f"on the chip)"])
    e2e = {"train_tokens_per_s":
           m["steps"] * m["tokens_per_step"] / m["window_s"],
           "setup_s": m["t_open_wall"] - t_start}
    traced = m.get("trace")
    if traced and traced.get("device_window"):
        say_device_window(
            traced["device_window"], "the traced steps' span",
            f"the host read {m['trace_window_s']:.6f}s from start_trace's "
            f"return to stop_trace's call")
    line = {"correct": bool(correct), "attempted": m["steps"], "failed": 0,
            "device": device_line(device,
                                  (traced or {}).get("device_window"))}
    if args.trace:
        ctx = {"cell": cell, "config": config, "mix": mix,
               "shape": shape_of(config, rehearse), "train": m,
               "trace": traced, "device": device, "rehearse": rehearse,
               "setup": {"weights_s": m["weights_s"],
                         "programs_s": m["programs_s"]},
               "peaks": None if rehearse else man.peaks(device["device_kind"])}
        line["metrics"] = layer_values(man, cell["name"], ctx)
        if not rehearse:
            every_listed_metric(man, cell["name"], line["metrics"],
                                ctx["program_lacks"])
        if traced and traced.get("n_devices"):
            line["breakdown"] = {
                "device_ops": traced["top_ops"],
                "idle_gaps": sorted(([k, v] for k, v in
                                     traced["idle_gaps"].items()),
                                    key=lambda kv: -kv[1])[:10]}
    else:
        line["metrics"] = {
            mm["name"]: {"value": float(e2e[mm["name"]]), "unit": mm["unit"]}
            for mm in man.metrics_for(cell["name"], "end_to_end")}
    return line
