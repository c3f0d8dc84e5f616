"""Training cells: ``JaxTrainer.fit`` -> one worker that holds the chips ->
the sharded AdamW step. The driver stays off jax; the loop in
``perfbench.trainloop`` measures, traces and checks where the chips are."""

from __future__ import annotations

import math
import os
import sys

from perfbench.manifest import Manifest, layer_values
from perfbench.program import shape_of
from perfbench.runners.common import (NoChip, check_device, device_line, say,
                                      start_cluster, stop_cluster,
                                      trace_sample_path)

TRACE_STEPS = 3


def run(man: Manifest, cell: dict, args, t_start: float) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from perfbench.trainloop import train_loop

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
    say(f"worker pid {device['pid']} on {device['platform']} "
        f"({device['device_kind']} x{device['device_count']}); "
        f"{m['params'] / 1e9:.3f} B parameters; weights {m['weights_s']:.1f}s,"
        f" programs {m['programs_s']:.1f}s, reference check "
        f"{m['reference_s']:.1f}s")
    say(f"reference check at depth 2: sharded step's first loss "
        f"{ref['step_loss']:.5f}, float32 reference {ref['reference_loss']:.5f}"
        f" -> {'ok' if ref['ok'] else 'FAILED'}")
    say(f"{m['steps']} whole steps of {m['tokens_per_step']} tokens in "
        f"{m['window_s']:.3f}s; losses {losses[0]:.4f} -> {losses[-1]:.4f}; "
        f"compilations inside the window {m['compiles_in_window']}; flash "
        f"kernel in the step: {m['has_tpu_custom_call']}; bytes in use "
        f"{m['bytes_in_use']}")
    correct = (ref["ok"] and all(math.isfinite(x) for x in m["losses"])
               and losses[-1] < losses[0] and m["compiles_in_window"] == 0
               and (rehearse or m["has_tpu_custom_call"]))
    e2e = {"train_tokens_per_s":
           m["steps"] * m["tokens_per_step"] / m["window_s"],
           "setup_s": m["t_open_wall"] - t_start}
    traced = m.get("trace")
    line = {"correct": bool(correct), "attempted": m["steps"], "failed": 0,
            "device": device_line(device, traced, m.get("trace_window_s"))}
    if args.trace:
        ctx = {"cell": cell, "config": config, "mix": mix,
               "shape": shape_of(config, rehearse), "train": m,
               "trace": traced, "device": device, "rehearse": rehearse,
               "setup": {"weights_s": m["weights_s"],
                         "programs_s": m["programs_s"]},
               "peaks": None if rehearse else man.peaks(device["device_kind"])}
        line["metrics"] = layer_values(man, cell["name"], ctx)
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
