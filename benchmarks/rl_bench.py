"""RL throughput benchmark: PPO and the Podracer IMPALA tier.

Modes (``--mode``):

* ``ppo`` (default) — the original PPO env-steps/s row.
* ``impala-classic`` — the driver-centric IMPALA path (rl/impala.py):
  driver materializes every aggregated batch and re-ships it to the
  learner. Uses only APIs that exist at the pre-PR HEAD, so the SAME
  file runs unmodified in a pre-PR worktree — that run is the honest
  "before" side of the r10 A/B.
* ``impala`` — the Podracer (Sebulba) three-tier path
  (rl/podracer.py): same-shape CartPole A/B leg plus a multi-node
  pixel-env leg that exercises the broadcast plane (per-source egress
  accounting) and the direct arg lane, reporting env-steps/s,
  updates/s, queue occupancy, and the measured broadcast-staleness
  histogram. Writes ``records/RL_BENCH_r10.json``; set
  ``RL_BENCH_PRE=<json>`` to merge a pre-PR classic run into the
  record.

Run: ``python benchmarks/rl_bench.py [--mode ...]`` — prints JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU policy/value nets: a tiny MLP is dispatch-bound on a TPU chip.
os.environ.setdefault("RAY_TPU_JAX_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = "cpu"
# The mesh learner runs in a WORKER process: the virtual device count
# must be in the env before the cluster spawns so workers inherit it.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import ray_tpu  # noqa: E402


def run_ppo() -> dict:
    iters = int(os.environ.get("RL_BENCH_ITERS", "8"))
    runners = int(os.environ.get("RL_BENCH_RUNNERS", "2"))

    from ray_tpu.rl import PPOConfig

    ray_tpu.init(num_cpus=4, probe_tpu=False, ignore_reinit_error=True)
    algo = (PPOConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=runners,
                         num_envs_per_env_runner=8,
                         rollout_fragment_length=128)
            .learners(mesh_devices=int(os.environ.get(
                "RL_BENCH_MESH", "1")) or None)
            .training(train_batch_size=2048, minibatch_size=256,
                      num_epochs=2)
            ).build()
    algo.train()  # warmup: compile + env spin-up
    t0 = time.perf_counter()
    steps = 0
    reward = 0.0
    for _ in range(iters):
        out = algo.train()
        steps += out["num_env_steps_sampled"]
        reward = out.get("episode_return_mean") or reward
    dt = time.perf_counter() - t0
    result = {
        "metric": "ppo_env_steps_per_sec",
        "value": round(steps / dt, 1),
        "unit": "env_steps/s",
        "extra": {"iters": iters, "runners": runners,
                  "episode_return_mean": round(float(reward or 0.0), 1),
                  "seconds": round(dt, 2)},
    }
    algo.stop()
    ray_tpu.shutdown()
    return result


# Shared A/B shape: big enough MLP that the weight broadcast is a real
# shm object (> inline_threshold), same sampling geometry both sides.
_AB = dict(runners=int(os.environ.get("RL_BENCH_RUNNERS", "4")),
           envs=int(os.environ.get("RL_BENCH_ENVS", "8")),
           rollout=int(os.environ.get("RL_BENCH_ROLLOUT", "64")),
           mesh=int(os.environ.get("RL_BENCH_MESH", "4")),
           fanin=int(os.environ.get("RL_BENCH_FANIN", "2")),
           updates=int(os.environ.get("RL_BENCH_UPDATES", "300")),
           hidden=(256, 256))


def run_impala_classic() -> dict:
    """Driver-centric IMPALA (the pre-PR architecture): aggregation
    actors return batches TO the driver, which re-ships them to the
    mesh learner; weights re-broadcast via the learner-ref chain. Only
    pre-PR APIs — this function must run unmodified at the old HEAD."""
    from ray_tpu.rl import IMPALAConfig

    ab = _AB
    ray_tpu.init(num_cpus=6, probe_tpu=False, ignore_reinit_error=True)
    algo = (IMPALAConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=ab["runners"],
                         num_envs_per_env_runner=ab["envs"],
                         rollout_fragment_length=ab["rollout"])
            .learners(mesh_devices=ab["mesh"])
            .training(num_aggregation_workers=1, broadcast_interval=1,
                      model={"hidden": list(ab["hidden"])})
            ).build()
    algo.train()  # warmup: compile + env spin-up
    t0 = time.perf_counter()
    steps = 0
    updates = 0
    while updates < ab["updates"]:
        out = algo.train()
        steps += out["num_env_steps_sampled"]
        if out["num_env_steps_sampled"]:
            updates += 1
        if time.perf_counter() - t0 > 300:
            break
    dt = time.perf_counter() - t0
    result = {
        "metric": "impala_classic_env_steps_per_sec",
        "value": round(steps / dt, 1),
        "unit": "env_steps/s",
        "updates_per_sec": round(updates / dt, 2),
        "extra": {"updates": updates, "env_steps": steps,
                  "seconds": round(dt, 2), **{k: ab[k] for k in
                  ("runners", "envs", "rollout", "mesh")}},
    }
    algo.stop()
    ray_tpu.shutdown()
    return result


def _drive_pod(pod, target_updates: int, wall_s: float = 300.0) -> dict:
    pod.step(max_wall_s=60)  # warmup: compile + env spin-up
    base_steps = pod._total_env_steps
    base_updates = pod._updates_done
    t0 = time.perf_counter()
    while (pod._updates_done - base_updates < target_updates
           and time.perf_counter() - t0 < wall_s):
        pod.step(max_wall_s=30)
    dt = time.perf_counter() - t0
    m = pod.metrics()
    return {
        "env_steps_per_sec": round(
            (pod._total_env_steps - base_steps) / dt, 1),
        "updates_per_sec": round(
            (pod._updates_done - base_updates) / dt, 2),
        "updates": pod._updates_done - base_updates,
        "env_steps": pod._total_env_steps - base_steps,
        "seconds": round(dt, 2),
        "staleness": m["staleness"],
        "queue_occupancy": m["queue_occupancy"],
        "published_versions": m["published_versions"],
        "weight_bcast_puts": m["transport"]["weight_bcast_puts"],
        "agg_transport": {k: v for k, v in m["agg_transport"].items()
                          if k in ("inline_args", "direct_lane_args",
                                   "direct_lane_bytes", "shm_args")},
        "runner_restarts": m["runner_restarts"],
    }


def run_podracer_ab() -> dict:
    """The A/B leg: identical shape to ``run_impala_classic`` on the
    same host — only the architecture differs."""
    from ray_tpu._private.serialization import reset_transport_stats
    from ray_tpu.rl import PodracerConfig

    ab = _AB
    reset_transport_stats()  # puts-per-version must be THIS leg's count
    ray_tpu.init(num_cpus=6, probe_tpu=False, ignore_reinit_error=True)
    pod = (PodracerConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=ab["runners"],
                        num_envs_per_env_runner=ab["envs"],
                        rollout_fragment_length=ab["rollout"])
           .aggregation(num_aggregators=1, agg_fanin=ab["fanin"],
                        queue_depth=4)
           .learners(mesh_devices=ab["mesh"])
           .training(broadcast_interval=1,
                     model={"hidden": list(ab["hidden"])})
           ).build()
    try:
        out = _drive_pod(pod, ab["updates"])
    finally:
        pod.stop()
        ray_tpu.shutdown()
    out["shape"] = {k: ab[k] for k in
                    ("runners", "envs", "rollout", "mesh", "fanin")}
    return out


def run_podracer_pixel_multinode() -> dict:
    """The plane-evidence leg: pixel Catch through the ViT path on a
    multi-node cluster — runners pinned OFF the head node so weight
    pulls cross the cooperative broadcast plane (per-source egress
    accounted by the GCS) and rollout refs resolve cross-node in the
    aggregators; batch pushes are direct-arg-lane sized."""
    import numpy as np

    from object_broadcast import xfer_stats
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.rl import PodracerConfig
    from ray_tpu.rl.pixel_env import CatchEnv

    from ray_tpu._private.serialization import reset_transport_stats

    nodes = int(os.environ.get("RL_BENCH_NODES", "2"))
    runners = int(os.environ.get("RL_BENCH_PIXEL_RUNNERS", "4"))
    updates = int(os.environ.get("RL_BENCH_PIXEL_UPDATES", "150"))
    reset_transport_stats()  # puts-per-version must be THIS leg's count
    c = Cluster(connect=True)
    for i in range(nodes):
        c.add_node(num_cpus=2, resources={f"rn{i}": 8})
    pod = None
    try:
        assert c.wait_for_nodes(nodes + 1, timeout=120)
        assert c.wait_for_workers(timeout=120)
        cfg = (PodracerConfig()
               .environment("catch", env_fn=lambda: CatchEnv(8))
               .env_runners(num_env_runners=runners,
                            num_envs_per_env_runner=16,
                            rollout_fragment_length=16)
               .aggregation(num_aggregators=1, agg_fanin=2,
                            queue_depth=3)
               .learners(mesh_devices=4)
               .training(lr=1e-3, broadcast_interval=1,
                         pixel_model={"d_model": 64, "n_layers": 2,
                                      "d_ff": 128}))
        pod = cfg.build()
        # Move the runner tier off the head: replacements (and the
        # fresh set below) carry the per-node pins.
        pins = [{"resources": {f"rn{i % nodes}": 1}}
                for i in range(runners)]
        pod.env_runner_group.set_placement(pins)
        for i in range(runners):
            try:
                ray_tpu.kill(pod.env_runner_group.runners[i])
            except Exception:
                pass
            pod.env_runner_group.restart_runner(i)
        out = _drive_pod(pod, updates)
        served = xfer_stats()
        total = sum(r[2] for r in served) or 1
        head = sum(r[2] for r in served if r[1] == "")
        out["broadcast_egress"] = {
            "bytes_total": int(total), "source_share":
            round(head / total, 3),
            "served_by_source": [[r[0], r[1], int(r[2])]
                                 for r in served]}
        out["shape"] = {"nodes": nodes + 1, "runners": runners,
                        "envs": 16, "rollout": 16, "mesh": 4,
                        "pixel_model": {"d_model": 64, "n_layers": 2}}
        return out
    finally:
        if pod is not None:
            pod.stop()
        c.shutdown()


def _leg_subprocess(fn_name: str) -> dict:
    """One leg per subprocess (the chaos-suite convention): each leg
    gets a pristine process — clean transport counters, no cross-leg
    cluster state, and a wedged leg cannot take the record down."""
    import subprocess

    code = (f"import sys; sys.path.insert(0, {_BENCH_DIR!r}); "
            f"import json, rl_bench; "
            f"print('LEG=' + json.dumps(rl_bench.{fn_name}()))")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=900,
                          env=dict(os.environ))
    if proc.returncode != 0:
        raise RuntimeError(f"{fn_name} failed:\n{proc.stdout[-2000:]}\n"
                           f"{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("LEG="):
            return json.loads(line[len("LEG="):])
    raise RuntimeError(f"no LEG result from {fn_name}")


_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def run_impala() -> dict:
    record = {"host": os.uname().nodename,
              "when": time.strftime("%Y-%m-%d %H:%M:%S"),
              "notes": [
                  "pre_pr_classic = this harness's impala-classic mode "
                  "run in a pre-PR worktree (same host, same day); "
                  "post_classic = same mode at this HEAD (surgery "
                  "no-regression control).",
                  "staleness histogram keys = learner published_version"
                  " - batch weights_version, counted per aggregated "
                  "rollout at update time (learner-side measurement).",
                  "pixel-leg broadcast_egress covers EVERY accounted "
                  "cross-node object serve: weight-version pulls "
                  "(driver put -> runner nodes; ~260KB single-chunk "
                  "objects serve whole from the source) plus rollout "
                  "results resolving runner-node -> aggregator "
                  "(en-route fix r10: actor-call results now register "
                  "their true holder node, so these ride the P2P "
                  "plane instead of the GCS relay).",
              ],
              "impala": {}}
    pre = os.environ.get("RL_BENCH_PRE")
    if pre and os.path.exists(pre):
        with open(pre) as f:
            record["impala"]["pre_pr_classic"] = json.load(f)
    classic = os.environ.get("RL_BENCH_CLASSIC")
    if classic and os.path.exists(classic):
        with open(classic) as f:
            record["impala"]["post_classic"] = json.load(f)
    print("== podracer A/B leg ==", flush=True)
    record["impala"]["podracer"] = _leg_subprocess("run_podracer_ab")
    print(json.dumps(record["impala"]["podracer"]), flush=True)
    print("== podracer pixel multi-node leg ==", flush=True)
    record["impala"]["podracer_pixel_multinode"] = \
        _leg_subprocess("run_podracer_pixel_multinode")
    print(json.dumps(record["impala"]["podracer_pixel_multinode"]),
          flush=True)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "records", "RL_BENCH_r10.json")
    with open(os.path.abspath(path), "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {os.path.abspath(path)}")
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="ppo",
                    choices=["ppo", "impala", "impala-classic"])
    args = ap.parse_args()
    if args.mode == "ppo":
        print(json.dumps(run_ppo()))
    elif args.mode == "impala-classic":
        print(json.dumps(run_impala_classic()))
    else:
        run_impala()


if __name__ == "__main__":
    main()
