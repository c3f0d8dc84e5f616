"""Train: a 2-worker gang-scheduled JAX training run with checkpoints.

Reference-Ray equivalent: ``doc/source/train/getting-started`` (TorchTrainer
there; the TPU-native trainer runs a JAX loop with cross-worker collectives
and orbax-style checkpointing).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Two host workers share this machine, so the demo pins JAX to CPU (a
# TPU chip is process-exclusive). On a real slice — one worker per host —
# drop this pin and each worker initializes its own chips.
# The head and every worker inherit the pin.
os.environ.setdefault("RAY_TPU_JAX_PLATFORM", "cpu")

import tempfile

import numpy as np

import ray_tpu
import ray_tpu.train as train
from ray_tpu.train import Checkpoint, JaxTrainer, RunConfig, ScalingConfig


def train_loop(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.collectives import HostCollectiveGroup
    from ray_tpu.train.checkpoint import save_pytree

    ctx = train.get_context()
    rank, world = ctx.get_world_rank(), ctx.get_world_size()
    group = HostCollectiveGroup("example-dp", world, rank)

    # Each worker holds its own shard of the data (data parallelism).
    rng = np.random.RandomState(rank)
    x = rng.rand(256, 8).astype(np.float32)
    y = x @ np.arange(8, dtype=np.float32)
    w = jnp.zeros(8)

    @jax.jit
    def grad_fn(w, x, y):
        return jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)

    for step in range(config["steps"]):
        g = grad_fn(w, x, y)
        # The gang allreduce is host-mediated: one batched fetch per
        # step is this example's contract (RTL111 would flag a
        # PER-ELEMENT coercion loop).  # raylint: disable=RTL111
        g = jnp.asarray(group.allreduce(np.asarray(g), op="mean"))  # raylint: disable=RTL111
        w = w - config["lr"] * g
        loss = float(np.mean((x @ np.asarray(w) - y) ** 2))  # raylint: disable=RTL111 (per-step loss log)
        ckpt = None
        if rank == 0 and step % 10 == 9:
            d = tempfile.mkdtemp()
            save_pytree({"w": w, "step": step}, d)
            ckpt = Checkpoint.from_directory(d)
        train.report({"loss": loss, "step": step}, checkpoint=ckpt)


def main():
    ray_tpu.init(num_cpus=4, probe_tpu=False)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"steps": 80, "lr": 0.05},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="example",
                             storage_path=tempfile.mkdtemp()),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    print("final loss:", result.metrics["loss"])
    print("checkpoint at:", result.checkpoint and result.checkpoint.path)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
