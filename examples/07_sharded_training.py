"""Multi-chip SPMD: shard a transformer train step over a device mesh.

This is the TPU-native path the framework is built around: pick a mesh,
annotate shardings, let XLA insert the collectives. Runs here on 8
virtual CPU devices; the same code runs unchanged on a TPU slice.

Reference-Ray equivalent: none directly — the reference delegates tensor
parallelism to torch/NCCL libraries; here it is first-class
(``ray_tpu/parallel/``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
# CPU demo. The head and every worker inherit this pin: take the line out
# (and ask for a TPU grant) to run on a chip.
os.environ.setdefault("RAY_TPU_JAX_PLATFORM", "cpu")

import jax
import jax.numpy as jnp
import numpy as np


def main():
    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models import LlamaConfig, init_params, loss_fn
    from ray_tpu.parallel import (MeshSpec, apply_shardings,
                                  batch_sharding, make_mesh,
                                  shardings_for_tree)

    cfg = LlamaConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=8,
                      n_kv_heads=4, d_ff=256, max_seq_len=128,
                      dtype=jnp.float32)

    # fsdp=2 shards parameters, tp=2 shards attention/mlp heads,
    # sp=2 shards the sequence axis (ring attention under the hood).
    spec = MeshSpec(fsdp=2, sp=2, tp=2)
    mesh = make_mesh(spec.resolve(8))
    with mesh:
        params = init_params(cfg, jax.random.PRNGKey(0))
        params = apply_shardings(params, shardings_for_tree(params, mesh))
        tokens = np.random.randint(0, cfg.vocab_size, (4, 128))
        batch = {"tokens": jax.device_put(tokens, batch_sharding(mesh))}

        @jax.jit
        def step(params, batch):
            return jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg))(params)

        loss, grads = step(params, batch)
        print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)))
        print("loss:", float(loss))
        # Parameters live distributed across the mesh:
        one = jax.tree_util.tree_leaves(params)[1]
        print("a param's sharding:", one.sharding)


if __name__ == "__main__":
    main()
