"""Compile the lightning / block-sparse family's engine programs at their
real sizes for a described ``v5e:2x2``, with no chip attached
(``aot_check.py`` does it for the dense decoder's, ``aot_nemotron.py`` for
the hybrid family's). Nothing runs: this says what fits and how long it
compiles, never a time or a rate. A script, not a test: run it by hand
before the first chip call (it loads libtpu, which one process at a time may
do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_sala.py [config ...]

The prefill chunk's ``live_at_peak`` counts the engine's pools beside it:
they are resident while a chunk runs, and are added by hand below.
"""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def serve(config: dict, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import minicpm_sala as ms
    from ray_tpu.models.nemotron_h import _write_state

    from perfbench import program, sala_bytes
    from perfbench.aot_check import report
    from perfbench.manifest import resolve

    chip = SingleDeviceSharding(topo.devices[0])
    shape = program.shape_of(config, False)
    cfg = program.model_config(config, shape)
    e = config["engine"]
    S, pages, page, max_len = (e["max_slots"], e["num_pages"], e["page_size"],
                               e["max_len"])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    init = resolve(config["program"]["init_params"])
    params = on(jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    n = cfg.n_sparse_layers
    pools = [sd((pages, page, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)] * n
    pools_c = [sd((pages, page // cfg.stride, cfg.n_kv_heads, cfg.head_dim),
                  cfg.dtype)] * n
    states = on(jax.eval_shape(lambda: ms.init_state(cfg, S)))
    held = (sala_bytes.weight_bytes(shape)
            + S * sala_bytes.slot_state_bytes(shape)
            + pages * shape["num_key_value_heads"] * n
            * (sala_bytes.page_head_bytes(shape)
               + sala_bytes.ckey_page_head_bytes(shape)))
    what = f"{config['name']}: "
    print(f"{what}{cfg.param_count() / 1e9:.3f} B parameters held; weights + "
          f"state of {S} slots + {pages} pages and their compressed keys = "
          f"{held / 1e9:.3f} GB resident", flush=True)
    t0 = time.perf_counter()
    compiled = ms._sala_step.lower(
        params, pools, pools, pools_c, states,
        sd((S, max_len // page), jnp.int32), sd((S,), jnp.int32),
        sd((S,), jnp.int32), sd((S,), jnp.float32), sd((S,), jnp.int32),
        sd((S,), jnp.float32), sd((S, 2), jnp.uint32),
        cfg=cfg, page=page).compile()
    report(what + f"_sala_step, {S} slots, {pages} pages of {page}, max_len "
           f"{max_len}, depth {cfg.n_layers}", compiled,
           time.perf_counter() - t0)
    carry = on(jax.eval_shape(lambda: ms.prefill_carry(cfg, max_len)))
    t0 = time.perf_counter()
    compiled = ms._sala_prefill_chunk.lower(
        params, sd((cfg.prefill_chunk,), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32), *carry, cfg=cfg).compile()
    report(what + f"_sala_prefill_chunk of {cfg.prefill_chunk} tokens (the "
           f"pools, compressed keys and slots' state, "
           f"{(held - sala_bytes.weight_bytes(shape)) / 1e9:.3f} GB, are "
           "resident beside it)", compiled, time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = ms._scatter_sala.lower(
        pools, pools, pools_c, carry[1], carry[2],
        sd((max_len // page,), jnp.int32)).compile()
    report(what + "_scatter_sala", compiled, time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = _write_state.lower(
        states, [], [(s,) for s in carry[0]], sd((), jnp.int32)).compile()
    report(what + "_write_state", compiled, time.perf_counter() - t0)


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from perfbench.manifest import Manifest

    # an entry compiled for a described device cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    man = Manifest(ROOT)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv or ["minicpm-sala-9b-serve1"]:
        serve(man.config(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
