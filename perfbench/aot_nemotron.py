"""Compile the hybrid family's engine programs at their real sizes for a
described ``v5e:2x2``, with no chip attached (``aot_check.py`` does it for
the dense decoder's). Nothing runs: this says what fits and how long it
compiles, never a time or a rate. A script, not a test: run it by hand
before the first chip call (it loads libtpu, which one process at a time may
do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_nemotron.py [config ...]
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


def serve(config: dict, topo, buckets):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import nemotron_h as nh
    from ray_tpu.models.paged import _scatter_pages

    from perfbench import program
    from perfbench.aot_check import report
    from perfbench.manifest import resolve

    chip = SingleDeviceSharding(topo.devices[0])
    cfg = program.model_config(config, program.shape_of(config, False))
    e = config["engine"]
    S, pages, page, max_len = (e["max_slots"], e["num_pages"], e["page_size"],
                               e["max_len"])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    init = resolve(config["program"]["init_params"])
    params = on(jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    pools = [sd((pages, page, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
             ] * cfg.n_attn_layers
    ssm, conv = on(jax.eval_shape(lambda: nh.init_state(cfg, S)))
    none = [0] * cfg.n_attn_layers
    what = f"{config['name']}: "
    t0 = time.perf_counter()
    compiled = nh._hybrid_step.lower(
        params, pools, pools, none, none, ssm, conv,
        sd((S, max_len // page), jnp.int32), sd((S,), jnp.int32),
        sd((S,), jnp.int32), sd((S,), jnp.float32), sd((S,), jnp.int32),
        sd((S,), jnp.float32), sd((S, 2), jnp.uint32),
        cfg=cfg, page=page, kv_int8=False).compile()
    report(what + f"_hybrid_step, {S} slots, {pages} pages of {page}, "
           f"max_len {max_len}, depth {cfg.n_layers}, "
           f"{cfg.param_count() / 1e9:.3f} B parameters held", compiled,
           time.perf_counter() - t0)
    for pad in buckets:
        t0 = time.perf_counter()
        compiled = nh._hybrid_prefill.lower(
            params, sd((pad,), jnp.int32), 1, max_len, cfg, pad).compile()
        report(what + f"_hybrid_prefill at the {pad} bucket", compiled,
               time.perf_counter() - t0)
    state, caches = jax.eval_shape(
        lambda p: nh._hybrid_prefill(p, jnp.zeros((16,), jnp.int32), 1,
                                     max_len, cfg, 16)[2:0:-1], params)
    t0 = time.perf_counter()
    compiled = nh._write_state.lower(ssm, conv, on(state),
                                     sd((), jnp.int32)).compile()
    report(what + "_write_state", compiled, time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = _scatter_pages.lower(
        pools, pools, none, none, on(caches),
        sd((max_len // page,), jnp.int32), sd((), jnp.float32), page=page,
        kv_int8=False).compile()
    report(what + "_scatter_pages", compiled, time.perf_counter() - t0)


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
    for name in argv or ["nemotron-3-nano-30b-a3b-serve1"]:
        config = man.config(name)
        serve(config, topo, (16, 64, 256, config["engine"]["max_len"]))


if __name__ == "__main__":
    main(sys.argv[1:])
