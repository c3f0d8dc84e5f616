"""Compile the benchmark's step programs at their real sizes for a described
``v5e:2x2``, with no chip attached. Nothing runs: this says what fits and how
long it compiles, never a time or a rate. A script, not a test: run it by
hand before the first chip call of a configuration (it loads libtpu, which
only one process at a time may do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_check.py [config ...]
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def report(what: str, compiled, seconds: float):
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)   # noqa: E731
    print(json.dumps({
        "program": what, "compile_s": round(seconds, 1),
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "per_device_GB": {
            "arguments": gb(m.argument_size_in_bytes),
            "outputs": gb(m.output_size_in_bytes),
            "aliased": gb(m.alias_size_in_bytes),
            "temporaries": gb(m.temp_size_in_bytes),
            "live_at_peak": gb(m.argument_size_in_bytes
                               + m.output_size_in_bytes
                               - m.alias_size_in_bytes
                               + m.temp_size_in_bytes)}}), flush=True)


def serve(config: dict, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.engine import _prefill_one
    from ray_tpu.models.paged import _paged_step
    from ray_tpu.ops.layers import rope_frequencies

    from perfbench import program
    from perfbench.manifest import resolve

    chip = SingleDeviceSharding(topo.devices[0])
    cfg = program.model_config(config, program.shape_of(config, False))
    e = config["engine"]
    S, pages, page, max_len = (e["max_slots"], e["num_pages"], e["page_size"],
                               e["max_len"])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    init = resolve(config["program"]["init_params"])
    params = on(jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    pool = jax.ShapeDtypeStruct((pages, page, cfg.n_kv_heads, cfg.head_dim),
                                cfg.dtype, sharding=chip)
    cos, sin = on(jax.eval_shape(
        lambda: rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta)))
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    t0 = time.perf_counter()
    compiled = _paged_step.lower(
        params, [pool] * cfg.n_layers, [pool] * cfg.n_layers,
        [0] * cfg.n_layers, [0] * cfg.n_layers,
        sd((S, max_len // page), jnp.int32), sd((S,), jnp.int32),
        sd((S,), jnp.int32), sd((S,), jnp.float32), sd((S,), jnp.int32),
        sd((S,), jnp.float32), sd((S, 2), jnp.uint32),
        cfg=cfg, cos=cos, sin=sin, page=page, kv_int8=False).compile()
    report(f"{config['name']}: _paged_step, {S} slots, {pages} pages of "
           f"{page}, depth {cfg.n_layers}", compiled,
           time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = _prefill_one.lower(
        params, sd((max_len,), jnp.int32), 1, max_len, cfg, cos, sin,
        max_len).compile()
    report(f"{config['name']}: _prefill_one at the {max_len} bucket",
           compiled, time.perf_counter() - t0)


def train(config: dict, topo, traffic: dict):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import AXES, MeshSpec, batch_sharding

    from perfbench import program, trainloop

    import numpy as np

    # flash_attention asks jax for the platform, which here is the CPU: answer
    # for the chip the program is compiled for
    attention._on_tpu = lambda: True
    spec = MeshSpec(**config["mesh"]).resolve(len(topo.devices))
    mesh = Mesh(np.asarray(topo.devices).reshape(
        tuple(spec.sizes()[a] for a in AXES)), AXES)
    cfg = program.model_config(config, program.shape_of(config, False))
    opt = optax.adamw(**config["optimizer"]["adamw"])
    params, param_sh, opt_state, opt_sh = trainloop.abstract_state(
        config, cfg, mesh, opt)
    place = lambda tree, sh: jax.tree.map(   # noqa: E731
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sh)
    tokens = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq_len"]),
                                  jnp.int32, sharding=batch_sharding(mesh))
    t0 = time.perf_counter()
    compiled, _ = trainloop.compile_step(
        config, cfg, opt, mesh, place(params, param_sh),
        place(opt_state, opt_sh), tokens, param_sh, opt_sh)
    report(f"{config['name']}: sharded AdamW step, mesh {config['mesh']}, "
           f"{traffic['batch']} x {traffic['seq_len']} tokens, depth "
           f"{cfg.n_layers}, {cfg.param_count() / 1e9:.3f} B parameters",
           compiled, time.perf_counter() - t0)


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
    names = argv or [c["name"] for c in man.doc["configs"]]
    for name in names:
        config = man.config(name)
        if config["runner"].endswith("serve:run"):
            serve(config, topo)
        else:
            cell = next(w for w in man.doc["workloads"]
                        if w["config"] == name)
            train(config, topo, man.traffic(cell["traffic"]))


if __name__ == "__main__":
    main(sys.argv[1:])
