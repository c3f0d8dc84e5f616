"""Compile the Granite-MoE-hybrid family's engine programs at their real sizes
for a described ``v5e:2x2``, with no chip attached (``aot_check.py`` does it
for the dense decoder's, ``aot_nemotron.py``, ``aot_sala.py``,
``aot_longcat.py``, ``aot_commanda.py`` and ``aot_lfm2.py`` for the five other
families'). Nothing runs: this says what fits and how long it compiles, never
a time or a rate. A script, not a test: run it by hand before the first chip
call (it loads libtpu, which one process at a time may do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_granite.py [config ...]

The prefill chunk's ``live_at_peak`` counts the engine's pools, SSM states
and tails beside it: they are resident while a chunk runs, and are added by
hand below. It also asserts that the decode step holds no array of gathered
keys or values as wide as the table (``[S, max_len, ...]``) and that every
donated pool, SSM state and tail comes back aliased: 2.45 GB of float32 state
copied once would not fit beside the weights.
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

HBM = 15.75e9   # what a v5e chip gives a process


def serve(config: dict, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import granite_moe_hybrid as gm
    from ray_tpu.models.paged import _scatter_pages

    from perfbench import granite_bytes as gb, program
    from perfbench.aot_check import report
    from perfbench.aot_lfm2 import table_wide_shapes
    from perfbench.manifest import resolve

    chip = SingleDeviceSharding(topo.devices[0])
    shape = program.shape_of(config, False)
    cfg = program.model_config(config, shape)
    e = config["engine"]
    S, pages, page, max_len = (e["max_slots"], e["num_pages"], e["page_size"],
                               e["max_len"])

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def on(tree):
        return jax.tree.map(lambda a: sd(a.shape, a.dtype), tree)

    init = resolve(config["program"]["init_params"])
    params = on(jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    pool = sd((pages, page, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    pk = pv = [pool] * cfg.n_attn_layers
    ssm, tails = on(jax.eval_shape(lambda: gm.init_state(cfg, S)))
    small = (sd((S, max_len // page), jnp.int32), sd((S,), jnp.int32),
             sd((S,), jnp.int32), sd((S,), jnp.float32), sd((S,), jnp.int32),
             sd((S,), jnp.float32), sd((S, 2), jnp.uint32))
    pool_bytes = pages * page * gb.kv_row_bytes(shape) * cfg.n_attn_layers
    state_bytes = S * gb.slot_state_bytes(shape)
    assert state_bytes == S * cfg.slot_state_bytes
    held = gb.weight_bytes(shape) + pool_bytes + state_bytes
    what = f"{config['name']}: "
    print(f"{what}{cfg.param_count() / 1e9:.3f} B parameters held "
          f"({gb.weight_bytes(shape) / 1e9:.3f} GB); weights + {pages} pages "
          f"of {page} of {cfg.n_attn_layers} attention layer(s) "
          f"({pool_bytes / 1e9:.3f} GB) + the SSM states and tails of "
          f"{cfg.n_mamba_layers} Mamba layers of {S} slots "
          f"({state_bytes / 1e9:.3f} GB) = {held / 1e9:.3f} GB resident",
          flush=True)
    t0 = time.perf_counter()
    compiled = gm._granite_step.lower(params, pk, pv, ssm, tails, *small,
                                      cfg=cfg, page=page).compile()
    report(what + f"_granite_step, {S} slots, {pages} pages of {page}, "
           f"max_len {max_len}, depth {cfg.n_layers}", compiled,
           time.perf_counter() - t0)
    mem = compiled.memory_analysis()
    donated = pool_bytes + state_bytes
    step_peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{what}donated pools, states and tails {donated / 1e9:.3f} GB, "
          f"aliased {mem.alias_size_in_bytes / 1e9:.3f} GB; the step's "
          f"arguments + temporaries + outputs - aliased "
          f"{step_peak / 1e9:.3f} GB of {HBM / 1e9:.2f}", flush=True)
    hlo = compiled.as_text()
    bad = table_wide_shapes(hlo, S, max_len, cfg.n_kv_heads * cfg.head_dim)
    print(f"{what}arrays of the step as wide as the table: {bad or 'none'}; "
          f"ragged-dot: {hlo.count('ragged_dot_tiling=')}", flush=True)
    if bad or mem.alias_size_in_bytes < donated or step_peak > HBM:
        raise SystemExit("the decode step gathers a table-wide array, copies "
                         "a donated pool or state, or does not fit")
    carry = on(jax.eval_shape(lambda: gm.prefill_carry(cfg, max_len)))
    t0 = time.perf_counter()
    compiled = gm._granite_prefill_chunk.lower(
        params, sd((cfg.prefill_chunk,), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32), *carry, cfg=cfg).compile()
    report(what + f"_granite_prefill_chunk of {cfg.prefill_chunk} tokens "
           f"(the pools, states and tails, {donated / 1e9:.3f} GB, are "
           f"resident beside it)", compiled, time.perf_counter() - t0)
    mem = compiled.memory_analysis()
    chunk_peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                  + mem.output_size_in_bytes - mem.alias_size_in_bytes
                  + donated)
    print(f"{what}the chunk's temporaries {mem.temp_size_in_bytes / 1e9:.3f} "
          f"GB; with the engine's pools and state beside it "
          f"{chunk_peak / 1e9:.3f} GB of {HBM / 1e9:.2f}; ragged-dot: "
          f"{compiled.as_text().count('ragged_dot_tiling=')}", flush=True)
    if chunk_peak > HBM:
        raise SystemExit("the prefill chunk does not fit beside the engine's "
                         "state: a smaller prefill_chunk is the lever")
    t0 = time.perf_counter()
    compiled = _scatter_pages.lower(
        pk, pv, [None] * len(pk), [None] * len(pk), carry[0],
        sd((max_len // page,), jnp.int32), sd((), jnp.float32), page=page,
        kv_int8=False).compile()
    report(what + "_scatter_pages (the attention layer's rows)", compiled,
           time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = gm._write_state.lower(ssm, tails, carry[1],
                                     sd((), jnp.int32)).compile()
    report(what + "_write_state", compiled, time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda k: init(cfg, k)).lower(
        jax.ShapeDtypeStruct((), jax.random.key(0, impl="rbg").dtype,
                             sharding=chip)).compile()
    report(what + "init_params (one jitted call)", compiled,
           time.perf_counter() - t0)


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
    for name in argv or ["granite-4.0-h-small-serve1"]:
        serve(man.config(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
