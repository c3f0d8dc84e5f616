"""Compile the DeepSeek-V3 family's engine programs at their real sizes for a
described ``v5e:2x2``, with no chip attached (``aot_longcat.py`` does it for
the other latent-attention family's). Nothing runs: this says what fits and
how long it compiles, never a time or a rate. A script, not a test: run it by
hand before the first chip call (it loads libtpu, which one process at a time
may do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_gigachat.py [config ...]

The chunk's and the first draft's ``live_at_peak`` count the engine's pools
and the drafts' distributions beside them where they are not arguments: they
are resident while the program runs, and are added by hand below. It also
asserts that the two-row step never expands a cached latent row per head
(``aot_longcat.expanded_shapes``) and gives every pool back aliased.
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

    from ray_tpu.models import deepseek_v3 as ds
    from ray_tpu.models.longcat_flash import _scatter_latent, prefill_carry
    from ray_tpu.models.paged_ops import latent_pool_shape

    from perfbench import gigachat_bytes, program
    from perfbench.aot_check import report
    from perfbench.aot_longcat import expanded_shapes
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
    pool = sd(latent_pool_shape(pages, page, cfg.latent_width), cfg.dtype)
    pools = [pool] * cfg.n_sublayers
    pool_bytes = (pages * page * cfg.n_sublayers
                  * gigachat_bytes.latent_row_bytes(shape))
    q_bytes = 4 * S * cfg.vocab_size
    held = gigachat_bytes.weight_bytes(shape) + pool_bytes + q_bytes
    what = f"{config['name']}: "
    print(f"{what}{cfg.param_count() / 1e9:.3f} B parameters held "
          f"({gigachat_bytes.weight_bytes(shape) / 1e9:.3f} GB); weights + "
          f"{pages} latent pages of {page} in {cfg.n_sublayers} pools "
          f"({pool_bytes / 1e9:.3f} GB) + the drafts' distributions "
          f"({q_bytes / 1e6:.1f} MB) = {held / 1e9:.3f} GB resident; softmax "
          f"scale {cfg.attn_scale:.5f}", flush=True)
    slots = (sd((S,), jnp.int32), sd((S,), jnp.int32), sd((S,), jnp.float32),
             sd((S,), jnp.int32), sd((S,), jnp.float32),
             sd((S, 2), jnp.uint32))
    t0 = time.perf_counter()
    compiled = ds._deepseek_step.lower(
        params, pools, sd((S, max_len // page), jnp.int32), *slots,
        sd((S, cfg.vocab_size), jnp.float32), sd((S,), jnp.int32),
        cfg=cfg).compile()
    report(what + f"_deepseek_step, {S} slots of two rows, {pages} pages of "
           f"{page}, max_len {max_len}, depth {cfg.n_layers} + MTP", compiled,
           time.perf_counter() - t0)
    hlo = compiled.as_text()
    bad = expanded_shapes(hlo, S * max_len, cfg)
    aliased = compiled.memory_analysis().alias_size_in_bytes
    print(f"{what}arrays of the step that expand cached rows per head: "
          f"{bad or 'none'}; aliased {aliased / 1e9:.3f} GB of "
          f"{(pool_bytes + q_bytes) / 1e9:.3f} GB donated; ragged-dot: "
          f"{'ragged-dot' in hlo}", flush=True)
    if bad or aliased < pool_bytes:
        raise SystemExit("the step expands cached latent rows, or copies a "
                         "pool")
    carry = on(jax.eval_shape(lambda: prefill_carry(cfg, max_len)))
    t0 = time.perf_counter()
    compiled = ds._deepseek_prefill_chunk.lower(
        params, sd((cfg.prefill_chunk,), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32), carry, sd((max_len + 1,), jnp.int32),
        cfg=cfg).compile()
    report(what + f"_deepseek_prefill_chunk of {cfg.prefill_chunk} tokens "
           f"(the pools and the distributions, "
           f"{(pool_bytes + q_bytes) / 1e9:.3f} GB, are resident beside it)",
           compiled, time.perf_counter() - t0)
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes + pool_bytes
            + q_bytes)
    print(f"{what}the chunk's peak with what is resident beside it: "
          f"{peak / 1e9:.3f} GB", flush=True)
    t0 = time.perf_counter()
    compiled = ds._deepseek_first_draft.lower(
        params, pool, sd((1, max_len // page), jnp.int32),
        sd((cfg.d_model,), cfg.dtype), sd((), jnp.int32), sd((), jnp.int32),
        sd((), jnp.float32), sd((), jnp.int32), sd((), jnp.float32),
        sd((S,), jnp.int32), sd((S, cfg.vocab_size), jnp.float32),
        sd((2,), jnp.uint32), sd((), jnp.int32), cfg=cfg).compile()
    report(what + "_deepseek_first_draft (five of the six pools, "
           f"{pool_bytes * (cfg.n_sublayers - 1) / cfg.n_sublayers / 1e9:.3f}"
           " GB, are resident beside it)", compiled,
           time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = _scatter_latent.lower(
        pools, carry, sd((max_len // page,), jnp.int32)).compile()
    report(what + "_scatter_latent", compiled, time.perf_counter() - t0)
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
    for name in argv or ["gigachat3.1-702b-a36b-serve1"]:
        serve(man.config(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
