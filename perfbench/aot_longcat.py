"""Compile the latent-attention / zero-expert family's engine programs at
their real sizes for a described ``v5e:2x2``, with no chip attached
(``aot_check.py`` does it for the dense decoder's, ``aot_nemotron.py`` for
the hybrid family's, ``aot_sala.py`` for the sparse one's). Nothing runs: this
says what fits and how long it compiles, never a time or a rate. A script,
not a test: run it by hand before the first chip call (it loads libtpu, which
one process at a time may do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_longcat.py [config ...]

The prefill chunk's ``live_at_peak`` counts the engine's pools beside it:
they are resident while a chunk runs, and are added by hand below. It also
asserts that the decode step never expands a cached latent row: its optimised
HLO holds no array of the gathered positions x heads x (dn, dv or dn + dr).
"""

from __future__ import annotations

import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def expanded_shapes(hlo: str, positions: int, cfg) -> list:
    """Array shapes in the HLO text that hold ``positions`` (or more) cached
    rows beside a per-head key or value: what the absorbed form never
    builds."""
    heads = cfg.n_heads
    widths = {cfg.qk_nope_head_dim, cfg.v_head_dim,
              cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
              cfg.qk_nope_head_dim + cfg.v_head_dim}
    found = set()
    for dims in re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", hlo):
        d = [int(x) for x in dims.split(",")]
        n = 1
        for x in d:
            n *= x
        if heads in d and any(w in d for w in widths) \
                and n >= positions * heads * min(widths):
            found.add(dims)
    return sorted(found)


def serve(config: dict, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import longcat_flash as lc
    from ray_tpu.models.paged_ops import latent_pool_shape

    from perfbench import longcat_bytes, program
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
    pools = [sd(latent_pool_shape(pages, page, cfg.latent_width),
                cfg.dtype)] * cfg.n_sublayers
    pool_bytes = (pages * page * cfg.n_sublayers
                  * longcat_bytes.latent_row_bytes(shape))
    held = longcat_bytes.weight_bytes(shape) + pool_bytes
    what = f"{config['name']}: "
    print(f"{what}{cfg.param_count() / 1e9:.3f} B parameters held "
          f"({longcat_bytes.weight_bytes(shape) / 1e9:.3f} GB); weights + "
          f"{pages} latent pages of {page} ({pool_bytes / 1e9:.3f} GB) = "
          f"{held / 1e9:.3f} GB resident", flush=True)
    t0 = time.perf_counter()
    compiled = lc._longcat_step.lower(
        params, pools, sd((S, max_len // page), jnp.int32),
        sd((S,), jnp.int32), sd((S,), jnp.int32), sd((S,), jnp.float32),
        sd((S,), jnp.int32), sd((S,), jnp.float32), sd((S, 2), jnp.uint32),
        cfg=cfg, page=page).compile()
    report(what + f"_longcat_step, {S} slots, {pages} pages of {page}, "
           f"max_len {max_len}, depth {cfg.n_layers}", compiled,
           time.perf_counter() - t0)
    bad = expanded_shapes(compiled.as_text(), S * max_len, cfg)
    print(f"{what}arrays of the step that expand cached rows per head: "
          f"{bad or 'none'}", flush=True)
    if bad:
        raise SystemExit("the decode step expands cached latent rows")
    carry = on(jax.eval_shape(lambda: lc.prefill_carry(cfg, max_len)))
    t0 = time.perf_counter()
    compiled = lc._longcat_prefill_chunk.lower(
        params, sd((cfg.prefill_chunk,), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32), carry, cfg=cfg).compile()
    report(what + f"_longcat_prefill_chunk of {cfg.prefill_chunk} tokens "
           f"(the pools, {pool_bytes / 1e9:.3f} GB, are resident beside it)",
           compiled, time.perf_counter() - t0)
    hlo = compiled.as_text()
    print(f"{what}the chunk holds no L x L array: "
          f"{not re.search(rf'[,\[]{max_len},{max_len}[,\]]', hlo)}; "
          f"ragged-dot: {'ragged-dot' in hlo}", flush=True)
    t0 = time.perf_counter()
    compiled = lc._scatter_latent.lower(
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
    for name in argv or ["longcat-flash-omni-serve1"]:
        serve(man.config(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
