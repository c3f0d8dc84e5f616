"""Compile the window / full attention family's engine programs at their real
sizes for a described ``v5e:2x2``, with no chip attached (``aot_check.py``
does it for the dense decoder's, ``aot_nemotron.py``, ``aot_sala.py`` and
``aot_longcat.py`` for the three other families'). Nothing runs: this says
what fits and how long it compiles, never a time or a rate. A script, not a
test: run it by hand before the first chip call (it loads libtpu, which one
process at a time may do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_commanda.py [config ...]

The prefill chunk's ``live_at_peak`` counts the engine's pools and rings
beside it: they are resident while a chunk runs, and are added by hand below.
It also asserts that the decode step holds no array of gathered keys or
values as wide as the table (``[S, max_len, ...]``), that every donated
pool and ring comes back aliased, and that none is copied whole.
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


def table_wide_shapes(hlo: str, slots: int, max_len: int, cfg) -> list:
    """Array shapes in the HLO text that hold a K/V row (``n_kv_heads`` beside
    ``head_dim``) for ``slots x max_len`` positions or more: what the blocked
    read never builds."""
    found = set()
    for dims in re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", hlo):
        d = [int(x) for x in dims.split(",")]
        n = 1
        for x in d:
            n *= x
        if cfg.head_dim in d and n >= slots * max_len * cfg.n_kv_heads \
                * cfg.head_dim and slots in d:
            found.add(dims)
    return sorted(found)


def serve(config: dict, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import cohere2_moe as cm
    from ray_tpu.models.paged import _scatter_pages

    from perfbench import commanda_bytes as cb, program
    from perfbench.aot_check import report
    from perfbench.manifest import resolve

    chip = SingleDeviceSharding(topo.devices[0])
    shape = program.shape_of(config, False)
    cfg = program.model_config(config, shape)
    e = config["engine"]
    S, pages, page, max_len = (e["max_slots"], e["num_pages"], e["page_size"],
                               e["max_len"])

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    init = resolve(config["program"]["init_params"])
    params = jax.tree.map(
        lambda a: sd(a.shape, a.dtype),
        jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    pool = sd((pages, page, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    ring = sd((S, cfg.n_kv_heads, cfg.sliding_window, cfg.head_dim),
              cfg.dtype)
    pk = pv = [pool] * cfg.n_full_layers
    rk = rv = [ring] * cfg.n_window_layers
    small = (sd((S, max_len // page), jnp.int32), sd((S,), jnp.int32),
             sd((S,), jnp.int32), sd((S,), jnp.float32), sd((S,), jnp.int32),
             sd((S,), jnp.float32), sd((S, 2), jnp.uint32))
    row = cb.kv_row_bytes(shape)
    pool_bytes = pages * page * row * cfg.n_full_layers
    ring_bytes = S * cfg.sliding_window * row * cfg.n_window_layers
    held = cb.weight_bytes(shape) + pool_bytes + ring_bytes
    what = f"{config['name']}: "
    print(f"{what}{cfg.param_count() / 1e9:.3f} B parameters held "
          f"({cb.weight_bytes(shape) / 1e9:.3f} GB); weights + {pages} pages "
          f"of {page} of {cfg.n_full_layers} full layer(s) "
          f"({pool_bytes / 1e9:.3f} GB) + {cfg.n_window_layers} rings of "
          f"{S} x {cfg.sliding_window} ({ring_bytes / 1e9:.3f} GB) = "
          f"{held / 1e9:.3f} GB resident", flush=True)
    t0 = time.perf_counter()
    compiled = cm._cohere_step.lower(params, pk, pv, rk, rv, *small, cfg=cfg,
                                     page=page).compile()
    report(what + f"_cohere_step, {S} slots, {pages} pages of {page}, "
           f"max_len {max_len}, depth {cfg.n_layers}", compiled,
           time.perf_counter() - t0)
    mem = compiled.memory_analysis()
    donated = pool_bytes + ring_bytes
    print(f"{what}donated pools and rings {donated / 1e9:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB", flush=True)
    hlo = compiled.as_text()
    bad = table_wide_shapes(hlo, S, max_len, cfg)
    print(f"{what}arrays of the step as wide as the table: {bad or 'none'}",
          flush=True)
    whole = ["bf16[%d,%d,%d,%d]" % a.shape for a in (pk[0], rk[0])]
    copies = [ln.split(" = ")[0].strip() for ln in hlo.splitlines()
              if " copy(" in ln and any(f"= {w}" in ln for w in whole)]
    print(f"{what}whole pools or rings the step copies: {copies or 'none'}",
          flush=True)
    bad = bad or copies
    if bad or mem.alias_size_in_bytes < donated:
        raise SystemExit("the decode step gathers a table-wide array or "
                         "copies a donated pool or ring")
    carry = jax.tree.map(
        lambda a: sd(a.shape, a.dtype),
        jax.eval_shape(lambda: cm.prefill_carry(cfg, max_len)))
    t0 = time.perf_counter()
    compiled = cm._cohere_prefill_chunk.lower(
        params, sd((cfg.prefill_chunk,), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32), carry, cfg=cfg).compile()
    report(what + f"_cohere_prefill_chunk of {cfg.prefill_chunk} tokens "
           f"(the pools and rings, {donated / 1e9:.3f} GB, are resident "
           f"beside it)", compiled, time.perf_counter() - t0)
    hlo = compiled.as_text()
    print(f"{what}the chunk holds no L x L array: "
          f"{not re.search(rf'[,\[]{max_len},{max_len}[,\]]', hlo)}; "
          f"ragged-dot: {'ragged-dot' in hlo}", flush=True)
    full = [c for k, c in zip(cfg.kinds, carry) if k == cm.FULL]
    t0 = time.perf_counter()
    compiled = _scatter_pages.lower(
        pk, pv, [None] * len(pk), [None] * len(pk), full,
        sd((max_len // page,), jnp.int32), sd((), jnp.float32), page=page,
        kv_int8=False).compile()
    report(what + "_scatter_pages (the full layers' rows)", compiled,
           time.perf_counter() - t0)
    window = [c for k, c in zip(cfg.kinds, carry) if k == cm.WINDOW]
    t0 = time.perf_counter()
    compiled = cm._write_rings.lower(rk, rv, window, sd((), jnp.int32),
                                     sd((), jnp.int32)).compile()
    report(what + "_write_rings", compiled, time.perf_counter() - t0)
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
    for name in argv or ["command-a-plus-serve1"]:
        serve(man.config(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
