"""Compile the gated-short-convolution / attention family's engine programs at
their real sizes for a described ``v5e:2x2``, with no chip attached
(``aot_check.py`` does it for the dense decoder's, ``aot_nemotron.py``,
``aot_sala.py``, ``aot_longcat.py`` and ``aot_commanda.py`` for the four other
families'). Nothing runs: this says what fits and how long it compiles, never
a time or a rate. A script, not a test: run it by hand before the first chip
call (it loads libtpu, which one process at a time may do).

    JAX_PLATFORMS=cpu python3 perfbench/aot_lfm2.py [config ...]

The prefill chunk's ``live_at_peak`` counts the engine's pools and tails
beside it: they are resident while a chunk runs, and are added by hand below.
It also asserts that the decode step holds no array of gathered keys or
values as wide as the table (``[S, max_len, ...]``), that every donated pool
and tail comes back aliased, and that NO COPY AS WIDE AS A POOL is in the
step: a head of 64 is half a lane, and a 4-D pool of such heads is turned
whole twice a step (``paged_ops._lane_rows``); this family's pools are
``paged_ops.lane_pool_shape``'s.
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


def pool_wide_copies(hlo: str, pool_elems: int) -> list:
    """The ``copy`` and ``transpose`` instructions of the HLO text whose
    result holds as many elements as a pool or more."""
    found = []
    for ln in hlo.splitlines():
        m = re.search(r"= (?:bf16|f32|s8)\[([0-9,]+)\][^ ]* "
                      r"(copy|transpose)\(", ln)
        if not m:
            continue
        n = 1
        for x in m.group(1).split(","):
            n *= int(x)
        if n >= pool_elems:
            found.append(ln.split(" = ")[0].strip() + ": " + m.group(2)
                         + " [" + m.group(1) + "]")
    return found


def table_wide_shapes(hlo: str, slots: int, max_len: int, row: int) -> list:
    """Array shapes in the HLO text that hold ``slots x max_len`` positions'
    K or V rows or more with ``max_len`` (a slot's whole table, gathered) as
    one of their dimensions: what the blocked read never builds.
    (``aot_commanda.table_wide_shapes`` looks for the head's width beside the
    slots; here both are 64, as the experts are, and a layer's experts have
    that many elements.)"""
    found = set()
    for dims in re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", hlo):
        d = [int(x) for x in dims.split(",")]
        n = 1
        for x in d:
            n *= x
        if max_len in d and n >= slots * max_len * row:
            found.add(dims)
    return sorted(found)


def serve(config: dict, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import lfm2_moe as lm
    from ray_tpu.models.paged import _scatter_pages
    from ray_tpu.models.paged_ops import lane_pool_shape

    from perfbench import lfm2_bytes as lb, program
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

    def on(tree):
        return jax.tree.map(lambda a: sd(a.shape, a.dtype), tree)

    init = resolve(config["program"]["init_params"])
    params = on(jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))))
    pool = sd(lane_pool_shape(pages, page, cfg.n_kv_heads, cfg.head_dim),
              cfg.dtype)
    pk = pv = [pool] * cfg.n_attn_layers
    tails = on(jax.eval_shape(lambda: lm.init_state(cfg, S)))
    small = (sd((S, max_len // page), jnp.int32), sd((S,), jnp.int32),
             sd((S,), jnp.int32), sd((S,), jnp.float32), sd((S,), jnp.int32),
             sd((S,), jnp.float32), sd((S, 2), jnp.uint32))
    pool_bytes = pages * page * lb.kv_row_bytes(shape) * cfg.n_attn_layers
    tail_bytes = int(lb.conv_tail_bytes(shape, S))
    held = lb.weight_bytes(shape) + pool_bytes + tail_bytes
    what = f"{config['name']}: "
    print(f"{what}{cfg.param_count() / 1e9:.3f} B parameters held "
          f"({lb.weight_bytes(shape) / 1e9:.3f} GB); weights + {pages} pages "
          f"of {page} of {cfg.n_attn_layers} attention layer(s) "
          f"({pool_bytes / 1e9:.3f} GB) + {cfg.n_conv_layers} conv tails of "
          f"{S} slots ({tail_bytes / 1e9:.4f} GB) = {held / 1e9:.3f} GB "
          f"resident", flush=True)
    t0 = time.perf_counter()
    compiled = lm._lfm2_step.lower(params, pk, pv, tails, *small, cfg=cfg,
                                   page=page).compile()
    report(what + f"_lfm2_step, {S} slots, {pages} pages of {page}, "
           f"max_len {max_len}, depth {cfg.n_layers}", compiled,
           time.perf_counter() - t0)
    mem = compiled.memory_analysis()
    donated = pool_bytes + tail_bytes
    print(f"{what}donated pools and tails {donated / 1e9:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB", flush=True)
    hlo = compiled.as_text()
    bad = table_wide_shapes(hlo, S, max_len, cfg.n_kv_heads * cfg.head_dim)
    print(f"{what}arrays of the step as wide as the table: {bad or 'none'}",
          flush=True)
    copies = pool_wide_copies(hlo, pages * page * cfg.n_kv_heads
                              * cfg.head_dim)
    print(f"{what}copies of the step as wide as a pool: {copies or 'none'}; "
          f"ragged-dot: {hlo.count('ragged_dot_tiling=')}", flush=True)
    if bad or copies or mem.alias_size_in_bytes < donated:
        raise SystemExit("the decode step gathers a table-wide array or "
                         "copies a donated pool")
    carry = on(jax.eval_shape(lambda: lm.prefill_carry(cfg, max_len)))
    t0 = time.perf_counter()
    compiled = lm._lfm2_prefill_chunk.lower(
        params, sd((cfg.prefill_chunk,), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32), *carry, cfg=cfg).compile()
    report(what + f"_lfm2_prefill_chunk of {cfg.prefill_chunk} tokens "
           f"(the pools and tails, {donated / 1e9:.3f} GB, are resident "
           f"beside it)", compiled, time.perf_counter() - t0)
    # chunk x max_len scores of one layer's heads would be 1.6 GB in float32
    # (the shape's own spelling is W_in's here: 2048 x 6144)
    print(f"{what}the chunk's temporaries hold no chunk x max_len scores: "
          f"{compiled.memory_analysis().temp_size_in_bytes < 0.5e9}; "
          f"ragged-dot: {compiled.as_text().count('ragged_dot_tiling=')}",
          flush=True)
    t0 = time.perf_counter()
    compiled = _scatter_pages.lower(
        pk, pv, [None] * len(pk), [None] * len(pk), carry[0],
        sd((max_len // page,), jnp.int32), sd((), jnp.float32), page=page,
        kv_int8=False).compile()
    report(what + "_scatter_pages (the attention layers' rows)", compiled,
           time.perf_counter() - t0)
    copies = pool_wide_copies(compiled.as_text(), pages * page
                              * cfg.n_kv_heads * cfg.head_dim)
    print(f"{what}copies of the scatter as wide as a pool: "
          f"{copies or 'none'}", flush=True)
    t0 = time.perf_counter()
    compiled = lm._write_tails.lower(tails, carry[1],
                                     sd((), jnp.int32)).compile()
    report(what + "_write_tails", compiled, time.perf_counter() - t0)
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
    for name in argv or ["lfm2-24b-a2b-serve1"]:
        serve(man.config(name), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
