"""AOT compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed wherever jax[tpu] is: it compiles for a
``v5e:2x2`` topology that is described, not present, and refuses what the
chip's compiler would refuse (untileable blocks, too much VMEM, a program
that does not fit 16 GB). Nothing runs, so these say nothing about results
or speed — ``chip_smoke.py`` is the run. They keep the main paths' kernels
and step programs compiling at real widths between chip runs.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports this file.
"""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models.llama import LLAMA3_1B, init_params, loss_fn
from ray_tpu.ops import attention


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An entry compiled for a described device cannot be read back without
    # one; keep these out of the persistent cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    """Abstract twin of ``tree`` placed on the described chip."""
    return jax.tree.map(lambda a: _shape(sharding, a.shape, a.dtype), tree)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _grouped_products(text):
    """(grouped-matmul kernel calls, ``ragged-dot`` calls) in a compiled
    program's text: the two forms of ``parallel.moe.moe_ffn_grouped``'s
    products (``grouped_product_form``). The kernel is jax's ``megablox.gmm``,
    whose custom call carries its name."""
    return (len(re.findall(r"^\s*(?:ROOT )?%gmm[.\d]* = [^\n]*"
                           r'custom_call_target="tpu_custom_call"', text,
                           re.M)),
            text.count("ragged_dot_tiling="))


def _chunk_attention_calls(text):
    """Calls of the flash kernel ``ops.attention.chunk_attention`` (the
    kernel form of ``cohere2_moe._prompt_attention``) in a compiled
    program's text; its custom call carries its name."""
    return len(re.findall(r"^\s*(?:ROOT )?%ray_tpu_chunk_attention[.\d]* = "
                          r'[^\n]*custom_call_target="tpu_custom_call"', text,
                          re.M))


def _chunk_form(cfg):
    """What the engine's ``serve.admit.prefill`` rows say of this family's
    chunk (``_Family.experts_form``), held to the compiled chunk's text."""
    from ray_tpu.models.paged import _FAMILIES

    return _FAMILIES[type(cfg)].experts_form(cfg)


def _table_wide(text, S, max_len, page, cfg):
    """The spellings of every slot's whole table gathered from a K/V pool
    (``[S, max_len, kvh, d]`` or the gather's own ``[S * P, page, kvh, d]``)
    that ``text`` holds."""
    tail = f"{cfg.n_kv_heads},{cfg.head_dim}]"
    return [w for w in (f"[{S},{max_len},{tail}",
                        f"[{S * max_len // page},{page},{tail}")
            if w in text]


def _assert_no_repeated_table(compiled, S, cap, kvh, rep, d):
    """The decode attention contracts grouped heads against the gathered
    table at ``kvh`` heads in the pool's dtype: no float32 array of the
    table repeated to every head, in either spelling of its shape."""
    text = compiled.as_text()
    for shape in (f"f32[{S},{cap},{kvh},{rep},{d}]",
                  f"f32[{S},{cap},{kvh * rep},{d}]"):
        assert shape not in text, shape


def _assert_vocabulary_sorted_under_a_conditional(compiled, S, V):
    """The chip's compiler keeps ``_pick_tokens``' choice a choice: one
    ``conditional`` in the entry computation, and the vocabulary-wide
    sort and the two flat gathers of S x V elements (``f32[S*V]``, the
    two largest operations of a step before PR 31) in a branch of it,
    not beside it."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert entry.count(" conditional(") == 1
    wide_sort = [ln for ln in text.splitlines()
                 if " sort(" in ln and f"[{S},{V}]" in ln]
    assert wide_sort and not [ln for ln in wide_sort if ln in entry]
    assert f"f32[{S * V}]" in text and f"f32[{S * V}]" not in entry


@pytest.mark.parametrize("B,L,heads,kv_heads,head_dim", [
    (2, 2048, 32, 8, 64),      # chip_smoke.py's LLAMA3_1B
    (2, 2048, 16, 8, 128),
    (1, 4096, 16, 4, 128),     # one chip's share of train-fsdp2-tp2
    (1, 8192, 16, 4, 128),
    (1, 640, 8, 2, 128),       # only 128 tiles it (min(512, L) handed it 512)
    (1, 1536, 8, 2, 64),       # 512 tiles it, 1024 does not
    (1, 4096, 8, 2, 256),
    (1, 16384, 8, 2, 256),     # dkv minors of 1024 overran scoped VMEM here
])
def test_mosaic_flash_fwd_bwd(one_chip, B, L, heads, kv_heads, head_dim):
    """The library's three kernels through ``_tpu_flash`` with the blocks
    ``flash_block_sizes`` returns from its rule of the length (PR 50; no
    autotune record exists), at the shapes the chip sweep ran and at
    lengths and heads it did not: a block that does not tile L, or a tile
    too large for the 16 MiB of scoped VMEM, is refused here and not at a
    trainer's jit."""
    scale = head_dim ** -0.5

    def loss(q, k, v):
        return attention._tpu_flash(q, k, v, True, scale).astype(
            jnp.float32).sum()

    q = _shape(one_chip, (B, L, heads, head_dim))
    kv = _shape(one_chip, (B, L, kv_heads, head_dim))
    _assert_kernel(jax.jit(loss).lower(q, kv, kv).compile())
    _assert_kernel(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile())


@pytest.mark.parametrize("L", [2048, 8192])
def test_flash_attention_stats(one_chip, L):
    """The ring-attention block kernel, compiled (not interpreted)."""
    B, H, Hk, D = 2, 16, 8, 128
    fn = functools.partial(attention.flash_attention_stats,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        _shape(one_chip, (B, L, H, D)), _shape(one_chip, (B, L, Hk, D)),
        _shape(one_chip, (B, L, Hk, D)),
        _shape(one_chip, (B, H, L), jnp.int32)).compile()
    _assert_kernel(compiled)


def _compile_paged_step(one_chip, cfg, S, pages, page, max_len,
                        kv_int8=False):
    """The dense family's decode step compiled for the described chip.
    -> (compiled, the pools' shape)"""
    from ray_tpu.models.paged import _paged_step
    from ray_tpu.ops.layers import rope_frequencies

    params = _on(one_chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    dims = (pages, page, cfg.n_kv_heads, cfg.head_dim)
    pools = [_shape(one_chip, dims, jnp.int8 if kv_int8 else cfg.dtype)
             ] * cfg.n_layers
    scales = [_shape(one_chip, dims[:-1], jnp.float32) if kv_int8 else 0
              ] * cfg.n_layers
    cos, sin = _on(one_chip, jax.eval_shape(
        lambda: rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta)))
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    return _paged_step.lower(
        params, pools, pools, scales, scales,
        i32((S, max_len // page)), i32((S,)), i32((S,)), f32((S,)),
        i32((S,)), f32((S,)), _shape(one_chip, (S, 2), jnp.uint32),
        cfg=cfg, cos=cos, sin=sin, page=page, kv_int8=kv_int8).compile(), dims


def test_paged_step_llama3_1b_widths(one_chip):
    """The serving decode step: 8 slots over 2048 pages of 16, depth 2."""
    cfg = dataclasses.replace(LLAMA3_1B, n_layers=2)
    S, pages, page, max_len = 8, 2048, 16, 2048
    compiled, dims = _compile_paged_step(one_chip, cfg, S, pages, page,
                                         max_len)
    rep = cfg.n_heads // cfg.n_kv_heads
    _assert_no_repeated_table(compiled, S, max_len, cfg.n_kv_heads, rep,
                              cfg.head_dim)
    # All that the step holds beyond its arguments (its temporaries, and
    # what it returns that aliases no argument) is smaller than TWO float32
    # copies of a layer's table repeated to every head: 45 MB against 268
    # at these widths (a step that repeats the table holds 404 MB). The
    # pools come back aliased to the donated arguments. A head of 64, half
    # a lane, makes the chip keep a pool with its pages as the minor axis,
    # here and not at a head of 128, and turn it whole for a step's scatter
    # and gather: the step indexes it a position a row
    # (``paged_ops._lane_rows``), so one turned copy a pool serves both and
    # goes when its layer's read ends (the table-wide read held 153 MB;
    # the blocked read over the 4-D pool 408, every copy to the program's
    # end, and 3.19 GB at depth 16 where this holds 0.08).
    m = compiled.memory_analysis()
    repeated = S * max_len * cfg.n_heads * cfg.head_dim * 4
    assert m.alias_size_in_bytes >= 2 * cfg.n_layers * math.prod(dims) * 2
    assert (m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes) < 2 * repeated
    _assert_vocabulary_sorted_under_a_conditional(compiled, S,
                                                  cfg.vocab_size)


def test_hybrid_step_and_prefill_nemotron_widths(one_chip):
    """The hybrid family's programs at the benchmark's widths and one period
    of its pattern (``MEM*E``), 32 slots: the decode step gives its pools
    and per-slot state back aliased to the donated arguments, multiplies the
    held experts where and as the tree stores them (no ``ragged-dot``
    kernel, no copy of a layer's 160 MB of ``w_up`` or ``w_down`` into
    another layout: 65 of a 76 ms step until PR 33) in under a sixth of the
    temporaries the grouped product took, and so does the prefill of a
    prompt padded to 256 or to ``max_len`` rows, beside its chunked scan."""
    from ray_tpu.models import nemotron_h as nh

    cfg = nh.NemotronHConfig(vocab_size=16384, pattern="MEM*E",
                             experts_held=16)
    S, pages, page, max_len = 32, 2048, 16, 1280
    params = _on(one_chip, jax.eval_shape(
        lambda: nh.init_params(cfg, jax.random.PRNGKey(0))))
    pools = [_shape(one_chip, (pages, page, cfg.n_kv_heads, cfg.head_dim))]
    ssm, conv = _on(one_chip, jax.eval_shape(lambda: nh.init_state(cfg, S)))
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    compiled = nh._hybrid_step.lower(
        params, pools, pools, [0], [0], ssm, conv,
        i32((S, max_len // page)), i32((S,)), i32((S,)), f32((S,)),
        i32((S,)), f32((S,)), _shape(one_chip, (S, 2), jnp.uint32),
        cfg=cfg, page=page, kv_int8=False).compile()
    m = compiled.memory_analysis()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pools, pools, ssm, conv)))
    assert m.alias_size_in_bytes >= donated
    _assert_no_repeated_table(compiled, S, max_len, cfg.n_kv_heads,
                              cfg.n_heads // cfg.n_kv_heads, cfg.head_dim)
    # nor every slot's whole table gathered (1.7 ms of the step until PR 44)
    assert not _table_wide(compiled.as_text(), S, max_len, page, cfg)
    _assert_vocabulary_sorted_under_a_conditional(compiled, S,
                                                  cfg.vocab_size)
    Eh, D, F = cfg.experts_held, cfg.d_model, cfg.expert_d_ff

    def held_weights_read_in_place(compiled):
        text = compiled.as_text()
        return "ragged-dot" not in text and not [
            ln for ln in text.splitlines() if " copy(" in ln
            and (f"= bf16[{Eh},{D},{F}]" in ln
                 or f"= bf16[{Eh},{F},{D}]" in ln)]

    assert held_weights_read_in_place(compiled)
    # the grouped product's two layers took 172 MB at this depth
    assert m.temp_size_in_bytes < 30e6
    for rows in (256, max_len):
        compiled = nh._hybrid_prefill.lower(
            params, i32((rows,)), 1, max_len, cfg, rows).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 2e9
        assert held_weights_read_in_place(compiled)


def test_sala_step_and_prefill_chunk_minicpm_sala_widths(one_chip):
    """The lightning / block-sparse family's programs at the benchmark's
    widths and four of its layers (``L M L L``), 8 slots of 704 pages: the
    decode step gives its pools, compressed-key pools and states back
    aliased to the donated arguments; it holds no copy of a whole pool (a
    gather of one K/V head's slices of the chosen pages made the compiler
    transpose every pool every step: PR 32) and no gather of every slot's
    whole table outside the dense branch's ``conditional``; the prefill
    chunk builds no array of chunk x ``max_len`` scores."""
    from ray_tpu.models import minicpm_sala as ms

    cfg = ms.MiniCPMSALAConfig(
        mixer_types=(ms.LIGHTNING, ms.SPARSE, ms.LIGHTNING, ms.LIGHTNING),
        layer_offset=8)
    S, pages, page, max_len = 8, 5120, 64, 45056
    params = _on(one_chip, jax.eval_shape(
        lambda: ms.init_params(cfg, jax.random.PRNGKey(0))))
    pools = [_shape(one_chip, (pages, page, cfg.n_kv_heads, cfg.head_dim))]
    pools_c = [_shape(one_chip, (pages, page // cfg.stride, cfg.n_kv_heads,
                                 cfg.head_dim))]
    states = _on(one_chip, jax.eval_shape(lambda: ms.init_state(cfg, S)))
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    compiled = ms._sala_step.lower(
        params, pools, pools, pools_c, states, i32((S, max_len // page)),
        i32((S,)), i32((S,)), f32((S,)), i32((S,)), f32((S,)),
        _shape(one_chip, (S, 2), jnp.uint32), cfg=cfg, page=page).compile()
    m = compiled.memory_analysis()
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pools, pools, pools_c, states)))
    assert m.alias_size_in_bytes >= donated
    text = compiled.as_text()
    pool = f"bf16[{pages},{page},{cfg.n_kv_heads},{cfg.head_dim}]"
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"= {pool}" in ln]
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    # the dense branch (a slot of at most dense_len positions) and the
    # sampler's sort are choices of the program, not paid by every step
    assert entry.count(" conditional(") == 2
    whole_table = f"[{S},{max_len},{cfg.n_kv_heads},{cfg.head_dim}]"
    assert whole_table not in text
    assert m.temp_size_in_bytes < 0.3e9
    carry = _on(one_chip, jax.eval_shape(
        lambda: ms.prefill_carry(cfg, max_len)))
    compiled = ms._sala_prefill_chunk.lower(
        params, i32((cfg.prefill_chunk,)), i32(()), i32(()), *carry,
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(carry))
    assert m.temp_size_in_bytes < 2e9
    assert f"{cfg.prefill_chunk},{max_len}]" not in compiled.as_text()


def test_longcat_step_and_prefill_chunk_longcat_flash_widths(one_chip,
                                                             monkeypatch):
    """The latent-attention / zero-expert family's programs at the
    benchmark's widths and one of its double layers, 16 slots of 288 pages:
    the decode step gives its pools back aliased to the donated arguments,
    holds no copy of a whole pool (a pool of one 576-wide row a position is
    kept with its pages minor and copied whole each way every step: PR 34;
    two positions a row are not) and never expands a cached latent row (no
    array of the gathered positions x heads x a per-head key or value); the
    prefill chunk builds no array of chunk x ``max_len`` scores and no
    expanded cache. The held experts' products, three an expert layer, are
    ``ragged-dot`` in the step (16 rows: 192 sorted pairs, no whole row
    tile) and the grouped-matmul kernel in the chunk (2048 sorted pairs), as
    ``grouped_product_form`` picks on the chip (the platform answered as
    one: a described device is not ``jax.devices()``'s)."""
    from perfbench.aot_longcat import expanded_shapes
    from ray_tpu.models import longcat_flash as lc
    from ray_tpu.models.paged_ops import latent_pass_shape, latent_pool_shape

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = lc.LongcatFlashConfig(vocab_size=16384, n_layers=1,
                                experts_held=16)
    S, pages, page, max_len = 16, 4096, 64, 18432
    params = _on(one_chip, jax.eval_shape(
        lambda: lc._seeded_params(cfg, jax.random.PRNGKey(0))))
    shape = latent_pool_shape(pages, page, cfg.latent_width)
    assert shape == (4096, 32, 1152)
    pools = [_shape(one_chip, shape)] * cfg.n_sublayers
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    compiled = lc._longcat_step.lower(
        params, pools, i32((S, max_len // page)), i32((S,)), i32((S,)),
        f32((S,)), i32((S,)), f32((S,)), _shape(one_chip, (S, 2), jnp.uint32),
        cfg=cfg, page=page).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    text = compiled.as_text()
    pool = "bf16[%d,%d,%d]" % shape
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"= {pool}" in ln]
    assert expanded_shapes(text, S * max_len, cfg) == []
    # the guard sees an expansion where there is one
    assert expanded_shapes(f"bf16[{S},{max_len},64,128]", S * max_len, cfg)
    # the held experts' products are grouped by expert (PR 34): three a layer
    assert _grouped_products(text) == (0, 3 * cfg.n_layers)
    # the absorbed read goes pass by pass over each slot's own blocks: the
    # gathered pages of a pass, and no array of a slot's whole table (eight
    # slots' tables at once were ``[2304, 32, 1152]`` until PR 61)
    block, items = latent_pass_shape(S, max_len // page, pools[0],
                                     2 * cfg.n_heads)
    assert f"bf16[{items * block},32,1152]" in text
    assert f"[{8 * max_len // page},32,1152]" not in text
    assert f"[{S},{max_len // 2},1152]" not in text
    assert f",{cfg.n_heads},{max_len // 2}]" not in text     # nor of scores
    assert m.temp_size_in_bytes < 0.15e9     # 0.238 until PR 61, 0.086 now
    carry = _on(one_chip, jax.eval_shape(
        lambda: lc.prefill_carry(cfg, max_len)))
    compiled = lc._longcat_prefill_chunk.lower(
        params, i32((cfg.prefill_chunk,)), i32(()), i32(()), carry,
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(carry))
    assert m.temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    assert f"{cfg.prefill_chunk},{max_len}]" not in text
    assert f"[{max_len},{cfg.n_heads}," not in text     # no expanded cache
    assert _chunk_form(cfg) == "kernel"
    assert _grouped_products(text) == (3 * cfg.n_layers, 0)


def test_cohere_step_and_prefill_chunk_command_a_plus_widths(one_chip,
                                                            monkeypatch):
    """The window / full attention family's programs at the benchmark's
    widths and one period of its layers (three window, one full), 32 slots of
    512 pages: the decode step gives every pool and every ring back aliased
    to the donated argument, copies none whole, and holds no array of
    gathered keys or values as wide as the table (32 x 32 768 positions
    would be 2.1 GB of keys and as much of values: the full layer is read in
    blocks of 16 table columns); the prefill chunk builds no array of chunk x
    ``max_len`` scores, gives its carried rows back aliased, multiplies
    its rows grouped by expert in the grouped-matmul kernel, three calls a
    layer, and attends in the flash kernel, one call a layer (the platform
    answered as the chip): no float32 accumulator ``[kvh 8, rep 16, 2048
    rows, 128]`` and no block of float32 scores of the loop form, which
    carried both through HBM at every key block (1.09 s of a traced 6 in the
    cell before PR 59), comes back."""
    from perfbench.aot_commanda import table_wide_shapes
    from ray_tpu.models.paged import _prompt_attn_form
    from ray_tpu.models import cohere2_moe as cm

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = cm.Cohere2MoeConfig(vocab_size=32768, n_layers=4, experts_held=16)
    S, pages, page, max_len = 32, 7168, 64, 32768
    params = _on(one_chip, jax.eval_shape(
        lambda: cm.init_params(cfg, jax.random.PRNGKey(0))))
    pool = _shape(one_chip, (pages, page, cfg.n_kv_heads, cfg.head_dim))
    ring = _shape(one_chip, (S, cfg.n_kv_heads, cfg.sliding_window,
                             cfg.head_dim))
    held = [[pool], [pool], [ring] * 3, [ring] * 3]
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    compiled = cm._cohere_step.lower(
        params, *held, i32((S, max_len // page)), i32((S,)), i32((S,)),
        f32((S,)), i32((S,)), f32((S,)), _shape(one_chip, (S, 2), jnp.uint32),
        cfg=cfg, page=page).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(held))
    text = compiled.as_text()
    for whole in ("bf16[%d,%d,%d,%d]" % pool.shape,
                  "bf16[%d,%d,%d,%d]" % ring.shape):
        assert not [ln for ln in text.splitlines()
                    if " copy(" in ln and f"= {whole}" in ln]
    assert table_wide_shapes(text, S, max_len, cfg) == []
    # the guard sees a table-wide gather where there is one
    assert table_wide_shapes(f"bf16[{S},{max_len},8,128]", S, max_len, cfg)
    assert _grouped_products(text) == (0, 0)    # 32 rows: every held expert
    assert m.temp_size_in_bytes < 1e9
    carry = _on(one_chip, jax.eval_shape(
        lambda: cm.prefill_carry(cfg, max_len)))
    compiled = cm._cohere_prefill_chunk.lower(
        params, i32((cfg.prefill_chunk,)), i32(()), i32(()), carry,
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(carry))
    assert m.temp_size_in_bytes < 0.8e9
    text = compiled.as_text()
    assert f"{cfg.prefill_chunk},{max_len}]" not in text
    # the held experts' products are grouped by expert at a chunk's rows
    assert _chunk_form(cfg) == "kernel"
    assert _grouped_products(text) == (3 * cfg.n_layers, 0)
    # a chunk's queries meet their keys in the flash kernel
    assert _prompt_attn_form(cfg, max_len) == "kernel"
    assert _chunk_attention_calls(text) == cfg.n_layers
    rows = f"{cfg.n_kv_heads},{cfg.n_heads // cfg.n_kv_heads},2048"
    for carried in (f"f32[{rows},{cfg.head_dim}]",
                    f"f32[{rows},{cfg.key_block}]", f"f32[{rows}]"):
        assert carried not in text, carried


def test_lfm2_step_and_prefill_chunk_lfm2_24b_a2b_widths(one_chip,
                                                         monkeypatch):
    """The gated-short-convolution / attention family's programs at the
    benchmark's widths and its first four layers (conv, conv, attention,
    conv: both dense layers, then two expert layers of all 64 experts), 64
    slots of 384 pages: the decode step gives every pool and conv tail back
    aliased to the donated argument and holds NO COPY AS WIDE AS A POOL (a
    head of 64 is half a lane: a 4-D pool of such heads is turned whole twice
    a step, this family's pools are ``lane_pool_shape``'s), no array as wide
    as the table, and no ``ragged-dot`` (a step's 64 rows take every expert
    on every row); on the chip (the platform answered as one: a described
    device is not ``jax.devices()``'s) the step reads its pools through the
    Pallas kernel ``ops.paged_decode.kv_decode``, whose buffers fit the
    scoped VMEM at these widths (Mosaic refuses what does not), and holds no
    gathered block list of the XLA read (``bf16[3072,16,512]``: 64 blocks of
    48 table columns) nor any array of its width; the prefill chunk groups
    its rows by expert, three calls of the grouped-matmul kernel an expert
    layer, and gives its carried rows back aliased."""
    from perfbench.aot_lfm2 import pool_wide_copies, table_wide_shapes
    from ray_tpu.models import lfm2_moe as lm
    from ray_tpu.models.paged_ops import lane_pool_shape
    from ray_tpu.ops import paged_decode

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)

    cfg = lm.Lfm2MoeConfig(n_layers=4)
    S, pages, page, max_len = 64, 24576, 16, 6144
    params = _on(one_chip, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    pool = _shape(one_chip, lane_pool_shape(pages, page, cfg.n_kv_heads,
                                            cfg.head_dim))
    tails = _on(one_chip, jax.eval_shape(lambda: lm.init_state(cfg, S)))
    held = [[pool], [pool], tails]
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    compiled = lm._lfm2_step.lower(
        params, *held, i32((S, max_len // page)), i32((S,)), i32((S,)),
        f32((S,)), i32((S,)), f32((S,)), _shape(one_chip, (S, 2), jnp.uint32),
        cfg=cfg, page=page).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(held))
    text = compiled.as_text()
    elems = math.prod(pool.shape)
    assert pool_wide_copies(text, elems) == []
    # the guard sees such a copy where there is one
    assert pool_wide_copies(
        "  %c = bf16[24576,16,512]{2,1,0} copy(%p)", elems)
    row = cfg.n_kv_heads * cfg.head_dim
    assert table_wide_shapes(text, S, max_len, row) == []
    assert table_wide_shapes(f"bf16[{S},{max_len},{row}]", S, max_len, row)
    assert _grouped_products(text) == (0, 0)
    assert m.temp_size_in_bytes < 0.5e9
    # one kernel an attention layer, and nothing as wide as the XLA read's
    # list of gathered blocks (64 items of 48 pages of [16, 512])
    assert text.count("tpu_custom_call") == cfg.n_attn_layers == 1
    assert "ray_tpu_kv_decode" in text
    gathered = [dims for dims in re.findall(r"bf16\[([0-9,]+)\]", text)
                if dims.endswith(f",{page},{row}")
                and S <= math.prod(map(int, dims.split(","))) // (page * row)
                < pages]
    assert gathered == [], gathered
    # a turn's blocks of both pools, KV_DEPTH times, are what the kernel
    # holds of VMEM beside the queries and the output: under the 16 MiB a
    # kernel may scope (Mosaic refused the compile above otherwise)
    ring = (2 * paged_decode.KV_DEPTH * paged_decode.KV_TURN * page * row * 2
            * paged_decode.kv_block_pages(max_len // page, page))
    assert ring + 3 * S * cfg.n_heads * row * 2 < 16 << 20
    carry = _on(one_chip, jax.eval_shape(
        lambda: lm.prefill_carry(cfg, max_len)))
    compiled = lm._lfm2_prefill_chunk.lower(
        params, i32((cfg.prefill_chunk,)), i32(()), i32(()), *carry,
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(carry))
    assert m.temp_size_in_bytes < 0.5e9     # no chunk x max_len scores
    # the held experts' products are grouped by expert at a chunk's rows
    assert _chunk_form(cfg) == "kernel"
    assert _grouped_products(compiled.as_text()) == (3 * cfg.n_moe_layers, 0)
    # heads of 64 are half a lane: its chunk's attention stays the loop
    from ray_tpu.models.paged import _prompt_attn_form
    assert _prompt_attn_form(cfg, max_len) == "loop"
    assert _chunk_attention_calls(compiled.as_text()) == 0


def test_granite_step_and_prefill_chunk_granite_4_0_h_small_widths(
        one_chip, monkeypatch):
    """The Granite-MoE-hybrid family's programs at the benchmark's widths and
    layers 4-6 of its period (mamba, attention, mamba; each with 36 of the 72
    experts and the shared expert), 64 slots of 640 pages: the decode step
    gives the pool, every SSM state (float32, 268 MB a layer) and every tail
    back aliased to the donated argument, holds no array as wide as the table
    and no ``ragged-dot`` (a step's 64 rows take every held expert on every
    row), and its temporaries stay far under one layer's state (a copied
    state would be 0.27 GB); the prefill chunk gives its carried K/V rows,
    SSM states and tails back aliased, groups its rows by expert (three
    calls of the grouped-matmul kernel a layer, the platform answered as the
    chip), attends in the flash kernel (one call an attention layer, no
    float32 accumulator of the loop form), and its temporaries (the SSD form's float32 blocks at 2048 positions x 128 heads)
    stay under the 1.3 GB the engine leaves beside its resident 13.6 GB."""
    from perfbench.aot_lfm2 import table_wide_shapes
    from ray_tpu.models import granite_moe_hybrid as gm
    from ray_tpu.models.paged import _prompt_attn_form

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = gm.GraniteMoeHybridConfig(
        n_layers=3, layer_types=("mamba", "attention", "mamba"),
        experts_held=36, vocab_size=50176)
    S, pages, page, max_len = 64, 24576, 16, 10240
    params = _on(one_chip, jax.eval_shape(
        lambda: gm.init_params(cfg, jax.random.PRNGKey(0))))
    pool = _shape(one_chip, (pages, page, cfg.n_kv_heads, cfg.head_dim))
    ssm, tails = _on(one_chip, jax.eval_shape(
        lambda: gm.init_state(cfg, S)))
    assert ssm[0].dtype == jnp.float32 and tails[0].dtype == jnp.bfloat16
    held = [[pool], [pool], ssm, tails]
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    compiled = gm._granite_step.lower(
        params, *held, i32((S, max_len // page)), i32((S,)), i32((S,)),
        f32((S,)), i32((S,)), f32((S,)), _shape(one_chip, (S, 2), jnp.uint32),
        cfg=cfg, page=page).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(held))
    text = compiled.as_text()
    row = cfg.n_kv_heads * cfg.head_dim
    assert table_wide_shapes(text, S, max_len, row) == []
    assert _grouped_products(text) == (0, 0)
    assert m.temp_size_in_bytes < 0.2e9
    carry = _on(one_chip, jax.eval_shape(
        lambda: gm.prefill_carry(cfg, max_len)))
    compiled = gm._granite_prefill_chunk.lower(
        params, i32((cfg.prefill_chunk,)), i32(()), i32(()), *carry,
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(carry))
    assert m.temp_size_in_bytes < 1.3e9
    text = compiled.as_text()
    assert f"{cfg.prefill_chunk},{max_len}]" not in text    # no L x T scores
    assert _chunk_form(cfg) == "kernel"
    assert _grouped_products(text) == (3 * cfg.n_layers, 0)
    assert _prompt_attn_form(cfg, max_len) == "kernel"
    assert _chunk_attention_calls(text) == cfg.n_attn_layers == 1
    assert f"f32[{cfg.n_kv_heads},{cfg.n_heads // cfg.n_kv_heads},2048," \
        not in text


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_paged_step_writes_the_pools_in_place(one_chip, kv_int8):
    """The dense family's step at the benchmark's widths (Mistral-7B's; 16
    slots, pages of 16 x 8 x 128), depth 1: every pool, and every scale of
    int8 pages, comes back aliased to the donated argument, and the program
    copies no pool whole (un-donated it copied each one for the one row a
    slot writes: 32 copies of 67 MB a step at the benchmark's depth and
    2048 pages). 4096 pages here, so that a pool's shape is not the shape
    of the 16 slots' gathered tables, 16 x 128 pages."""
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=32768, n_layers=1, rope_theta=1e6)
    S, pages, page, max_len = 16, 4096, 16, 2048
    compiled, dims = _compile_paged_step(one_chip, cfg, S, pages, page,
                                         max_len, kv_int8)
    m = compiled.memory_analysis()
    held = 2 * cfg.n_layers * pages * page * cfg.n_kv_heads * (
        cfg.head_dim + 4 if kv_int8 else 2 * cfg.head_dim)
    # beside the pools the step returns the slots' tokens and keys
    assert held <= m.alias_size_in_bytes <= m.output_size_in_bytes \
        < held + 65536
    pool = "[" + ",".join(map(str, dims)) + "]"
    text = compiled.as_text()
    assert not [ln for ln in text.splitlines()
                if (" copy(" in ln or " copy-start(" in ln)
                and pool in ln.split(" copy", 1)[0]]
    # and it gathers no slot's whole table, in either spelling of its shape
    # (3.6 ms of a 15.4 ms step until PR 44): the read goes block by block
    assert not _table_wide(text, S, max_len, page, cfg)
    assert _table_wide(f"bf16[{S * max_len // page},{page},8,128]", S,
                       max_len, page, cfg)      # the guard sees one


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_scatter_pages_writes_the_pools_in_place(one_chip, kv_int8):
    """The admission's one scatter at the benchmark's widths (16 layers of
    2048 pages x 16 x 8 x 128): every pool and scale comes back aliased to
    the donated argument, so an admission holds no second copy of them."""
    from ray_tpu.models.paged import _scatter_pages

    L, pages, page, kvh, d, max_len = 16, 2048, 16, 8, 128, 2048
    pool = _shape(one_chip, (pages, page, kvh, d),
                  jnp.int8 if kv_int8 else jnp.bfloat16)
    scale = _shape(one_chip, (pages, page, kvh), jnp.float32) \
        if kv_int8 else None
    dense = _shape(one_chip, (max_len, kvh, d))
    compiled = _scatter_pages.lower(
        [pool] * L, [pool] * L, [scale] * L, [scale] * L,
        [(dense, dense)] * L, _shape(one_chip, (max_len // page,), jnp.int32),
        _shape(one_chip, (), jnp.float32), page=page,
        kv_int8=kv_int8).compile()
    m = compiled.memory_analysis()
    held = 2 * L * pages * page * kvh * (d + 4 if kv_int8 else 2 * d)
    assert held <= m.alias_size_in_bytes <= m.output_size_in_bytes < held + 4096
    assert m.temp_size_in_bytes < 0.1e9


def test_train_step_llama3_1b_widths_takes_flash(one_chip, monkeypatch):
    """One AdamW step at 2x2048, depth 2. ``flash_attention`` asks jax for
    the platform, which here is the CPU: the test answers for the chip the
    program is compiled for, and the kernel must then be in the step."""
    import optax

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(LLAMA3_1B, n_layers=2)
    opt = optax.adamw(3e-4, weight_decay=0.1)

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        _on(one_chip, params), _on(one_chip, opt_state),
        _shape(one_chip, (2, 2048), jnp.int32)).compile()
    _assert_kernel(compiled)
