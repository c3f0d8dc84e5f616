"""The DeepSeek-V3 family's programs compiled for a described TPU v5e, as
``test_tpu_compile.py`` compiles the other families' (its fixture and
helpers). A file of its own: ``--dist loadfile`` gives a file to one worker,
and that file's ten families' worth of compiles is the run's longest already.
"""

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention
from test_tpu_compile import (_chunk_form, _grouped_products, _on, _shape,
                              one_chip)  # noqa: F401 (the fixture)


def test_deepseek_step_and_prefill_chunk_gigachat_widths(one_chip,
                                                         monkeypatch):
    """The DeepSeek-V3 family's programs at the benchmark's widths, one dense
    and one expert layer with the MTP module, 64 slots of 224 pages: the
    two-row step gives every pool and the drafts' distributions back aliased
    to the donated arguments, holds no copy of a whole pool, never expands a
    cached latent row per head, and gathers its slots' pages a block of slots
    at a time (1.7 GB of temporaries for all 64 at once: AOT, PR 60); its 128
    rows meet every held expert (no grouped product). The prefill chunk, the
    MTP block one token on, builds no array of chunk x ``max_len`` scores and
    no expanded cache, and its experts' products are the grouped-matmul
    kernel, as the chip picks. The first draft's one row donates the MTP
    block's pool and both per-slot arrays."""
    from perfbench.aot_longcat import expanded_shapes
    from ray_tpu.models import deepseek_v3 as ds
    from ray_tpu.models.longcat_flash import prefill_carry
    from ray_tpu.models.paged_ops import latent_pass_shape, latent_pool_shape

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = ds.DeepseekV3Config(vocab_size=16032, n_layers=2, n_dense=1,
                              experts_held=16)
    S, pages, page, max_len = 64, 6656, 64, 14336
    params = _on(one_chip, jax.eval_shape(
        lambda: ds._seeded_params(cfg, jax.random.PRNGKey(0))))
    shape = latent_pool_shape(pages, page, cfg.latent_width)
    assert shape == (6656, 32, 1152) and cfg.n_sublayers == 3
    pools = [_shape(one_chip, shape)] * cfg.n_sublayers
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    draft_q = f32((S, cfg.vocab_size))
    compiled = ds._deepseek_step.lower(
        params, pools, i32((S, max_len // page)), i32((S,)), i32((S,)),
        f32((S,)), i32((S,)), f32((S,)), _shape(one_chip, (S, 2), jnp.uint32),
        draft_q, i32((S,)), cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves([pools, draft_q]))
    text = compiled.as_text()
    pool = "bf16[%d,%d,%d]" % shape
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"= {pool}" in ln]
    assert expanded_shapes(text, S * max_len, cfg) == []
    assert _grouped_products(text) == (0, 0)
    # the absorbed read goes pass by pass over each slot's own blocks: the
    # gathered pages of a pass, and no array of a slot's whole table (eight
    # slots' tables at once were ``[1792, 32, 1152]`` until PR 61)
    block, items = latent_pass_shape(S, max_len // page, pools[0],
                                     2 * 2 * cfg.n_heads)
    assert f"bf16[{items * block},32,1152]" in text
    assert f"[{8 * max_len // page},32,1152]" not in text
    assert f"bf16[{S},{max_len // 2},1152]" not in text
    assert f",{cfg.n_heads},{max_len // 2}]" not in text     # nor of scores
    assert m.temp_size_in_bytes < 0.1e9     # 0.196 until PR 61, 0.053 now
    carry = _on(one_chip, jax.eval_shape(lambda: prefill_carry(cfg, max_len)))
    compiled = ds._deepseek_prefill_chunk.lower(
        params, i32((cfg.prefill_chunk,)), i32(()), i32(()), carry,
        i32((max_len + 1,)), cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(carry))
    assert m.temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    # (``[chunk, max_len]`` alone is the MTP block's input here: 2 x 7168)
    assert f"{cfg.n_heads},{cfg.prefill_chunk},{max_len}]" not in text
    assert f"[{max_len},{cfg.n_heads}," not in text     # no expanded cache
    assert _chunk_form(cfg) == "kernel"
    # one expert layer's three; the MTP block's are not in the program: its
    # cache rows come from its INPUT alone, and nothing the chunk returns
    # reads its attention's output or its experts'
    assert _grouped_products(text) == (3, 0)
    compiled = ds._deepseek_first_draft.lower(
        params, pools[-1], i32((1, max_len // page)),
        _shape(one_chip, (cfg.d_model,)), i32(()), i32(()), f32(()), i32(()),
        f32(()), i32((S,)), draft_q, _shape(one_chip, (2,), jnp.uint32),
        i32(()), cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves([pools[-1], draft_q]))
    assert m.temp_size_in_bytes < 0.1e9
