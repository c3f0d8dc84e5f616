"""The sharded training step's layout (PR 53): a mesh-bound attention makes
``llama.forward_hidden`` hold its residual stream to the batch axes, and
GSPMD then partitions the step as ZeRO-3 x Megatron. What the compiled step
holds is counted here on the CPU's partitioner, four to eight host devices,
at the train configuration's rehearsal shape: the same difference the chip's
compiler shows at full size (PERF.md section 6, PR 53)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.llama import LLAMA_DEBUG, init_params, loss_fn
from ray_tpu.ops.attention import make_flash_attention
from ray_tpu.parallel.mesh import MeshSpec, batch_sharding, make_mesh
from ray_tpu.parallel.sharding import apply_shardings, shardings_for_tree

from perfbench import program, trainloop
from perfbench.manifest import Manifest

CONFIG = "mistral-7b-v0.3-train4"

#: ``<kind>`` and the shapes of its result, from one line of compiled HLO
_COLLECTIVE = re.compile(
    r"= (\(?[a-z0-9]+\[[^=]*?) (all-to-all|all-reduce|all-gather)"
    r"(?:-start)?\(")


@functools.lru_cache(maxsize=None)
def _collectives(mesh_items, depth):
    """``(kind, [dims of each result])`` of every collective operation in the
    compiled sharded AdamW step, and ``(shape, batch, seq)`` it was compiled
    at."""
    man = Manifest()
    config = man.config(CONFIG)
    sizes = dict(mesh_items)
    n = int(np.prod(list(sizes.values())))
    mesh = make_mesh(MeshSpec(**sizes), jax.devices("cpu")[:n])
    # the rehearsal's two K/V heads do not split four ways
    shape = {**program.shape_of(config, True), "num_hidden_layers": depth,
             "num_key_value_heads": 4}
    cfg = program.model_config(config, shape)
    opt = optax.adamw(**config["optimizer"]["adamw"])
    params, param_sh, opt_state, opt_sh = trainloop.abstract_state(
        config, cfg, mesh, opt)

    def place(tree, sh):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, sh)

    mix = man.traffic("fixed-2x4096")
    # one row a data shard, as the cell has
    batch = max(2, n // sizes.get("tp", 1))
    seq = mix["rehearsal"]["seq_len"]
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=batch_sharding(mesh))
    compiled, _ = trainloop.compile_step(
        config, cfg, opt, mesh, place(params, param_sh),
        place(opt_state, opt_sh), tokens, param_sh, opt_sh)
    found = []
    for line in compiled.as_text().splitlines():
        m = _COLLECTIVE.search(line)
        if m:
            found.append((m.group(2), [
                tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\[([0-9,]*)\]", m.group(1))]))
    return found, shape, batch, seq


FSDP2_TP2 = (("fsdp", 2), ("tp", 2))


@pytest.mark.parametrize("mesh_items", [
    FSDP2_TP2, (("fsdp", 4),), (("tp", 4),), (("dp", 2), ("fsdp", 2))],
    ids=lambda items: ",".join(f"{a}={n}" for a, n in items))
def test_all_to_alls_do_not_grow_with_depth(cpu_mesh8, mesh_items):
    """Unpinned, GSPMD moved the stream between a ``d_model`` split and a
    batch split around every norm and every attention: ~12 all-to-alls a
    layer. Pinned, what is left sits at the embedding and the head."""
    count = {depth: sum(kind == "all-to-all" for kind, _ in
                        _collectives(mesh_items, depth)[0])
             for depth in (2, 4)}
    assert count[2] == count[4], count
    assert count[4] <= 2, count


def test_no_collective_holds_the_whole_batch(cpu_mesh8):
    """Partial sums over a split ``d_model`` were all-reduced at the WHOLE
    batch (``[2, L, ...]`` where FSDP holds one row a chip)."""
    found, _, batch, seq = _collectives(FSDP2_TP2, 2)
    whole = [(kind, dims) for kind, results in found for dims in results
             if kind != "all-gather" and dims[:2] == (batch, seq)]
    assert not whole, whole
    # the stream's own all-reduces are there, one data shard's rows each
    assert any(kind == "all-reduce" and dims[:2] == (batch // 2, seq)
               for kind, results in found for dims in results), found


def test_weights_are_gathered_over_fsdp(cpu_mesh8):
    """ZeRO-3: a layer's weights are all-gathered over ``fsdp`` where they
    are used (``w_gate`` / ``w_up`` come out ``[d_model, d_ff / tp]``)."""
    found, shape, _, _ = _collectives(FSDP2_TP2, 2)
    want = (shape["hidden_size"], shape["intermediate_size"] // 2)
    assert any(kind == "all-gather" and want in results
               for kind, results in found), (want, found)


# ------------------------------------------------------------------ parity

def _tokens(cfg, batch=4, seq=32):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                              cfg.vocab_size)


def test_pinned_loss_and_gradients_equal_one_device(cpu_mesh8):
    """Only which chip holds which slice changes: float32, same products."""
    cfg = LLAMA_DEBUG
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = _tokens(cfg)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, t: loss_fn(p, {"tokens": t}, cfg)))(params, tokens)

    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), cpu_mesh8[:4])
    attn = make_flash_attention(mesh)
    assert attn.mesh is mesh
    sharded = apply_shardings(params, shardings_for_tree(params, mesh))
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p, t: loss_fn(p, {"tokens": t}, cfg, attn_impl=attn)))(
        sharded, jax.device_put(tokens, batch_sharding(mesh)))
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-4)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-6), got, want)


def _constraints(attn_impl):
    cfg = LLAMA_DEBUG
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    text = jax.jit(jax.grad(lambda p, t: loss_fn(
        p, {"tokens": t}, cfg, attn_impl=attn_impl))).lower(
        params, _tokens(cfg)).as_text()
    return len(re.findall(r"sharding_constraint|@Sharding", text))


def test_only_a_mesh_bound_attention_pins_the_stream(cpu_mesh8):
    """With ``attn_impl=None`` or a plain function the traced program holds
    no sharding constraint: one chip's program is what it was."""
    from ray_tpu.ops.attention import dense_attention

    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), cpu_mesh8[:4])
    assert _constraints(None) == 0
    assert _constraints(dense_attention) == 0
    assert _constraints(make_flash_attention(mesh)) > 0
