"""Pipeline parallelism (SPMD GPipe over the ``pp`` axis) tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models import LlamaConfig, init_params, loss_fn
from jax import shard_map
from ray_tpu.parallel import (
    MeshSpec,
    make_mesh,
    make_pipelined_loss,
    make_stage_fn,
    pipeline_shardings,
    shardings_for_tree,
    spmd_pipeline,
    stack_layers,
    to_pipeline_params,
    unstack_layers,
)


def test_stack_unstack_roundtrip():
    layers = [{"w": jnp.ones((2, 2)) * i, "b": jnp.zeros((2,))}
              for i in range(4)]
    stacked = stack_layers(layers)
    assert stacked["w"].shape == (4, 2, 2)
    back = unstack_layers(stacked)
    np.testing.assert_allclose(back[2]["w"], layers[2]["w"])


def test_spmd_pipeline_linear_stages(cpu_mesh8):
    """4-stage pipeline of y = x @ w against sequential application."""
    mesh = make_mesh(MeshSpec(pp=4, dp=2), devices=cpu_mesh8)
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (8, 16, 16)) * 0.3  # 8 layers, 2/stage
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

    def layer_fn(w, x):
        return jnp.tanh(x @ w)

    stage_fn = make_stage_fn(layer_fn, remat=False)

    def run(ws_local, x):
        mb = x.reshape(4, 1, 16)
        out = spmd_pipeline(stage_fn, ws_local, mb)
        return out.reshape(4, 16)

    out = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False))(ws, x)

    expect = x
    for i in range(8):
        expect = jnp.tanh(expect @ ws[i])
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("spec", [MeshSpec(pp=4, dp=2, fsdp=-1),
                                  MeshSpec(pp=2, dp=2, fsdp=-1),
                                  MeshSpec(pp=2, tp=2, dp=2, fsdp=-1),
                                  MeshSpec(pp=2, tp=4, fsdp=-1)])
def test_pipelined_llama_loss_matches_plain(cpu_mesh8, spec):
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=4, n_heads=4,
                      n_kv_heads=4, d_ff=64, max_seq_len=64,
                      dtype=jnp.float32)
    mesh = make_mesh(spec, devices=cpu_mesh8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    ref = loss_fn(params, {"tokens": tokens}, cfg, remat=False)

    pparams = to_pipeline_params(params)
    sh = {k: shardings_for_tree(v, mesh) for k, v in pparams.items()
          if k != "stacked"}
    sh["stacked"] = pipeline_shardings(pparams["stacked"], mesh)
    pparams = jax.tree.map(jax.device_put, pparams, sh)

    ploss = make_pipelined_loss(mesh, cfg, n_microbatches=2, remat=False)
    got = jax.jit(ploss)(pparams, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_pipelined_llama_grads(cpu_mesh8):
    """Backward through the pipeline (autodiff of scan+ppermute) is exact."""
    cfg = LlamaConfig(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                      n_kv_heads=1, d_ff=32, max_seq_len=32,
                      dtype=jnp.float32)
    mesh = make_mesh(MeshSpec(pp=2, dp=2, fsdp=2), devices=cpu_mesh8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0,
                                cfg.vocab_size)

    ref_grads = jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg, remat=False))(params)

    pparams = to_pipeline_params(params)
    ploss = make_pipelined_loss(mesh, cfg, n_microbatches=2, remat=False)
    got_grads = jax.jit(jax.grad(
        lambda p: ploss(p, {"tokens": tokens})))(pparams)

    ref_stacked = stack_layers(ref_grads["layers"])
    np.testing.assert_allclose(np.asarray(got_grads["stacked"]["wq"]),
                               np.asarray(ref_stacked["wq"]),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_grads["embedding"]),
                               np.asarray(ref_grads["embedding"]),
                               rtol=1e-3, atol=1e-4)


def test_pipeline_shardings_specs(cpu_mesh8):
    mesh = make_mesh(MeshSpec(pp=2, tp=2, fsdp=2), devices=cpu_mesh8)
    cfg = LlamaConfig(vocab_size=64, d_model=16, n_layers=4, n_heads=2,
                      n_kv_heads=1, d_ff=32, max_seq_len=32,
                      dtype=jnp.float32)
    stacked = stack_layers(init_params(cfg, jax.random.PRNGKey(0))["layers"])
    sh = pipeline_shardings(stacked, mesh)
    assert sh["wq"].spec == P("pp", "fsdp", "tp")
    assert sh["attn_norm"].spec == P("pp")
