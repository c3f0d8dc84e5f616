"""Expert parallelism (MoE over the ``ep`` axis) + Mixtral model tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import MIXTRAL_DEBUG, MixtralConfig, mixtral, mixtral_shardings
from ray_tpu.parallel import (
    MeshSpec,
    make_ep_moe_ffn,
    make_mesh,
    moe_ffn_dense,
)
from ray_tpu.parallel.moe import default_capacity, ep_moe_ffn


def _moe_weights(key, E, D, F, dtype=jnp.float32):
    k = jax.random.split(key, 4)
    router = jax.random.normal(k[0], (D, E)) * 0.5
    experts = {
        "w_gate": jax.random.normal(k[1], (E, D, F), dtype) * 0.2,
        "w_up": jax.random.normal(k[2], (E, D, F), dtype) * 0.2,
        "w_down": jax.random.normal(k[3], (E, F, D), dtype) * 0.2,
    }
    return router, experts


def test_dense_moe_topk_full_equals_weighted_sum():
    """k=E dense MoE == softmax-weighted sum of all experts."""
    E, D, F = 4, 8, 16
    router, experts = _moe_weights(jax.random.PRNGKey(0), E, D, F)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, D))
    out, aux = moe_ffn_dense(x, router, experts, k=E)
    probs = jax.nn.softmax(
        x.astype(jnp.float32) @ router)  # [B,L,E]
    ys = []
    for e in range(E):
        g = x @ experts["w_gate"][e]
        u = x @ experts["w_up"][e]
        ys.append((jax.nn.silu(g) * u) @ experts["w_down"][e])
    expect = sum(probs[..., e:e + 1] * ys[e] for e in range(E))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


@pytest.mark.parametrize("spec", [MeshSpec(ep=4, dp=2),
                                  MeshSpec(ep=2, tp=2, dp=2),
                                  MeshSpec(ep=8)])
def test_ep_moe_matches_dense(cpu_mesh8, spec):
    """Expert-parallel dispatch == dense oracle when nothing is dropped."""
    E, D, F = 8, 16, 32
    mesh = make_mesh(spec, devices=cpu_mesh8)
    router, experts = _moe_weights(jax.random.PRNGKey(0), E, D, F)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, D))

    ref, ref_aux = moe_ffn_dense(x, router, experts, k=2)
    ep_fn = make_ep_moe_ffn(mesh, k=2, capacity_factor=8.0)
    got, got_aux = jax.jit(ep_fn)(x, router, experts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    # aux is computed per token-shard then averaged (GShard convention),
    # which differs from the global-batch statistic — just sanity-check it.
    assert np.isfinite(float(got_aux)) and float(got_aux) > 0


def test_ep_moe_capacity_drops_are_finite(cpu_mesh8):
    """Tiny capacity drops tokens but never produces NaN/inf."""
    E, D, F = 4, 8, 16
    mesh = make_mesh(MeshSpec(ep=4, dp=2), devices=cpu_mesh8)
    router, experts = _moe_weights(jax.random.PRNGKey(0), E, D, F)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, D))
    ep_fn = make_ep_moe_ffn(mesh, k=2, capacity_factor=0.1)
    out, aux = jax.jit(ep_fn)(x, router, experts)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(float(aux))


def test_default_capacity():
    assert default_capacity(16, 8, 2, 2.0) == 8  # cf*T_local*k/E
    assert default_capacity(1, 64, 1, 1.0) == 1  # floor at k


def test_mixtral_forward_and_loss():
    cfg = MIXTRAL_DEBUG
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits, aux = mixtral.forward(params, tokens, cfg, remat=False)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = mixtral.loss_fn(params, {"tokens": tokens}, cfg, remat=False)
    assert np.isfinite(float(loss))
    grads = jax.grad(
        lambda p: mixtral.loss_fn(p, {"tokens": tokens}, cfg,
                                  remat=False))(params)
    g = grads["layers"][0]["experts"]["w_gate"]
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0  # router gradient flows to experts


def test_mixtral_ep_training_step(cpu_mesh8):
    """Sharded Mixtral train step: ep x tp x dp mesh, loss decreases."""
    import optax

    cfg = MixtralConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=64, max_seq_len=64, n_experts=4,
                        top_k=2, dtype=jnp.float32)
    mesh = make_mesh(MeshSpec(ep=2, tp=2, dp=2), devices=cpu_mesh8)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    sh = mixtral_shardings(params, mesh)
    params = jax.tree.map(jax.device_put, params, sh)
    moe_ffn = make_ep_moe_ffn(mesh, k=cfg.top_k, capacity_factor=4.0)

    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: mixtral.loss_fn(p, batch, cfg, remat=False,
                                      moe_ffn=moe_ffn))(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, {"tokens": tokens})
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_mixtral_shardings_specs(cpu_mesh8):
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshSpec(ep=2, tp=2, fsdp=2), devices=cpu_mesh8)
    cfg = MixtralConfig(vocab_size=128, d_model=32, n_layers=1, n_heads=4,
                        n_kv_heads=2, d_ff=64, max_seq_len=64, n_experts=4,
                        top_k=2, dtype=jnp.float32)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    sh = mixtral_shardings(params, mesh)
    assert sh["layers"][0]["experts"]["w_gate"].spec == P("ep", "fsdp", "tp")
    assert sh["layers"][0]["experts"]["w_down"].spec == P("ep", "tp", "fsdp")
    assert sh["layers"][0]["wq"].spec == P("fsdp", "tp")


# ------------------------------------------------- the sigmoid router's groups
def _router_case(seed, T=50, D=24, E=32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(T, D), jnp.float32),
            jnp.asarray(rng.randn(D, E) / 3, jnp.float32),
            jnp.asarray(rng.randn(E) * 0.1, jnp.float32))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("norm", [True, False], ids=["norm", "plain"])
def test_sigmoid_gates_with_one_group_are_the_router_without_groups(seed,
                                                                    norm):
    """``n_group`` 1 (the default, the hybrid family's router) is the top k of
    score + bias over all outputs, as before the groups: written out."""
    from ray_tpu.parallel.moe import sigmoid_gates

    x, w, b = _router_case(seed)
    vals, idx = sigmoid_gates(x, w, b, 4, 2.5, norm=norm)
    same = sigmoid_gates(x, w, b, 4, 2.5, norm=norm, n_group=1, topk_group=1)
    np.testing.assert_array_equal(idx, same[1])
    np.testing.assert_array_equal(vals, same[0])
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(w))))
    want = np.argsort(-(s + np.asarray(b)), axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(idx, want)
    g = np.take_along_axis(s, want, -1)
    if norm:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    np.testing.assert_allclose(vals, 2.5 * g, rtol=2e-6)
    # all groups kept is no limit either
    every = sigmoid_gates(x, w, b, 4, 2.5, norm=norm, n_group=4, topk_group=4)
    np.testing.assert_array_equal(idx, every[1])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_group, topk_group, k", [(8, 4, 8), (4, 2, 3),
                                                    (4, 1, 5)])
def test_group_limited_routing_is_the_written_out_loop(seed, n_group,
                                                       topk_group, k):
    from ray_tpu.parallel.moe import sigmoid_gates

    x, w, b = _router_case(seed)
    vals, idx = sigmoid_gates(x, w, b, k, 2.5, n_group=n_group,
                              topk_group=topk_group)
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(w))))
    size = w.shape[1] // n_group
    for t in range(x.shape[0]):
        biased = s[t] + np.asarray(b)
        score = [np.sort(biased[g * size:(g + 1) * size])[-2:].sum()
                 for g in range(n_group)]
        kept = np.argsort(-np.asarray(score), kind="stable")[:topk_group]
        masked = np.zeros_like(biased)
        for g in kept:
            masked[g * size:(g + 1) * size] = biased[g * size:(g + 1) * size]
        want = np.argsort(-masked, kind="stable")[:k]
        assert sorted(np.asarray(idx[t])) == sorted(want), t
        assert {int(e) // size for e in np.asarray(idx[t])} <= set(kept)
        gate = s[t][np.asarray(idx[t])]
        np.testing.assert_allclose(vals[t], 2.5 * gate / (gate.sum() + 1e-20),
                                   rtol=1e-5)
