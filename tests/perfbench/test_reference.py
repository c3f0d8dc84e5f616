"""The plain reference against ``models/llama.forward`` at a toy size in
float32: two independent implementations of one decoder agree."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, forward, init_params, loss_fn
from ray_tpu.ops.attention import dense_attention

from perfbench.reference import decoder

SHAPE = {"hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 3,
         "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
         "vocab_size": 97, "rope_theta": 1000000.0, "rms_norm_eps": 1e-5}


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=97, d_model=64, n_layers=3, n_heads=8,
                      n_kv_heads=2, d_ff=160, rope_theta=1e6, norm_eps=1e-5,
                      dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(3))
    # norms are stored as offsets from one: make them matter
    params["norm"] = params["norm"] + 0.25
    for i, layer in enumerate(params["layers"]):
        layer["attn_norm"] = layer["attn_norm"] - 0.1 * (i + 1)
    tokens = np.random.default_rng(0).integers(0, 97, (2, 33), dtype=np.int32)
    return cfg, params, tokens


def test_logits_agree(model):
    cfg, params, tokens = model
    with jax.default_matmul_precision("highest"):
        want = forward(params, jnp.asarray(tokens), cfg,
                       attn_impl=dense_attention, remat=False)
    weights = decoder.from_program_tree(params)
    for row in range(2):
        got = decoder.logits(weights, tokens[row], SHAPE)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[row]),
                                   atol=2e-4, rtol=2e-4)


def test_loss_agrees(model):
    cfg, params, tokens = model
    with jax.default_matmul_precision("highest"):
        want = float(loss_fn(params, {"tokens": jnp.asarray(tokens)}, cfg,
                             attn_impl=dense_attention, remat=False))
    got = float(decoder.next_token_loss(decoder.from_program_tree(params),
                                        tokens, SHAPE))
    assert got == pytest.approx(want, abs=2e-5)


def test_reference_is_causal(model):
    _, params, tokens = model
    weights = decoder.from_program_tree(params)
    a = decoder.logits(weights, tokens[0], SHAPE)
    changed = tokens[0].copy()
    changed[20:] = (changed[20:] + 1) % 97
    b = decoder.logits(weights, changed, SHAPE)
    np.testing.assert_allclose(np.asarray(a[:20]), np.asarray(b[:20]),
                               atol=1e-6)
    assert not np.allclose(np.asarray(a[20:]), np.asarray(b[20:]))
