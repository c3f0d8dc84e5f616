"""Weight-only int8 quantization (ops/quant.py): parity on the Llama
forward/decode paths + the byte-halving that doubles decode bandwidth
headroom (vLLM-style weight-only quant, framework-native here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, generate_greedy, init_params
from ray_tpu.models.llama import forward
from ray_tpu.ops.quant import (Q8, mm, quantize_array, quantize_params,
                               quantized_nbytes)


@pytest.fixture(scope="module")
def small():
    cfg = LlamaConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=64,
                      dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_quantize_array_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    q = quantize_array(w)
    assert q.w.dtype == jnp.int8
    deq = q.w.astype(jnp.float32) * q.s
    # per-channel symmetric int8: worst-case error ~ amax/127 per column
    col_amax = np.abs(np.asarray(w)).max(axis=0)
    assert np.all(np.abs(np.asarray(deq - w)) <= col_amax / 127 + 1e-7)


def test_mm_dispatch():
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (32, 8), jnp.float32)
    dense = mm(x, w)
    quant = mm(x, quantize_array(w))
    assert np.allclose(np.asarray(dense), np.asarray(x @ w), atol=1e-5)
    rel = np.abs(np.asarray(quant - dense)).max() / \
        np.abs(np.asarray(dense)).max()
    assert rel < 0.02  # int8 per-channel keeps ~2 decimal digits


def test_quantized_forward_parity(small):
    cfg, params = small
    qparams = quantize_params(params)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0,
                                cfg.vocab_size)
    full = forward(params, tokens, cfg, remat=False)
    quant = forward(qparams, tokens, cfg, remat=False)
    # logits track closely; argmax rarely flips on random weights
    rel = float(jnp.abs(quant - full).mean() / jnp.abs(full).mean())
    assert rel < 0.1, rel
    agree = float((jnp.argmax(quant, -1) == jnp.argmax(full, -1)).mean())
    assert agree > 0.9, agree


def test_quantized_decode_runs(small):
    cfg, params = small
    qparams = quantize_params(params)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 4), 0,
                                cfg.vocab_size)
    out = generate_greedy(qparams, prompt, cfg, max_new=8)
    assert out.shape == (1, 8)


def test_bytes_halved(small):
    cfg, params = small
    dense_b = quantized_nbytes(params)
    quant_b = quantized_nbytes(quantize_params(params))
    # projections dominate (embedding stays dense); expect a big cut
    assert quant_b < dense_b * 0.75
    ql = quantize_params(params)["layers"][0]["wq"]
    assert isinstance(ql, Q8)


def test_quant_composes_with_speculative(small):
    """int8 target + speculative decode: output equals the int8 model's
    own greedy decode (quantization changes the model, not the
    speculative machinery)."""
    from ray_tpu.models import generate_greedy
    from ray_tpu.models.speculative import generate_speculative

    cfg, params = small
    qparams = quantize_params(params)
    prompt = jax.random.randint(jax.random.PRNGKey(6), (1, 5), 0,
                                cfg.vocab_size)
    ref = generate_greedy(qparams, prompt, cfg, max_new=12)
    out, stats = generate_speculative(qparams, qparams, prompt, cfg, cfg,
                                      max_new=12, k=3)
    assert out.tolist() == ref.tolist()
    assert stats["acceptance_rate"] == 1.0


def test_quant_composes_with_engine(small):
    """int8 params drive the continuous-batching engine unchanged."""
    from ray_tpu.models import generate_greedy
    from ray_tpu.models.paged import PagedEngine

    cfg, params = small
    qparams = quantize_params(params)
    eng = PagedEngine(qparams, cfg, max_slots=2, max_len=48)
    eng.submit("a", [3, 4, 5], max_new_tokens=8)
    eng.submit("b", [9, 8], max_new_tokens=6)
    got = eng.run_to_completion()
    for rid, prompt, n in (("a", [3, 4, 5], 8), ("b", [9, 8], 6)):
        ref = generate_greedy(
            qparams, jnp.asarray(prompt, jnp.int32)[None, :], cfg,
            max_new=n)[0].tolist()
        assert got[rid] == ref, rid
