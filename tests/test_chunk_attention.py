"""``ops.attention.chunk_attention``: a prompt chunk's queries over a buffer
of every position so far, as a Pallas flash kernel, interpreted on the CPU,
against the two things it must equal: ``cohere2_moe._prompt_attention``'s
loop over key blocks (the form every CPU run takes) and a dense masked
float32 softmax."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import cohere2_moe as cm
from ray_tpu.ops import attention

N, T, D = 128, 512, 128     # a chunk of 128 rows over a buffer of four


def _dense(q, k, v, start, window):
    """Dense float32 softmax over the visible keys. -> [N, H * d]."""
    n, H, d = q.shape
    kvh = k.shape[1]
    qg = q.astype(jnp.float32).reshape(n, kvh, H // kvh, d)
    s = jnp.einsum("qgrd,kgd->grqk", qg, k.astype(jnp.float32),
                   precision="highest") * d ** -0.5
    t, j = start + jnp.arange(n)[:, None], jnp.arange(k.shape[0])[None]
    ok = (j <= t) & ((t - j < window) if window else True)
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", p, v.astype(jnp.float32),
                   precision="highest")
    return np.asarray(o.reshape(n, H * d))


def _inputs(kvh, rep, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((N, kvh * rep, D)), dtype),
            jnp.asarray(rng.standard_normal((T, kvh, D)), dtype),
            jnp.asarray(rng.standard_normal((T, kvh, D)), dtype))


@pytest.mark.parametrize("start, window, rep, dtype, tiles", [
    # start: 0, one key tile in, mid-tile, the buffer's last chunk
    (0, 0, 4, "float32", (64, 128, 2)),
    (128, 0, 4, "float32", (64, 128, 2)),
    (200, 0, 4, "bfloat16", (64, 128, 4)),
    (384, 0, 4, "bfloat16", (64, 128, 2)),
    # window: under a tile, the chunk's rows, wider than what lies before
    (384, 48, 4, "float32", (64, 128, 2)),
    (200, 48, 1, "bfloat16", (32, 64, 1)),
    (256, 128, 4, "bfloat16", (64, 64, 2)),
    (300, 128, 4, "float32", (32, 128, 4)),
    (100, 300, 4, "bfloat16", (64, 128, 2)),
    (0, 300, 16, "float32", (32, 128, 4)),
    # heads a K/V head: 1, 4 (above), 16
    (128, 0, 1, "float32", (128, 128, 1)),
    (384, 0, 16, "bfloat16", (32, 128, 4)),
    (200, 200, 16, "bfloat16", (64, 256, 16)),
    # the tiles the shapes give (one query tile, one key tile)
    (384, 0, 4, "bfloat16", None),
    (100, 130, 16, "float32", None),
])
def test_the_kernel_equals_the_loop_and_the_dense_softmax(
        start, window, rep, dtype, tiles):
    """The chunk's rows past its prompt's end (a padded tail) are rows like
    any other: the buffer holds their keys and they are computed."""
    kvh = 2 if rep < 16 else 1
    q, k, v = _inputs(kvh, rep, jnp.dtype(dtype), seed=start + window + rep)
    if tiles is None:
        assert attention.chunk_attention_tiles(
            N, T, rep, D, q.dtype.itemsize) == (128, 512, min(rep, 8))
        got = attention.chunk_attention(q, k, v, jnp.int32(start), window,
                                        interpret=True)
    else:
        got = attention._chunk_attention(q, k, v, jnp.int32(start), window,
                                         tiles, True)
    assert got.shape == (N, kvh * rep * D) and got.dtype == q.dtype
    cfg = types.SimpleNamespace(n_kv_heads=kvh, head_dim=D, key_block=64)
    loop = cm._prompt_attention(q, k, v, jnp.int32(start), window, cfg)
    got, loop = (np.asarray(a.astype(jnp.float32)) for a in (got, loop))
    # one arithmetic in other tiles: sums in another order, and in bfloat16
    # the weights' and the output's rounding to the other neighbour
    tol = 2e-6 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, loop, atol=tol, rtol=0)
    np.testing.assert_allclose(got, _dense(q, k, v, start, window),
                               atol=tol, rtol=0)


def test_a_padded_tail_leaves_the_valid_rows_as_they_are():
    """Rows past ``n_valid`` hold the keys of padding tokens: no valid row
    sees them (they lie after it), so what the tail holds changes none."""
    q, k, v = _inputs(2, 4, jnp.float32)
    start, n_valid = 256, 256 + 70
    other = k.at[n_valid:].set(7.0), v.at[n_valid:].set(-3.0)
    a = attention._chunk_attention(q, k, v, jnp.int32(start), 0,
                                   (64, 128, 2), True)
    b = attention._chunk_attention(q, *other, jnp.int32(start), 0,
                                   (64, 128, 2), True)
    np.testing.assert_array_equal(np.asarray(a)[:70], np.asarray(b)[:70])
    assert np.isfinite(np.asarray(b)).all()


@pytest.mark.parametrize("on_tpu, N, T, H, kvh, d, dtype, form", [
    (True, 2048, 32768, 128, 8, 128, "bfloat16", "kernel"),   # Command A+
    (True, 2048, 10240, 32, 8, 128, "bfloat16", "kernel"),    # granite
    (True, 2048, 6144, 32, 8, 64, "bfloat16", "loop"),        # lfm2: head 64
    (True, 2048, 32768, 128, 8, 128, "int8", "loop"),
    (True, 2048 + 64, 32768, 128, 8, 128, "bfloat16", "loop"),
    (True, 2048, 10240 + 64, 32, 8, 128, "bfloat16", "loop"),
    (False, 2048, 32768, 128, 8, 128, "bfloat16", "loop"),    # off the chip
    (False, 2048, 10240, 32, 8, 128, "float32", "loop"),
])
def test_the_form_follows_the_shapes_the_dtype_and_the_platform(
        on_tpu, N, T, H, kvh, d, dtype, form, monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    assert attention.chunk_attention_form(N, T, H, kvh, d,
                                          jnp.dtype(dtype)) == form


@pytest.mark.parametrize("N, T, rep, itemsize, tiles", [
    (2048, 32768, 16, 2, (256, 1024, 2)),   # Command A+: 15.0 MiB by count
    (2048, 10240, 4, 2, (256, 1024, 4)),    # granite
    (2048, 32768, 16, 4, (128, 1024, 4)),   # float32: half the rows
    (2048, 8192, 1, 2, (256, 1024, 1)),
    (640, 1536, 4, 2, (128, 512, 4)),       # what tiles 640 rows, 1536 keys
    (2048, 32768, 64, 2, None),             # no tile of 128 rows fits
    (2048 + 64, 32768, 16, 2, None)])
def test_the_tiles_follow_the_shapes_and_fit_the_default_vmem(
        N, T, rep, itemsize, tiles):
    """The keys' tile is the largest under 1024 that tiles the buffer; the
    query tile and the heads a product are the most whose call fits the 16
    MiB a kernel gets unasked (``tests/test_tpu_compile.py`` has Mosaic take
    the first two at that limit)."""
    assert attention.chunk_attention_tiles(N, T, rep, D, itemsize) == tiles


def test_prompt_attention_takes_the_form_the_function_names(monkeypatch):
    """``_prompt_attention`` asks ``chunk_attention_form`` and nothing else:
    on the CPU the loop (no kernel in its jaxpr), the kernel once the
    function names it."""
    q, k, v = _inputs(2, 4, jnp.float32)
    cfg = types.SimpleNamespace(n_kv_heads=2, head_dim=D, key_block=64)
    call = lambda: jax.make_jaxpr(
        lambda q, k, v: cm._prompt_attention(q, k, v, jnp.int32(128), 0,
                                             cfg))(q, k, v)
    assert "pallas_call" not in str(call())
    monkeypatch.setattr(cm, "chunk_attention_form", lambda *a: "kernel")
    assert "pallas_call" in str(call())


@pytest.mark.parametrize("T, H, kvh, start, window", [
    (32768, 128, 8, 0, 0), (32768, 128, 8, 24576 + 40, 0),      # Command A+
    (32768, 128, 8, 2048, 4096), (32768, 128, 8, 30720, 4096),
    (10240, 32, 8, 0, 0), (10240, 32, 8, 8192, 0)])             # granite
def test_the_compiled_kernel_equals_the_loop_on_the_chip(
        T, H, kvh, start, window, monkeypatch):
    """At the benchmark's shapes and the tiles the shapes give, Mosaic's
    build of the kernel against the loop, both in bfloat16. Runs where the
    kernel runs: ``chiprun -- python -m pytest --noconftest -s
    tests/test_chunk_attention.py -k on_the_chip`` (``tests/conftest.py``
    pins every test process to the CPU)."""
    if not attention._on_tpu():
        pytest.skip("Mosaic compiles the kernel for a TPU only")
    ks = jax.random.split(jax.random.PRNGKey(start + window), 3)
    q = jax.random.normal(ks[0], (2048, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (T, kvh, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (T, kvh, D), jnp.bfloat16)
    cfg = types.SimpleNamespace(n_kv_heads=kvh, head_dim=D, key_block=256)
    call = jax.jit(lambda q, k, v, start: cm._prompt_attention(
        q, k, v, start, window, cfg))
    assert attention.chunk_attention_form(2048, T, H, kvh, D,
                                          q.dtype) == "kernel"
    got = call(q, k, v, jnp.int32(start))
    monkeypatch.setattr(cm, "chunk_attention_form", lambda *a: "loop")
    jax.clear_caches()
    want = call(q, k, v, jnp.int32(start))
    err = np.abs(np.asarray(got.astype(jnp.float32))
                 - np.asarray(want.astype(jnp.float32)))
    print(f"\nchunk kernel vs loop at T {T} H {H} start {start} window "
          f"{window}: max {err.max():.5f} mean {err.mean():.6f}")
    assert err.max() < 2e-2
