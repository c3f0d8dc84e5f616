"""Lightning attention: the block form against the recurrence over time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import linear_attn as la

H, D = 3, 8


def _qkv(L, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, kk, v = (jax.random.normal(k[i], (L, H, D)) for i in range(3))
    return q, kk, v, 0.3 * jax.random.normal(k[3], (H, D, D))


def test_slopes_follow_the_published_layer_index():
    s0 = np.asarray(la.decay_slopes(32, 0, 32))
    s31 = np.asarray(la.decay_slopes(32, 31, 32))
    assert s0[0] == pytest.approx(2 ** (-8 / 32) * (1 + 1e-5))
    assert s0[31] == pytest.approx(2 ** -8 * (1 + 1e-5))
    assert np.all(np.diff(s0) < 0)               # later heads decay slower
    np.testing.assert_allclose(s31, s0 / (1 + 1e-5) * 1e-5, rtol=1e-3)


@pytest.mark.parametrize("L,block", [(5, 8), (8, 8), (19, 8), (37, 16),
                                     (64, 16)])
def test_the_block_form_is_the_recurrence(L, block):
    q, k, v, s0 = _qkv(L, seed=L)
    slopes = la.decay_slopes(H, 2, 8)
    want_o, want_s = la.recurrent_sequential(q, k, v, slopes, s0)
    got_o, got_s = la.chunkwise(q, k, v, slopes, s0, L, block)
    np.testing.assert_allclose(got_o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_valid", [0, 1, 7, 8, 13, 24])
def test_a_padded_tail_neither_adds_to_nor_decays_the_state(n_valid):
    L, block = 24, 8
    q, k, v, s0 = _qkv(L, seed=3)
    slopes = la.decay_slopes(H, 0, 4)               # the strongest decay
    _, want = la.recurrent_sequential(q[:n_valid], k[:n_valid], v[:n_valid],
                                      slopes, s0)
    o, got = la.chunkwise(q, k, v, slopes, s0, n_valid, block)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    want_o, _ = la.recurrent_sequential(q[:n_valid], k[:n_valid],
                                        v[:n_valid], slopes, s0)
    np.testing.assert_allclose(o[:n_valid], want_o, rtol=2e-5, atol=2e-5)
    assert bool(jnp.isfinite(o).all())


def test_the_carried_state_chains_two_calls():
    q, k, v, s0 = _qkv(40, seed=9)
    slopes = la.decay_slopes(H, 1, 8)
    want_o, want_s = la.chunkwise(q, k, v, slopes, s0, 40, 8)
    o1, s1 = la.chunkwise(q[:16], k[:16], v[:16], slopes, s0, 16, 8)
    o2, s2 = la.chunkwise(q[16:], k[16:], v[16:], slopes, s1, 24, 8)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), want_o,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s2, want_s, rtol=2e-5, atol=2e-5)


def test_an_inactive_lanes_state_stands_still():
    q, k, v, _ = _qkv(4, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(5), (4, H, D, D))
    active = jnp.asarray([True, False, True, False])
    _, new = la.recurrent_step(state, q, k, v, la.decay_slopes(H, 0, 4),
                               active)
    assert bool((new[1] == state[1]).all() and (new[3] == state[3]).all())
    assert not bool((new[0] == state[0]).all())
