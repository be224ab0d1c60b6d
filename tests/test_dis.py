"""Algorithm 1 (DIS): marginal correctness, weights, communication bounds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import CommLedger, theoretical_dis_cost
from repro.core.dis import dis_marginals, dis_sample, uniform_sample


def _scores(key, n, T):
    keys = jax.random.split(key, T)
    return [jax.random.uniform(k, (n,), minval=0.0, maxval=1.0) for k in keys]


def test_dis_shapes_and_weights():
    n, T, m = 500, 3, 100
    scores = _scores(jax.random.PRNGKey(0), n, T)
    S, w = dis_sample(jax.random.PRNGKey(1), scores, m)
    assert S.shape == (m,) and w.shape == (m,)
    assert bool(jnp.all(S >= 0)) and bool(jnp.all(S < n))
    # w(i) = G / (m * g_i)
    g = jnp.sum(jnp.stack(scores), axis=0)
    G = g.sum()
    np.testing.assert_allclose(np.asarray(w), np.asarray(G / (m * g[S])), rtol=1e-5)


def test_dis_comm_within_theoretical_bounds():
    n, T, m = 300, 4, 64
    led = CommLedger()
    dis_sample(jax.random.PRNGKey(0), _scores(jax.random.PRNGKey(2), n, T), m, led)
    lo, hi = theoretical_dis_cost(m, T)
    assert lo <= led.total <= hi, (led.total, lo, hi)


def test_dis_marginals_match_empirically():
    """The induced sampling marginal equals g_i/G (proof of Thm 3.1)."""
    n, T, m = 20, 3, 20000
    scores = _scores(jax.random.PRNGKey(3), n, T)
    probs = np.asarray(dis_marginals(scores))
    S, _ = dis_sample(jax.random.PRNGKey(4), scores, m)
    emp = np.bincount(np.asarray(S), minlength=n) / m
    # chi-square-ish: each cell within 5 sigma
    sigma = np.sqrt(probs * (1 - probs) / m)
    assert np.all(np.abs(emp - probs) < 5 * sigma + 1e-3)


def test_dis_unbiased_sum_estimator():
    """E[sum_{i in S} w_i f_i] = sum_i f_i — the coreset estimator core."""
    n, T, m = 100, 2, 4000
    scores = _scores(jax.random.PRNGKey(5), n, T)
    f = np.asarray(jax.random.uniform(jax.random.PRNGKey(6), (n,)))
    S, w = dis_sample(jax.random.PRNGKey(7), scores, m)
    est = float(np.sum(np.asarray(w) * f[np.asarray(S)]))
    true = float(f.sum())
    assert abs(est - true) / true < 0.1


def test_uniform_sample_weights():
    led = CommLedger()
    S, w = uniform_sample(jax.random.PRNGKey(0), 1000, 50, 3, led)
    assert np.allclose(np.asarray(w), 1000 / 50)
    assert led.total == 50 * 3        # broadcast only


def test_dis_rejects_zero_scores():
    with pytest.raises(ValueError):
        dis_sample(jax.random.PRNGKey(0), [jnp.zeros((10,))], 5)


@pytest.mark.parametrize("cap,n,rows", [(7, 33, 2), (64, 100, 5), (5, 50, 1)])
@pytest.mark.parametrize("typed_key", [False, True])
def test_row_chunked_categorical_is_bit_identical(monkeypatch, cap, n, rows,
                                                  typed_key):
    """The round-2 draw in row chunks replays jax.random.categorical draw
    for draw, including a last chunk that overhangs cap, and under vmap."""
    from repro.core import dis

    monkeypatch.setattr(dis, "GUMBEL_CHUNK_BYTES", 4 * n * rows)
    key = jax.random.key(4) if typed_key else jax.random.PRNGKey(3)
    lg = jax.random.normal(jax.random.PRNGKey(1), (n,))
    np.testing.assert_array_equal(
        np.asarray(dis._categorical_rows(key, lg, cap)),
        np.asarray(jax.random.categorical(key, lg, shape=(cap,))))
    keys = jax.random.split(key, 3)
    lgs = jax.random.normal(jax.random.PRNGKey(2), (3, n))
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(lambda k, l: dis._categorical_rows(k, l, cap))(
            keys, lgs)),
        np.asarray(jax.vmap(
            lambda k, l: jax.random.categorical(k, l, shape=(cap,)))(keys, lgs)))


def test_dis_plan_full_unchanged_by_row_chunking(monkeypatch):
    from repro.core import dis

    scores = jnp.stack(_scores(jax.random.PRNGKey(5), 300, 3))
    whole = dis.dis_plan_full(jax.random.PRNGKey(6), scores, 40)
    monkeypatch.setattr(dis, "GUMBEL_CHUNK_BYTES", 4 * 300 * 3)
    chunked = dis.dis_plan_full(jax.random.PRNGKey(6), scores, 40)
    for a, b in zip(whole, chunked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
