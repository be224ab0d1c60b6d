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
    for draw in each party's first a_j entries, including a last chunk that
    overhangs cap, a party that draws nothing, and under an outer vmap."""
    from repro.core import dis

    monkeypatch.setattr(dis, "GUMBEL_CHUNK_BYTES", 4 * n * rows)
    key = jax.random.key(4) if typed_key else jax.random.PRNGKey(3)
    keys = jax.random.split(key, 6).reshape(2, 3, *key.shape)
    lgs = jax.random.normal(jax.random.PRNGKey(2), (2, 3, n))
    counts = jnp.array([[cap, 0, cap // 2 + 1], [1, cap, 0]], jnp.int32)
    got = jax.vmap(lambda k, l, a: dis._round2_candidates(k, l, a, cap))(
        keys, lgs, counts)
    want = jax.vmap(jax.vmap(
        lambda k, l: jax.random.categorical(k, l, shape=(cap,))))(keys, lgs)
    assert got.shape == want.shape == (2, 3, cap)
    for b in range(2):
        for j in range(3):
            a = int(counts[b, j])
            np.testing.assert_array_equal(np.asarray(got[b, j, :a]),
                                          np.asarray(want[b, j, :a]))
            single = dis._round2_candidates(keys[b], lgs[b], counts[b], cap)
            np.testing.assert_array_equal(np.asarray(single[j, :a]),
                                          np.asarray(want[b, j, :a]))


def _whole_stream_plan(monkeypatch, plan):
    """``plan`` run with round 2 drawing every party's whole (cap,) stream
    with ``jax.random.categorical`` — the same mask, argsort and round 3."""
    from repro.core import dis

    with monkeypatch.context() as mp:
        mp.setattr(dis, "_round2_candidates", lambda keys, lgs, a, cap: (
            jax.vmap(lambda k, l: jax.random.categorical(k, l, shape=(cap,)))(
                keys, lgs)))
        return plan()


@pytest.mark.parametrize("batched", [False, True], ids=["static", "batched"])
@pytest.mark.parametrize("case,m,rows,empty", [
    ("party_draws_nothing", 20, 3, (1,)),
    ("one_party_takes_all", 20, 3, (0, 2)),
    ("last_chunk_overhangs", 40, 6, ()),
    ("one_chunk_per_party", 17, 64, ()),
])
def test_dis_plan_full_partial_draw_matches_whole_stream(
        monkeypatch, case, m, rows, empty, batched):
    """Round 2 computes only each party's first ceil(a_j/rows) chunks, and
    the plan (indices, weights, counts, totals) is bit-identical to one
    whose every party draws its whole cap stream; under the batched
    engine's vmap with ``m_cap`` and a traced ``m`` too."""
    from repro.core import dis

    n, T = 50, 3
    monkeypatch.setattr(dis, "GUMBEL_CHUNK_BYTES", 4 * n * rows)
    scores = jnp.stack(_scores(jax.random.PRNGKey(len(case)), n, T))
    for j in empty:
        scores = scores.at[j].set(0.0)
    if batched:
        # the batched engine's nesting: seeds outside, a budget grid inside
        keys = jax.random.split(jax.random.PRNGKey(9), 2)
        ms = jnp.array([m, m - 5, 1], jnp.int32)

        def plan():
            return jax.jit(jax.vmap(lambda k: jax.vmap(
                lambda mm: dis.dis_plan_full(k, scores, mm, m_cap=m))(ms)))(
                    keys)
    else:
        def plan():
            return dis.dis_plan_full(jax.random.PRNGKey(9), scores, m)
    got = plan()
    want = _whole_stream_plan(monkeypatch, plan)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    counts = np.asarray(got.counts).reshape(-1, T)
    assert (counts[:, list(empty)] == 0).all()
    if case == "one_party_takes_all":
        assert (counts[0] == [0, m, 0]).all()
    if not batched:
        want_rows = sum(-(-int(a) // min(rows, m)) for a in counts[0])
        assert dis.round2_rows(jax.random.PRNGKey(9), counts[0], n, m) == (
            want_rows * min(rows, m))


def test_partial_draw_keeps_the_whole_draw_off_its_layout():
    """Outside the partitionable float32 threefry layout every party draws
    its whole stream with ``jax.random.categorical``, as before."""
    from repro.core import dis

    lgs = jax.random.normal(jax.random.PRNGKey(2), (3, 40))
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    with jax.threefry_partitionable(False):
        assert dis._chunk_rows(keys, 40, 12) is None
        assert dis.round2_rows(keys, [4, 0, 8], 40, 12) == 3 * 12
        got = dis._round2_candidates(keys, lgs, jnp.array([4, 0, 8]), 12)
        want = jax.vmap(
            lambda k, l: jax.random.categorical(k, l, shape=(12,)))(keys, lgs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert dis._chunk_rows(keys, 40, 12) == 12
    assert dis._chunk_rows(keys, 40, 0) is None
    assert dis._chunk_rows(jax.random.split(jax.random.key(1, impl="rbg"), 3),
                           40, 12) is None


def test_dis_plan_full_unchanged_by_row_chunking(monkeypatch):
    from repro.core import dis

    scores = jnp.stack(_scores(jax.random.PRNGKey(5), 300, 3))
    whole = dis.dis_plan_full(jax.random.PRNGKey(6), scores, 40)
    monkeypatch.setattr(dis, "GUMBEL_CHUNK_BYTES", 4 * 300 * 3)
    chunked = dis.dis_plan_full(jax.random.PRNGKey(6), scores, 40)
    for a, b in zip(whole, chunked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
