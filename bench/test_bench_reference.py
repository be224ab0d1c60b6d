"""The float64 references against independent computations."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference


def test_threefry_replay_equals_jax_bits():
    key = jax.random.PRNGKey(12345)
    R, W = 5, 37
    want = jax.random.uniform(key, (R, W), minval=np.finfo(np.float32).tiny)
    rows = np.array([0, 3, 4])
    got = reference.uniform_rows(jnp.asarray(np.repeat(reference.raw_key(key)[None], 3, 0)),
                                 jnp.asarray(rows, jnp.uint32), width=W)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[rows])


def test_categorical_replay_picks_jax_draws():
    key = jax.random.PRNGKey(7)
    logits = jnp.log(jax.random.uniform(jax.random.PRNGKey(8), (513,)) + 0.1)
    want = np.asarray(jax.random.categorical(key, logits, shape=(9,)))
    g = reference.gumbel_rows(reference.raw_key(key), np.arange(9), 513)
    got = np.argmax(g + np.asarray(logits, np.float64)[None], axis=1)
    np.testing.assert_array_equal(got, want)


def test_key_chain_matches_sequential_split():
    k = jax.random.PRNGKey(3)
    subs = reference.key_chain(reference.raw_key(k), 3)
    for i in range(3):
        k, sub = jax.random.split(k)
        np.testing.assert_array_equal(subs[i], np.asarray(sub))


def test_vrlr_scores_are_leverage_plus_one_over_n():
    rng = np.random.default_rng(0)
    n = 400
    parts = [rng.normal(size=(n, 4)), rng.normal(size=(n, 3))]
    y = rng.normal(size=n) + 2000.0
    g = reference.vrlr_scores(parts, y)
    for j, f in enumerate([parts[0], np.column_stack([parts[1], y])]):
        q, _ = np.linalg.qr(f)
        np.testing.assert_allclose(g[j], (q * q).sum(1) + 1.0 / n, rtol=1e-9)
    np.testing.assert_allclose(g.sum(1), [4 + 1, 4 + 1], rtol=1e-9)


def test_round1_gap_explains_a_tie_and_refuses_a_shift():
    v = np.array([[0.0, 1.0, -5.0], [2.0, 2.0 - 1e-6, -5.0], [3.0, 0.0, -5.0]])
    assert reference._round1_gap(v, np.array([1, 1, 1])) == 2.0
    assert reference._round1_gap(v, np.array([2, 1, 0])) == 0.0
    assert reference._round1_gap(v, np.array([1, 2, 0])) == pytest.approx(1e-6)
    assert reference._round1_gap(v, np.array([0, 1, 2])) >= 1.0


def test_cell_counts_read_off_a_cell_ordered_draw():
    S = np.array([1, 5, 12, 3, 14])           # party 0: blocks 0,0,1; party 1: 0,1
    counts, cell, pos = reference.cell_counts(S, [3, 2], nb=2, bs=10)
    assert counts.tolist() == [2, 1, 1, 1]
    assert cell.tolist() == [0, 0, 1, 2, 3]
    assert pos.tolist() == [0, 1, 0, 0, 0]
    assert reference.cell_counts(np.array([12, 1, 3, 4, 5]), [3, 2], nb=2, bs=10) is None
