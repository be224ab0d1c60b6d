"""Hypothesis property tests on the system's invariants."""

import pytest

pytest.importorskip("hypothesis")

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.core.comm import CommLedger, theoretical_dis_cost
from repro.core.dis import dis_marginals, dis_sample
from repro.core.selector import SelectorConfig, local_scores, sample_coreset
from repro.core.vfl import split_columns
from repro.sharding.specs import MESH_SIZES, sanitize

SETTINGS = dict(max_examples=25, deadline=None)


@given(st.integers(1, 64), st.integers(1, 6))
@settings(**SETTINGS)
def test_split_columns_partition(d, T):
    if T > d:
        T = d
    slices = split_columns(d, T)
    cover = sorted(i for s in slices for i in range(s.start, s.stop))
    assert cover == list(range(d))
    assert len(slices) == T


@given(st.integers(2, 40), st.integers(1, 4), st.integers(1, 60),
       st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_dis_protocol_invariants(n, T, m, seed):
    key = jax.random.PRNGKey(seed)
    scores = [jax.random.uniform(jax.random.fold_in(key, j), (n,)) + 1e-3
              for j in range(T)]
    led = CommLedger()
    S, w = dis_sample(jax.random.fold_in(key, 99), scores, m, led)
    assert S.shape == (m,) and w.shape == (m,)
    assert bool(jnp.all((S >= 0) & (S < n)))
    assert bool(jnp.all(w > 0))
    lo, hi = theoretical_dis_cost(m, T)
    assert lo <= led.total <= hi
    # weight identity: w_i * m * g_i == G for every sample
    g = jnp.sum(jnp.stack(scores), 0)
    np.testing.assert_allclose(np.asarray(w * m * g[S]),
                               float(g.sum()), rtol=1e-4)


@given(st.lists(st.integers(1, 4096), min_size=1, max_size=4),
       st.integers(0, 2))
@settings(**SETTINGS)
def test_sanitize_always_divisible(dims, n_axes):
    axes = ["model", "data", ("pod", "data")][: n_axes + 1]
    spec = P(*(axes[i % len(axes)] for i in range(len(dims))))
    out = sanitize(spec, tuple(dims))
    for dim, ax in zip(dims, tuple(out) + (None,) * (len(dims) - len(out))):
        if ax is None:
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= MESH_SIZES[a]
        assert dim % size == 0


@given(st.integers(2, 32), st.integers(1, 16), st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_selector_weights_unbiased_scale(B, d, seed):
    key = jax.random.PRNGKey(seed)
    feats = jax.random.normal(key, (B, d))
    g = local_scores(feats, "norm", 1e-4)
    m = max(1, B // 2)
    S, w = sample_coreset(jax.random.fold_in(key, 1), g, m)
    # E[sum w] = B; single-draw bound: every weight is positive and finite
    assert bool(jnp.all(w > 0)) and bool(jnp.all(jnp.isfinite(w)))
    assert S.shape == (m,)


@given(st.integers(1, 200), st.integers(1, 199))
@settings(**SETTINGS)
def test_selector_m_of(B, pct):
    cfg = SelectorConfig(fraction=pct / 100)
    m = cfg.m_of(B)
    assert 1 <= m <= 2 * B


@given(st.integers(5, 200), st.integers(1, 8), st.integers(1, 32),
       st.booleans(), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_fused_kernel_matches_composition(n, k, d, weighted, seed):
    """The fused single-pass kmeans_assign_update equals the seed data flow
    (kmeans_assign + segment_sum composition) across shapes and weights."""
    from repro.kernels import kmeans_assign_update as _kau
    from repro.kernels import ops

    kx, kc, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(kx, (n, d))
    C = jax.random.normal(kc, (k, d))
    w = jax.random.uniform(kw, (n,)) + 0.1 if weighted else None
    a_f, d2_f, cs_f, ws_f, cc_f = _kau.kmeans_assign_update(
        X, C, w, interpret=True)
    # compose from the SAME (pallas) assignment so ties cannot diverge
    a_c, d2_c = ops.kmeans_assign(X, C)
    ww = jnp.ones((n,)) if w is None else w
    ws_c = jax.ops.segment_sum(ww, a_c, num_segments=k)
    cs_c = jax.ops.segment_sum(ww[:, None] * X, a_c, num_segments=k)
    cc_c = jax.ops.segment_sum(ww * d2_c, a_c, num_segments=k)
    np.testing.assert_array_equal(np.asarray(a_f), np.asarray(a_c))
    np.testing.assert_allclose(np.asarray(d2_f), np.asarray(d2_c), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ws_f), np.asarray(ws_c), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cs_f), np.asarray(cs_c), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(cc_f), np.asarray(cc_c), rtol=1e-4, atol=1e-3)


@given(st.integers(2, 4), st.integers(5, 60), st.integers(1, 5),
       st.integers(1, 16), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_kernels_vmap_safe_in_interpret_mode(B, n, k, d, seed):
    """vmap folds a leading batch dim into the kernel grid for all three
    kernels; every batch slice equals its standalone call."""
    from repro.kernels import kmeans_assign as _ka
    from repro.kernels import kmeans_assign_update as _kau
    from repro.kernels import leverage as _lev

    key = jax.random.PRNGKey(seed)
    X = jax.random.normal(jax.random.fold_in(key, 0), (n, d))
    Cs = jax.random.normal(jax.random.fold_in(key, 1), (B, k, d))
    A = jax.random.normal(jax.random.fold_in(key, 2), (B, d, d))
    Ms = jnp.einsum("bij,bkj->bik", A, A) / d

    # block_n=16 forces multi-step grids for n > 16 — the vmapped scratch
    # init/flush across grid steps is the load-bearing part of the claim
    a_v, d_v = jax.vmap(
        lambda c: _ka.kmeans_assign(X, c, block_n=16, interpret=True))(Cs)
    lev_v = jax.vmap(
        lambda m: _lev.leverage(X, m, block_n=16, interpret=True))(Ms)
    f_v = jax.vmap(
        lambda c: _kau.kmeans_assign_update(X, c, block_n=16, interpret=True))(Cs)
    for b in range(B):
        a_b, d_b = _ka.kmeans_assign(X, Cs[b], block_n=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(a_v[b]), np.asarray(a_b))
        np.testing.assert_allclose(np.asarray(d_v[b]), np.asarray(d_b),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(lev_v[b]),
            np.asarray(_lev.leverage(X, Ms[b], block_n=16, interpret=True)),
            rtol=1e-5, atol=1e-5)
        f_b = _kau.kmeans_assign_update(X, Cs[b], block_n=16, interpret=True)
        for o_v, o_b in zip(f_v, f_b):
            np.testing.assert_allclose(np.asarray(o_v[b]), np.asarray(o_b),
                                       rtol=1e-5, atol=1e-5)


@given(st.integers(2, 300), st.integers(1, 4), st.integers(1, 350),
       st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_blocked_marginals_match_flat_for_random_partitions(n, T, block_size, seed):
    """Hierarchical DIS correctness: for ANY block partition the induced
    marginal of dis_plan_blocked telescopes to exactly the flat dis_marginals
    (float64, unsimplified cell-sum vs the direct g/G)."""
    from repro.core.dis import dis_blocked_marginals

    key = jax.random.PRNGKey(seed)
    scores = [jax.random.uniform(jax.random.fold_in(key, j), (n,)) + 1e-3
              for j in range(T)]
    mb = dis_blocked_marginals(scores, block_size)
    g64 = np.stack([np.asarray(g, np.float64) for g in scores]).sum(axis=0)
    np.testing.assert_allclose(mb, g64 / g64.sum(), rtol=1e-12)
    np.testing.assert_allclose(mb, np.asarray(dis_marginals(scores)), rtol=1e-5)


@given(st.integers(10, 200), st.integers(1, 3), st.integers(1, 40),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_blocked_plan_reduces_to_full_and_keeps_invariants(n, T, m, seed):
    """block_size >= n is bit-identical to the flat plan; a random smaller
    block size keeps the protocol invariants (index range, positive weights,
    counts summing to m, weight identity w*m*g = G)."""
    from repro.core.dis import dis_plan_blocked, dis_plan_full

    key = jax.random.PRNGKey(seed)
    scores = jnp.stack(
        [jax.random.uniform(jax.random.fold_in(key, j), (n,)) + 1e-3
         for j in range(T)])
    pkey = jax.random.fold_in(key, 99)
    pf = dis_plan_full(pkey, scores, m)
    pb = dis_plan_blocked(pkey, scores, m, block_size=n)
    np.testing.assert_array_equal(np.asarray(pf.indices), np.asarray(pb.indices))
    np.testing.assert_array_equal(np.asarray(pf.weights), np.asarray(pb.weights))
    np.testing.assert_array_equal(np.asarray(pf.counts), np.asarray(pb.counts))

    bsz = max(1, n // max(1, (seed % 7) + 1) - 3)
    ps = dis_plan_blocked(pkey, scores, m, block_size=bsz)
    assert bool(jnp.all((ps.indices >= 0) & (ps.indices < n)))
    assert bool(jnp.all(ps.weights > 0))
    assert int(ps.counts.sum()) == m
    g = np.asarray(scores.sum(axis=0))
    np.testing.assert_allclose(
        np.asarray(ps.weights) * m * g[np.asarray(ps.indices)],
        float(np.asarray(scores).sum()), rtol=1e-3)


@given(st.integers(4, 64), st.integers(2, 8), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_dis_estimator_positive_combination(n, T, seed):
    """Coreset cost estimates of a non-negative objective stay non-negative
    and finite for arbitrary scores."""
    key = jax.random.PRNGKey(seed)
    scores = [jax.random.uniform(jax.random.fold_in(key, j), (n,)) + 1e-6
              for j in range(T)]
    f = jax.random.uniform(jax.random.fold_in(key, 777), (n,))
    S, w = dis_sample(jax.random.fold_in(key, 1), scores, max(1, n // 2))
    est = jnp.sum(w * f[S])
    assert bool(est >= 0) and bool(jnp.isfinite(est))
