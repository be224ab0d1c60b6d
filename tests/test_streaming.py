"""Streaming block-scan scoring + hierarchical DIS.

The acceptance chain, tested link by link:

  1. ``dis_plan_blocked`` with ``block_size >= n`` is BIT-identical to
     ``dis_plan_full`` (the flat plan is the one-block degeneration);
  2. the hierarchical marginal telescopes exactly to the flat g_i/G
     (``dis_blocked_marginals``, computed without simplification);
  3. ``dis_plan_streamed`` is draw-identical to the in-memory
     ``dis_plan_blocked`` on the same scores (touched-block recomputation
     changes nothing);
  4. ``build_coreset_streaming`` therefore matches ``build_coreset`` bit for
     bit whenever the blockwise scores do (row-local ``norm`` backend), and
     statistically (empirical marginals, weight identity) always;
  5. the data-parallel mass table (``vrlr_block_masses_sharded``) agrees
     with the host block-scan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommLedger,
    VFLDataset,
    build_coreset,
    build_coreset_streaming,
    build_coresets_batched,
    resolve_backend,
    theoretical_dis_cost,
)
from repro.core.dis import (
    blocked_geometry,
    dis_blocked_marginals,
    dis_marginals,
    dis_plan_blocked,
    dis_plan_full,
)
from repro.core.sensitivity import norm_scores, vrlr_scores_stacked
from repro.core.streaming import (
    dis_plan_streamed,
    make_stream_scorer,
    vrlr_block_masses_sharded,
)


def _dataset(key, n=1200, d=12, T=3):
    kx, kt, kn = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n, d))
    theta = jax.random.normal(kt, (d,))
    y = X @ theta + 0.1 * jax.random.normal(kn, (n,))
    return VFLDataset.from_dense(X, y, T=T)


def _scores(key, n, T):
    keys = jax.random.split(key, T)
    return jnp.stack([jax.random.uniform(k, (n,)) + 1e-3 for k in keys])


# --------------------------------------------------------------------------
# 1+2: the hierarchical DIS core
# --------------------------------------------------------------------------

def test_blocked_geometry():
    assert blocked_geometry(100, 30) == (4, 30)
    assert blocked_geometry(100, 100) == (1, 100)
    assert blocked_geometry(100, 1000) == (1, 100)   # bs clamps to n
    assert blocked_geometry(7, 1) == (7, 1)
    with pytest.raises(ValueError):
        blocked_geometry(10, 0)


def test_blocked_reduces_to_full_plan_bit_identical():
    """block_size >= n: same key chain, same cell masses, same draws —
    the flat plan IS the one-block hierarchical plan."""
    for trial in range(4):
        n, T, m = 200 + 31 * trial, trial % 3 + 1, 50 + trial
        scores = _scores(jax.random.PRNGKey(100 + trial), n, T)
        key = jax.random.PRNGKey(trial)
        pf = dis_plan_full(key, scores, m)
        for bsz in (n, n + 1, 10 * n):
            pb = dis_plan_blocked(key, scores, m, block_size=bsz)
            np.testing.assert_array_equal(np.asarray(pf.indices),
                                          np.asarray(pb.indices))
            np.testing.assert_array_equal(np.asarray(pf.weights),
                                          np.asarray(pb.weights))
            np.testing.assert_array_equal(np.asarray(pf.counts),
                                          np.asarray(pb.counts))
            np.testing.assert_array_equal(np.asarray(pf.totals),
                                          np.asarray(pb.totals))


@pytest.mark.parametrize("block_size", [1, 7, 64, 500, 2000])
def test_blocked_marginals_telescope_exactly(block_size):
    """P(i) = sum_cells P(cell) P(i|cell) collapses to g_i/G — computed
    unsimplified in float64, compared at float64 resolution."""
    scores = _scores(jax.random.PRNGKey(1), 500, 3)
    local = [scores[j] for j in range(3)]
    mb = dis_blocked_marginals(local, block_size)
    g64 = np.stack([np.asarray(x, np.float64) for x in local]).sum(axis=0)
    np.testing.assert_allclose(mb, g64 / g64.sum(), rtol=1e-12)
    # and against the float32 public helper at its own resolution
    np.testing.assert_allclose(mb, np.asarray(dis_marginals(local)), rtol=1e-5)


def test_blocked_plan_empirical_marginal():
    """Draws from the hierarchical sampler hit the flat marginal (5 sigma)."""
    n, T, m = 20, 3, 20000
    scores = _scores(jax.random.PRNGKey(3), n, T)
    probs = np.asarray(dis_marginals([scores[j] for j in range(T)]))
    plan = dis_plan_blocked(jax.random.PRNGKey(4), scores, m, block_size=7)
    emp = np.bincount(np.asarray(plan.indices), minlength=n) / m
    sigma = np.sqrt(probs * (1 - probs) / m)
    assert np.all(np.abs(emp - probs) < 5 * sigma + 1e-3)


def test_blocked_plan_weight_identity_and_counts():
    n, T, m = 333, 4, 80
    scores = _scores(jax.random.PRNGKey(5), n, T)
    plan = dis_plan_blocked(jax.random.PRNGKey(6), scores, m, block_size=50)
    assert int(plan.counts.sum()) == m
    assert bool(jnp.all((plan.indices >= 0) & (plan.indices < n)))
    g = np.asarray(scores.sum(axis=0))
    np.testing.assert_allclose(
        np.asarray(plan.weights) * m * g[np.asarray(plan.indices)],
        float(g.sum()), rtol=1e-4)


# --------------------------------------------------------------------------
# 3: streamed sampler == in-memory blocked plan on the same scores
# --------------------------------------------------------------------------

def test_streamed_plan_matches_blocked_plan():
    """The touched-block recomputation path produces the exact draws of the
    in-memory plan — norm scores are row-local, so the streamed scorer's
    blockwise values are bitwise the flat ones."""
    ds = _dataset(jax.random.PRNGKey(7), n=1100)
    key = jax.random.PRNGKey(8)
    st = ds.stacked(with_labels=True)
    sc = norm_scores(st.blocks) + 1.0 / ds.n
    for bsz in (128, 333, 2000):
        pb = dis_plan_blocked(key, sc, 90, block_size=bsz)
        scorer = make_stream_scorer("vrlr", key, ds, bsz, "norm")
        ps = dis_plan_streamed(scorer, 90)
        np.testing.assert_array_equal(np.asarray(pb.indices),
                                      np.asarray(ps.indices))
        np.testing.assert_array_equal(np.asarray(pb.weights),
                                      np.asarray(ps.weights))
        np.testing.assert_array_equal(np.asarray(pb.counts),
                                      np.asarray(ps.counts))


# --------------------------------------------------------------------------
# 4: the streaming entry point
# --------------------------------------------------------------------------

def test_streaming_build_bit_identical_to_flat_norm_backend():
    """block_size >= n + row-local scores => build_coreset_streaming ==
    build_coreset exactly, including the ledger bill."""
    ds = _dataset(jax.random.PRNGKey(9))
    key = jax.random.PRNGKey(10)
    led_f, led_s = CommLedger(), CommLedger()
    cs_f = build_coreset("vrlr", ds, 120, key=key, backend="norm", ledger=led_f)
    cs_s = build_coreset_streaming("vrlr", ds, 120, key=key, backend="norm",
                                   block_size=ds.n, ledger=led_s)
    np.testing.assert_array_equal(np.asarray(cs_f.indices),
                                  np.asarray(cs_s.indices))
    np.testing.assert_array_equal(np.asarray(cs_f.weights),
                                  np.asarray(cs_s.weights))
    assert led_f.total == led_s.total == cs_s.comm_units


@pytest.mark.parametrize("task,params", [("vrlr", {}), ("vkmc", {"k": 4})])
def test_streaming_build_ref_backend(task, params):
    ds = _dataset(jax.random.PRNGKey(11))
    led = CommLedger()
    cs = build_coreset_streaming(task, ds, 100, key=jax.random.PRNGKey(12),
                                 backend="ref", block_size=128, ledger=led,
                                 **params)
    assert cs.m == 100
    assert bool(jnp.all(cs.weights > 0))
    lo, hi = theoretical_dis_cost(100, ds.T)
    assert lo <= led.total <= hi


def test_streaming_marginals_match_flat_scores():
    """vrlr ref scores blockwise: the streamed empirical marginal tracks the
    materialized path's marginal (scores agree to fp, blocking is
    marginal-invariant)."""
    ds = _dataset(jax.random.PRNGKey(13), n=600)
    st = ds.stacked(with_labels=True)
    sc = np.asarray(vrlr_scores_stacked(st.blocks, use_kernel=False))
    g = sc.sum(axis=0)
    probs = g / g.sum()
    m = 20000
    scorer = make_stream_scorer("vrlr", jax.random.PRNGKey(14), ds, 97, "ref")
    plan = dis_plan_streamed(scorer, m)
    emp = np.bincount(np.asarray(plan.indices), minlength=ds.n) / m
    sigma = np.sqrt(probs * (1 - probs) / m)
    assert np.all(np.abs(emp - probs) < 5 * sigma + 1e-3)


def test_streaming_numpy_backed_dataset():
    """Host-resident (numpy) parts stream block by block; results match the
    jnp-backed dataset draw for draw (same scores, same keys)."""
    ds = _dataset(jax.random.PRNGKey(15), n=700)
    ds_np = VFLDataset([np.asarray(p) for p in ds.parts], np.asarray(ds.y))
    key = jax.random.PRNGKey(16)
    cs_j = build_coreset_streaming("vrlr", ds, 60, key=key, backend="ref",
                                   block_size=128)
    cs_n = build_coreset_streaming("vrlr", ds_np, 60, key=key, backend="ref",
                                   block_size=128)
    np.testing.assert_array_equal(np.asarray(cs_j.indices),
                                  np.asarray(cs_n.indices))
    np.testing.assert_allclose(np.asarray(cs_j.weights),
                               np.asarray(cs_n.weights), rtol=1e-6)


def test_streaming_uniform_and_label_validation():
    ds = _dataset(jax.random.PRNGKey(17), n=300)
    cs = build_coreset_streaming("uniform", ds, 30, key=jax.random.PRNGKey(0))
    assert cs.m == 30 and cs.comm_units == 30 * ds.T
    with pytest.raises(ValueError):
        build_coreset_streaming("vrlr", VFLDataset(ds.parts, None), 10,
                                key=jax.random.PRNGKey(0))
    with pytest.raises(KeyError):
        build_coreset_streaming("no-such-task", ds, 10,
                                key=jax.random.PRNGKey(0))
    # a registered task without a streaming scorer fails with a clear error
    from repro.core.api import CoresetTask
    task = CoresetTask(name="no-stream",
                       score_fn=lambda key, ds2, backend="ref": (None, key))
    with pytest.raises(ValueError, match="no streaming scorer"):
        build_coreset_streaming(task, ds, 10, key=jax.random.PRNGKey(0))


def test_block_view_matches_stacked():
    """VFLDataset.block(b) is exactly the corresponding slice of stacked()."""
    ds = _dataset(jax.random.PRNGKey(18), n=505)
    st = ds.stacked(with_labels=True)
    nb, bs = ds.block_geometry(100)
    assert (nb, bs) == (6, 100)
    for b in range(nb):
        blk, nvalid = ds.block(b, 100, with_labels=True)
        lo = b * bs
        want = np.asarray(st.blocks[:, lo:lo + nvalid, :])
        np.testing.assert_array_equal(np.asarray(blk[:, :nvalid]), want)
        assert float(jnp.abs(blk[:, nvalid:]).sum()) == 0.0
    assert nvalid == 505 - 5 * 100


# --------------------------------------------------------------------------
# 5: data-parallel mass table over the mesh
# --------------------------------------------------------------------------

def test_sharded_masses_match_block_scan():
    from repro.launch.mesh import make_debug_mesh

    ds = _dataset(jax.random.PRNGKey(19), n=800)
    mesh = make_debug_mesh(n_data=1, n_model=1)
    ms = vrlr_block_masses_sharded(mesh, ds, 100)
    scorer = make_stream_scorer("vrlr", jax.random.PRNGKey(0), ds, 100, "ref")
    assert ms.shape == (ds.T, 8)
    np.testing.assert_allclose(np.asarray(ms), np.asarray(scorer.masses),
                               rtol=1e-4, atol=1e-6)


def test_sharded_masses_rejects_misaligned_grid():
    from repro.launch.mesh import make_debug_mesh

    ds = _dataset(jax.random.PRNGKey(20), n=101)
    with pytest.raises(ValueError):
        vrlr_block_masses_sharded(make_debug_mesh(1, 1), ds, 100)


# --------------------------------------------------------------------------
# Satellites: backend="auto" and batched budget validation
# --------------------------------------------------------------------------

def test_backend_auto_resolution():
    assert resolve_backend("ref") == "ref"
    assert resolve_backend("norm") == "norm"
    resolved = resolve_backend("auto")
    if jax.default_backend() == "tpu":
        assert resolved == "pallas"
    else:
        assert resolved == "ref"
    with pytest.raises(ValueError):
        resolve_backend("bogus")


def test_build_coreset_auto_default_matches_resolved():
    """The default backend="auto" build equals an explicit build with the
    resolved backend, draw for draw."""
    ds = _dataset(jax.random.PRNGKey(21), n=400)
    key = jax.random.PRNGKey(22)
    cs_auto = build_coreset("vrlr", ds, 50, key=key)
    cs_expl = build_coreset("vrlr", ds, 50, key=key,
                            backend=resolve_backend("auto"))
    np.testing.assert_array_equal(np.asarray(cs_auto.indices),
                                  np.asarray(cs_expl.indices))
    np.testing.assert_array_equal(np.asarray(cs_auto.weights),
                                  np.asarray(cs_expl.weights))


def test_batched_budget_grid_validation():
    ds = _dataset(jax.random.PRNGKey(23), n=200)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="budgets"):
        build_coresets_batched("vrlr", ds, [0, 20], key=key)
    with pytest.raises(ValueError, match="budgets"):
        build_coresets_batched("vrlr", ds, [-3], key=key)
    with pytest.raises(ValueError, match="budgets"):
        build_coresets_batched("vrlr", ds, [10, 20], key=key, m_cap=15)
    with pytest.raises(ValueError):
        build_coresets_batched("vrlr", ds, [], key=key)
    # valid explicit m_cap > max(ms) still works (larger draw capacity)
    grid = build_coresets_batched("vrlr", ds, [10], key=key, m_cap=16)
    assert grid.indices.shape == (1, 1, 16)
    cs = grid.coreset(0, 0)
    assert cs.m == 10 and bool(jnp.all(cs.weights > 0))
