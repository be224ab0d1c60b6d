"""Fused single-pass kernel, batch-safe kernels and the stacked party view.

Covers the perf_opt acceptance criteria:
  * ``kmeans_assign_update`` (Pallas, interpret on CPU) matches the
    assignment + segment_sum composition across shapes/dtypes/weights;
  * the fused Lloyd step is STRUCTURALLY one pass over X — exactly one
    pallas_call, zero scatter-add (segment_sum) in its jaxpr — while the
    seed data flow is three;
  * all three kernels (kmeans_assign, leverage, kmeans_assign_update) are
    batch-safe: leading batch dims / jax.vmap fold into the grid and match
    the per-slice results;
  * ``build_coresets_batched`` runs with ``backend="pallas"`` and matches
    the ``ref`` backend numerically;
  * the stacked party view pads/masks correctly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.fused_lloyd import count_primitives, structural_passes
from repro.core import (
    VFLDataset,
    build_coresets_batched,
)
from repro.core.vkmc import kmeans, lloyd
from repro.kernels import kmeans_assign_update as _kau
from repro.kernels import ops, ref

SHAPES_NKD = [(17, 3, 5), (128, 8, 32), (300, 13, 90), (257, 10, 129), (1000, 64, 7)]


def _data(n, k, d, dtype=jnp.float32, seed=0):
    kx, kc, kw = jax.random.split(jax.random.PRNGKey(seed + n * 31 + k), 3)
    X = jax.random.normal(kx, (n, d), dtype)
    C = jax.random.normal(kc, (k, d), dtype)
    w = jax.random.uniform(kw, (n,))
    return X, C, w


def _dataset(key, n=400, d=9, T=3):
    kx, kt, kn = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n, d))
    y = X @ jax.random.normal(kt, (d,)) + 0.1 * jax.random.normal(kn, (n,))
    return VFLDataset.from_dense(X, y, T=T)


# --------------------------------------------------------------------------
# Fused kernel vs the assignment + segment_sum composition
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,d", SHAPES_NKD)
@pytest.mark.parametrize("weighted", [False, True])
def test_fused_matches_composition_sweep(n, k, d, weighted):
    X, C, w = _data(n, k, d)
    w = w if weighted else None
    a_f, d2_f, cs_f, ws_f, cc_f = ops.kmeans_assign_update(X, C, w)
    # composition oracle on the SAME assignment (ties are then irrelevant)
    a_r, d2_r, cs_r, ws_r, cc_r = ref.kmeans_assign_update(X, C, w)
    np.testing.assert_allclose(np.asarray(d2_f), np.asarray(d2_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(cs_f), np.asarray(cs_r),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(ws_f), np.asarray(ws_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cc_f), np.asarray(cc_r),
                               rtol=1e-4, atol=1e-3)
    # unweighted wsum is an exact integer count partition of n
    if not weighted:
        assert float(np.asarray(ws_f).sum()) == n


def test_fused_bf16_points():
    X, C, w = _data(300, 7, 33, dtype=jnp.bfloat16)
    _, d2_f, cs_f, ws_f, _ = ops.kmeans_assign_update(X, C, w)
    _, d2_r, cs_r, ws_r, _ = ref.kmeans_assign_update(X, C, w)
    np.testing.assert_allclose(np.asarray(d2_f), np.asarray(d2_r), rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(cs_f), np.asarray(cs_r), rtol=5e-2, atol=5e-1)


def test_fused_block_size_invariance():
    X, C, w = _data(517, 9, 33)
    outs = [ops.kmeans_assign_update(X, C, w, block_n=bn) for bn in (64, 512)]
    np.testing.assert_array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0]))
    for i in (1, 2, 3, 4):
        np.testing.assert_allclose(np.asarray(outs[0][i]), np.asarray(outs[1][i]),
                                   rtol=1e-5, atol=1e-5)


def test_fused_assignment_matches_assign_kernel():
    """The fused kernel's assignment is the SAME computation as
    kmeans_assign — bit-equal including tie behaviour."""
    X, C, _ = _data(513, 17, 40)
    a1, d1 = ops.kmeans_assign(X, C)
    a2, d2, *_ = ops.kmeans_assign_update(X, C)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


# --------------------------------------------------------------------------
# Structural single-pass criterion
# --------------------------------------------------------------------------

def test_fused_step_is_one_pass():
    X, C, w = _data(400, 6, 24)

    def fused_step(x, c, ww):
        return _kau.kmeans_assign_update(x, c, ww, interpret=True)

    # 1 pallas_call, no segment_sum, 1 X-sized pass total
    assert structural_passes(fused_step, X, C, w) == (1, 0, 1)


def test_seed_step_is_multi_pass():
    X, C, w = _data(400, 6, 24)

    def seed_step(x, c, ww):
        from repro.kernels import kmeans_assign as _ka
        a, _ = _ka.kmeans_assign(x, c, interpret=True)
        k = c.shape[0]
        wsum = jax.ops.segment_sum(ww, a, num_segments=k)
        csum = jax.ops.segment_sum(ww[:, None] * x, a, num_segments=k)
        return wsum, csum

    # 1 pallas_call + 2 scatter-adds; 2 X-sized passes (the csum scatter
    # streams X again, the wsum scatter only streams the (n,) weights)
    assert structural_passes(seed_step, X, C, w) == (1, 2, 2)


def test_lloyd_is_one_pallas_call_per_iteration_no_segment_sum():
    """The fused Lloyd body: exactly one pallas_call in the scanned
    iteration, no segment_sum anywhere in the solver's jaxpr."""
    X, C, _ = _data(400, 6, 24)
    jx = jax.make_jaxpr(lambda x, c: lloyd(x, c, iters=3, use_kernel=True))(X, C)
    assert count_primitives(jx.jaxpr, {"scatter-add"}) == 0
    # the single fused call sits inside the scan body, traced once
    assert count_primitives(jx.jaxpr, {"pallas_call"}) == 1


# --------------------------------------------------------------------------
# Batch safety: leading batch dims / vmap fold into the grid
# --------------------------------------------------------------------------

def test_kmeans_assign_vmap_over_centers():
    X, _, _ = _data(300, 5, 13)
    Cs = jax.random.normal(jax.random.PRNGKey(3), (4, 5, 13))
    a_v, d_v = jax.vmap(lambda c: ops.kmeans_assign(X, c))(Cs)
    for b in range(4):
        a_b, d_b = ops.kmeans_assign(X, Cs[b])
        np.testing.assert_array_equal(np.asarray(a_v[b]), np.asarray(a_b))
        np.testing.assert_allclose(np.asarray(d_v[b]), np.asarray(d_b), rtol=1e-6)
    # leading-batch-dim form takes the same path
    a_l, d_l = ops.kmeans_assign(X, Cs)
    np.testing.assert_array_equal(np.asarray(a_l), np.asarray(a_v))


def test_leverage_vmap_both_batched():
    Xs = jax.random.normal(jax.random.PRNGKey(4), (3, 200, 17))
    A = jax.random.normal(jax.random.PRNGKey(5), (3, 17, 17))
    Ms = jnp.einsum("bij,bkj->bik", A, A) / 17.0
    out_v = jax.vmap(ops.leverage)(Xs, Ms)
    for b in range(3):
        np.testing.assert_allclose(np.asarray(out_v[b]),
                                   np.asarray(ops.leverage(Xs[b], Ms[b])),
                                   rtol=1e-5, atol=1e-5)
    out_l = ops.leverage(Xs, Ms)
    np.testing.assert_allclose(np.asarray(out_l), np.asarray(out_v), rtol=1e-6)


def test_fused_vmap_over_centers_and_parties():
    # seeds axis: X shared, C batched; block_n=64 -> 5-step grid, so the
    # scratch init/flush logic is exercised across steps under the
    # prepended vmap grid axis
    X, _, w = _data(300, 5, 13)
    Cs = jax.random.normal(jax.random.PRNGKey(6), (4, 5, 13))
    out_v = jax.vmap(lambda c: ops.kmeans_assign_update(X, c, w, block_n=64))(Cs)
    for b in range(4):
        out_b = ops.kmeans_assign_update(X, Cs[b], w, block_n=64)
        for o_v, o_b in zip(out_v, out_b):
            np.testing.assert_allclose(np.asarray(o_v[b]), np.asarray(o_b),
                                       rtol=1e-5, atol=1e-5)
    # party axis: X and C both batched, unit weights
    Xs = jax.random.normal(jax.random.PRNGKey(7), (3, 300, 13))
    Cp = jax.random.normal(jax.random.PRNGKey(8), (3, 5, 13))
    out_p = ops.kmeans_assign_update(Xs, Cp, block_n=64)
    for b in range(3):
        out_b = ops.kmeans_assign_update(Xs[b], Cp[b], block_n=64)
        for o_p, o_b in zip(out_p, out_b):
            np.testing.assert_allclose(np.asarray(o_p[b]), np.asarray(o_b),
                                       rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Stacked party view
# --------------------------------------------------------------------------

def test_stacked_view_pads_and_masks():
    ds = _dataset(jax.random.PRNGKey(9), n=50, d=8, T=3)   # dims (3, 3, 2)
    st = ds.stacked()
    assert st.blocks.shape == (3, 50, 3) and st.dims == (3, 3, 2)
    for j, p in enumerate(ds.parts):
        dj = p.shape[1]
        np.testing.assert_array_equal(np.asarray(st.blocks[j, :, :dj]), np.asarray(p))
        assert float(jnp.abs(st.blocks[j, :, dj:]).sum()) == 0.0
        np.testing.assert_array_equal(np.asarray(st.mask[j]),
                                      np.arange(3) < dj)


def test_stacked_view_appends_labels():
    ds = _dataset(jax.random.PRNGKey(10), n=40, d=6, T=3)  # dims (2, 2, 2)
    st = ds.stacked(with_labels=True)
    assert st.blocks.shape == (3, 40, 3) and st.dims == (2, 2, 3)
    np.testing.assert_array_equal(np.asarray(st.blocks[-1, :, 2]), np.asarray(ds.y))
    unlabeled = VFLDataset(ds.parts, None)
    with pytest.raises(ValueError):
        unlabeled.stacked(with_labels=True)


# --------------------------------------------------------------------------
# Batched construction on the kernels
# --------------------------------------------------------------------------

@pytest.mark.parametrize("task,kw", [
    ("vrlr", {}), ("vkmc", {"k": 3, "local_iters": 2})])
def test_batched_pallas_matches_ref(task, kw):
    """Acceptance: the batched builder runs with backend="pallas"
    (interpret on CPU) and agrees with the ref backend."""
    ds = _dataset(jax.random.PRNGKey(13), n=200, d=6, T=2)
    keys = jax.random.split(jax.random.PRNGKey(14), 2)
    gp = build_coresets_batched(task, ds, [25], keys=keys, backend="pallas", **kw)
    gr = build_coresets_batched(task, ds, [25], keys=keys, backend="ref", **kw)
    np.testing.assert_array_equal(np.asarray(gp.indices), np.asarray(gr.indices))
    np.testing.assert_allclose(np.asarray(gp.weights), np.asarray(gr.weights),
                               rtol=1e-5)


def test_kmeans_plusplus_cached_norm_d2_nonnegative():
    """The expanded-form D^2 seeding keeps sane geometry: centers are data
    rows and the incremental min-distances stay >= 0 (fp clamp)."""
    X = jax.random.normal(jax.random.PRNGKey(15), (500, 12)) * 3.0
    from repro.core.vkmc import kmeans_plusplus
    C = kmeans_plusplus(jax.random.PRNGKey(16), X, 6)
    Xn = np.asarray(X)
    for c in np.asarray(C):
        assert np.min(np.sum((Xn - c) ** 2, axis=1)) < 1e-6   # c is a data row
    # distinct centers with overwhelming probability on random data
    assert len({tuple(np.round(c, 5)) for c in np.asarray(C)}) == 6
