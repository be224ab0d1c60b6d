"""Party-local sensitivity scores — the per-problem halves of Algorithms 2
(VRLR) and 3 (VKMC).

Everything here is computed from ONE party's block `X^(j)` only; the
cross-party combination happens inside DIS (Algorithm 1).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref

# The TPU's DEFAULT precision for an f32 dot is one bf16 pass.  Over a short
# contraction (a quadratic form, a k-means cross term) its 2^-9 rounding
# survives into the result — measured on a v5e at the paper's
# YearPredictionMSD size: 2.3e-2 per-row leverage error, 2.1e-3 in k-means
# distances — so those dots run at HIGHEST.  A Gram's contraction over all
# n rows averages the rounding out (5.8e-5 per-row score error at DEFAULT,
# inside the float32 reference tolerance), so the Grams keep DEFAULT.
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# Algorithm 2: VRLR leverage scores
# --------------------------------------------------------------------------

def leverage_scores(Xj: jax.Array, rcond: float = 1e-6, use_kernel: bool = True) -> jax.Array:
    """Row leverage scores ||u_i^(j)||^2 of the orthonormal basis U^(j) of
    col(X^(j)).

    Computed Gram-side: lev_i = x_i^T (X^T X)^+ x_i, which equals the QR-row
    norm but costs O(n d^2 + d^3) instead of an n x d QR, and whose O(n d^2)
    inner loop is the Pallas ``leverage`` kernel (row-wise quadratic form).
    Handles rank deficiency via the equilibrated eigen-pseudo-inverse of
    :func:`batched_gram_pinv`.
    """
    Xj = jnp.asarray(Xj)
    G = Xj.T @ Xj                                             # (d_j, d_j)
    M = batched_gram_pinv(G[None], rcond)[0]                  # pinv of Gram
    if use_kernel:
        lev = kops.leverage(Xj, M)                  # row-wise x_i^T M x_i
    else:
        lev = jnp.einsum("nd,de,ne->n", Xj, M, Xj, precision=HIGHEST)
    # numerical clamp: true leverage lies in [0, 1]
    return jnp.clip(lev, 0.0, 1.0)


def ridge_leverage_scores(
    X: jax.Array, ridge: float = 1e-4, use_kernel: bool = False
) -> jax.Array:
    """Regularised leverage x_i^T (X^T X + ridge*I)^{-1} x_i, clipped to [0,1].

    The well-conditioned variant used on mesh feature slices (the selector's
    per-shard scores); :func:`leverage_scores` is the exact pseudo-inverse
    form for the paper-fidelity path.
    """
    f32 = X.astype(jnp.float32)
    dl = f32.shape[-1]
    G = f32.T @ f32 + ridge * jnp.eye(dl, dtype=jnp.float32)
    M = jnp.linalg.inv(G)
    if use_kernel:
        lev = kops.leverage(f32, M)
    else:
        lev = jnp.einsum("nd,de,ne->n", f32, M, f32)
    return jnp.clip(lev, 0.0, 1.0)


def norm_scores(X: jax.Array) -> jax.Array:
    """Plain row-norm^2 — the cheap ablation backend shared by the selector
    and the ``norm`` ScoreBackend of :mod:`repro.core.api`."""
    f32 = X.astype(jnp.float32)
    return jnp.sum(f32 * f32, axis=-1)


def vrlr_local_scores(
    Xj: jax.Array, y: Optional[jax.Array] = None, use_kernel: bool = True
) -> jax.Array:
    """Algorithm 2 lines 2-3: g_i^(j) = ||u_i^(j)||^2 + 1/n.

    Party T passes its labels: the basis is taken over [X^(T), y].
    """
    if y is not None:
        Xj = jnp.concatenate([Xj, y[:, None]], axis=1)
    n = Xj.shape[0]
    return leverage_scores(Xj, use_kernel=use_kernel) + 1.0 / n


def batched_gram_pinv(G: jax.Array, rcond: float = 1e-6,
                      return_cond: bool = False, expected_rank=None):
    """Eigen-pseudo-inverse of a (T, s, s) stack of party Grams.

    The shared core of :func:`vrlr_scores_stacked` (one-shot Gram) and the
    streaming block-scan path (:mod:`repro.core.streaming`, Gram accumulated
    over row blocks): zero padding contributes zero eigenvalues that fall
    below the rcond cutoff, so the batched pinv equals the per-party one
    embedded.  The rcond cutoff is itself the conditioning guardrail — the
    retained spectrum's condition number never exceeds 1/rcond, and a fully
    degenerate Gram (constant-zero feature slice) inverts to the zero
    matrix instead of exploding.

    ``return_cond=True`` additionally returns the (T,) retained condition
    numbers (top eigenvalue over the smallest eigenvalue clearing the
    cutoff; +inf when nothing clears it) for the build's
    :class:`~repro.core.integrity.HealthReport`.  Zero-padded columns
    contribute legitimate below-cutoff eigenvalues, so real rank
    deficiency is detected against ``expected_rank`` (the per-party valid
    widths): a party whose RETAINED rank falls short — a constant or
    duplicated feature slice — reports +inf.  The pinv itself is
    bit-identical either way.

    The Gram is Jacobi-equilibrated first (G -> D G D, D = diag(G)^-1/2,
    and M = D pinv(D G D) D).  Leverage is invariant to column scaling, but
    the rcond cutoff is not: an un-centered label column (YearPredictionMSD's
    years, ~2000) puts the top eigenvalue ~10^7 above the feature spectrum,
    so an unscaled cutoff drops real feature directions, and which ones
    flips with float32 rounding.  The cutoff and the condition numbers are
    therefore those of the equilibrated Gram; an all-zero column scales by
    0 and stays a zero eigenvalue.
    """
    dg = jnp.diagonal(G, axis1=1, axis2=2)                 # (T, s)
    scale = jnp.where(dg > 0, jax.lax.rsqrt(jnp.where(dg > 0, dg, 1.0)), 0.0)
    Gs = G * scale[:, :, None] * scale[:, None, :]
    evals, evecs = jnp.linalg.eigh(Gs)
    top = jnp.maximum(evals.max(axis=1), 0.0)              # (T,)
    cutoff = rcond * top
    keep = evals > cutoff[:, None]
    inv = jnp.where(keep, 1.0 / jnp.maximum(evals, 1e-30), 0.0)
    M = jnp.einsum("tsu,tu,tru->tsr", evecs, inv, evecs, precision=HIGHEST)
    M = M * scale[:, :, None] * scale[:, None, :]
    if not return_cond:
        return M
    small = jnp.min(jnp.where(keep, evals, jnp.inf), axis=1)
    cond = jnp.where(jnp.isfinite(small) & (small > 0.0),
                     top / jnp.maximum(small, 1e-30), jnp.inf)
    if expected_rank is not None:
        rank = keep.sum(axis=1)
        cond = jnp.where(rank < jnp.asarray(expected_rank), jnp.inf, cond)
    return M, cond


def vrlr_scores_stacked(
    blocks: jax.Array, rcond: float = 1e-6, use_kernel: bool = True
) -> jax.Array:
    """Algorithm 2 lines 2-3 for ALL parties in one dispatch.

    ``blocks`` is the (T, n, s) zero-padded stack from
    :meth:`repro.core.vfl.VFLDataset.stacked` (labels already appended to
    party T's block).  Zero padding is transparent: the padded Gram gains
    zero rows/columns whose eigenvalues fall below the rcond cutoff, so the
    batched eigen-pseudo-inverse equals the per-party one embedded, and the
    rows' quadratic forms are untouched (x is 0 on padded coordinates).
    Returns (T, n) scores.  The O(T n s^2) row sweep is ONE batched
    ``leverage`` kernel call (party axis folded into the grid).
    """
    f = blocks.astype(jnp.float32)
    T, n, s = f.shape
    G = jnp.einsum("tns,tnu->tsu", f, f)                   # (T, s, s)
    M = batched_gram_pinv(G, rcond)                        # batched pinv(Gram)
    if use_kernel:
        lev = kops.leverage(f, M)                          # (T, n), one dispatch
    else:
        lev = jnp.einsum("tns,tsr,tnr->tn", f, M, f, precision=HIGHEST)
    return jnp.clip(lev, 0.0, 1.0) + 1.0 / n


# --------------------------------------------------------------------------
# Algorithm 3: VKMC local sensitivities
# --------------------------------------------------------------------------

def kmeans_assignment(
    Xj: jax.Array, centers: jax.Array, use_kernel: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """(argmin_l d(x_i, c_l), min_l d(x_i, c_l)^2) — the O(nkd) hot loop,
    served by the Pallas ``kmeans_assign`` kernel."""
    if use_kernel:
        return kops.kmeans_assign(Xj, centers)
    d2 = (
        jnp.sum(Xj * Xj, axis=1, keepdims=True)
        - 2.0 * jnp.matmul(Xj, centers.T, precision=HIGHEST)
        + jnp.sum(centers * centers, axis=1)[None, :]
    )
    d2 = jnp.maximum(d2, 0.0)
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


def kmeans_update(
    Xj: jax.Array,
    centers: jax.Array,
    w: Optional[jax.Array] = None,
    use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused Lloyd read: (assign, d2, csum, wsum, ccost).

    ``use_kernel=True`` is the single-pass Pallas ``kmeans_assign_update``
    kernel (one HBM read of X per Lloyd iteration, no segment_sum);
    ``use_kernel=False`` is the pure-jnp assignment + segment-sum
    composition — the seed's 3-pass data flow, kept as the semantic oracle.
    """
    if use_kernel:
        return kops.kmeans_assign_update(Xj, centers, w)
    return kref.kmeans_assign_update(Xj, centers, w)


def vkmc_local_scores(
    Xj: jax.Array,
    centers: jax.Array,
    alpha: float,
    use_kernel: bool = True,
) -> jax.Array:
    """Algorithm 3 lines 3-11 for one party.

    g_i^(j) = alpha*d(x_i, c_pi(i))^2 / cost
            + alpha * (sum_{i' in B_pi(i)} d(x_i', c_pi(i'))^2) / (|B_pi(i)| * cost)
            + 2*alpha / |B_pi(i)|

    ``cluster_cost``/``cluster_size`` fall out of the same fused pass that
    computes the assignment (unit weights: wsum = |B_l|, ccost = cost_l) —
    the scoring pass reads X exactly once.
    """
    assign, d2, _, cluster_size, cluster_cost = kmeans_update(
        Xj, centers, use_kernel=use_kernel)
    cost = jnp.maximum(d2.sum(), 1e-30)
    cluster_size = jnp.maximum(cluster_size, 1.0)
    term1 = alpha * d2 / cost
    term2 = alpha * cluster_cost[assign] / (cluster_size[assign] * cost)
    term3 = 2.0 * alpha / cluster_size[assign]
    return term1 + term2 + term3


def total_sensitivity_bound_vrlr(dims, T: int) -> float:
    """Thm 4.2: G = sum_j d'_j + T <= d + T + 1 (used by tests)."""
    return float(sum(dims) + T)


def total_sensitivity_bound_vkmc(k: int, T: int, alpha: float) -> float:
    """Lemma F.2: G = 2(k+1) * alpha * T exactly (used by tests)."""
    return 2.0 * (k + 1) * alpha * T
