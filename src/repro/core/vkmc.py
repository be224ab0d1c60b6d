"""Vertical k-means clustering (Definition 2.2): solvers and baselines.

  * ``kmeans_plusplus``  — D^2 seeding (Arthur & Vassilvitskii), weighted;
  * ``lloyd``            — weighted Lloyd iterations; the assignment step is
    the Pallas ``kmeans_assign`` kernel (the O(nkd) hot loop);
  * ``kmeans``           — seeding + Lloyd, the paper's KMEANS++ baseline;
  * ``distdim``          — Ding et al. [19] "k-means with distributed
    dimensions": the O(nT)-communication VFL baseline the paper compares
    against (each party clusters locally and ships *assignments*, the server
    clusters the concatenated local-center surrogates);
  * ``kmeans_cost``      — cost^C evaluation.

All solvers take optional per-point weights so they run unchanged on (S, w)
coresets (Theorem 2.5 composition).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.comm import CommLedger, null_ledger
from repro.core.sensitivity import HIGHEST, kmeans_assignment, kmeans_update
from repro.core.vfl import VFLDataset


def kmeans_cost(
    X: jax.Array, centers: jax.Array, w: Optional[jax.Array] = None, use_kernel: bool = True
) -> jax.Array:
    _, d2 = kmeans_assignment(X, centers, use_kernel=use_kernel)
    return jnp.sum(d2 if w is None else w * d2)


@functools.partial(jax.jit, static_argnames=("k",))
def kmeans_plusplus(
    key: jax.Array,
    X: jax.Array,
    k: int,
    w: Optional[jax.Array] = None,
) -> jax.Array:
    """Weighted D^2 seeding.  O(nkd) total, via incremental min-distances.

    Distances to each new center use the cached-norm expansion
    ``||x - c||^2 = ||x||^2 - 2 x.c + ||c||^2``: the per-step cost is one
    (n, d) matvec instead of materialising the full (n, d) difference —
    one fewer (n, d) array per seeding step, and the row norms ``||x||^2``
    are computed once for the whole sweep.  The matvec runs at HIGHEST,
    as every distance dot of the package does: the TPU's DEFAULT (one bf16
    pass) rounds the cross term by about 2^-9 (see
    :data:`repro.core.sensitivity.HIGHEST`), enough to change which row a
    D^2 draw takes at a near-tie.

    Jitted like :func:`lloyd`: one compiled program per shape and ``k``,
    where an eager call (or an eager ``vmap`` of one) would trace and
    lower the seeding's scan anew on every call.
    """
    n, d = X.shape
    ww = jnp.ones((n,)) if w is None else jnp.maximum(w, 0.0)
    x2 = jnp.sum(X * X, axis=1)                                    # cached once

    def d2_to(c):
        # clamp: the expanded form can go slightly negative under fp
        return jnp.maximum(x2 - 2.0 * jnp.matmul(X, c, precision=HIGHEST)
                           + jnp.sum(c * c), 0.0)

    k0, key = jax.random.split(key)
    first = jax.random.categorical(k0, jnp.log(jnp.maximum(ww, 1e-30)))
    centers0 = jnp.zeros((k, d), X.dtype).at[0].set(X[first])
    d2_0 = d2_to(X[first])

    def body(carry, key_l):
        centers, d2, l = carry
        probs = jnp.maximum(ww * d2, 1e-30)
        idx = jax.random.categorical(key_l, jnp.log(probs))
        c_new = X[idx]
        centers = centers.at[l].set(c_new)
        d2 = jnp.minimum(d2, d2_to(c_new))
        return (centers, d2, l + 1), None

    keys = jax.random.split(key, k - 1)
    (centers, _, _), _ = jax.lax.scan(body, (centers0, d2_0, 1), keys)
    return centers


@functools.partial(jax.jit, static_argnames=("iters", "use_kernel"))
def lloyd(
    X: jax.Array,
    init_centers: jax.Array,
    w: Optional[jax.Array] = None,
    iters: int = 25,
    use_kernel: bool = True,
) -> jax.Array:
    """Weighted Lloyd. Empty clusters keep their previous center.

    With ``use_kernel=True`` each iteration is ONE fused
    ``kmeans_assign_update`` dispatch (one HBM read of X: assignment,
    weighted cluster sums and counts come out of the same pass — the seed
    path's assign kernel + two segment_sums collapsed).  ``use_kernel=False``
    keeps the 3-pass pure-jnp composition.
    """
    n, d = X.shape
    ww = jnp.ones((n,)) if w is None else w

    def body(centers, _):
        _, _, csum, wsum, _ = kmeans_update(X, centers, ww, use_kernel=use_kernel)
        new = jnp.where(wsum[:, None] > 0, csum / jnp.maximum(wsum, 1e-30)[:, None], centers)
        return new, None

    centers, _ = jax.lax.scan(body, init_centers, None, length=iters)
    return centers


def kmeans(
    key: jax.Array,
    X: jax.Array,
    k: int,
    w: Optional[jax.Array] = None,
    iters: int = 25,
    use_kernel: bool = True,
) -> jax.Array:
    """k-means++ seeding + Lloyd — the paper's KMEANS++ central baseline."""
    init = kmeans_plusplus(key, X, k, w)
    return lloyd(X, init, w, iters=iters, use_kernel=use_kernel)


def kmeans_central_comm_cost(n: int, dims, ledger: Optional[CommLedger] = None) -> int:
    """Central baseline ships all raw blocks: sum_j n*d_j units."""
    led = null_ledger(ledger)
    for j, dj in enumerate(dims):
        led.party_to_server("kmeans_central/raw_block", j, n * int(dj))
    return led.total


# --------------------------------------------------------------------------
# DistDim (Ding et al. 2016): the O(nT) VFL baseline
# --------------------------------------------------------------------------

def distdim(
    key: jax.Array,
    ds: VFLDataset,
    k: int,
    w: Optional[jax.Array] = None,
    local_iters: int = 15,
    global_iters: int = 25,
    ledger: Optional[CommLedger] = None,
    use_kernel: bool = True,
) -> jax.Array:
    """K-means with distributed dimensions.

    Party j clusters its block into k local centers and sends (i) the n-vector
    of local assignments and (ii) its k local centers to the server
    (communication n + k*d_j each -> O(nT) total, the cost the paper
    improves on).  The server replaces each point by the concatenation of its
    local centers (the product-partition surrogate) and runs weighted k-means
    over the surrogate points; the returned global centers live in R^d.
    """
    led = null_ledger(ledger)
    T = ds.T
    n = ds.n
    surrogate_parts: List[jax.Array] = []
    for j, Xj in enumerate(ds.parts):
        key, sub = jax.random.split(key)
        local_c = kmeans(sub, Xj, k, w, iters=local_iters, use_kernel=use_kernel)
        assign, _ = kmeans_assignment(Xj, local_c, use_kernel=use_kernel)
        surrogate_parts.append(local_c[assign])                     # (n, d_j)
        led.party_to_server("distdim/assignments", j, n)
        led.party_to_server("distdim/local_centers", j, k * Xj.shape[1])
    surrogate = jnp.concatenate(surrogate_parts, axis=1)            # (n, d)
    key, sub = jax.random.split(key)
    return kmeans(sub, surrogate, k, w, iters=global_iters, use_kernel=use_kernel)
