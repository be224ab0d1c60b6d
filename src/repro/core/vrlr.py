"""Vertical regularized linear regression (Definition 2.1): objectives and
solvers.

Solvers implemented from scratch in JAX (no sklearn in the image):
  * ``ridge_closed_form``  — weighted normal equations (the paper's CENTRAL
    baseline for R(theta)=lambda*||theta||^2), Gram built by the Pallas
    ``weighted_gram`` kernel;
  * ``fista``              — proximal gradient for lasso / elastic net
    (appendix A.2 regularizers);
  * ``saga``               — Defazio et al. incremental gradient, run "in a
    VFL fashion": each step touches one row, whose inner products require a
    scalar from every party, accounted per-step on the CommLedger (this is
    why full-data SAGA costs ~1e8 units in Table 1).

All solvers accept per-row weights so they run unchanged on (S, w) coresets —
exactly the composition of Theorem 2.5.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.comm import CommLedger, null_ledger
from repro.core.sensitivity import HIGHEST
from repro.kernels import ops as kops


# --------------------------------------------------------------------------
# Objectives (Definitions 2.1 / 2.3)
# --------------------------------------------------------------------------

def sq_loss(X: jax.Array, y: jax.Array, theta: jax.Array, w: Optional[jax.Array] = None) -> jax.Array:
    r = jnp.matmul(X, theta, precision=HIGHEST) - y   # contraction over d
    if w is None:
        return jnp.sum(r * r)
    return jnp.sum(w * r * r)


def ridge_cost(X, y, theta, lam: float, w=None) -> jax.Array:
    """cost^R with R(theta) = lam * ||theta||^2."""
    return sq_loss(X, y, theta, w) + lam * jnp.sum(theta * theta)


def lasso_cost(X, y, theta, lam: float, w=None) -> jax.Array:
    return sq_loss(X, y, theta, w) + lam * jnp.sum(jnp.abs(theta))


def elastic_cost(X, y, theta, lam1: float, lam2: float, w=None) -> jax.Array:
    return sq_loss(X, y, theta, w) + lam1 * jnp.sum(jnp.abs(theta)) + lam2 * jnp.sum(theta * theta)


# --------------------------------------------------------------------------
# Closed-form weighted ridge (CENTRAL)
# --------------------------------------------------------------------------

def ridge_closed_form(
    X: jax.Array, y: jax.Array, lam: float, w: Optional[jax.Array] = None
) -> jax.Array:
    """argmin_theta sum_i w_i (x_i^T theta - y_i)^2 + lam ||theta||^2."""
    n, d = X.shape
    ww = jnp.ones((n,)) if w is None else w
    G = kops.weighted_gram(X, ww) + lam * jnp.eye(d, dtype=jnp.float32)
    b = X.T @ (ww * y)
    return jnp.linalg.solve(G, b.astype(jnp.float32))


def central_comm_cost(n: int, dims, ledger: Optional[CommLedger] = None) -> int:
    """CENTRAL transfers every party's raw block to the server: n * d_j each
    (plus labels already at the server's side party).  Matches Table 1's
    4.2e7 for (n=463715, d=90)."""
    led = null_ledger(ledger)
    for j, dj in enumerate(dims):
        led.party_to_server("central/raw_block", j, n * int(dj))
    return led.total


# --------------------------------------------------------------------------
# FISTA for lasso / elastic net
# --------------------------------------------------------------------------

def _soft(x: jax.Array, t) -> jax.Array:
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


@functools.partial(jax.jit, static_argnames=("iters",))
def fista(
    X: jax.Array,
    y: jax.Array,
    lam1: float,
    lam2: float = 0.0,
    w: Optional[jax.Array] = None,
    iters: int = 500,
) -> jax.Array:
    """Proximal-gradient solve of weighted lasso/elastic net.

    min_theta sum w_i (x_i^T theta - y_i)^2 + lam1 |theta|_1 + lam2 |theta|_2^2
    """
    n, d = X.shape
    ww = jnp.ones((n,)) if w is None else w
    Xw = X * ww[:, None]
    # Lipschitz constant of the smooth part: 2*(sigma_max(X^T W X) + lam2)
    G = Xw.T @ X
    L = 2.0 * (jnp.linalg.norm(G, ord=2) + lam2) + 1e-6
    b = Xw.T @ y

    def smooth_grad(theta):
        return 2.0 * (G @ theta - b + lam2 * theta)

    def body(_, carry):
        theta, z, t = carry
        theta_new = _soft(z - smooth_grad(z) / L, lam1 / L)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = theta_new + (t - 1.0) / t_new * (theta_new - theta)
        return theta_new, z_new, t_new

    theta0 = jnp.zeros((d,), jnp.float32)
    theta, _, _ = jax.lax.fori_loop(0, iters, body, (theta0, theta0, jnp.float32(1.0)))
    return theta


# --------------------------------------------------------------------------
# SAGA in the VFL fashion
# --------------------------------------------------------------------------

def saga_ridge(
    key: jax.Array,
    X: jax.Array,
    y: jax.Array,
    lam: float,
    w: Optional[jax.Array] = None,
    steps: int = 20000,
    lr: Optional[float] = None,
    dims: Optional[Tuple[int, ...]] = None,
    ledger: Optional[CommLedger] = None,
) -> jax.Array:
    """SAGA on the (weighted) ridge objective, with VFL comm accounting.

    Per step on row i: every party j sends the scalar partial inner product
    x_i^(j).theta^(j) to the server (T units), the server returns the shared
    residual scalar to every party (T units) -> 2T units/step.  Parameter
    updates stay party-local.  (This per-step 2T is what makes full-data
    SAGA's communication blow up to O(steps*T) ~ 1e8 in Table 1.)
    """
    n, d = X.shape
    ww = jnp.ones((n,)) if w is None else w
    lam_n = lam / n
    if lr is None:
        # 1/(3 * max_i L_i): per-sample smoothness of f_i = w_i(x'th-y)^2 + lam/n |th|^2
        L = 2.0 * jnp.max(ww * jnp.sum(X * X, axis=1)) + 2.0 * lam_n
        lr = float(1.0 / (3.0 * jnp.maximum(L, 1e-9)))

    def grad_i(theta, i):
        r = X[i] @ theta - y[i]
        return 2.0 * ww[i] * r * X[i] + 2.0 * lam_n * theta

    @jax.jit
    def run(key, theta0):
        table0 = jnp.zeros((n, d), jnp.float32)  # stored per-row gradients
        avg0 = jnp.zeros((d,), jnp.float32)

        def body(carry, k):
            theta, table, avg = carry
            i = jax.random.randint(k, (), 0, n)
            g_new = grad_i(theta, i)
            g_old = table[i]
            theta = theta - lr * (g_new - g_old + avg)
            avg = avg + (g_new - g_old) / n
            table = table.at[i].set(g_new)
            return (theta, table, avg), None

        keys = jax.random.split(key, steps)
        (theta, _, _), _ = jax.lax.scan(body, (theta0, table0, avg0), keys)
        return theta

    theta = run(key, jnp.zeros((d,), jnp.float32))
    if ledger is not None:
        T = len(dims) if dims is not None else 1
        ledger.party_to_server("saga/partials", 0, steps * T)
        ledger.server_to_party("saga/residuals", 0, steps * T)
    return theta


def solve(
    kind: str,
    X: jax.Array,
    y: jax.Array,
    w: Optional[jax.Array] = None,
    *,
    lam: float = 0.0,
    lam1: float = 0.0,
    lam2: float = 0.0,
    key: Optional[jax.Array] = None,
    saga_steps: int = 20000,
    saga_lr: float = 1e-3,
) -> jax.Array:
    """Uniform solver entry point used by benchmarks."""
    if kind == "ridge":
        return ridge_closed_form(X, y, lam, w)
    if kind == "linear":
        return ridge_closed_form(X, y, 1e-6, w)  # tiny jitter for conditioning
    if kind == "lasso":
        return fista(X, y, lam1, 0.0, w)
    if kind == "elastic":
        return fista(X, y, lam1, lam2, w)
    if kind == "saga":
        assert key is not None
        return saga_ridge(key, X, y, lam, w, steps=saga_steps, lr=saga_lr)
    raise ValueError(f"unknown solver {kind!r}")
