"""Deployment data made on the device from ``--seed``.

The YearPredictionMSD-shaped generator is a copy of the program's
``year_prediction_like`` (latent-factor features with a Pareto row tail,
labels from a noisy linear response around the year 1998), kept here so
that no change to the program can change what the benchmark feeds it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of up to 64 bits (benchmark seeds may
    exceed 32 signed bits)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _latent_features(key, n: int, d: int, n_latent: int, noise: float,
                     heavy_tail: float):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    Z = jax.random.normal(k1, (n, n_latent))
    W = jax.random.normal(k2, (n_latent, d)) / jnp.sqrt(n_latent)
    X = Z @ W + noise * jax.random.normal(k3, (n, d))
    u = jax.random.uniform(k4, (n, 1), minval=1e-3, maxval=1.0)
    return X * (1.0 + 0.1 * u ** (-heavy_tail))


@functools.partial(jax.jit, static_argnames=("n", "d"))
def year_msd(key, n: int, d: int):
    """(X (n, d) float32, y (n,) float32) in the YearPredictionMSD profile."""
    kx, kt, kn = jax.random.split(key, 3)
    X = _latent_features(kx, n, d, n_latent=12, noise=0.4, heavy_tail=0.4)
    theta = jax.random.normal(kt, (d,)) / jnp.sqrt(d)
    y = (1998.0 + 8.0 * (X @ theta) + 1.5 * jnp.tanh(X[:, 0])
         + 3.0 * jax.random.normal(kn, (n,)))
    return X, y


def party_widths(d: int, T: int):
    """Near-even column split, the first ``d % T`` parties one wider."""
    base, rem = divmod(d, T)
    return [base + (1 if j < rem else 0) for j in range(T)]


def split_parties(X, T: int):
    """Column slices of X, one per party, in party order."""
    out, lo = [], 0
    for w in party_widths(X.shape[1], T):
        out.append(X[:, lo:lo + w])
        lo += w
    return out
