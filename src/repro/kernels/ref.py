"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth; kernel tests sweep shapes/dtypes
and ``assert_allclose`` the Pallas output (interpret mode on CPU) against
these.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# f32 semantics for the short contractions on every backend (the TPU's
# DEFAULT f32 dot is one bf16 pass; see repro.core.sensitivity.HIGHEST)
HIGHEST = jax.lax.Precision.HIGHEST


def _batched(fn, *args, axes):
    """vmap ``fn`` over axis 0 of the args whose entry in ``axes`` is 0 —
    the oracles mirror the kernels' leading-batch-dim support, with the
    2-D path left bit-identical."""
    return jax.vmap(fn, in_axes=axes)(*args)


def kmeans_assign(X: jax.Array, C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(argmin_l ||x_i - c_l||^2, min_l ||x_i - c_l||^2).

    X: (n, d) float; C: (k, d) float.  Returns (int32 (n,), float32 (n,)).
    Leading batch dims on either operand vmap through.
    """
    if X.ndim > 2 or C.ndim > 2:
        return _batched(kmeans_assign, X, C,
                        axes=(0 if X.ndim > 2 else None,
                              0 if C.ndim > 2 else None))
    x2 = jnp.sum(X.astype(jnp.float32) ** 2, axis=1, keepdims=True)        # (n, 1)
    c2 = jnp.sum(C.astype(jnp.float32) ** 2, axis=1)[None, :]              # (1, k)
    xc = jnp.matmul(X.astype(jnp.float32), C.astype(jnp.float32).T,
                    precision=HIGHEST)                                     # (n, k)
    d2 = jnp.maximum(x2 + c2 - 2.0 * xc, 0.0)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


def kmeans_assign_update(
    X: jax.Array, C: jax.Array, w: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The fused kernel's semantic ground truth: assignment followed by the
    seed path's segment-sum composition.

    Returns (assign (n,) i32, d2 (n,) f32, csum (k, d) f32 = sum_i w_i x_i,
    wsum (k,) f32 = sum_i w_i, ccost (k,) f32 = sum_i w_i d2_i), grouped by
    assigned cluster.  With ``w=None`` weights default to ones, so wsum is
    the cluster size and ccost the cluster cost of Algorithm 3.
    """
    if X.ndim > 2 or C.ndim > 2 or (w is not None and w.ndim > 1):
        if w is None:
            return _batched(lambda x, c: kmeans_assign_update(x, c), X, C,
                            axes=(0 if X.ndim > 2 else None,
                                  0 if C.ndim > 2 else None))
        return _batched(kmeans_assign_update, X, C, w,
                        axes=(0 if X.ndim > 2 else None,
                              0 if C.ndim > 2 else None,
                              0 if w.ndim > 1 else None))
    n = X.shape[0]
    k = C.shape[0]
    assign, d2 = kmeans_assign(X, C)
    ww = jnp.ones((n,), jnp.float32) if w is None else w.astype(jnp.float32)
    wsum = jax.ops.segment_sum(ww, assign, num_segments=k)
    csum = jax.ops.segment_sum(
        ww[:, None] * X.astype(jnp.float32), assign, num_segments=k)
    ccost = jax.ops.segment_sum(ww * d2, assign, num_segments=k)
    return assign, d2, csum, wsum, ccost


def leverage(X: jax.Array, M: jax.Array) -> jax.Array:
    """Row-wise quadratic form x_i^T M x_i.  X: (n, d); M: (d, d) symmetric.
    Leading batch dims on either operand vmap through."""
    if X.ndim > 2 or M.ndim > 2:
        return _batched(leverage, X, M,
                        axes=(0 if X.ndim > 2 else None,
                              0 if M.ndim > 2 else None))
    Xf = X.astype(jnp.float32)
    Mf = M.astype(jnp.float32)
    return jnp.einsum("nd,de,ne->n", Xf, Mf, Xf, precision=HIGHEST)


def weighted_gram(X: jax.Array, w: jax.Array) -> jax.Array:
    """X^T diag(w) X.  X: (n, d); w: (n,).  Returns (d, d) float32."""
    if X.ndim > 2 or w.ndim > 1:
        return _batched(weighted_gram, X, w,
                        axes=(0 if X.ndim > 2 else None,
                              0 if w.ndim > 1 else None))
    Xf = X.astype(jnp.float32)
    return (Xf * w.astype(jnp.float32)[:, None]).T @ Xf
