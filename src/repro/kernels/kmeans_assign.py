"""Pallas TPU kernel: blocked k-means assignment (distance + argmin).

This is the O(n*k*d) hot loop of Algorithm 3 (VKMC sensitivities) and of the
Lloyd/k-means++ solvers — by far the dominant FLOP cost of the paper's
clustering pipeline at scale.

TPU-native design (vs. the usual CUDA one-thread-per-point port):
  * the (bn, d) x (d, k) distance cross-term runs on the MXU as a single
    matmul per tile — tiles are chosen as multiples of (8, 128) so the
    systolic array is fully fed;
  * points are tiled over the grid's only axis; the full center block
    (k_pad, d_pad) stays resident in VMEM across the sweep (centers are tiny:
    k <= O(1e3)), so HBM traffic is exactly one read of X — the kernel is
    memory-bound at roofline, arithmetic intensity ~ k MAC/byte;
  * min + argmin are computed in-register on the (bn, k_pad) distance tile;
    padded center columns are masked to +inf;
  * both outputs leave as lane-dense (1, n_pad) rows, which Mosaic and XLA
    tile alike, also with a batch axis folded into the grid.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, cn_ref, assign_ref, d2_ref, *, k: int):
    """One grid step: assign a (bn, d_pad) tile of points.

    x_ref:  (bn, d_pad) points tile            (VMEM)
    c_ref:  (k_pad, d_pad) all centers         (VMEM, same block every step)
    cn_ref: (1, k_pad) precomputed ||c||^2     (VMEM)
    assign_ref: (1, bn) int32 out              (lane-dense row)
    d2_ref: (1, bn) float32 out
    """
    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)                 # (bn, 1)
    # MXU: (bn, d) @ (d, k_pad)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )                                                          # (bn, k_pad)
    d2 = x2 + cn_ref[...] - 2.0 * xc
    k_pad = d2.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2 = jnp.where(col < k, d2, jnp.inf)                       # mask padding
    assign_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)[None, :]
    d2_ref[...] = jnp.maximum(jnp.min(d2, axis=1), 0.0)[None, :]


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(
    X: jax.Array,
    C: jax.Array,
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Blocked assignment.  X: (n, d); C: (k, d) -> (assign int32 (n,), d2 f32 (n,)).

    Leading batch dimensions on either operand (X (..., n, d), C (..., k, d))
    fold into the grid via the native pallas_call batching rule — one
    dispatch, no broadcast of the unbatched operand.
    """
    if X.ndim > 2 or C.ndim > 2:
        return jax.vmap(
            lambda x, c: kmeans_assign(x, c, block_n=block_n, interpret=interpret),
            in_axes=(0 if X.ndim > 2 else None, 0 if C.ndim > 2 else None),
        )(X, C)
    n, d = X.shape
    k = C.shape[0]
    # MXU/VPU alignment: lanes = 128, sublanes = 8.
    d_pad = _round_up(max(d, 1), 128)
    k_pad = _round_up(max(k, 1), 128)
    bn = min(block_n, _round_up(n, 128))
    n_pad = _round_up(n, bn)

    Xp = jnp.zeros((n_pad, d_pad), X.dtype).at[:n, :d].set(X)
    Cp = jnp.zeros((k_pad, d_pad), C.dtype).at[:k, :d].set(C)
    cn = jnp.sum(Cp.astype(jnp.float32) ** 2, axis=1)[None, :]  # (1, k_pad)

    grid = (n_pad // bn,)
    assign, d2 = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_assign",
    )(Xp, Cp, cn)
    return assign[0, :n], d2[0, :n]
