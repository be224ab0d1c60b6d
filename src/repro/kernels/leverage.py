"""Pallas TPU kernel: row-wise quadratic form lev_i = x_i^T M x_i.

This is the O(n*d^2) hot loop of Algorithm 2 (VRLR leverage scores): after a
party inverts its (d_j x d_j) local Gram matrix once, every row's leverage
score is a quadratic form against that inverse.  On TPU the (bn, d) @ (d, d)
product runs on the MXU; the Hadamard-and-reduce epilogue runs on the VPU in
the same VMEM residency, so X is read from HBM exactly once.  The scores
leave as a lane-dense (1, n_pad) row: a 1-D output is tiled differently by
Mosaic and XLA, and a batched 1-D block breaks Mosaic's (8, 128) rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, m_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)                       # (bn, d_pad)
    m = m_ref[...].astype(jnp.float32)                       # (d_pad, d_pad)
    xm = jax.lax.dot_general(
        x, m, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )                                                        # (bn, d_pad)
    out_ref[...] = jnp.sum(xm * x, axis=1)[None, :]         # (1, bn) lane-dense


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def leverage(
    X: jax.Array,
    M: jax.Array,
    *,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """X: (n, d); M: (d, d) -> (n,) float32 quadratic forms.

    Leading batch dimensions (X (..., n, d), M (..., d, d)) fold into the
    grid via the native pallas_call batching rule — one dispatch per call,
    stacked-party scoring uses this with both operands batched over T.
    """
    if X.ndim > 2 or M.ndim > 2:
        return jax.vmap(
            lambda x, m: leverage(x, m, block_n=block_n, interpret=interpret),
            in_axes=(0 if X.ndim > 2 else None, 0 if M.ndim > 2 else None),
        )(X, M)
    n, d = X.shape
    d_pad = _round_up(max(d, 1), 128)
    bn = min(block_n, _round_up(n, 128))
    n_pad = _round_up(n, bn)

    Xp = jnp.zeros((n_pad, d_pad), X.dtype).at[:n, :d].set(X)
    Mp = jnp.zeros((d_pad, d_pad), jnp.float32).at[:d, :d].set(M.astype(jnp.float32))

    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((bn, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((d_pad, d_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
        name="leverage",
    )(Xp, Mp)
    return out[0, :n]
