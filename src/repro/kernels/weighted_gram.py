"""Pallas TPU kernel: weighted Gram accumulation G = X^T diag(w) X.

The coreset-side ridge solve (Theorem 2.5's downstream scheme A) reduces to
normal equations over the *weighted* coreset; at full-data scale the same
primitive builds each party's local Gram for leverage scoring.  The kernel
streams X through VMEM in (bn, d) tiles and accumulates the (d, d) output
block in place across the grid — a classic TPU reduction pattern (the output
BlockSpec maps every grid step to the same block, initialised at step 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)                     # (bn, d_pad)
    w = w_ref[...].astype(jnp.float32)                     # (bn, 1)
    xw = x * w                                             # VPU broadcast
    out_ref[...] += jax.lax.dot_general(
        xw, x, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )                                                      # MXU (d, d) update


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def weighted_gram(
    X: jax.Array,
    w: jax.Array,
    *,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """X: (n, d); w: (n,) -> (d, d) float32 = X^T diag(w) X.

    Leading batch dimensions (X (..., n, d), w (..., n)) fold into the grid
    via the native pallas_call batching rule — the streaming Gram block-scan
    uses this with both operands batched over the party axis.
    """
    if X.ndim > 2 or w.ndim > 1:
        return jax.vmap(
            lambda x, ww: weighted_gram(x, ww, block_n=block_n,
                                        interpret=interpret),
            in_axes=(0 if X.ndim > 2 else None, 0 if w.ndim > 1 else None),
        )(X, w)
    n, d = X.shape
    d_pad = _round_up(max(d, 1), 128)
    bn = min(block_n, _round_up(n, 8))
    n_pad = _round_up(n, bn)

    Xp = jnp.zeros((n_pad, d_pad), X.dtype).at[:n, :d].set(X)
    wp = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(w.astype(jnp.float32))

    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((bn, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((d_pad, d_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad, d_pad), jnp.float32),
        interpret=interpret,
        name="weighted_gram",
    )(Xp, wp)
    return out[:d, :d]
