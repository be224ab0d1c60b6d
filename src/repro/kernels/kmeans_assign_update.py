"""Pallas TPU kernel: fused single-pass k-means assign + cluster update.

One Lloyd iteration of the seed path is three separate passes over the
data: the ``kmeans_assign`` kernel (distances + argmin, one X-sized HBM
read) and two ``segment_sum`` scatters — the coordinate-sum scatter
streams X again (a second X-sized read, plus its (n, d) weighted temp),
the weight-sum scatter streams the (n,) weights.  This kernel collapses
all of it to exactly ONE pass over X: in the same VMEM residency that
computes each (bn, d) tile's distances it also accumulates, into output
blocks that stay resident across the sequential grid,

  * ``csum``  (k, d) — per-cluster weighted coordinate sums  sum_i w_i x_i,
  * ``wsum``  (k,)   — per-cluster weight mass               sum_i w_i,
  * ``ccost`` (k,)   — per-cluster weighted cost             sum_i w_i d2_i,

(the last two as the rows of one lane-dense (2, k_pad) block).  With
unit weights ``wsum``/``ccost`` are the cluster sizes and costs Algorithm 3
(VKMC sensitivities) needs — so the scoring pass gets them for free from
the assignment read.

The per-tile cluster reduction is a one-hot matmul on the MXU:
``csum += (w * onehot(assign))^T @ x`` — a (bn, k) x (bn, d) contraction,
the transpose-side twin of the distance matmul, so arithmetic intensity
stays ~2k MAC/byte while X-sized HBM reads drop from 2 to 1 (and the
n-sized weight scatter disappears entirely).

Leading batch dimensions (stacked parties, multi-seed grids) fold into the
grid through jax.vmap's native pallas_call batching rule — the batch
becomes a new leading grid axis; unbatched operands are NOT broadcast, and
the accumulators re-initialise per batch step because the i == 0 condition
is evaluated on the original (remapped) grid axis.  Every output block has
its last two dims either (8, 128)-aligned or equal to the array's, so the
batched form lowers for Mosaic too.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(
    x_ref, c_ref, cn_ref, w_ref,
    assign_ref, d2_ref, csum_ref, stat_ref,
    *, k: int,
):
    """One grid step: assign a (bn, d_pad) tile and fold it into the
    resident accumulator outputs.

    x_ref:   (bn, d_pad) points tile             (VMEM)
    c_ref:   (k_pad, d_pad) all centers          (VMEM, same block every step)
    cn_ref:  (1, k_pad) precomputed ||c||^2      (VMEM)
    w_ref:   (bn, 1) per-point weights           (VMEM; 0 on padded rows)
    assign_ref: (1, bn) int32 out                (lane-dense row)
    d2_ref:  (1, bn) float32 out
    csum_ref: (k_pad, d_pad) out — csum accumulator, same block every step
    stat_ref: (2, k_pad) out — [wsum; ccost] accumulators, same block
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        csum_ref[...] = jnp.zeros_like(csum_ref)
        stat_ref[...] = jnp.zeros_like(stat_ref)

    x = x_ref[...].astype(jnp.float32)                         # (bn, d_pad)
    c = c_ref[...].astype(jnp.float32)                         # (k_pad, d_pad)
    w = w_ref[...].astype(jnp.float32)                         # (bn, 1)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)                 # (bn, 1)
    # MXU: (bn, d) @ (d, k_pad) — same distance tile as kmeans_assign
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )                                                          # (bn, k_pad)
    d2 = x2 + cn_ref[...] - 2.0 * xc
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2 = jnp.where(col < k, d2, jnp.inf)                       # mask padding
    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)
    d2min = jnp.maximum(jnp.min(d2, axis=1), 0.0)
    assign_ref[...] = assign[None, :]
    d2_ref[...] = d2min[None, :]

    # weighted one-hot fold: wh[i, l] = w_i * [assign_i == l]
    wh = jnp.where(col == assign[:, None], w, 0.0)             # (bn, k_pad)
    # MXU: (k_pad, bn) @ (bn, d_pad) — per-cluster coordinate sums
    csum_ref[...] += jax.lax.dot_general(
        wh, x, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    stat_ref[0:1, :] += jnp.sum(wh, axis=0, keepdims=True)
    stat_ref[1:2, :] += jnp.sum(wh * d2min[:, None], axis=0, keepdims=True)


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_update(
    X: jax.Array,
    C: jax.Array,
    w: Optional[jax.Array] = None,
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused single-pass assign + cluster update.

    X: (n, d); C: (k, d); w: optional (n,) weights (defaults to ones).
    Returns (assign int32 (n,), d2 f32 (n,), csum f32 (k, d),
    wsum f32 (k,), ccost f32 (k,)).

    Leading batch dimensions on any operand vmap into the grid:
    X (..., n, d) / C (..., k, d) / w (..., n) -> batched outputs.
    """
    if X.ndim > 2 or C.ndim > 2 or (w is not None and w.ndim > 1):
        xa = 0 if X.ndim > 2 else None
        ca = 0 if C.ndim > 2 else None
        wa = 0 if (w is not None and w.ndim > 1) else None
        if w is None:
            return jax.vmap(
                lambda x, c: kmeans_assign_update(
                    x, c, block_n=block_n, interpret=interpret),
                in_axes=(xa, ca),
            )(X, C)
        return jax.vmap(
            lambda x, c, ww: kmeans_assign_update(
                x, c, ww, block_n=block_n, interpret=interpret),
            in_axes=(xa, ca, wa),
        )(X, C, w)

    n, d = X.shape
    k = C.shape[0]
    d_pad = _round_up(max(d, 1), 128)
    k_pad = _round_up(max(k, 1), 128)
    bn = min(block_n, _round_up(n, 128))
    n_pad = _round_up(n, bn)
    nb = n_pad // bn

    Xp = jnp.zeros((n_pad, d_pad), X.dtype).at[:n, :d].set(X)
    Cp = jnp.zeros((k_pad, d_pad), C.dtype).at[:k, :d].set(C)
    cn = jnp.sum(Cp.astype(jnp.float32) ** 2, axis=1)[None, :]   # (1, k_pad)
    # zero weights on padded rows mask them out of every accumulator
    wn = jnp.ones((n,), jnp.float32) if w is None else w.astype(jnp.float32)
    wp = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(wn)

    assign, d2, csum, stat = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((2, k_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((k_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((2, k_pad), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_assign_update",
    )(Xp, Cp, cn, wp)
    return assign[0, :n], d2[0, :n], csum[:k, :d], stat[0, :k], stat[1, :k]
