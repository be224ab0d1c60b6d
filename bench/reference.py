"""Plain float64 references of what a build must produce.

Nothing here imports the program.  The references follow the paper's
definitions and the protocol's published randomness:

* Algorithm 2 scores: ridge leverage over each party's slice (party T with
  the label column), through an equilibrated eigen-pseudo-inverse.
* Algorithm 1 (DIS), flat or over (party, row-block) cells: the draws are
  Gumbel-max categoricals on JAX's threefry stream, so the reference
  recomputes the stream's bits at the sampled positions and takes the
  argmax in float64.
"""

from __future__ import annotations

import functools
import itertools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

RCOND = 1e-6            # pseudo-inverse cutoff of Algorithm 2's Gram
MAX_R1_SWAPS = 10       # round-1 draws that may be explained as ties

_TINY32 = np.float32(np.finfo(np.float32).tiny)


# --------------------------------------------------------------------------
# JAX's threefry stream, replayed at arbitrary positions
# --------------------------------------------------------------------------

def _rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """The Threefry-2x32 block cipher (20 rounds) as JAX applies it."""
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in rot[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


@functools.partial(jax.jit, static_argnames=("width",))
def uniform_rows(keys, rows, *, width: int):
    """Float32 uniforms in [tiny, 1) of rows ``rows`` of a ``(R, width)``
    draw, one raw (2,) uint32 key per row: the bits at flat position
    ``row * width + i`` of JAX's partitionable threefry layout, through
    ``jax.random.uniform``'s mantissa construction."""
    pos = (rows.astype(jnp.uint32)[:, None] * jnp.uint32(width)
           + jnp.arange(width, dtype=jnp.uint32)[None, :])
    b1, b2 = threefry2x32(keys[:, 0:1], keys[:, 1:2], jnp.zeros_like(pos), pos)
    bits = (b1 ^ b2) >> np.uint32(9) | np.uint32(0x3F800000)
    f = jax.lax.bitcast_convert_type(bits, jnp.float32) - np.float32(1.0)
    return jnp.maximum(_TINY32, f * (np.float32(1.0) - _TINY32) + _TINY32)


def gumbel_rows(keys: np.ndarray, rows: Sequence[int], width: int) -> np.ndarray:
    """float64 Gumbel noise of the given rows (see :func:`uniform_rows`)."""
    keys = np.asarray(keys, np.uint32).reshape(-1, 2)
    rows = np.asarray(rows, np.int64)
    if rows.size and (int(rows.max()) + 1) * width > 2 ** 32:
        raise ValueError("draw positions exceed the 32-bit counter")
    if len(keys) == 1 and len(rows) > 1:
        keys = np.repeat(keys, len(rows), axis=0)
    u = np.asarray(uniform_rows(jnp.asarray(keys), jnp.asarray(rows, jnp.uint32),
                                width=int(width)), np.float64)
    return -np.log(-np.log(u))


def raw_key(key) -> np.ndarray:
    key = jnp.asarray(key) if not isinstance(key, jax.Array) else key
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key, np.uint32).reshape(2)


def split(key: np.ndarray, num: int = 2) -> np.ndarray:
    return np.asarray(jax.random.split(jnp.asarray(key, jnp.uint32), num),
                      np.uint32)


def key_chain(key: np.ndarray, num: int) -> np.ndarray:
    """``num`` subkeys of the ``key, sub = split(key)`` chain."""
    subs = []
    for _ in range(num):
        key, sub = split(key)
        subs.append(sub)
    return np.stack(subs)


# --------------------------------------------------------------------------
# Algorithm 2: ridge leverage scores
# --------------------------------------------------------------------------

def vrlr_scores(parts: Sequence[np.ndarray], y: np.ndarray) -> np.ndarray:
    """(T, n) float64 scores ||u_i^(j)||^2 + 1/n, party T over [X^(T), y]."""
    T, n = len(parts), len(y)
    out = np.empty((T, n))
    for j, p in enumerate(parts):
        f = np.asarray(p, np.float64)
        if j == T - 1:
            f = np.concatenate([f, np.asarray(y, np.float64)[:, None]], axis=1)
        G = f.T @ f
        dg = np.diag(G)
        sc = np.where(dg > 0, 1.0 / np.sqrt(np.where(dg > 0, dg, 1.0)), 0.0)
        ev, V = np.linalg.eigh(G * sc[:, None] * sc[None, :])
        keep = ev > RCOND * max(ev.max(), 0.0)
        M = (V[:, keep] / ev[keep]) @ V[:, keep].T * sc[:, None] * sc[None, :]
        lev = np.einsum("nd,nd->n", f @ M, f)
        out[j] = np.clip(lev, 0.0, 1.0) + 1.0 / n
    return out


# --------------------------------------------------------------------------
# Algorithm 1 (DIS): the draw that produced (S, w)
# --------------------------------------------------------------------------

def cell_counts(S: np.ndarray, party_counts: Sequence[int], nb: int, bs: int):
    """Per-(party, block) counts read off a party-major, cell-ordered S, and
    each slot's cell and position within it; ``None`` where S is not in
    cell order."""
    T = len(party_counts)
    counts = np.zeros(T * nb, np.int64)
    cell = np.empty(len(S), np.int64)
    pos = np.empty(len(S), np.int64)
    t = 0
    for j, a in enumerate(party_counts):
        blocks = np.asarray(S[t:t + a], np.int64) // bs
        if a and (np.any(np.diff(blocks) < 0) or blocks.min() < 0
                  or blocks.max() >= nb):
            return None
        for b in range(nb):
            c = j * nb + b
            sel = np.flatnonzero(blocks == b)
            counts[c] = len(sel)
            cell[t + sel] = c
            pos[t + sel] = np.arange(len(sel))
        t += a
    return counts, cell, pos


def _round1_gap(v: np.ndarray, counts: np.ndarray) -> float:
    """The largest float64 top-two gap among round-1 draws that must have
    gone to their runner-up cell for the program's counts to arise; 1 plus
    the count mismatch where no such reassignment explains them."""
    ncells = v.shape[1]
    order = np.argsort(-v, axis=1)[:, :2]
    best, second = order[:, 0], order[:, 1]
    rows = np.arange(len(v))
    gaps = v[rows, best] - v[rows, second]
    diff = counts - np.bincount(best, minlength=ncells)
    if not diff.any():
        return 0.0
    ranked = np.argsort(gaps)
    for K in range(1, min(MAX_R1_SWAPS, len(v)) + 1):
        amb = ranked[:K]
        base = np.bincount(np.delete(best, amb), minlength=ncells)
        need = counts - base
        if need.min() < 0:
            continue
        for choice in itertools.product((0, 1), repeat=K):
            got = np.bincount(np.where(choice, second[amb], best[amb]),
                              minlength=ncells)
            if np.array_equal(got, need):
                return float(gaps[amb[np.asarray(choice, bool)]].max())
    return 1.0 + float(np.abs(diff).sum()) / 2.0


SCREEN = 1e-3           # a device (float32) margin this clear decides the float64 one
SCREEN_ROWS = 64        # draw rows screened per device call


@functools.partial(jax.jit, static_argnames=("width",))
def _screen(keys, rows, table, cell, choice, *, width: int):
    """float32 estimate, per draw row, of the margin by which the best other
    candidate beats the drawn one: Gumbel noise plus the row's cell logits
    (``table[cell]``)."""
    v = -jnp.log(-jnp.log(uniform_rows(keys, rows, width=width))) + table[cell]
    top, idx = jax.lax.top_k(v, 2)
    other = jnp.where(idx[:, 0] == choice, top[:, 1], top[:, 0])
    return other - jnp.take_along_axis(v, choice[:, None], axis=1)[:, 0]


def _gap64(key, row, logits, p) -> float:
    """The float64 margin of one draw row (see :func:`check_draw`);
    ``logits`` are the cell's float64 log-scores."""
    v = gumbel_rows(key, [row], len(logits))[0] + logits
    mine = v[p]
    v[p] = -np.inf
    return float(max(v.max() - mine, 0.0))


def check_draw(dis_key: np.ndarray, g: np.ndarray, m: int, block_size: int,
               S: np.ndarray, w: np.ndarray,
               party_counts: Sequence[int]) -> Dict[str, float]:
    """Compare one build's (S, w) with DIS replayed on the (T, n) reference
    scores ``g``.  Returns

    * ``draw_gap``: the largest float64 margin by which the replay's best
      candidate beats what the program drew, over the round-1 draws (see
      :func:`_round1_gap`) and every round-2 slot; 0 when all agree.  Each
      slot is screened on the device in float32 and recomputed in float64
      wherever the screen's margin is within ``SCREEN`` of a tie;
    * ``weight_rel``: over all m slots, the largest relative distance of
      w from the reference weight G / (m * sum_j g_j(S)).

    An S that is not a cell-ordered draw of m rows reads 1e9 on both.
    """
    T, n = g.shape
    nb = -(-n // min(block_size, n))
    bs = min(block_size, n)
    ncells = T * nb
    S = np.asarray(S, np.int64)
    w = np.asarray(w, np.float64)
    pad = np.zeros((T, nb * bs))
    pad[:, :n] = g
    masses = pad.reshape(T, nb, bs).sum(2).reshape(-1)
    G = masses.sum()
    subs = key_chain(dis_key, ncells + 1)

    if len(S) != m or sum(party_counts) != m or np.any((S < 0) | (S >= n)):
        return {"draw_gap": 1e9, "weight_rel": 1e9}
    cc = cell_counts(S, party_counts, nb, bs)
    if cc is None:
        return {"draw_gap": 1e9, "weight_rel": 1e9}
    counts, cell, pos = cc

    v1 = gumbel_rows(subs[0], np.arange(m), ncells) + np.log(np.maximum(masses, 1e-30))
    gap = _round1_gap(v1, counts)

    tab = np.full((T, nb * bs), -np.inf)
    tab[:, :n] = np.log(np.maximum(g, 1e-30))
    tab = tab.reshape(ncells, bs)
    choice = S - (cell % nb) * bs
    est = np.empty(m)
    table = jnp.asarray(tab, jnp.float32)
    for a in range(0, m, SCREEN_ROWS):
        sl = np.arange(a, a + SCREEN_ROWS) % m            # fixed shape, wraps
        est[sl] = np.asarray(_screen(
            jnp.asarray(subs[1 + cell[sl]]), jnp.asarray(pos[sl], jnp.uint32),
            table, jnp.asarray(cell[sl]), jnp.asarray(choice[sl], jnp.int32),
            width=bs))
    for t in np.flatnonzero(est > -SCREEN):
        c = int(cell[t])
        gap = max(gap, _gap64(subs[1 + c], int(pos[t]), tab[c], int(choice[t])))

    ref = G / (m * g[:, S].sum(0))
    return {"draw_gap": gap, "weight_rel": float(np.max(np.abs(w - ref) / ref))}


def comm_units(T: int, m: int) -> int:
    """Algorithm 1's bill in units: 2T (round 1) + m + mT (round 2) + mT
    (round 3)."""
    return 2 * T + m + 2 * m * T
