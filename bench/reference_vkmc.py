"""Plain float64 reference of Algorithm 3 (vertical k-means coresets) as a
build draws it.

Nothing here imports the program.  The threefry stream, the key chain and
the check of Algorithm 1's draw are :mod:`bench.reference`'s.  Per party j,
on the party's own columns and on the key the build gives it:

* seeding: weighted D^2 seeding (Arthur & Vassilvitskii) under unit
  weights.  The first centre is a Gumbel-max draw over uniform logits, each
  of the k - 1 others the argmax of ``gumbel + log max(d2, 1e-30)`` over
  all n rows, ``d2`` the squared distance to the nearest centre so far;
* Lloyd: each iteration assigns every row to its nearest centre and moves
  each centre to its cluster's mean; an empty cluster keeps its centre;
* scores: ``g_i = a d2_i / cost + a cost_l / (|B_l| cost) + 2a / |B_l|``
  for row i in cluster l, ``|B_l|`` clamped at 1 (Algorithm 3, lines 3-11).

Keys: the build key is split T times for the parties, then once for DIS
(``key_chain(key, T + 1)``); a party key splits into the first draw's key
and the key that splits k - 1 ways for the rest.

Each step is checked from the program's own state before it, not from a
float64 run of the whole chain.  Lloyd is not continuous: a row whose two
nearest centres tie within float32's rounding may go either way, moves two
centres by about ``|x - c| / |B|``, and that moves the next iteration's
boundaries.  A float64 run of 15 iterations from the same seeding ended
with 33 rows in other clusters and centres 1.4e-3 apart in one party of
three builds (CPU, n=65,536), enough to move the cluster sizes that
dominate the scores.  So:

* seeding: every draw is replayed in float64 given the centres the program
  picked before it; its gap is the float64 margin by which the best row
  beats the program's pick (0 where they agree);
* Lloyd: every iteration is replayed in float64 from the program's centres
  before it; its gap is the distance of each new centre from the float64
  one, less what the rows at a near-tie could move it;
* scores: computed in float64 at the program's final centres, and the draw
  checked on them by :func:`bench.reference.check_draw`.

Near-ties, each where float32 and float64 may honestly disagree:

* a seeding draw whose float64 top-two margin is under ``SEED_SCREEN`` is
  counted.  The program's float32 ``gumbel + log d2`` is off the float64
  one by a few 1e-6 (the Gumbel's ulp near 16, the expanded-form
  distance), so such a draw may go to the runner-up, and its gap is then
  that margin, under the draw limit;
* a row whose two nearest centres' distances differ by less than
  ``ASSIGN_MARGIN`` times ``|x|^2 + |c|^2``, the size float32 cancels in
  the expanded form ``|x|^2 - 2 x.c + |c|^2``, may sit in either cluster.
  In a Lloyd step those rows set the allowance; a drawn such row is scored
  under both assignments and the one whose weight the program's matches is
  kept (its score, dominated by ``2a / |B_l|``, differs between the two by
  the ratio of the cluster sizes).

A build is never dropped from the check to get past a tie: the counts of
both kinds are returned beside the numbers compared.
"""

from __future__ import annotations

import itertools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

SEED_SCREEN = 1e-4      # float64 top-two margin under which a seeding draw is a near-tie
ASSIGN_MARGIN = 1e-5    # distance gap, relative to |x|^2 + |c|^2, of an ambiguous row
SCREEN = 1e-4           # a float32 gap this clear of a tie decides the float64 assignment
FLOOR = 1e-30           # the log floor of the D^2 draws


def _gumbel(key: np.ndarray, n: int) -> np.ndarray:
    return reference.gumbel_rows(key, [0], n)[0]


def _top2_margin(v: np.ndarray) -> float:
    a, b = np.partition(v, len(v) - 2)[-2:]
    return float(b - a)


def seeding_gaps(key: np.ndarray, X: np.ndarray, centres: np.ndarray
                 ) -> Tuple[float, int]:
    """(largest gap, near-ties) of the D^2 seeding whose picks are the rows
    of X nearest the program's ``centres`` (k, d), in pick order."""
    n, k = len(X), len(centres)
    x2 = np.einsum("nd,nd->n", X, X)
    k0, rest = reference.split(key)
    keys = reference.split(rest, k - 1)
    gap, ties = 0.0, 0
    d2 = None
    for l, c in enumerate(centres):
        pick = int(np.argmin(x2 - 2.0 * (X @ c)))              # nearest row to c
        v = _gumbel(k0, n) if l == 0 else (_gumbel(keys[l - 1], n)
                                           + np.log(np.maximum(d2, FLOOR)))
        gap = max(gap, float(v.max() - v[pick]))
        ties += _top2_margin(v) < SEED_SCREEN
        r = X[pick]
        dr = np.maximum(x2 - 2.0 * (X @ r) + r @ r, 0.0)
        d2 = dr if d2 is None else np.minimum(d2, dr)
    return gap, ties


def _nearest_two(X: np.ndarray, x2: np.ndarray, C: np.ndarray):
    """Per row: nearest and second-nearest cluster, their distances, and
    whether the two are within ``ASSIGN_MARGIN``."""
    c2 = np.einsum("kd,kd->k", C, C)
    D = np.maximum(x2[:, None] - 2.0 * (X @ C.T) + c2[None], 0.0)
    rows = np.arange(len(X))
    a = np.argmin(D, axis=1)
    da = D[rows, a]
    D[rows, a] = np.inf
    b = np.argmin(D, axis=1)
    db = D[rows, b]
    amb = (db - da) <= ASSIGN_MARGIN * (x2 + np.maximum(c2[a], c2[b]))
    return a, b, da, db, amb


@jax.jit
def _screen(X, C):
    """float32 nearest and second-nearest cluster of every row, and the
    gap between their distances relative to ``|x|^2 + |c|^2``."""
    x2 = jnp.sum(X * X, axis=1)
    c2 = jnp.sum(C * C, axis=1)
    D = x2[:, None] - 2.0 * jnp.matmul(X, C.T, precision=jax.lax.Precision.HIGHEST) + c2
    neg, ab = jax.lax.top_k(-D, 2)
    rel = (neg[:, 0] - neg[:, 1]) / (x2 + jnp.maximum(c2[ab[:, 0]], c2[ab[:, 1]]))
    return ab[:, 0], ab[:, 1], rel


def _assign(X: np.ndarray, X32, x2: np.ndarray, C: np.ndarray):
    """:func:`_nearest_two`'s clusters and near-ties, its distances left
    out: screened on the device in float32 (``X32``), recomputed in float64
    for every row whose screened gap is within ``SCREEN`` of a tie."""
    a, b, rel = (np.asarray(v) for v in _screen(X32, jnp.asarray(C, jnp.float32)))
    a, b = a.astype(np.int64), b.astype(np.int64)
    amb = np.zeros(len(X), bool)
    close = np.flatnonzero(rel < SCREEN)
    if len(close):
        a[close], b[close], _, _, amb[close] = _nearest_two(X[close], x2[close], C)
    return a, b, amb


def lloyd_step(X: np.ndarray, X32, x2: np.ndarray, C: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    """One Lloyd iteration from centres C (``X32`` the rows on the device
    in float32, ``x2`` their squared norms): the new centres, and per
    cluster how far the rows at a near-tie could move it."""
    k = len(C)
    a, b, amb = _assign(X, X32, x2, C)
    one = (a[:, None] == np.arange(k)[None]).astype(np.float64)
    size = one.sum(axis=0)
    sums = one.T @ X
    new = np.where(size[:, None] > 0, sums / np.maximum(size, 1.0)[:, None], C)
    reach = np.zeros(k)
    firm = size - np.bincount(a[amb], minlength=k)
    for i in np.flatnonzero(amb):
        for l in (a[i], b[i]):
            reach[l] += np.linalg.norm(X[i] - new[l]) / max(firm[l], 1.0)
    return new, reach


def lloyd_gap(X: np.ndarray, path: np.ndarray) -> float:
    """Largest distance, over the iterations of ``path`` (iters + 1, k, d)
    and its clusters, of the program's centre from the float64 Lloyd step
    of the centres before it, less the near-tie reach."""
    x2 = np.einsum("nd,nd->n", X, X)
    X32 = jnp.asarray(X, jnp.float32)
    gap = 0.0
    for prev, got in zip(path[:-1], path[1:]):
        want, reach = lloyd_step(X, X32, x2, prev)
        gap = max(gap, float(np.max(np.linalg.norm(got - want, axis=1) - reach)))
    return max(gap, 0.0)


def scores(X: np.ndarray, C: np.ndarray, alpha: float):
    """(g, g_alt, ambiguous): Algorithm 3's scores at centres C, the scores
    of each row moved to its second-nearest cluster, and the rows at a
    near-tie."""
    k = len(C)
    x2 = np.einsum("nd,nd->n", X, X)
    a, b, da, db, amb = _nearest_two(X, x2, C)
    cost = max(da.sum(), FLOOR)
    size = np.maximum(np.bincount(a, minlength=k).astype(np.float64), 1.0)
    ccost = np.bincount(a, weights=da, minlength=k)

    def g_of(l, d2):
        return alpha * d2 / cost + alpha * ccost[l] / (size[l] * cost) + 2.0 * alpha / size[l]

    return g_of(a, da), g_of(b, db), amb


def _choose(g: np.ndarray, g_alt: np.ndarray, amb: np.ndarray, m: int,
            S: np.ndarray, w: np.ndarray) -> Tuple[np.ndarray, int]:
    """The (T, n) table the draw is checked on: each drawn row with
    ambiguous parties takes the assignments whose weight is nearest the
    program's; every other row its float64 nearest cluster.  Also the
    number of ambiguous (party, drawn row) pairs."""
    out = g.copy()
    G = g.sum()
    n_amb = 0
    first: Dict[int, int] = {}
    for t, r in enumerate(S):
        first.setdefault(int(r), t)
    for r, t in first.items():
        parts = np.flatnonzero(amb[:, r])
        if not len(parts):
            continue
        n_amb += len(parts)
        best = None
        for flip in itertools.product((False, True), repeat=len(parts)):
            col = g[:, r].copy()
            col[parts] = np.where(flip, g_alt[parts, r], g[parts, r])
            err = abs(w[t] - G / (m * col.sum()))
            if best is None or err < best[0]:
                best = (err, col)
        out[:, r] = best[1]
    return out, n_amb


def check_build(build_key: np.ndarray, parts: Sequence[np.ndarray], alpha: float,
                m: int, block_size: int, S: np.ndarray, w: np.ndarray,
                party_counts: Sequence[int], paths: Sequence[np.ndarray]
                ) -> Dict[str, float]:
    """Compare one build with Algorithm 3 and Algorithm 1 replayed in
    float64 on ``parts``.  ``paths[j]`` (iters + 1, k, d_j) are party j's
    centres after seeding and after each Lloyd iteration, as the program
    computes them for this build's key.  Returns

    * ``draw_gap``: the largest float64 margin by which the replay's best
      candidate beats the program's pick, over the seeding draws and
      Algorithm 1's (:func:`bench.reference.check_draw`);
    * ``weight_rel``: as :func:`bench.reference.check_draw`;
    * ``lloyd_gap``: as :func:`lloyd_gap`, over the parties;
    * ``seed_ties``, ``assign_ties``: the near-tie counts.
    """
    T = len(parts)
    subs = reference.key_chain(build_key, T + 1)
    S = np.asarray(S, np.int64)
    w = np.asarray(w, np.float64)
    seed_gap, seed_ties, lgap = 0.0, 0, 0.0
    g, g_alt, amb = [], [], []
    for j, (X, path) in enumerate(zip(parts, paths)):
        path = np.asarray(path, np.float64)
        sg, st = seeding_gaps(subs[j], X, path[0])
        seed_gap, seed_ties = max(seed_gap, sg), seed_ties + st
        lgap = max(lgap, lloyd_gap(X, path))
        gj, aj, mj = scores(X, path[-1], alpha)
        g.append(gj)
        g_alt.append(aj)
        amb.append(mj)
    g = np.stack(g)
    n_amb = 0
    if len(S) == m == len(w) and not np.any((S < 0) | (S >= g.shape[1])):
        g, n_amb = _choose(g, np.stack(g_alt), np.stack(amb), m, S, w)
    got = reference.check_draw(subs[T], g, m, block_size, S, w, party_counts)
    return {"draw_gap": max(got["draw_gap"], seed_gap), "weight_rel": got["weight_rel"],
            "lloyd_gap": lgap, "seed_ties": float(seed_ties), "assign_ties": float(n_amb)}
