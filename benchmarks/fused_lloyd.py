"""Fused vs seed Lloyd-iteration microbenchmark: passes-over-X and us/step.

One seed-path Lloyd iteration is 3 separate data passes — 2 of them
X-sized (assign kernel + the coordinate-sum segment_sum) plus the n-sized
weight-sum scatter; the fused ``kmeans_assign_update`` kernel is 1 X-sized
pass total.  This module measures both data flows in both execution modes:

  * ``pallas-interp`` (``pallas`` on TPU) — the kernel paths;
  * ``jnp-ref``       — XLA-compiled jnp: seed = assign + segment_sums,
    fused = assign + one-hot matmul fold (the scatter-free data flow the
    kernel implements, expressed as a matmul XLA can fuse).

Pass counts are derived STRUCTURALLY from the lowered jaxpr (number of
pallas_call + scatter ops touching X-sized operands), not asserted by
hand, and land in BENCH_kernels.json for the perf trajectory.

Off the TPU target the kernel paths only run under ``interpret=True``, so
their WALL TIME is interpreter overhead, not kernel performance — those
timings are skipped by default (the structural pass census, which needs
only the jaxpr, is still recorded as ``*/pallas-structural`` rows); pass
``--interpret`` to time them anyway, explicitly labeled with
``"interpret": true``.  The off-TPU interpret rule mirrors
``repro.kernels.ops._interpret`` — how the library itself executes the
kernels.
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from benchmarks.common import time_us, write_bench_json, write_rows
from repro.kernels import kmeans_assign as _ka
from repro.kernels import kmeans_assign_update as _kau
from repro.kernels import ref

BENCH = "fused_lloyd"


def _subjaxprs(v):
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _subjaxprs(x)


def count_primitives(jaxpr, names, pred=None) -> int:
    """Recursive primitive census over a jaxpr (descends into pjit/scan/
    pallas_call sub-jaxprs).  ``names``: exact primitive names to count;
    ``pred``: optional extra filter on the matching eqn."""
    cnt = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names and (pred is None or pred(eqn)):
            cnt += 1
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                cnt += count_primitives(sub, names, pred)
    return cnt


def _is_matrix_scatter(eqn):
    # scatter-add invars are (operand, indices, updates): X-sized iff the
    # scattered UPDATES are (n, d)-shaped (csum's segment_sum); the wsum
    # segment_sum only scatters the (n,) weight vector
    return getattr(eqn.invars[-1].aval, "ndim", 0) >= 2


def structural_passes(fn, *args):
    """(pallas_call count, scatter-add count, X-sized passes) for ``fn`` —
    the structural census of the single-pass acceptance check.

    X-sized passes = pallas_call count (each kernel reads its X block
    stream once) + scatter-adds whose scattered operand is (n, d)-sized
    (csum's segment_sum; the wsum segment_sum only streams the (n,)
    weights and is NOT an X-sized pass).  Zero-padding ``scatter`` copies
    are layout moves shared by both paths and also not counted.  Seed
    Lloyd step: 1 pallas_call + 2 scatter-adds, of which 1 is X-sized ->
    2 X-sized passes (+1 n-sized); fused: 1 pallas_call, 0 scatter-adds
    -> 1 pass.
    """
    jx = jax.make_jaxpr(fn)(*args).jaxpr
    n_pallas = count_primitives(jx, {"pallas_call"})
    n_scatter = count_primitives(jx, {"scatter-add"})
    n_xsized = count_primitives(jx, {"scatter-add"}, _is_matrix_scatter)
    return n_pallas, n_scatter, n_pallas + n_xsized


def _new_centers(csum, wsum, C):
    return jnp.where(wsum[:, None] > 0,
                     csum / jnp.maximum(wsum, 1e-30)[:, None], C)


def make_steps(interp: bool):
    """One Lloyd iteration, four ways: (name, fn) pairs."""
    suffix = "pallas-interp" if interp else "pallas"

    def seed_pallas(X, C, w):
        assign, _ = _ka.kmeans_assign(X, C, interpret=interp)       # pass 1
        k = C.shape[0]
        wsum = jax.ops.segment_sum(w, assign, num_segments=k)       # pass 2
        csum = jax.ops.segment_sum(w[:, None] * X, assign, num_segments=k)  # 3
        return _new_centers(csum, wsum, C)

    def fused_pallas(X, C, w):
        _, _, csum, wsum, _ = _kau.kmeans_assign_update(X, C, w, interpret=interp)
        return _new_centers(csum, wsum, C)

    def seed_jnp(X, C, w):
        assign, _ = ref.kmeans_assign(X, C)
        k = C.shape[0]
        wsum = jax.ops.segment_sum(w, assign, num_segments=k)
        csum = jax.ops.segment_sum(w[:, None] * X, assign, num_segments=k)
        return _new_centers(csum, wsum, C)

    def fused_jnp(X, C, w):
        # the kernel's data flow in pure jnp: scatter-free one-hot fold
        assign, _ = ref.kmeans_assign(X, C)
        k = C.shape[0]
        onehot = (assign[:, None] == jnp.arange(k)[None, :]).astype(jnp.float32)
        wh = onehot * w[:, None]                                    # (n, k)
        wsum = jnp.sum(wh, axis=0)
        csum = wh.T @ X.astype(jnp.float32)
        return _new_centers(csum, wsum, C)

    return [
        (f"seed-3pass/{suffix}", seed_pallas),
        (f"fused-1pass/{suffix}", fused_pallas),
        ("seed-3pass/jnp-ref", jax.jit(seed_jnp)),
        ("fused-1pass/jnp-ref", jax.jit(fused_jnp)),
    ]


def run(fast: bool = True, interpret: bool = False):
    n, d, k = (20000, 90, 10) if fast else (200000, 90, 10)
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (n, d))
    C = jax.random.normal(jax.random.fold_in(key, 1), (k, d))
    w = jax.random.uniform(jax.random.fold_in(key, 2), (n,))

    # pallas rows are timed by default only where the kernels run COMPILED
    # (interpret=False — the same off-TPU interpret rule as
    # repro.kernels.ops._interpret); same gate as kernel_micro
    interp = jax.default_backend() != "tpu"
    time_pallas = (not interp) or interpret
    if not time_pallas:
        print(f"# {BENCH}: backend={jax.default_backend()} runs pallas in "
              "interpret mode (repro.kernels.ops._interpret); pallas rows "
              "keep the structural census only (pass --interpret to time "
              "them)", file=sys.stderr)
    rows, json_entries = [], []
    for name, fn in make_steps(interp):
        is_pallas_path = "pallas" in name
        n_pallas, n_scatter, n_passes = structural_passes(fn, X, C, w)
        entry = {
            "method": name, "n": n, "d": d, "k": k,
            "pallas_calls": n_pallas,
            "segment_sum_scatters": n_scatter,
        }
        if n_pallas:       # the census is about the kernel data flow; the
            entry["x_sized_passes"] = n_passes  # jnp rows are wall-time refs
        if is_pallas_path and not time_pallas:
            # structural-only row: the pass census comes from the jaxpr and
            # costs nothing; interpreter wall time would mislead
            entry["method"] = name.split("/")[0] + "/pallas-structural"
            json_entries.append(entry)
            continue
        us = time_us(fn, X, C, w)
        rows.append({"bench": BENCH, "method": name, "size": n,
                     "cost_mean": round(us, 1), "cost_std": 0.0,
                     "comm": 0, "wall_s": round(us / 1e6, 4)})
        entry["us_per_step"] = round(us, 1)
        if is_pallas_path and interp:
            entry["interpret"] = True    # interpreter wall, NOT kernel perf
        json_entries.append(entry)
    write_rows(BENCH, rows)
    write_bench_json(BENCH, json_entries)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true", default=True)
    ap.add_argument("--full", dest="fast", action="store_false")
    ap.add_argument("--interpret", action="store_true",
                    help="time interpret-mode pallas rows even on CPU")
    args = ap.parse_args()
    for r in run(fast=args.fast, interpret=args.interpret):
        print(r)
