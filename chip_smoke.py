#!/usr/bin/env python3
"""Chip smoke test: the coreset build and the coreset service at the
paper's YearPredictionMSD size on a TPU, each output checked against a
float64 NumPy reference or the repo's own end-to-end gates.

  python3 chip_smoke.py              # one chip: phases 1-6
  python3 chip_smoke.py --chips 4    # four chips: the sharded mass table only

Phases (one chip): 1 device check; 2 vrlr on the materialized engine;
3 vkmc (k=10) on the materialized engine; 4 both tasks on the pipelined
engine over host-resident parts; 5 one CoresetService with three vrlr and
three vkmc tenants; 6 proof that a score pass compiled the Pallas kernels
natively.  Every build uses ``backend="pallas"``, no failover, no
``"auto"`` fallback.  Per phase the script prints compile seconds apart
from run seconds, errors against the references, rel_errors and the
device's peak bytes.  Any failed check raises and the exit code is
non-zero; only when every phase passes does the last stdout line read
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

It runs in one process, starts no other, and writes nothing into tracked
files.  JAX's persistent compilation cache goes to
``$JAX_COMPILATION_CACHE_DIR`` if set, else to ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.e2e import REL_ERROR_BOUND  # noqa: E402
from benchmarks.serve import REL_FLOOR, TREE_VS_FLAT_GATE  # noqa: E402
from repro.core import CommLedger, CoresetPipeline, CoresetSpec, VFLDataset  # noqa: E402
from repro.core.api import get_task, resolve_backend  # noqa: E402
from repro.core.sensitivity import vkmc_local_scores, vrlr_scores_stacked  # noqa: E402
from repro.core.solve import evaluate, fit_kmeans, fit_ridge, full_data_coreset  # noqa: E402
from repro.core.streaming import (  # noqa: E402
    make_stream_scorer,
    vkmc_block_masses_sharded,
    vkmc_local_centers,
    vrlr_block_masses_sharded,
)
from repro.core.vfl import split_columns  # noqa: E402
from repro.data.synthetic import year_prediction_like  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import CoresetService  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

BACKEND = "pallas"

# Tolerances against the float64 references (float32 arithmetic throughout).
TOL_MASS = 1e-4         # per-party score mass G_j, relative
TOL_ROW = 1e-3          # per-row vrlr score, relative (scores >= 1/n > 0)
TOL_D2 = 1e-4           # k-means d2, relative to ||x||^2 + ||c||^2
TOL_TIE = 1e-4          # a differing assignment must be this close to a tie
TOL_CSUM = 1e-4         # per-cluster coordinate sums, relative to sum |x|
TOL_CCOST = 1e-4        # per-cluster cost, relative
TOL_BLOCK_MASS = 1e-4   # streamed round-1 block masses, relative

RCOND = 1e-6            # the score path's pseudo-inverse cutoff
SEEDS = 3               # service tenants per task, and flat builds, averaged


@dataclasses.dataclass(frozen=True)
class Config:
    """R-W1: YearPredictionMSD's 515,345 rows x 90 features over T=3."""

    seed: int = 0
    n: int = 515_345
    d: int = 90
    T: int = 3
    m: int = 2048
    k: int = 10
    block_size: int = 65_536
    chunk_blocks: int = 8
    svc_rows: int = 131_072
    svc_inserts: int = 4
    svc_m: int = 512
    shard_block_size: int = 16_384


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


_COMPILE_S = [0.0]


def _on_event(name: str, secs: float, **_) -> None:
    if name.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += secs


jax.monitoring.register_event_duration_secs_listener(_on_event)


def compile_seconds() -> float:
    return _COMPILE_S[0]


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def timed(fn):
    """(result, wall seconds, compile seconds) of one call, ended by
    ``block_until_ready`` on everything it returns."""
    c0, t0 = compile_seconds(), time.perf_counter()
    out = fn()
    jax.block_until_ready(jax.tree_util.tree_leaves(out))
    return out, time.perf_counter() - t0, compile_seconds() - c0


def cold_warm(fn) -> Tuple[Any, Dict[str, float]]:
    """Run ``fn`` twice: the first call pays compilation, the second is the
    run time.  Returns the second result and the split."""
    _, cold_s, comp_s = timed(fn)
    out, warm_s, _ = timed(fn)
    return out, {"compile_s": comp_s, "cold_s": cold_s, "run_s": warm_s}


# --------------------------------------------------------------------------
# float64 references
# --------------------------------------------------------------------------

def ref_vrlr_scores(blocks: np.ndarray, dims) -> np.ndarray:
    """Algorithm 2 in float64: per party, the equilibrated Gram
    pseudo-inverse (rcond cutoff) and the row quadratic forms, clipped to
    [0, 1], plus 1/n."""
    T, n, _ = blocks.shape
    out = np.empty((T, n))
    for j in range(T):
        f = blocks[j, :, :dims[j]].astype(np.float64)
        G = f.T @ f
        dg = np.diag(G)
        sc = np.where(dg > 0, 1.0 / np.sqrt(np.where(dg > 0, dg, 1.0)), 0.0)
        ev, V = np.linalg.eigh(G * sc[:, None] * sc[None, :])
        keep = ev > RCOND * max(ev.max(), 0.0)
        M = (V[:, keep] / ev[keep]) @ V[:, keep].T * sc[:, None] * sc[None, :]
        out[j] = np.clip(np.einsum("nd,de,ne->n", f, M, f), 0.0, 1.0) + 1.0 / n
    return out


def max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def block_sums(scores: np.ndarray, bs: int) -> np.ndarray:
    T, n = scores.shape
    nb = -(-n // bs)
    pad = np.zeros((T, nb * bs))
    pad[:, :n] = scores
    return pad.reshape(T, nb, bs).sum(axis=2)


# --------------------------------------------------------------------------
# Data
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Data:
    ds: VFLDataset          # device-resident parts (materialized engine)
    host: VFLDataset        # the same rows as host numpy (pipelined engine)


def make_data(cfg: Config, n: Optional[int] = None, salt: int = 0) -> Data:
    X, y = year_prediction_like(jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                                   salt), n=n or cfg.n, d=cfg.d)
    ds = VFLDataset.from_dense(X, y, T=cfg.T)
    host = VFLDataset([np.asarray(p) for p in ds.parts], np.asarray(ds.y))
    return Data(ds, host)


def _key(cfg: Config, salt: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 1000 + salt)


def _build(ds: VFLDataset, spec: CoresetSpec, key):
    """One build through the public pipeline; returns (coreset, plan,
    ledger) after checking the bill equals the plan's prediction."""
    pipeline = CoresetPipeline(ds)
    plan = pipeline.plan(spec)
    led = CommLedger()
    cs = pipeline.build(plan, key=key, ledger=led)
    check(led.total == plan.predicted_comm_units,
          f"{spec.task}/{plan.engine}: ledger {led.total} != predicted "
          f"{plan.predicted_comm_units}")
    return cs, plan, led


def _kmeans_rel_errors(ds: VFLDataset, fits, baseline_fit) -> List[float]:
    """rel_error of each fit against the best-known centers (the e2e
    benchmark's protection against Lloyd's basin roulette)."""
    cands = [baseline_fit] + list(fits)
    costs = [evaluate(ds, f, baseline=baseline_fit.params,
                      backend=BACKEND).cost_fit for f in cands]
    best = cands[int(np.argmin(costs))].params
    return [evaluate(ds, f, baseline=best, backend=BACKEND).rel_error
            for f in fits]


def _rel_gate(task: str, rel: float, rel_ref: float) -> None:
    check(rel < REL_ERROR_BOUND[task],
          f"{task}: rel_error {rel:.6g} fails the e2e gate "
          f"{REL_ERROR_BOUND[task]}")
    check(rel <= max(2.0 * rel_ref, 0.02),
          f"{task}: rel_error {rel:.6g} exceeds max(2 x ref {rel_ref:.6g}, 0.02)")


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------

def phase_vrlr(cfg: Config, data: Data) -> np.ndarray:
    """Phase 2: vrlr, materialized engine.  Returns the (T, n) scores."""
    ds, key = data.ds, _key(cfg, 2)
    score_fn = get_task("vrlr").score_fn
    (scores, _), score_t = cold_warm(lambda: score_fn(key, ds, backend=BACKEND))
    st = ds.stacked(with_labels=True)
    ref = ref_vrlr_scores(np.asarray(st.blocks), st.dims)
    got = np.asarray(scores, np.float64)
    mass_err = [max_rel(got[j].sum(), ref[j].sum()) for j in range(ds.T)]
    row_err = [max_rel(got[j], ref[j]) for j in range(ds.T)]

    spec = CoresetSpec(task="vrlr", budgets=cfg.m, engine="materialized",
                       backend=BACKEND)
    (cs, plan, led), build_t = cold_warm(lambda: _build(ds, spec, key))
    lam = 0.1 * ds.n
    baseline = fit_ridge(ds, full_data_coreset(ds), lam).params
    rel = evaluate(ds, fit_ridge(ds, cs, lam), baseline=baseline,
                   backend=BACKEND).rel_error
    cs_ref, _, _ = _build(ds, dataclasses.replace(spec, backend="ref"), key)
    rel_ref = evaluate(ds, fit_ridge(ds, cs_ref, lam), baseline=baseline,
                       backend=BACKEND).rel_error
    emit("vrlr_materialized", n=ds.n, m=cfg.m, score=score_t, build=build_t,
         mass_G=[float(got[j].sum()) for j in range(ds.T)],
         mass_rel_err=mass_err, row_rel_err=row_err,
         ledger=led.total, rel_error=rel, rel_error_ref_backend=rel_ref,
         peak_bytes=peak_bytes())
    check(max(mass_err) <= TOL_MASS, f"vrlr mass error {mass_err} > {TOL_MASS}")
    check(max(row_err) <= TOL_ROW, f"vrlr row score error {row_err} > {TOL_ROW}")
    _rel_gate("vrlr", rel, rel_ref)
    return got


def ref_kmeans_update(X: np.ndarray, C: np.ndarray, assign: np.ndarray):
    """float64 distances to every center, and the per-cluster sums grouped
    by the kernel's own assignment (so a near-tie cannot move a row)."""
    X, C = X.astype(np.float64), C.astype(np.float64)
    x2 = (X * X).sum(axis=1)
    c2 = (C * C).sum(axis=1)
    d2 = np.maximum(x2[:, None] + c2[None, :] - 2.0 * X @ C.T, 0.0)
    k = C.shape[0]
    csum = np.zeros_like(C)
    np.add.at(csum, assign, X)
    cabs = np.zeros_like(C)
    np.add.at(cabs, assign, np.abs(X))
    wsum = np.bincount(assign, minlength=k).astype(np.float64)
    ccost = np.bincount(assign, weights=d2[np.arange(len(X)), assign],
                        minlength=k)
    return d2, x2, c2, csum, cabs, wsum, ccost


def phase_vkmc(cfg: Config, data: Data) -> None:
    """Phase 3: the fused assign-update kernel at fixed centers against
    float64, then the vkmc build on the materialized engine."""
    ds, key = data.ds, _key(cfg, 3)
    st = ds.stacked()
    rng = np.random.default_rng(cfg.seed)
    rows = rng.choice(ds.n, size=cfg.k, replace=False)
    C = st.blocks[:, rows, :]                                  # (T, k, s)
    outs, kern_t = cold_warm(lambda: kops.kmeans_assign_update(st.blocks, C))
    assign, d2, csum, wsum, ccost = (np.asarray(o) for o in outs)
    blocks, Cn = np.asarray(st.blocks), np.asarray(C)
    err = {"d2": 0.0, "tie": 0.0, "csum": 0.0, "ccost": 0.0,
           "wsum_mismatch": 0, "assign_differs": 0}
    for j in range(ds.T):
        w = st.dims[j]
        X, Cj, a = blocks[j, :, :w], Cn[j, :, :w], assign[j]
        d2r, x2, c2, csr, cabs, wsr, ccr = ref_kmeans_update(X, Cj, a)
        scale = x2 + c2[a]
        best = d2r.min(axis=1)
        err["d2"] = max(err["d2"], float(np.max(np.abs(d2[j] - best) / scale)))
        err["tie"] = max(err["tie"], float(np.max(
            (d2r[np.arange(ds.n), a] - best) / scale)))
        err["assign_differs"] += int((a != d2r.argmin(axis=1)).sum())
        err["csum"] = max(err["csum"], float(
            np.max(np.abs(csum[j, :, :w] - csr)) / max(cabs.max(), 1e-300)))
        err["wsum_mismatch"] += int((wsum[j] != wsr).sum())
        err["ccost"] = max(err["ccost"], max_rel(ccost[j], np.maximum(ccr, 1e-300)))

    spec = CoresetSpec(task="vkmc", budgets=cfg.m, engine="materialized",
                       backend=BACKEND, params={"k": cfg.k})
    (cs, plan, led), build_t = cold_warm(lambda: _build(ds, spec, key))
    cs_ref, _, _ = _build(ds, dataclasses.replace(spec, backend="ref"), key)
    skey = _key(cfg, 30)
    fits = [fit_kmeans(ds, c, cfg.k, key=skey, restarts=5, backend=BACKEND)
            for c in (cs, cs_ref)]
    full = fit_kmeans(ds, full_data_coreset(ds), cfg.k, key=skey, restarts=5,
                      backend=BACKEND)
    rel, rel_ref = _kmeans_rel_errors(ds, fits, full)
    emit("vkmc_materialized", n=ds.n, m=cfg.m, k=cfg.k, kernel=kern_t,
         build=build_t, kernel_err=err, ledger=led.total, rel_error=rel,
         rel_error_ref_backend=rel_ref, peak_bytes=peak_bytes())
    check(err["d2"] <= TOL_D2, f"vkmc d2 error {err['d2']} > {TOL_D2}")
    check(err["tie"] <= TOL_TIE, f"vkmc assignment off a near-tie by {err['tie']}")
    check(err["csum"] <= TOL_CSUM, f"vkmc csum error {err['csum']} > {TOL_CSUM}")
    check(err["wsum_mismatch"] == 0, "vkmc wsum differs from the counts")
    check(err["ccost"] <= TOL_CCOST, f"vkmc ccost error {err['ccost']} > {TOL_CCOST}")
    _rel_gate("vkmc", rel, rel_ref)


def phase_pipelined(cfg: Config, data: Data, vrlr_scores: np.ndarray) -> None:
    """Phase 4: both tasks on the pipelined engine over host-resident parts,
    prefetch as the planner resolves it.  The round-1 block-mass table must
    equal the materialized score function summed per block: phase 2's
    scores for vrlr, and for vkmc the phase-3 score function evaluated at
    the centers the streaming scorer solves for (it clusters a bounded row
    subsample, so its centers are its own)."""
    host = data.host
    for task, params in (("vrlr", {}), ("vkmc", {"k": cfg.k})):
        key = _key(cfg, 2 if task == "vrlr" else 3)
        spec = CoresetSpec(task=task, budgets=cfg.m, engine="pipelined",
                           backend=BACKEND, block_size=cfg.block_size,
                           chunk_blocks=cfg.chunk_blocks, params=params)
        (cs, plan, led), build_t = cold_warm(lambda: _build(host, spec, key))
        scorer, scorer_t = cold_warm(lambda: make_stream_scorer(
            task, key, host, plan.bs, BACKEND, chunk_blocks=plan.chunk_blocks,
            prefetch=plan.prefetch, **params))
        if task == "vrlr":
            ref_scores = vrlr_scores
        else:
            centers, _ = vkmc_local_centers(key, host, k=cfg.k,
                                            use_kernel=True)
            blocks = data.ds.stacked().blocks
            ref_scores = np.asarray(jax.vmap(
                lambda X, c: vkmc_local_scores(X, c, 2.0))(blocks, centers),
                np.float64)
        ref_masses = block_sums(ref_scores, plan.bs)
        mass_err = max_rel(np.asarray(scorer.masses), ref_masses)
        emit(f"{task}_pipelined", n=host.n, m=cfg.m, bs=plan.bs, nb=plan.nb,
             chunk_blocks=plan.chunk_blocks, prefetch=plan.prefetch,
             build=build_t, mass_table=scorer_t, block_mass_rel_err=mass_err,
             ledger=led.total, peak_bytes=peak_bytes())
        check(mass_err <= TOL_BLOCK_MASS,
              f"{task} pipelined block masses off by {mass_err} > {TOL_BLOCK_MASS}")


def _stream_chunks(cfg: Config):
    X, y = year_prediction_like(jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 5),
                                n=cfg.svc_rows * cfg.svc_inserts, d=cfg.d)
    X, y = np.asarray(X), np.asarray(y)
    cols = split_columns(cfg.d, cfg.T)
    chunks = []
    for i in range(cfg.svc_inserts):
        r = slice(i * cfg.svc_rows, (i + 1) * cfg.svc_rows)
        chunks.append(([X[r, c] for c in cols], y[r]))
    return chunks, VFLDataset([X[:, c] for c in cols], y)


def phase_service(cfg: Config) -> None:
    """Phase 5: one service holding SEEDS vrlr and SEEDS vkmc tenants, each
    taking the same 4 inserts and one reduced query.  Every tenant's ledger
    equals its receipts, and the trees' mean rel_error stays within the
    serve benchmark's gate of the mean flat build on the same stream (both
    sides averaged over seeds, as ``benchmarks/serve.py`` does: a tree
    carries the flat build's error plus its final reduce's, so one sample
    of each sits at the 2x gate by construction)."""
    chunks, stream = _stream_chunks(cfg)
    svc = CoresetService(backend=BACKEND)
    lam = 0.1 * stream.n
    for task in ("vrlr", "vkmc"):
        params = {} if task == "vrlr" else {"k": cfg.k}
        c0 = compile_seconds()
        trees, insert_s, query_s, ledgers = [], [], [], []
        for i in range(SEEDS):
            name = f"{task}/{i}"
            svc.register(name, task=task, budget=cfg.svc_m, seed=cfg.seed + i,
                         block_size=cfg.block_size, **params)
            recs = [svc.insert(name, parts, y if task == "vrlr" else None)
                    for parts, y in chunks]
            q = svc.query(name, reduce_to=cfg.svc_m)
            billed = sum(r.stats.comm_delta for r in recs) + q.comm_delta
            total = svc.state(name).ledger.total
            check(total == billed,
                  f"service {name}: ledger {total} != receipts {billed}")
            check(q.m == cfg.svc_m, f"service {name}: query returned {q.m} rows")
            trees.append(q.result.coreset())
            insert_s.append([r.latency_s for r in recs])
            query_s.append(q.latency_s)
            ledgers.append(total)
        compile_s = compile_seconds() - c0
        spec = CoresetSpec(task=task, budgets=cfg.svc_m, engine="materialized",
                           backend=BACKEND, params=params)
        flats = [_build(stream, spec, _key(cfg, 5 + i))[0]
                 for i in range(SEEDS)]
        if task == "vrlr":
            base = fit_ridge(stream, full_data_coreset(stream), lam).params
            rels = [evaluate(stream, fit_ridge(stream, c, lam), baseline=base,
                             backend=BACKEND).rel_error
                    for c in trees + flats]
        else:
            skey = _key(cfg, 50)
            fits = [fit_kmeans(stream, c, cfg.k, key=skey, restarts=5,
                               backend=BACKEND) for c in trees + flats]
            full = fit_kmeans(stream, full_data_coreset(stream), cfg.k,
                              key=skey, restarts=5, backend=BACKEND)
            rels = _kmeans_rel_errors(stream, fits, full)
        rel_tree = float(np.mean(rels[:SEEDS]))
        rel_flat = float(np.mean(rels[SEEDS:]))
        emit(f"service_{task}", tenants=SEEDS, inserts=len(chunks),
             rows=cfg.svc_rows, compile_s=compile_s, insert_s=insert_s,
             query_s=query_s, ledgers=ledgers, rel_error_trees=rels[:SEEDS],
             rel_error_flats=rels[SEEDS:], rel_error_tree=rel_tree,
             rel_error_flat=rel_flat, peak_bytes=peak_bytes())
        gate = max(TREE_VS_FLAT_GATE * rel_flat, REL_FLOOR)
        check(rel_tree <= gate,
              f"service {task}: mean tree rel_error {rel_tree:.6g} > gate "
              f"{gate:.6g}")


def phase_native(cfg: Config, data: Data) -> None:
    """Phase 6: the score pass's compiled program holds the Pallas kernel
    as a Mosaic custom call — it ran natively, not interpreted and not as
    the jnp reference."""
    blocks = data.ds.stacked(with_labels=True).blocks
    fn = jax.jit(functools.partial(vrlr_scores_stacked, use_kernel=True))
    text = fn.lower(blocks).compile().as_text()
    calls = text.count("tpu_custom_call")
    emit("native_kernels", program="vrlr_scores_stacked",
         tpu_custom_calls=calls)
    check(calls > 0, "score pass compiled without a tpu_custom_call: the "
                     "kernels did not run natively")


def phase_sharded(cfg: Config) -> None:
    """Four chips: the sharded_masses pipelined build of both tasks against
    the same spec unsharded, in one process.  n is cut to the largest
    count <= cfg.n that divides by devices x shard block size."""
    D = len(jax.devices())
    grid = D * cfg.shard_block_size
    n = cfg.n // grid * grid
    emit("sharded_cut", n_from=cfg.n, n=n, devices=D,
         block_size=cfg.shard_block_size)
    data = make_data(cfg, n=n)
    host = data.host
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    for task, params in (("vrlr", {}), ("vkmc", {"k": cfg.k})):
        key = _key(cfg, 2 if task == "vrlr" else 3)
        spec = CoresetSpec(task=task, budgets=cfg.m, engine="pipelined",
                           backend=BACKEND, block_size=cfg.shard_block_size,
                           chunk_blocks=cfg.chunk_blocks, params=params)
        sspec = dataclasses.replace(spec, sharded_masses=True)
        (_, plan, led), flat_t = cold_warm(lambda: _build(host, spec, key))
        (_, splan, sled), shard_t = cold_warm(lambda: _build(host, sspec, key))
        check(sled.total == led.total,
              f"{task}: sharded bill {sled.total} != unsharded {led.total}")
        if task == "vrlr":
            table = vrlr_block_masses_sharded(mesh, host, plan.bs)
        else:
            table = vkmc_block_masses_sharded(mesh, host, plan.bs, key=key,
                                              k=cfg.k, use_kernel=True)
        flat = make_stream_scorer(task, key, host, plan.bs, BACKEND,
                                  chunk_blocks=plan.chunk_blocks,
                                  prefetch=plan.prefetch, **params).masses
        err = max_rel(np.asarray(table), np.asarray(flat, np.float64))
        emit(f"{task}_sharded", n=n, devices=D, nb=plan.nb,
             unsharded_build=flat_t, sharded_build=shard_t,
             mass_table_rel_err=err, ledger=sled.total,
             peak_bytes=peak_bytes())
        check(err <= TOL_BLOCK_MASS,
              f"{task}: sharded mass table off by {err} > {TOL_BLOCK_MASS}")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={count}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); not "
              f"running on another backend", file=sys.stderr)
        return 2
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {count}", file=sys.stderr)
        return 2
    check(resolve_backend("auto") == "pallas",
          "resolve_backend('auto') does not pick the Pallas kernels on TPU")
    use_compile_cache(ROOT)          # before the first compile

    cfg = Config(seed=args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(cfg)
    else:
        data = make_data(cfg)
        scores = phase_vrlr(cfg, data)
        phase_vkmc(cfg, data)
        phase_pipelined(cfg, data, scores)
        phase_service(cfg)
        phase_native(cfg, data)
    emit("total", wall_s=time.perf_counter() - t0, compile_s=compile_seconds())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
