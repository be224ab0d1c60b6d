"""Online coreset service walkthrough: two tenants stream superchunks into
one CoresetService, query fresh summaries as they go, and redeem a batched
one-shot build — with the composed merge-and-reduce ledger printed at the
end.

  PYTHONPATH=src python examples/serve_coresets.py
"""

import jax
import numpy as np

from repro.core import VFLDataset
from repro.core.solve import evaluate, fit_kmeans, fit_ridge, full_data_coreset
from repro.serve import CoresetService

CHUNKS, ROWS, D, T, M = 6, 5000, 16, 3, 384


def make_stream(seed, labels):
    rng = np.random.default_rng(seed)
    centers = 2.0 * rng.standard_normal((6, D)).astype(np.float32)
    theta = rng.standard_normal(D).astype(np.float32)
    widths = [D // T + (1 if j < D % T else 0) for j in range(T)]
    chunks = []
    for _ in range(CHUNKS):
        X = (centers[rng.integers(0, 6, ROWS)]
             + rng.standard_normal((ROWS, D)).astype(np.float32))
        y = (X @ theta + 0.1 * rng.standard_normal(ROWS).astype(np.float32)
             if labels else None)
        parts, start = [], 0
        for w in widths:
            parts.append(X[:, start:start + w])
            start += w
        chunks.append((parts, y))
    return chunks


def main() -> None:
    svc = CoresetService()
    svc.register("ridge-co", task="vrlr", budget=M, seed=0, block_size=2048)
    svc.register("cluster-co", task="vkmc", budget=M, seed=1,
                 block_size=2048, k=6)
    streams = {"ridge-co": make_stream(10, True),
               "cluster-co": make_stream(11, False)}

    for r in range(CHUNKS):
        for name in ("ridge-co", "cluster-co"):
            parts, y = streams[name][r]
            rec = svc.insert(name, parts, y)
            print(f"[{name}] chunk {rec.chunk_idx}: {rec.stats.merges} merge(s), "
                  f"rescored {rec.stats.rescored_rows} rows "
                  f"(stream has {svc.state(name).tree.n_total}), "
                  f"plan {'hit' if rec.plan_hit else 'MISS'}, "
                  f"{rec.latency_s * 1e3:.0f} ms, ledger {rec.ledger_total}")

    # fresh summaries, evaluated against the FULL stream (global row ids)
    for name, labels in (("ridge-co", True), ("cluster-co", False)):
        chunks = streams[name]
        stream = VFLDataset(
            [np.concatenate([c[0][j] for c in chunks]) for j in range(T)],
            np.concatenate([c[1] for c in chunks]) if labels else None)
        q = svc.query(name, reduce_to=M)
        if labels:
            lam = 0.1 * stream.n
            base = fit_ridge(stream, full_data_coreset(stream), lam).params
            rep = evaluate(stream, fit_ridge(stream, q.result.coreset(), lam),
                           baseline=base)
        else:
            base = fit_kmeans(stream, full_data_coreset(stream), 6,
                              key=jax.random.PRNGKey(5), restarts=3,
                              backend="ref").params
            rep = evaluate(stream, fit_kmeans(stream, q.result.coreset(), 6,
                                              key=jax.random.PRNGKey(6),
                                              restarts=3, backend="ref"),
                           baseline=base)
        tree = svc.state(name).tree
        print(f"\n[{name}] m={q.m} summary of n={tree.n_total} "
              f"(height {tree.height}): rel_error={rep.rel_error:.4f}, "
              f"query {q.latency_s * 1e3:.0f} ms")
        print(tree.describe())

    # one-shot builds against a shared reference dataset batch ACROSS tenants
    ref_parts, ref_y = streams["ridge-co"][0]
    svc.attach_dataset("ref", VFLDataset(ref_parts, ref_y))
    t1 = svc.submit("ridge-co", "ref", 128, key=jax.random.PRNGKey(20))
    t2 = svc.submit("cluster-co", "ref", 256, key=jax.random.PRNGKey(21))
    built = svc.flush()                      # ONE batched dispatch
    print(f"\nbatched flush: tickets {sorted(built)} -> "
          f"{[int(built[t].indices.shape[0]) for t in sorted(built)]} rows")
    print(svc.describe())


if __name__ == "__main__":
    main()
