"""Vertical federated k-means with coresets (Algorithm 3) vs DistDim.

Plants k Gaussian clusters whose geometry is visible to every party
(Assumption 5.1 regime), then compares:
  KMEANS++ (centralised), DISTDIM (Ding et al., O(nT) comm),
  C-KMEANS++ (coreset), U-KMEANS++ (uniform).

Coresets are declared as ``CoresetSpec``s and built by ``CoresetPipeline``;
the downstream weighted k-means and the full-data relative error come from
the ``fit_kmeans``/``evaluate`` layer (Theorem 5.2's composition).

  PYTHONPATH=src python examples/vfl_kmeans.py
"""

import jax

from repro.core import (
    CommLedger,
    CoresetPipeline,
    CoresetSpec,
    VFLDataset,
    distdim,
    evaluate,
    fit_kmeans,
    full_data_coreset,
    kmeans_cost,
)
from repro.core.vkmc import kmeans_central_comm_cost
from repro.data.synthetic import correlated_vfl_data


def main() -> None:
    key = jax.random.PRNGKey(1)
    n, d, T, k, m = 30000, 24, 3, 8, 1000
    X = correlated_vfl_data(key, n, d, T, cross_correlation=0.8, k_clusters=k)
    ds = VFLDataset.from_dense(X, None, T=T)
    pipeline = CoresetPipeline(ds)

    led = CommLedger()
    kmeans_central_comm_cost(n, ds.dims, led)
    # the CENTRAL baseline is the identity coreset through the same solver;
    # best-of-5 restarts keeps the baseline out of bad Lloyd basins
    fit_full = fit_kmeans(ds, full_data_coreset(ds), k,
                          key=jax.random.fold_in(key, 1), restarts=5)
    print(f"KMEANS++   cost={fit_full.objective/n:9.4f} comm={led.total:>12,}")

    led = CommLedger()
    cent_dd = distdim(jax.random.fold_in(key, 2), ds, k, ledger=led)
    print(f"DISTDIM    cost={float(kmeans_cost(ds.full(), cent_dd))/n:9.4f} "
          f"comm={led.total:>12,}")

    for name, task in (("C-KMEANS++", "vkmc"), ("U-KMEANS++", "uniform")):
        led = CommLedger()
        spec = CoresetSpec(task=task, budgets=m,
                           params={"k": k} if task == "vkmc" else {})
        cs = pipeline.build(spec, key=jax.random.fold_in(key, 3), ledger=led)
        for j in range(T):
            led.party_to_server("rows", j, m * ds.dims[j])
        fit = fit_kmeans(ds, cs, k, key=jax.random.fold_in(key, 4),
                         restarts=3)
        rep = evaluate(ds, fit, baseline=fit_full.params)
        print(f"{name} cost={rep.cost_fit/n:9.4f} comm={led.total:>12,}   "
              f"(m={m}, rel err {rep.rel_error:+.4f})")


if __name__ == "__main__":
    main()
