"""Vertical federated regression walkthrough: every method of the paper's
Table 1 on one synthetic YearPrediction-profile dataset, with per-round
communication bills printed from the ledger.

All coreset construction goes through ONE declarative surface —
``CoresetSpec`` compiled and dispatched by ``CoresetPipeline`` — and the
downstream ridge solve + full-data relative error come from the
``fit_ridge``/``evaluate`` layer (Theorem 4.1's composition).

  PYTHONPATH=src python examples/vfl_regression.py
"""

import jax

from repro.core import (
    CommLedger,
    CoresetPipeline,
    CoresetSpec,
    VFLDataset,
    central_comm_cost,
    evaluate,
    fit_ridge,
    ridge_closed_form,
    ridge_cost,
    saga_ridge,
)
from repro.data.synthetic import year_prediction_like


def main() -> None:
    key = jax.random.PRNGKey(0)
    X, y = year_prediction_like(key, n=20000)
    y = y - y.mean()
    ds = VFLDataset.from_dense(X, y, T=3)
    n, lam, m = ds.n, 0.1 * ds.n, 2000
    pipeline = CoresetPipeline(ds)

    def report(name, theta, led):
        c = float(ridge_cost(ds.full(), ds.y, theta, lam)) / n
        print(f"{name:12s} cost/n={c:8.3f}  comm={led.total:>12,}")

    led = CommLedger()
    central_comm_cost(n, ds.dims, led)
    theta_full = ridge_closed_form(ds.full(), ds.y, lam)
    report("CENTRAL", theta_full, led)

    led = CommLedger()
    theta = saga_ridge(jax.random.fold_in(key, 1), ds.full(), ds.y, lam,
                       steps=20000, dims=ds.dims, ledger=led)
    report("SAGA", theta, led)

    for name, task in (("C-CENTRAL", "vrlr"), ("U-CENTRAL", "uniform")):
        led = CommLedger()
        spec = CoresetSpec(task=task, budgets=m)
        cs = pipeline.build(spec, key=jax.random.fold_in(key, 2), ledger=led)
        for j in range(ds.T):
            led.party_to_server("rows", j, m * ds.dims[j])
        fit = fit_ridge(ds, cs, lam)
        report(f"{name}({m})", fit.params, led)
        rel = evaluate(ds, fit, baseline=theta_full).rel_error
        print(f"    full-data relative error: {rel:.4f}")
        if name == "C-CENTRAL":
            print("    DIS round bill:")
            for tag, units in sorted(led.by_tag().items()):
                print(f"      {tag:24s} {units:>10,}")


if __name__ == "__main__":
    main()
