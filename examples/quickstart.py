"""Quickstart: the paper's pipeline end-to-end in ~50 lines.

Declares ONE :class:`CoresetSpec`, compiles it into an ExecutionPlan
(`pipeline.plan(spec).describe()` shows the engine, memory model, and the
exact predicted communication bill BEFORE anything runs), builds the VRLR
coreset (Algorithm 2 + DIS), then closes the loop with the downstream
solve layer: ``fit_ridge`` on the coreset and ``evaluate`` for the paper's
full-data relative error — and finally sweeps seeds x budgets in a single
compiled call through the batched engine.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core import (
    CommLedger,
    CoresetPipeline,
    CoresetSpec,
    VFLDataset,
    central_comm_cost,
    evaluate,
    fit_ridge,
    ridge_closed_form,
)


def main() -> None:
    key = jax.random.PRNGKey(0)
    n, d, T, m = 20000, 30, 3, 800
    X = jax.random.normal(key, (n, d))
    theta_true = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    y = X @ theta_true + 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (n,))
    ds = VFLDataset.from_dense(X, y, T=T)
    lam = 0.1 * n

    # --- one declarative spec, compiled into an explicit plan --------------
    pipeline = CoresetPipeline(ds)
    spec = CoresetSpec(task="vrlr", budgets=m)
    print(pipeline.plan(spec).describe(), "\n")

    # --- build (Algorithm 2 + DIS) + downstream solve (Theorem 4.1) --------
    led_cs = CommLedger()
    cs = pipeline.build(spec, key=jax.random.fold_in(key, 3), ledger=led_cs)
    for j in range(T):                        # ship the m raw rows centrally
        led_cs.party_to_server("rows", j, m * ds.dims[j])
    fit = fit_ridge(ds, cs, lam)
    report = evaluate(ds, fit)

    led_full = CommLedger()
    central_comm_cost(n, ds.dims, led_full)
    theta_full = ridge_closed_form(ds.full(), ds.y, lam)

    print(f"n={n}  T={T}  coreset m={m}")
    print(f"CENTRAL   cost={report.cost_opt:12.2f}  comm={led_full.total:>12,} units")
    print(f"C-CENTRAL cost={report.cost_fit:12.2f}  comm={led_cs.total:>12,} units")
    print(f"relative error {report.rel_error:.4f}  "
          f"comm reduction {led_full.total / led_cs.total:.1f}x")

    # --- batched sweep: 4 seeds x 3 budgets, ONE compiled call -------------
    budgets = (200, 400, 800)
    grid_spec = CoresetSpec(task="vrlr", budgets=budgets, num_seeds=4,
                            backend="ref")
    grid = pipeline.build(grid_spec, key=jax.random.fold_in(key, 4))
    print(f"\nbatched sweep ({grid.num_seeds} seeds x {budgets}):")
    for mi, mm in enumerate(budgets):
        rels = []
        for r in range(grid.num_seeds):
            fit_b = fit_ridge(ds, grid.coreset(r, mi), lam)
            rels.append(evaluate(ds, fit_b, baseline=theta_full).rel_error)
        print(f"  m={mm:4d}  rel error mean={jnp.mean(jnp.array(rels)):.4f}  "
              f"comm={grid.coreset(0, mi).comm_units:>7,} units")


if __name__ == "__main__":
    main()
