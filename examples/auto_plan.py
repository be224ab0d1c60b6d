"""Auto-planner demo: ONE spec, three engines, chosen by memory budget.

The same declarative ``CoresetSpec`` is compiled against three different
``memory_budget_bytes`` values.  The planner's memory model (calibrated
against the measured yardsticks in BENCH_kernels.json) picks:

  * a LOOSE budget  -> materialized (everything fits on device),
  * a MEDIUM budget -> pipelined   (double-buffered superchunks fit),
  * a TIGHT budget  -> streamed    (one block at a time — minimum footprint).

Every plan prints its full ``describe()`` (engine, resolved knobs, memory
model, exact predicted comm bill), and every build is checked
DRAW-IDENTICAL to its forced-engine plan — the auto-planner changes where
the computation runs, never what it draws.

  PYTHONPATH=src python examples/auto_plan.py
"""

import jax
import numpy as np

from repro.core import CoresetPipeline, CoresetSpec, VFLDataset


def main() -> None:
    n, d, T, m = 200_000, 30, 3, 512
    rng = np.random.default_rng(0)
    X = rng.standard_normal((n, d), dtype=np.float32)
    y = X @ rng.standard_normal(d).astype(np.float32)
    # numpy-backed parts stay host-resident: the streaming engines only ever
    # put one superchunk on device
    base, rem = divmod(d, T)
    widths = [base + (1 if j < rem else 0) for j in range(T)]
    offs = np.cumsum([0] + widths)
    ds = VFLDataset([X[:, offs[j]:offs[j + 1]] for j in range(T)], y)
    pipeline = CoresetPipeline(ds)
    key = jax.random.PRNGKey(0)

    budgets = {
        "loose (256MB)": 256 << 20,
        "medium (16MB)": 16 << 20,
        "tight (2MB)": 2 << 20,
    }
    draws = {}
    for label, budget in budgets.items():
        spec = CoresetSpec(task="vrlr", budgets=m, block_size=8192,
                           chunk_blocks=4, memory_budget_bytes=budget)
        plan = pipeline.plan(spec)
        print(f"--- {label} ---")
        print(plan.describe())
        cs = pipeline.build(plan, key=key)
        # the same spec FORCED onto the chosen engine draws identically
        forced = pipeline.build(spec.replace(engine=plan.engine,
                                             memory_budget_bytes=None),
                                key=key)
        assert np.array_equal(np.asarray(cs.indices), np.asarray(forced.indices))
        print(f"engine={plan.engine}: {cs.m} draws, comm={cs.comm_units} "
              f"(matches forced plan)\n")
        draws[plan.engine] = np.asarray(cs.indices)

    engines = sorted(draws)
    print(f"engines exercised: {engines}")
    # materialized vs streaming draws differ (flat vs hierarchical key
    # chains) — but every streaming engine draws the same multiset
    if "streamed" in draws and "pipelined" in draws:
        assert np.array_equal(draws["streamed"], draws["pipelined"])
        print("streamed == pipelined draws: identical (pinned)")


if __name__ == "__main__":
    main()
