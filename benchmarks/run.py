"""Benchmark aggregator — one module per paper table/figure.

Prints the harness CSV ``name,us_per_call,derived`` (one line per method
cell; us_per_call = method wall time; derived = "cost=<avg loss>
comm=<units>") and writes the full per-bench CSVs to
benchmarks/artifacts/.

  PYTHONPATH=src python -m benchmarks.run           # fast (CPU-budget) sizes
  PYTHONPATH=src python -m benchmarks.run --full    # paper-scale n / repeats
  PYTHONPATH=src python -m benchmarks.run --sections kernel_micro,streaming
  PYTHONPATH=src python -m benchmarks.run --list    # show section names
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from repro.utils.compile_cache import use_compile_cache

# The paper benchmarks measure LOSS and COMMUNICATION.  Every section
# resolves its score backend through repro.core.api.resolve_backend:
# compiled Pallas kernels on TPU, the jnp references on CPU (interpret-mode
# wall numbers are only recorded behind kernel_micro / fused_lloyd's
# explicit --interpret flag, clearly labeled).
MODULES = [
    "vrlr_main",        # Table 1 left / Fig 2
    "vkmc_main",        # Table 1 right / Fig 3
    "parties",          # Fig 4/5 (T=5)
    "regularizers",     # Fig 6-8 (linear / lasso / elastic)
    "centers",          # Fig 9 (k=5)
    "second_dataset",   # Fig 10/11 (KC-House profile)
    "kernel_micro",     # Pallas kernel us/call
    "fused_lloyd",      # fused vs seed Lloyd step: passes-over-X + us/step
    "streaming",        # streaming vs materialized: rows/sec + peak bytes
    "e2e",              # spec-build + downstream fit: wall time + rel error
    "serve",            # online service: tenant latency + tree-vs-flat quality
    "selector_step",    # beyond-paper: LLM coreset batch selection
    "assumption_sweep",  # beyond-paper: Assumption 4.1/5.1 violation sweep
    "chaos",            # fault injection: retry billing + degrade + resume
    "integrity",        # silent corruption: detection + quarantine + overhead
    "overload",         # hostile tenant mix: shed/breaker/failover gates
    "compression",      # codec wire: raw identity + CRC/retry bits + tradeoff
]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--sections", "--only", dest="sections", default=None,
                    help="comma-separated subset of bench modules to run "
                         f"(known: {','.join(MODULES)})")
    ap.add_argument("--list", action="store_true",
                    help="print the section names and exit")
    ap.add_argument("--strict", action="store_true",
                    help="re-raise the first section failure instead of "
                         "continuing (non-zero exit with a traceback; used "
                         "by the CI gate steps)")
    args = ap.parse_args()
    use_compile_cache(Path(__file__).resolve().parents[1])
    if args.list:
        print("\n".join(MODULES))
        return 0
    mods = args.sections.split(",") if args.sections else MODULES
    unknown = [m for m in mods if m not in MODULES]
    if unknown:
        ap.error(f"unknown sections {unknown}; known: {','.join(MODULES)}")

    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run(fast=not args.full)
            for r in rows:
                label = f"{r['bench']}/{r['method']}({r['size']})"
                us = r["wall_s"] * 1e6
                derived = f"cost={r['cost_mean']:.4g} comm={r['comm']}"
                print(f"{label},{us:.0f},{derived}")
        except Exception as e:  # keep the suite going; report at the end
            if args.strict:
                raise
            # failures go to stderr ONLY — stdout stays parseable CSV
            failures += 1
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", file=sys.stderr)
            import traceback
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} finished in {time.time()-t0:.1f}s", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
