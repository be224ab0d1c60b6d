#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers over many
seeds, and the control's.

  python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3 [--control]

The control is the program fed party data rounded to bfloat16, the
precision below the configuration's float32: what a change that kept the
parties' slices in bfloat16 to halve every pass's bytes would compute.  Its
outputs are compared with the float64 reference on the original data; a
sound comparison calls it not correct.  Each run prints one JSON line:
workload, seed, arm and the numbers compared.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402


def bf16_parties(driver) -> None:
    """Replace the driver's party data by its bfloat16 rounding, in the
    layout the mix keeps it in."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import VFLDataset

    ds = driver.ds

    def rnd(a):
        r = jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)
        return np.asarray(r) if isinstance(a, np.ndarray) else r

    driver.ds = VFLDataset([rnd(p) for p in ds.parts],
                           None if ds.y is None else rnd(ds.y))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true",
                    help="also run the bfloat16 control on every seed")
    args = ap.parse_args(argv)
    arms = [("program", None)] + ([("control", bf16_parties)] if args.control else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        for arm, hook in arms:
            line = json.loads(bench_run.run(
                args.workload, seed, args.seconds, False,
                t_start=time.perf_counter(), driver_hook=hook))
            print(json.dumps({"workload": args.workload, "seed": seed, "arm": arm,
                              "correct": line["correct"],
                              "checks": {k: v["value"] for k, v in line["checks"].items()},
                              "metrics": line["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
