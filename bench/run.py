#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (device, data from the seed, warm-up from the compilation cache) is
timed from process start to the first timed request; then the cell's
traffic runs for ``--seconds``; then the outputs are compared with the
float64 references.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` traces the window and reports its per-layer metrics.  A run
that finds no TPU, or fewer chips than the cell asks for, prints no result
and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu would log under /tmp

from bench import harness  # noqa: E402


@dataclasses.dataclass
class Ctx:
    """What a metric reader sees."""

    config: Dict[str, Any]
    traffic: Dict[str, Any]
    device: Dict[str, Any]
    setup_s: float
    window_s: float
    attempted: int
    completed: int
    compiles: int
    spans: Any
    driver: Any
    trace: Optional[Any] = None


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, require_chip: bool = True, t_start: float = T_START,
        driver_hook=None) -> str:
    """One run of one cell; returns the result line.  ``driver_hook``
    (called with the driver after set-up) serves the control
    (``bench/control.py``) and the CPU tests; ``require_chip=False`` serves
    the CPU tests."""
    cell = harness.load_cell(workload, root)
    import jax

    if require_chip:
        device = harness.require_chips(jax, cell.chips)
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind, "count": 1}
    harness.use_compile_cache(jax, root)
    counter = harness.CompileCounter(jax)
    spans = harness.Spans(jax, trace)
    driver = harness.load_driver(cell.traffic, root)(jax, cell.config, cell.traffic,
                                                      seed, spans)
    driver.setup()
    if driver_hook is not None:
        driver_hook(driver)
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.3f} s, {counter.count} executables "
          f"({counter.seconds:.3f} s compiling or reading the cache)",
          file=sys.stderr, flush=True)

    trace_dir = root / ".bench_trace" / workload
    if trace:
        from bench import trace as tr

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    c0 = counter.count
    driver.window(seconds)
    compiles = counter.count - c0
    summary = None
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    if trace:
        summary = tr.reduce(tr.find_xplane(trace_dir), window="bench.window")
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    driver.free()

    t_check = time.perf_counter()
    checks = driver.check()
    print(f"check {time.perf_counter() - t_check:.3f} s over "
          f"{driver.completed} outputs", file=sys.stderr, flush=True)
    ctx = Ctx(cell.config, cell.traffic, device, setup_s, driver.window_s,
              driver.attempted, driver.completed, compiles, spans, driver, summary)
    metrics = harness.read_metrics(cell.per_layer if trace else cell.end_to_end,
                                   ctx, root)
    correct = (driver.failed == 0 and driver.completed > 0
               and all(c.ok for c in checks))
    harness.print_checks(checks)
    return harness.result_line(correct, driver.attempted, driver.failed, metrics,
                               device, checks,
                               summary.breakdown() if summary else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
