"""What every run shares: the benchmark's description, the files of a cell,
the device check, the compilation cache and counter, host spans, and the
result line.

A cell is found by name in ``BENCHMARK.json``.  Its configuration is
``bench/configs/<config>.json``, its traffic ``bench/traffic/<traffic>.json``
and each metric ``bench/metrics/<metric>.py`` (a ``read(ctx)`` that returns
a number, or ``None`` where it finds nothing to read).  The traffic file
names its driver, ``bench/drivers/<driver>.py``, whose ``Driver`` makes the
load and checks what it produced; metric readers see it as ``ctx.driver``.
Adding a cell, a mix, a driver or a metric adds files and entries; no file
here changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _metrics_of(metrics, cell: str) -> List[Dict[str, Any]]:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name, int(w["chips"]), config, traffic,
                _metrics_of(bench["end_to_end"], name),
                _metrics_of(bench["per_layer"], name))


def _load(kind: str, name: str, root: Path):
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod              # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT) -> Callable[[Any], Optional[float]]:
    return _load("metrics", metric, root).read


def load_driver(traffic: Dict[str, Any], root: Path = ROOT):
    """The ``Driver`` class of the mix's ``driver`` file.  A driver is built
    as ``Driver(jax, config, traffic, seed, spans)`` and has ``setup()``,
    ``window(seconds)``, ``free()`` and ``check() -> [Check]``, and the
    counts ``attempted``, ``failed``, ``completed`` and ``window_s``."""
    return _load("drivers", traffic["driver"], root).Driver


def read_metrics(metrics: List[Dict[str, Any]], ctx, root: Path = ROOT) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        value = load_reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# Device, compilation cache and compile counter
# --------------------------------------------------------------------------

def require_chips(jax, chips: int) -> Dict[str, Any]:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r}, not a TPU; the benchmark "
                     f"does not run on another backend")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def use_compile_cache(jax, root: Path = ROOT) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else the fixed ``.jax_cache``
    of the checkout; every program is written back, however quick its
    compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Executables JAX compiled or read from its cache (one
    ``backend_compile`` event each), and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_) -> None:
        if name == self.EVENT:
            self.count += 1
            self.seconds += secs


# --------------------------------------------------------------------------
# Host spans
# --------------------------------------------------------------------------

class Spans:
    """Host spans from the benchmark's own files around calls into the
    program: (name, start, end) on ``time.perf_counter``.  With ``trace``
    each span is also a ``TraceAnnotation`` in the profiler's trace."""

    def __init__(self, jax, trace: bool) -> None:
        self._jax = jax
        self.trace = trace
        self.items: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = (self._jax.profiler.TraceAnnotation(f"bench.{name}")
               if self.trace else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.items.append((name, t0, time.perf_counter()))

    def in_window(self, name: str) -> List[float]:
        """Durations of the ``name`` spans inside the last ``window`` span."""
        lo, hi = next((s, e) for n, s, e in reversed(self.items) if n == "window")
        return [e - s for n, s, e in self.items if n == name and s >= lo and e <= hi]


# --------------------------------------------------------------------------
# The result
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict], device: Dict[str, Any],
                checks: List[Check], breakdown: Optional[Dict] = None) -> str:
    """The last line a run prints: the five result keys, then ``breakdown``
    (traced runs), then every number compared beside its limit."""
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return json.dumps(out)


def print_checks(checks: List[Check], file=sys.stderr) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} <= {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=file, flush=True)
