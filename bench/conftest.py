"""Fixtures of the benchmark's CPU tests: a checkout of its own with tiny
cells, written as new files beside copies of the metric readers."""

from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent

TINY = {
    "tiny-ridge": {"name": "tiny-ridge", "task": "vrlr", "n": 3000, "d": 9, "T": 3,
                   "m": 32, "precision": "float32"},
}
TRAFFIC = {
    "tiny-mat": {"driver": "builds", "engine": "materialized", "resident": "device"},
    "tiny-pipe": {"driver": "builds", "engine": "pipelined", "resident": "host",
                  "block_size": 1024, "chunk_blocks": 2},
    "tiny-count": {"driver": "count_keys", "keys": 5},
}
CELLS = [("ridge.mat", "tiny-ridge", "tiny-mat"),
         ("ridge.pipe", "tiny-ridge", "tiny-pipe"),
         ("ridge.count", "tiny-ridge", "tiny-count")]
NEW_METRIC = "def read(ctx):\n    return ctx.completed\n"
NEW_DRIVER_METRIC = "def read(ctx):\n    return max(ctx.driver.seen)\n"
NEW_DRIVER = '''\
"""A driver that no file of the harness knows: it draws the mix's number
of keys per request and records each request's largest key word."""
import time

import numpy as np

from bench.harness import Check


class Driver:
    def __init__(self, jax, config, traffic, seed, spans):
        self.jax, self.traffic, self.seed = jax, traffic, seed
        self.seen, self.attempted, self.failed, self.window_s = [], 0, 0, 0.0

    def setup(self):
        self.base = self.jax.random.PRNGKey(self.seed % 2 ** 31)

    def window(self, seconds):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ks = self.jax.random.split(self.jax.random.fold_in(self.base, self.attempted),
                                       self.traffic["keys"])
            self.seen.append(int(np.asarray(ks).max()))
            self.attempted += 1
        self.window_s = time.perf_counter() - t0

    @property
    def completed(self):
        return len(self.seen)

    def free(self):
        pass

    def check(self):
        return [Check("requests_lost", float(self.attempted - self.completed), 0.0)]
'''


def _limits():
    with open(BENCH / "configs" / "yearmsd-ridge.json") as f:
        return json.load(f)["limits"]


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout holding a BENCHMARK.json of tiny cells; the compilation
    cache stays off so the test process's JAX settings are left alone."""
    from bench import harness

    monkeypatch.setattr(harness, "use_compile_cache", lambda jax, root: None)
    for sub in ("configs", "traffic"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(BENCH / "drivers", tmp_path / "bench" / "drivers")
    (tmp_path / "bench" / "metrics" / "builds_done.py").write_text(NEW_METRIC)
    (tmp_path / "bench" / "metrics" / "largest_key.py").write_text(NEW_DRIVER_METRIC)
    (tmp_path / "bench" / "drivers" / "count_keys.py").write_text(NEW_DRIVER)
    for name, cfg in TINY.items():
        cfg = dict(cfg, limits=_limits(),
                   check={"builds": 2})
        (tmp_path / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, t in TRAFFIC.items():
        (tmp_path / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 1,
        "configs": [{"name": n, "source": "test", "file": f"bench/configs/{n}.json",
                     "reduced": [], "why": "test"} for n in TINY],
        "workloads": [{"name": w, "config": c, "traffic": t, "chips": 1, "why": "test"}
                      for w, c, t in CELLS],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
            {"name": "build_s", "unit": "s", "better": "lower", "bound": 0.05,
             "source": "host_clock"},
            {"name": "builds_done", "unit": "count", "better": "higher", "bound": 0.05,
             "source": "host_clock", "workloads": ["ridge.mat"]},
            {"name": "largest_key", "unit": "count", "better": "higher", "bound": 0.05,
             "source": "host_clock", "workloads": ["ridge.count"]}],
        "per_layer": [
            {"name": "plan_s.build", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "planner", "moves": "build_s"},
            {"name": "device_idle.build", "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "device", "moves": "build_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.fixture
def run_cell(tiny_root):
    """Drive a whole run of a tiny cell on the CPU, the chip check skipped;
    returns the parsed result line and the line itself."""
    from bench import run

    def go(workload, seed=2 ** 33 + 17, hook=None, trace=False, seconds=0.5):
        line = run.run(workload, seed, seconds, trace, root=tiny_root,
                       require_chip=False, t_start=time.perf_counter(),
                       driver_hook=hook)
        return json.loads(line), line

    return go
