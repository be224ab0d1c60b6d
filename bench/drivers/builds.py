"""Driver of the build mixes (``"driver": "builds"``): back-to-back
coreset builds of the configuration's deployment, closed loop, a fresh key
per build, through ``CoresetPipeline.plan`` and ``.build`` to ``(S, w)``
ended by ``block_until_ready``.  The mix's data file names the engine,
where the party data lives (``device`` or ``host``) and the streaming
geometry.  The score backend is the program's own choice for the device
(``auto``: the Pallas kernels on a TPU).
"""

from __future__ import annotations

import dataclasses
import sys
import time
import traceback
from typing import Any, Dict, List

import numpy as np

from bench import data, reference
from bench.harness import Check

MAX_BUILDS = 4096
WARMUP_BUILDS = 2        # the first compiles or reads the cache, the second is warm


@dataclasses.dataclass
class Built:
    key: np.ndarray              # the build's raw key
    indices: Any                 # device (m,)
    weights: Any                 # device (m,)
    party_counts: List[int]      # round-2 uploads a_j as billed
    billed: int                  # ledger total
    predicted: int               # the plan's predicted bill


class Driver:
    def __init__(self, jax, config: Dict, traffic: Dict, seed: int, spans) -> None:
        self.jax, self.config, self.traffic = jax, config, traffic
        self.seed, self.spans = seed, spans
        self.built: List[Built] = []
        self.attempted = self.failed = 0
        self.window_s = 0.0

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        from repro.core import CoresetSpec, VFLDataset

        jax, c, t = self.jax, self.config, self.traffic
        if c["task"] != "vrlr":
            raise ValueError(f"the builds driver checks ridge (vrlr) builds only, "
                             f"not {c['task']!r}")
        key = data.seed_key(self.seed)
        with self.spans("data"):
            X, y = data.year_msd(jax.random.fold_in(key, 0), n=c["n"], d=c["d"])
            parts = data.split_parties(X, c["T"])
            jax.block_until_ready((parts, y))
        self._X, self._y = X, y
        labels = y
        if t["resident"] == "host":
            parts = [np.asarray(p) for p in parts]
            labels = np.asarray(labels)
        elif t["resident"] != "device":
            raise ValueError(f"resident must be 'device' or 'host', not {t['resident']!r}")
        self.ds = VFLDataset(parts, labels)
        self.spec = CoresetSpec(
            task=c["task"], budgets=c["m"], engine=t["engine"], backend="auto",
            block_size=t.get("block_size", 65536), chunk_blocks=t.get("chunk_blocks"))
        self.keys = np.asarray(jax.random.split(jax.random.fold_in(key, 1), MAX_BUILDS))
        warm = np.asarray(jax.random.split(jax.random.fold_in(key, 2), WARMUP_BUILDS))
        for k in warm:
            self._build(k)
        self.built.clear()

    def _build(self, k: np.ndarray) -> Built:
        from repro.core import CommLedger, CoresetPipeline

        with self.spans("handoff"):
            pipe = CoresetPipeline(self.ds)
        with self.spans("plan"):
            plan = pipe.plan(self.spec)
        led = CommLedger()
        with self.spans("build"):
            cs = pipe.build(plan, key=k, ledger=led)
            self.jax.block_until_ready((cs.indices, cs.weights))
        counts = [0] * self.ds.T
        for msg in led.messages:
            if msg.tag == "dis/round2/S_up":
                counts[int(msg.src.split(":")[1])] += msg.units
        b = Built(k, cs.indices, cs.weights, counts, led.total,
                  plan.predicted_comm_units)
        self.built.append(b)
        return b

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        with self.spans("window"):
            while self.attempted < MAX_BUILDS:
                k = self.keys[self.attempted]
                self.attempted += 1
                try:
                    self._build(k)
                except Exception:                     # a failed build is counted
                    self.failed += 1
                    traceback.print_exc(file=sys.stderr)
                if time.perf_counter() - t0 >= seconds:
                    break
        self.window_s = time.perf_counter() - t0

    @property
    def completed(self) -> int:
        return len(self.built)

    def free(self) -> None:
        """Keep the host copies the reference needs; drop the program's
        device state."""
        self._Xh = np.asarray(self._X, np.float64)
        self._yh = np.asarray(self._y, np.float64)
        self._outs = [(np.asarray(b.indices), np.asarray(b.weights)) for b in self.built]
        del self._X, self._y, self.ds
        for b in self.built:
            b.indices = b.weights = None

    # -- the comparison -------------------------------------------------------
    def check(self) -> List[Check]:
        c = self.config
        lim = c["limits"]
        T, m, n = c["T"], c["m"], c["n"]
        parts = data.split_parties(self._Xh, T)
        rng = np.random.default_rng([self.seed, 7])
        n_check = min(len(self.built), c["check"]["builds"])
        chosen = (sorted(rng.choice(len(self.built), size=n_check, replace=False))
                  if n_check else [])
        worst = {"bill_units": 0.0, "draw_gap": 0.0, "weight_rel": 0.0}
        for b in self.built:
            worst["bill_units"] = max(worst["bill_units"], float(
                abs(b.billed - b.predicted) + abs(b.billed - reference.comm_units(T, m))))
        g_ref = reference.vrlr_scores(parts, self._yh)
        for i in chosen:
            b = self.built[i]
            S, w = self._outs[i]
            got = reference.check_draw(reference.raw_key(b.key), g_ref, m, self._block(),
                                       S, w, b.party_counts)
            for k_, v in got.items():
                worst[k_] = max(worst[k_], v)
        return [Check(k, v, float(lim[k])) for k, v in worst.items()]

    def _block(self) -> int:
        if self.traffic["engine"] in ("streamed", "pipelined"):
            return int(self.traffic["block_size"])
        return int(self.config["n"])

