"""Driver of the k-means build mixes (``"driver": "kmeans_builds"``):
back-to-back Algorithm 3 coreset builds of the configuration's deployment,
closed loop, a fresh key per build, through ``CoresetPipeline.plan`` and
``.build`` to ``(S, w)`` ended by ``block_until_ready``, as the ``builds``
driver runs them.  Each party standardizes its own columns on the device
in float32; ``k``, ``alpha`` and ``local_iters`` go to the task through
``CoresetSpec.params``.  The check replays Algorithm 3 in float64
(``bench/reference_vkmc.py``) on the parts as set-up made them, one step
at a time from the program's own centres, which the driver computes again
for each build it checks with the program's ``kmeans_plusplus`` and
one-iteration ``lloyd`` on the data the program was given.  That copy is
tied to the timed build: its last centres must be the bits of the
``lloyd(iters=local_iters)`` call ``vkmc_scores`` makes (``lloyd_tie``),
and the timed build's weights must be those of the program's scoring at
them (``weight_tie``), so a fault in the timed call alone is not correct.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from bench import data, reference, reference_vkmc
from bench.drivers.builds import MAX_BUILDS, WARMUP_BUILDS
from bench.drivers.builds import Driver as Builds
from bench.harness import Check


def standardize(p):
    """Zero mean and unit variance per column, a constant column left at 0."""
    mu = p.mean(axis=0)
    sd = p.std(axis=0)
    return (p - mu) / jnp.where(sd > 0, sd, 1.0)


def weight_tie(g: np.ndarray, m: int, S: np.ndarray, w: np.ndarray) -> float:
    """Largest relative distance of the weights ``w`` of the draw ``S``
    from G / (m * sum_j g_j(S)) on the (T, n) scores ``g``; 1e9 for an S
    that is not m rows of the table."""
    S = np.asarray(S, np.int64)
    if len(S) != m or np.any((S < 0) | (S >= g.shape[1])):
        return 1e9
    ref = g.sum() / (m * g[:, S].sum(0))
    return float(np.max(np.abs(np.asarray(w, np.float64) - ref) / ref))


class Driver(Builds):
    def setup(self) -> None:
        from repro.core import CoresetSpec, VFLDataset

        jax, c, t = self.jax, self.config, self.traffic
        if c["task"] != "vkmc":
            raise ValueError(f"the kmeans_builds driver checks k-means (vkmc) builds "
                             f"only, not {c['task']!r}")
        if t["resident"] != "device":
            raise ValueError(f"resident must be 'device', not {t['resident']!r}")
        key = data.seed_key(self.seed)
        with self.spans("data"):
            X, _ = data.year_msd(jax.random.fold_in(key, 0), n=c["n"], d=c["d"])
            parts = [standardize(p) for p in data.split_parties(X, c["T"])]
            jax.block_until_ready(parts)
        self._parts = parts
        self.ds = VFLDataset(parts, None)
        self.spec = CoresetSpec(
            task=c["task"], budgets=c["m"], engine=t["engine"], backend="auto",
            params={"k": c["k"], "alpha": c["alpha"], "local_iters": c["local_iters"]})
        self.keys = np.asarray(jax.random.split(jax.random.fold_in(key, 1), MAX_BUILDS))
        warm = np.asarray(jax.random.split(jax.random.fold_in(key, 2), WARMUP_BUILDS))
        for k in warm:
            self._build(k)
        self.built.clear()

    def _chosen(self) -> List[int]:
        rng = np.random.default_rng([self.seed, 7])
        n_check = min(len(self.built), self.config["check"]["builds"])
        return (sorted(rng.choice(len(self.built), size=n_check, replace=False))
                if n_check else [])

    def _replay(self, key: np.ndarray, S: np.ndarray, w: np.ndarray
                ) -> Tuple[List[np.ndarray], Dict[str, float]]:
        """The program's state for the build ``key``, and its ties to the
        timed build.  Per party, the centres after seeding and after each
        Lloyd iteration: the program's own ``kmeans_plusplus`` and
        one-iteration ``lloyd`` steps, vmapped over the stacked parties.
        The ties, each an exact recomputation of what ``vkmc_scores`` ran:

        * ``lloyd_tie``: the largest distance of the last step's centres
          from ``lloyd(iters=local_iters)`` run from the seeding as
          ``vkmc_scores`` calls it; 0 where they are the same bits;
        * ``weight_tie``: :func:`weight_tie` of the timed build's ``(S, w)``
          on ``vkmc_local_scores`` at those centres, called as
          ``vkmc_scores`` calls it.  A build whose scoring ran from other
          centres (a Lloyd iteration left out, another precision or
          kernel) has other weights.
        """
        from repro.core import resolve_backend
        from repro.core.sensitivity import vkmc_local_scores
        from repro.core.vkmc import kmeans_plusplus, lloyd

        jax, c = self.jax, self.config
        k, alpha, iters = c["k"], c["alpha"], c["local_iters"]
        use_kernel = resolve_backend(self.spec.backend) == "pallas"
        st = self.ds.stacked()
        subs = reference.key_chain(reference.raw_key(key), self.ds.T + 1)[:-1]
        C = jax.vmap(lambda s, X: kmeans_plusplus(s, X, k))(jnp.asarray(subs),
                                                            st.blocks)
        path = [C]
        for _ in range(iters):
            C = jax.vmap(lambda X, C: lloyd(X, C, iters=1, use_kernel=use_kernel))(
                st.blocks, C)
            path.append(C)
        full = jax.vmap(lambda X, C: lloyd(X, C, iters=iters, use_kernel=use_kernel))(
            st.blocks, path[0])
        g = jax.vmap(lambda X, C: vkmc_local_scores(X, C, alpha, use_kernel=use_kernel))(
            st.blocks, full)
        ties = {"lloyd_tie": float(jnp.max(jnp.abs(full - C))),
                "weight_tie": weight_tie(np.asarray(g, np.float64), c["m"], S, w)}
        path = np.asarray(jnp.stack(path, axis=1), np.float64)
        return [path[j, :, :, :w_] for j, w_ in enumerate(st.dims)], ties

    def free(self) -> None:
        """Keep float64 copies of the parts as set-up made them, the
        outputs, and the program's centres and ties for the builds to
        check; drop the program's device state."""
        self._outs = [(np.asarray(b.indices), np.asarray(b.weights)) for b in self.built]
        self._check = [(i, *self._replay(self.built[i].key, *self._outs[i]))
                       for i in self._chosen()]
        self._parts64 = [np.asarray(p, np.float64) for p in self._parts]
        del self._parts, self.ds
        for b in self.built:
            b.indices = b.weights = None

    def check(self) -> List[Check]:
        c = self.config
        lim = c["limits"]
        T, m = c["T"], c["m"]
        worst: Dict[str, float] = {"bill_units": 0.0, "draw_gap": 0.0,
                                   "weight_rel": 0.0, "lloyd_gap": 0.0,
                                   "lloyd_tie": 0.0, "weight_tie": 0.0}
        self.ties = {"seed_ties": 0.0, "assign_ties": 0.0}
        for b in self.built:
            worst["bill_units"] = max(worst["bill_units"], float(
                abs(b.billed - b.predicted) + abs(b.billed - reference.comm_units(T, m))))
        for i, paths, ties in self._check:
            for k_, v in ties.items():
                worst[k_] = max(worst[k_], v)
            b = self.built[i]
            S, w = self._outs[i]
            got = reference_vkmc.check_build(
                reference.raw_key(b.key), self._parts64, c["alpha"], m, self._block(),
                S, w, b.party_counts, paths)
            for k_ in self.ties:
                self.ties[k_] += got.pop(k_)
            for k_, v in got.items():
                worst[k_] = max(worst[k_], v)
        print(f"near-ties over {len(self._check)} builds checked: "
              f"{self.ties['seed_ties']:.0f} seeding draws under "
              f"{reference_vkmc.SEED_SCREEN}, {self.ties['assign_ties']:.0f} drawn "
              f"(party, row) pairs under {reference_vkmc.ASSIGN_MARGIN}",
              file=sys.stderr, flush=True)
        return [Check(k_, v, float(lim[k_])) for k_, v in worst.items()]
