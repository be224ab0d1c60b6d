"""The :class:`Coreset` container + offline coreset-quality evaluation.

The end-to-end builders for Algorithms 2/3 live in :mod:`repro.core.api`
(``build_coreset`` / ``build_coresets_batched``); the seed-era
``build_vrlr_coreset`` / ``build_vkmc_coreset`` / ``build_uniform_coreset``
entry points survive as deprecation shims in :mod:`repro.core`.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import CommLedger, CommSchedule
from repro.core.vfl import VFLDataset

if TYPE_CHECKING:
    from repro.core.faults import DegradedBuild
    from repro.core.integrity import HealthReport


@dataclasses.dataclass
class Coreset:
    """Index coreset: indices into the original rows + importance weights.

    Per Problem 1, the coreset is indices/weights — never raw rows — so the
    construction itself moves no feature data across parties.

    ``degraded`` (default None: a full-federation build) is the
    :class:`~repro.core.faults.DegradedBuild` receipt when the construction
    continued without every party under ``fault_policy="degrade"`` or
    ``"quarantine"`` — it names the dropped parties/rounds and the widened
    sensitivity bound.  ``health`` (default None: engines that never leave
    the traced path, e.g. batched) is the
    :class:`~repro.core.integrity.HealthReport` of the scoring state the
    draw actually used.
    """

    indices: jax.Array   # (m,) int
    weights: jax.Array   # (m,) float
    comm_units: int      # construction cost in paper units
    #: Construction cost in wire bits — the packed bytes the codec actually
    #: moved (32 bits/unit on the raw path, measured blob sizes under a
    #: compressed codec, retransmissions included).  0 from engines that
    #: predate or bypass the bits column (batched cells extracted
    #: without a ledger).
    comm_bits: int = 0
    degraded: Optional["DegradedBuild"] = None
    health: Optional["HealthReport"] = None

    @property
    def m(self) -> int:
        return int(self.indices.shape[0])

    def materialize(
        self, ds: VFLDataset, ledger: Optional[CommLedger] = None
    ) -> Tuple[jax.Array, Optional[jax.Array], jax.Array]:
        """(X_S, y_S, w) on the server.

        Running the downstream scheme on the coreset costs Theorem 2.5's
        ``+2mT`` extra units (each party: m indices down, m per-row scalar
        shares up); pass ``ledger`` to record them via
        ``CommSchedule.materialize``.  Callers that instead ship the raw
        feature blocks to a central solver should charge ``sum_j m*d_j``
        explicitly, as the benchmarks do — not both.
        """
        CommSchedule.materialize(ds.T, self.m).record(ledger)
        sub = ds.rows(self.indices)
        return sub.full(), sub.y, self.weights


@dataclasses.dataclass
class MaterializedCoreset:
    """A coreset together with its (host-resident) rows — the unit of state
    a long-lived serving layer keeps after the source rows are gone.

    An index :class:`Coreset` only points into a live :class:`VFLDataset`;
    a merge-and-reduce tree (:mod:`repro.serve.tree`) must instead retain
    the m selected rows themselves (per party, numpy, host memory) so later
    merges can re-score them without the original data.  ``indices`` stay
    GLOBAL row ids into the full stream, so the result still evaluates
    against the full dataset; ``comm_units`` is the protocol cost that
    produced this node (Thm 2.5-composed across merges).
    """

    indices: np.ndarray                 # (m,) int — global row ids
    weights: np.ndarray                 # (m,) float
    parts: List[np.ndarray]             # party j's selected rows (m, d_j)
    y: Optional[np.ndarray] = None      # (m,), when the task carries labels
    comm_units: int = 0
    comm_bits: int = 0                  # wire bits behind those units

    @property
    def m(self) -> int:
        return int(self.indices.shape[0])

    @property
    def T(self) -> int:
        return len(self.parts)

    def dataset(self) -> VFLDataset:
        """The rows as a (numpy-backed, host-resident) VFLDataset — what a
        merge re-scores, or a downstream solver fits on."""
        return VFLDataset(list(self.parts), self.y)

    def coreset(self) -> Coreset:
        """The index/weight view (global ids) for ledger-free evaluation
        against the full dataset."""
        return Coreset(jnp.asarray(self.indices), jnp.asarray(self.weights),
                       self.comm_units, comm_bits=self.comm_bits)

    @staticmethod
    def from_coreset(
        cs: Coreset, ds: VFLDataset, offset: int = 0
    ) -> "MaterializedCoreset":
        """Materialize ``cs``'s rows out of ``ds`` host-side.  ``offset``
        shifts the (ds-local) indices into the global row space — the leaf
        case of the merge-and-reduce tree, where ``ds`` is one arriving
        superchunk starting at global row ``offset``."""
        offset = int(offset)
        if offset < 0:
            raise ValueError(f"offset must be >= 0, got {offset}")
        idx = np.asarray(cs.indices).astype(np.int64)
        if idx.size and offset > np.iinfo(np.int64).max - int(idx.max()):
            raise OverflowError(
                f"global id overflow: offset {offset} + max local index "
                f"{int(idx.max())} exceeds int64"
            )
        y = None if ds.y is None else np.asarray(ds.y)[idx]
        return MaterializedCoreset(
            indices=idx + offset,
            weights=np.asarray(cs.weights),
            parts=[np.asarray(p)[idx] for p in ds.parts],
            y=y,
            comm_units=int(cs.comm_units),
            comm_bits=int(cs.comm_bits),
        )

    @staticmethod
    def concat(mats: List["MaterializedCoreset"]) -> "MaterializedCoreset":
        """The weighted union of several materialized coresets (rows and
        weights concatenated; no re-sampling, no protocol cost — union is
        server-side bookkeeping).  ``comm_units`` sums the children's."""
        if not mats:
            raise ValueError("concat needs at least one coreset")
        T = mats[0].T
        if any(m.T != T for m in mats):
            raise ValueError("party counts differ across coresets")
        widths = tuple(p.shape[1] for p in mats[0].parts)
        for i, mt in enumerate(mats[1:], start=1):
            w = tuple(p.shape[1] for p in mt.parts)
            if w != widths:
                raise ValueError(
                    f"party widths differ across coresets: coreset 0 has "
                    f"{widths}, coreset {i} has {w}"
                )
        has_y = mats[0].y is not None
        if any((m.y is not None) != has_y for m in mats):
            raise ValueError("label presence differs across coresets")
        return MaterializedCoreset(
            indices=np.concatenate([m.indices for m in mats]),
            weights=np.concatenate([m.weights for m in mats]),
            parts=[np.concatenate([m.parts[j] for m in mats])
                   for j in range(T)],
            y=np.concatenate([m.y for m in mats]) if has_y else None,
            comm_units=sum(m.comm_units for m in mats),
            comm_bits=sum(m.comm_bits for m in mats),
        )


# --------------------------------------------------------------------------
# Offline coreset quality evaluation (used by tests / EXPERIMENTS.md)
# --------------------------------------------------------------------------

def vrlr_coreset_ratio(
    ds: VFLDataset, cs: Coreset, thetas: jax.Array, lam: float
) -> jax.Array:
    """max_theta |cost^R(S,theta)/cost^R(X,theta) - 1| over a probe set of
    thetas (empirical epsilon; Definition 2.3)."""
    X, y = ds.full(), ds.y
    XS, yS, w = cs.materialize(ds)

    def ratio(theta):
        reg = lam * jnp.sum(theta * theta)
        full = jnp.sum((X @ theta - y) ** 2) + reg
        sub = jnp.sum(w * (XS @ theta - yS) ** 2) + reg
        return jnp.abs(sub / full - 1.0)

    return jnp.max(jax.vmap(ratio)(thetas))


def vkmc_coreset_ratio(ds: VFLDataset, cs: Coreset, center_sets: jax.Array) -> jax.Array:
    """max_C |cost^C(S,C)/cost^C(X,C) - 1| over probe center sets
    (empirical epsilon; Definition 2.4)."""
    X = ds.full()
    XS, _, w = cs.materialize(ds)

    def ratio(C):
        d2_full = jnp.min(
            jnp.sum((X[:, None, :] - C[None, :, :]) ** 2, axis=-1), axis=1
        ).sum()
        d2_sub = (
            w * jnp.min(jnp.sum((XS[:, None, :] - C[None, :, :]) ** 2, axis=-1), axis=1)
        ).sum()
        return jnp.abs(d2_sub / d2_full - 1.0)

    return jnp.max(jax.vmap(ratio)(center_sets))
