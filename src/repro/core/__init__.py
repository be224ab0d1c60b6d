"""The paper's primary contribution: communication-efficient coreset
construction for vertical federated learning.

Public API:
  CoresetSpec, ExecutionPlan, compile_plan, ENGINES,
  PlanCache                                               (plan — declarative spec
                                                           + auto-planner)
  CoresetPipeline, build_coreset,
  build_coresets_batched, build_coreset_streaming,
  CoresetTask, register_task, get_task,
  CORESET_TASKS, SCORE_BACKENDS, resolve_backend          (api — spec-compiled engines)
  fit_ridge, fit_kmeans, evaluate, end_to_end,
  FitResult, EvalReport, full_data_coreset                (solve — downstream layer)
  VFLDataset, split_columns, standardize                  (vfl)
  CommLedger, CommSchedule, theoretical_dis_cost          (comm)
  FaultPlan, Transport, PartyUnavailable, DegradedBuild,
  DroppedParty, TransportStats, StreamCheckpoint,
  deliver_or_record, FAULT_POLICIES,
  SILENT_KINDS, perturb_payload                           (faults — party fault model)
  IntegrityError, WireEnvelope, Finding, HealthReport,
  payload_digest, check_mass_table, check_weights,
  check_merge_children, health_from_masses,
  require_valid_masses                                    (integrity — verified wire)
  Codec, get_codec, WIRE_CODECS, CODEC_LADDER,
  WirePayload, fmt_bits, UNIT_BITS,
  predict_dis_bits, predict_uniform_bits                  (wire — compressed codecs)
  dis_plan, dis_plan_full, dis_plan_blocked, server_plan, uniform_plan,
  dis_sample, uniform_sample, dis_marginals,
  dis_blocked_marginals, blocked_geometry                 (dis — Algorithm 1)
  StreamScorer, make_stream_scorer, dis_plan_streamed,
  dis_plan_streamed_batched, vkmc_local_centers,
  vrlr_block_masses_sharded, vkmc_block_masses_sharded    (streaming — block-scan n)
  vrlr_local_scores, vkmc_local_scores, ...               (sensitivity — Alg 2/3 local)
  Coreset, MaterializedCoreset,
  vrlr_coreset_ratio, vkmc_coreset_ratio                  (coreset)
  ridge_closed_form, fista, saga_ridge, solve             (vrlr solvers)
  kmeans, kmeans_plusplus, lloyd, distdim, ...            (vkmc solvers)
  SelectorConfig, make_mesh_selector                      (selector — LLM integration)

Deprecated (seed API, kept as bit-identical shims):
  build_vrlr_coreset, build_vkmc_coreset, build_uniform_coreset
"""

import warnings
from typing import Optional

import jax

from repro.core.api import (
    CORESET_TASKS,
    SCORE_BACKENDS,
    BatchedCoresets,
    CoresetPipeline,
    CoresetTask,
    FailoverAttempt,
    FailoverOutcome,
    build_coreset,
    build_coreset_streaming,
    build_coresets_batched,
    get_task,
    register_task,
    resolve_backend,
)
from repro.core.plan import (
    DEFAULT_CHUNK_BLOCKS,
    ENGINES,
    FAILOVER_LADDER,
    CoresetSpec,
    ExecutionPlan,
    MemoryBudgetExceeded,
    MemoryWatchdog,
    PlanCache,
    compile_plan,
    live_bytes,
    memory_model,
)
from repro.core.solve import (
    EvalReport,
    FitResult,
    end_to_end,
    evaluate,
    fit_kmeans,
    fit_ridge,
    full_data_coreset,
    solver_for,
)
from repro.core.comm import CommLedger, CommSchedule, theoretical_dis_cost
from repro.core.faults import (
    FAULT_POLICIES,
    SILENT_KINDS,
    Clock,
    Deadline,
    DeadlineExceeded,
    DegradedBuild,
    DroppedParty,
    FaultPlan,
    PartyUnavailable,
    SimClock,
    StreamCheckpoint,
    Transport,
    TransportStats,
    WallClock,
    deliver_or_record,
    perturb_payload,
)
from repro.core.integrity import (
    GRAM_COND_WARN,
    Finding,
    HealthReport,
    IntegrityError,
    WireEnvelope,
    check_mass_table,
    check_merge_children,
    check_weights,
    health_from_masses,
    payload_digest,
    require_valid_masses,
)
from repro.core.coreset import (
    Coreset,
    MaterializedCoreset,
    vkmc_coreset_ratio,
    vrlr_coreset_ratio,
)
from repro.core.dis import (
    blocked_geometry,
    dis_blocked_marginals,
    dis_marginals,
    dis_plan,
    dis_plan_blocked,
    dis_plan_full,
    dis_sample,
    server_plan,
    split_uploads,
    uniform_plan,
    uniform_sample,
)
from repro.core.streaming import (
    StreamScorer,
    dis_plan_streamed,
    dis_plan_streamed_batched,
    make_stream_scorer,
    register_stream_scorer,
    vkmc_block_masses_sharded,
    vkmc_local_centers,
    vrlr_block_masses_sharded,
)
from repro.core.sensitivity import (
    kmeans_assignment,
    leverage_scores,
    norm_scores,
    ridge_leverage_scores,
    total_sensitivity_bound_vkmc,
    total_sensitivity_bound_vrlr,
    vkmc_local_scores,
    vrlr_local_scores,
)
from repro.core.vfl import VFLDataset, split_columns, standardize
from repro.core.wire import (
    CODEC_LADDER,
    UNIT_BITS,
    WIRE_CODECS,
    Codec,
    WirePayload,
    fmt_bits,
    get_codec,
    predict_dis_bits,
    predict_uniform_bits,
)
from repro.core.vkmc import distdim, kmeans, kmeans_cost, kmeans_plusplus, lloyd
from repro.core.vrlr import (
    central_comm_cost,
    elastic_cost,
    fista,
    lasso_cost,
    ridge_closed_form,
    ridge_cost,
    saga_ridge,
    solve,
    sq_loss,
)


# --------------------------------------------------------------------------
# Deprecated seed-era builders — thin shims over build_coreset.
# Same PRNG key => bit-identical (S, w) and identical ledger totals.
# --------------------------------------------------------------------------

def _deprecated(old: str, new: str) -> None:
    warnings.warn(f"{old} is deprecated; use {new}",
                  DeprecationWarning, stacklevel=3)


def build_vrlr_coreset(
    key: jax.Array,
    ds: VFLDataset,
    m: int,
    ledger: Optional[CommLedger] = None,
    use_kernel: bool = True,
) -> Coreset:
    """Deprecated: use ``build_coreset("vrlr", ds, m, key=key, ...)``."""
    _deprecated("build_vrlr_coreset", 'build_coreset("vrlr", ...)')
    # use_kernel=True maps to "auto" (kernels where they profit — TPU),
    # so the shim keeps resolving to the same backend as build_coreset's
    # default and stays draw-identical to it on every platform.
    return build_coreset("vrlr", ds, m, key=key,
                         backend="auto" if use_kernel else "ref",
                         ledger=ledger)


def build_vkmc_coreset(
    key: jax.Array,
    ds: VFLDataset,
    k: int,
    m: int,
    alpha: float = 2.0,
    local_iters: int = 15,
    ledger: Optional[CommLedger] = None,
    use_kernel: bool = True,
) -> Coreset:
    """Deprecated: use ``build_coreset("vkmc", ds, m, key=key, k=k, ...)``."""
    _deprecated("build_vkmc_coreset", 'build_coreset("vkmc", ...)')
    return build_coreset("vkmc", ds, m, key=key,
                         backend="auto" if use_kernel else "ref",
                         ledger=ledger, k=k, alpha=alpha,
                         local_iters=local_iters)


def build_uniform_coreset(
    key: jax.Array,
    ds: VFLDataset,
    m: int,
    ledger: Optional[CommLedger] = None,
) -> Coreset:
    """Deprecated: use ``build_coreset("uniform", ds, m, key=key, ...)``."""
    _deprecated("build_uniform_coreset", 'build_coreset("uniform", ...)')
    return build_coreset("uniform", ds, m, key=key, ledger=ledger)


import inspect as _inspect

__all__ = [
    n for n, v in list(globals().items())
    if not n.startswith("_") and not _inspect.ismodule(v) and n != "Optional"
]
