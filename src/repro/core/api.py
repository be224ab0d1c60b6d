"""Unified CoresetPipeline API: one declarative entry point for every engine.

The paper's Algorithms 1-3 share a single shape — party-local scores ->
DIS sampling -> importance weights — which this module makes explicit:

  * :class:`CoresetTask` + :func:`register_task` — a declarative task spec in
    a string registry (``CORESET_TASKS``, built on ``repro.utils.registry``).
    Shipped tasks: ``vrlr`` (Algorithm 2), ``vkmc`` (Algorithm 3), ``uniform``
    (the U-* baseline).  New tasks (e.g. communication-compressed or DP
    score variants) plug in with one decorator and inherit the DIS core,
    accounting, and every engine for free.
  * :class:`CoresetPipeline` — the spec-compiled entry point.  A frozen
    :class:`repro.core.plan.CoresetSpec` is compiled by
    :func:`repro.core.plan.compile_plan` into an
    :class:`~repro.core.plan.ExecutionPlan` naming ONE concrete engine —
    ``materialized | batched | streamed | pipelined`` — with auto-selection
    driven by the memory model when the spec carries a
    ``memory_budget_bytes``; ``CoresetPipeline.build`` dispatches on the
    plan.  ``pipeline.plan(spec).describe()`` shows every planner decision
    (engine, clamps, predicted peak bytes, predicted comm units) before
    anything runs.
  * The three legacy entry points — :func:`build_coreset` (materialized),
    :func:`build_coreset_streaming` (streamed/pipelined), and
    :func:`build_coresets_batched` (batched) — are thin shims constructing
    forced-engine specs; each is DRAW-IDENTICAL to the same spec through
    ``CoresetPipeline.build`` (same code path, pinned by
    ``tests/test_plan.py``).
  * The single-cell engines (materialized, streamed, pipelined) run
    Algorithm 1's rounds through ONE protocol body, :func:`_build_one`;
    each engine supplies only its scoring, its round-1 mass table and its
    draw.

Key-consumption choreography matches the seed builders exactly, so the
deprecated ``build_vrlr_coreset`` / ``build_vkmc_coreset`` shims in
:mod:`repro.core` return bit-identical ``(S, w)`` for the same PRNG key.
The downstream solve layer (closed-form weighted ridge, weighted Lloyd,
relative-error evaluation) lives in :mod:`repro.core.solve`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import CommLedger, CommSchedule
from repro.core.coreset import Coreset
from repro.core.dis import (
    _float_dtype,
    dis_plan_compiled,
    dis_plan_full,
    round2_rows,
    split_uploads,
    uniform_plan,
)
from repro.core.faults import (
    DeadlineExceeded,
    DegradedBuild,
    DroppedParty,
    PartyUnavailable,
    StreamCheckpoint,
    Transport,
)
from repro.core.integrity import (
    HealthReport,
    IntegrityError,
    check_weights,
    health_from_masses,
    require_valid_masses,
)
from repro.core.plan import (
    DEFAULT_CHUNK_BLOCKS,
    ENGINES,
    SCORE_BACKENDS,
    CoresetSpec,
    ExecutionPlan,
    MemoryBudgetExceeded,
    MemoryWatchdog,
    PlanCache,
    compile_plan,
)
from repro.core.sensitivity import (
    norm_scores,
    total_sensitivity_bound_vkmc,
    total_sensitivity_bound_vrlr,
    vkmc_local_scores,
    vrlr_scores_stacked,
)
from repro.core.vfl import VFLDataset
from repro.core.vkmc import kmeans_plusplus, lloyd
from repro.core.wire import WirePayload, get_codec
from repro.utils import trace
from repro.utils.registry import Registry

CORESET_TASKS = Registry("coreset_task")


def resolve_backend(backend: str) -> str:
    """Resolve ``"auto"`` to a concrete ScoreBackend for this process.

    ``auto`` picks ``pallas`` on TPU (compiled kernels) and ``ref``
    everywhere else — the kernels only interpret on CPU, 25-60x slower than
    the compiled jnp references there (BENCH_kernels.json), and refuse any
    other backend.  Explicit names pass through (and are validated).
    """
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend not in SCORE_BACKENDS:
        raise ValueError(
            f"unknown score backend {backend!r}; expected 'auto' or one of "
            f"{SCORE_BACKENDS}"
        )
    return backend


def _key_data(k: jax.Array) -> np.ndarray:
    """Raw uint32 view of a PRNG key — works for both legacy uint32 keys and
    new-style typed keys (which np.asarray refuses to convert)."""
    if jnp.issubdtype(k.dtype, jax.dtypes.prng_key):
        k = jax.random.key_data(k)
    return np.asarray(k)


def _use_kernel(backend: str) -> bool:
    if backend not in SCORE_BACKENDS:
        raise ValueError(
            f"unknown score backend {backend!r}; expected one of {SCORE_BACKENDS}"
        )
    return backend == "pallas"


# ScoreFn(key, ds, backend=..., **params) -> (scores (T, n), dis_key).
# Returning the key for the DIS stage lets tasks that consume PRNG state
# while scoring (vkmc's local k-means seeding) keep the seed's exact
# split chain.
ScoreFn = Callable[..., Tuple[jax.Array, jax.Array]]


@dataclasses.dataclass(frozen=True)
class CoresetTask:
    """Declarative spec of one coreset-construction task.

    ``score_fn is None`` marks the uniform baseline: no scores travel, the
    schedule is broadcast-only.  ``deterministic_scores`` asserts the
    score_fn neither consumes nor transforms the PRNG key (it returns the
    key it was given, as ``vrlr`` does), letting the batched builder hoist
    scoring out of the vmapped hot path and share scores across all seeds;
    the builder verifies the contract and falls back to per-seed scoring if
    the returned dis_key differs.
    """

    name: str
    score_fn: Optional[ScoreFn]
    needs_labels: bool = False
    deterministic_scores: bool = True
    description: str = ""


def register_task(name: str, **spec_kwargs):
    """Decorator: register a score function as task ``name``.

    The decorated callable keeps its identity (so it stays directly
    importable/testable); the registry stores the wrapping
    :class:`CoresetTask`.
    """

    def deco(score_fn: ScoreFn) -> ScoreFn:
        CORESET_TASKS.register(name)(
            CoresetTask(name=name, score_fn=score_fn, **spec_kwargs)
        )
        return score_fn

    return deco


def get_task(task: Union[str, CoresetTask]) -> CoresetTask:
    if isinstance(task, CoresetTask):
        return task
    return CORESET_TASKS.get(task)


# --------------------------------------------------------------------------
# Shipped tasks
# --------------------------------------------------------------------------

@register_task("vrlr", needs_labels=True,
               description="Algorithm 2: per-party ridge-leverage scores + DIS")
def vrlr_scores(key, ds: VFLDataset, backend: str = "pallas"):
    """Algorithm 2 lines 2-3: g_i^(j) = ||u_i^(j)||^2 + 1/n per party, with
    party T scoring [X^(T), y].  Deterministic — the key passes through to
    DIS untouched (the seed's choreography).

    All T parties are scored by ONE dispatch over the padded stacked view
    ((T, n, s) blocks, labels pre-appended): batched Gram + eigh, then a
    single party-batched ``leverage`` kernel call — no Python party loop.
    """
    with jax.named_scope("score"):
        st = ds.stacked(with_labels=True)
        if backend == "norm":
            return norm_scores(st.blocks) + 1.0 / ds.n, key
        scores = vrlr_scores_stacked(st.blocks, use_kernel=_use_kernel(backend))
    return scores, key


@register_task("vkmc", deterministic_scores=False,
               description="Algorithm 3: local alpha-approx k-means sensitivities + DIS")
def vkmc_scores(key, ds: VFLDataset, backend: str = "pallas",
                k: int = 10, alpha: float = 2.0, local_iters: int = 15):
    """Algorithm 3: party j runs local k-means (alpha-approximate) and scores
    its block; the key is split once per party and once more for DIS —
    exactly the seed's chain (subkeys are pre-split host-side, then
    seeding, Lloyd and scoring each run as one vmap over the party axis of
    the stacked view).

    Zero column padding is distance-transparent (every point shares the
    same zeros), so local k-means and sensitivities on the padded blocks
    equal their per-party values.  ``alpha`` is the approximation factor
    credited to the local solver (k-means++ + Lloyd is O(log k) in theory,
    ~2 in practice).

    Seeding, Lloyd and scoring are the spans ``score.seed``,
    ``score.lloyd`` and ``score.sens``; ``lloyd_passes`` on the open span
    counts the fused assign-update passes over X: ``local_iters`` Lloyd
    passes and one scoring pass per party.
    """
    subs = []
    for _ in range(ds.T):                     # the seed's per-party key chain
        key, sub = jax.random.split(key)
        subs.append(sub)
    key, dis_key = jax.random.split(key)
    use_kernel = _use_kernel(backend)

    with jax.named_scope("score"):
        st = ds.stacked()
        if backend == "norm":
            return norm_scores(st.blocks) + 1.0 / ds.n, dis_key
        trace.add(lloyd_passes=ds.T * (local_iters + 1))
        with trace.span("score.seed"):
            init = jax.vmap(lambda s, X: kmeans_plusplus(s, X, k))(
                jnp.stack(subs), st.blocks)
        with trace.span("score.lloyd"):
            centers = jax.vmap(lambda X, c: lloyd(
                X, c, iters=local_iters, use_kernel=use_kernel))(st.blocks, init)
        with trace.span("score.sens"):
            scores = jax.vmap(lambda X, c: vkmc_local_scores(
                X, c, alpha, use_kernel=use_kernel))(st.blocks, centers)
        return scores, dis_key


CORESET_TASKS.register("uniform")(
    CoresetTask(name="uniform", score_fn=None,
                description="U-* baseline: uniform indices, weight n/m")
)


# --------------------------------------------------------------------------
# Engine executors.  The shims and the pipeline share ONE code path per
# engine (draw identity by construction, pinned by tests/test_plan.py).
# --------------------------------------------------------------------------

def _policy_retries(fault_policy: str) -> Optional[int]:
    """``fail`` is fail-fast (one attempt per message); ``retry``/``degrade``
    use the transport plan's own ``max_retries``."""
    return 0 if fault_policy == "fail" else None


def _faulted_round1(
    spec: CoresetTask, ds: VFLDataset, transport: Transport,
    ledger: Optional[CommLedger], fault_policy: str,
    payload: Optional[WirePayload] = None,
) -> Tuple[VFLDataset, Optional[list], Optional[DegradedBuild], int, int]:
    """Deliver DIS round 1 through the transport; under ``degrade`` a party
    exhausting its retries here — BEFORE any score travels — is dropped and
    the build continues over the survivors.

    ``payload`` is the wire descriptor for the mass-table row each party's
    G_j upload physically carries — it drives the bits column only.
    Returns ``(effective dataset, surviving original party ids or None,
    DegradedBuild receipt or None, round-1 units billed, round-1 bits
    billed)``.  The label party (T-1) is irreplaceable for a labels-bearing
    task, and losing every party is unrecoverable — both re-raise
    :exc:`PartyUnavailable`.
    """
    rep = transport.deliver(
        CommSchedule.dis_round1(ds.T, payload=payload), ledger,
        max_retries=_policy_retries(fault_policy),
        drop_on_exhaust=(fault_policy == "degrade"),
    )
    if not rep.failed:
        return ds, None, None, rep.units, rep.bits
    alive = sorted(set(range(ds.T)) - set(rep.failed))
    dropped = tuple(sorted(rep.failed.values(), key=lambda d: d.party))
    if not alive:
        d = dropped[0]
        raise PartyUnavailable(d.party, d.tag, d.attempts)
    if spec.needs_labels and (ds.T - 1) in rep.failed:
        # labels live ONLY at party T-1; no surviving subset can score vrlr
        d = rep.failed[ds.T - 1]
        raise PartyUnavailable(d.party, d.tag, d.attempts)
    degraded = DegradedBuild(dropped=dropped, surviving=tuple(alive),
                             total_parties=ds.T)
    return ds.select_parties(alive), alive, degraded, rep.units, rep.bits


def _validators_on(fault_policy: str) -> bool:
    """The policy matrix's defense column: ``fail`` and ``quarantine`` run
    the value-level validators on delivered payloads; ``retry``/``degrade``
    trust party values (they defend availability, not honesty — the
    undefended baseline the integrity benchmark measures against)."""
    return fault_policy in ("fail", "quarantine")


def _task_bound(spec: CoresetTask, eff_ds: VFLDataset, backend: str,
                params: dict) -> Optional[float]:
    """The task's total-sensitivity bound for the value-level validators —
    Thm 4.2 for VRLR (sum of effective widths + T, labels widening party
    T's block), Lemma F.2 for VKMC (2(k+1)*alpha*T exactly).  The ``norm``
    ablation backend scores row norms, which respect no such bound."""
    if backend == "norm":
        return None
    if spec.name == "vrlr":
        dims = list(eff_ds.dims)
        if eff_ds.y is not None:
            dims[-1] += 1
        return total_sensitivity_bound_vrlr(dims, eff_ds.T)
    if spec.name == "vkmc":
        return total_sensitivity_bound_vkmc(
            int(params.get("k", 10)), eff_ds.T,
            float(params.get("alpha", 2.0)))
    return None


def _integrity_round1(
    spec: CoresetTask, eff_ds: VFLDataset, transport: Transport,
    ledger: Optional[CommLedger], fault_policy: str, masses,
    backend: str, params: dict, codec: str = "raw_fp32",
):
    """The round-1 integrity seam: ship each party's mass row under a
    checksummed :class:`~repro.core.integrity.WireEnvelope`, then run the
    value-level validators on what was DELIVERED.

    ``masses`` is the host (T_eff, cells) table — per-row scores for the
    materialized engine, the (T, nb) block table for the streamed ones.
    The cross-check totals are the honest per-party scalars (the round-1
    ``G_j`` message the schedule already billed); a lying or corrupted row
    cannot match them.  Returns ``(delivered_table_or_None, offenders)``:
    the table is None when nothing changed (the clean path touches no
    bytes), ``offenders`` — local party indices — is nonempty only
    under ``quarantine`` (validator hits under ``fail`` raise a
    party-attributed :exc:`IntegrityError`; transport-level detections
    were already retried and billed inside ``ship``), and
    ``retry_units``/``retry_bits`` are the retransmission traffic ship
    billed, so the returned coreset's ``comm_units``/``comm_bits`` stay
    the composed ledger truth.

    ``codec`` packs each row through :mod:`repro.core.wire`: the envelope
    CRC covers the ENCODED bytes and a lossy codec delivers the quantized
    table (the draw consumes what crossed the wire).  A lossy codec also
    skips the row-sum/scalar cross-check — the quantized row cannot match
    the honest fp32 scalar by construction; the finiteness/nonnegativity/
    bound validators still run on the delivered values."""
    c = get_codec(codec)
    tbl = np.asarray(masses)
    totals = tbl.sum(axis=1)
    rows = {j: tbl[j] for j in range(tbl.shape[0])}
    r0 = transport.stats.units_retried
    b0 = transport.stats.bits_retried
    delivered, failed = transport.ship(
        "dis/round1/G_j", rows, ledger, units=1,
        max_retries=_policy_retries(fault_policy),
        drop_on_exhaust=(fault_policy == "quarantine"), codec=codec)
    retry_units = transport.stats.units_retried - r0
    retry_bits = transport.stats.bits_retried - b0
    changed = any(delivered.get(j) is not rows[j] for j in rows)
    out = (np.stack([np.asarray(delivered.get(j, rows[j]))
                     for j in range(len(rows))])
           if changed else None)
    offenders = set(failed)
    if _validators_on(fault_policy):
        offenders |= set(require_valid_masses(
            tbl if out is None else out,
            totals if c.lossless else None,
            bound=_task_bound(spec, eff_ds, backend, params),
            policy=fault_policy))
    return out, tuple(sorted(offenders)), retry_units, retry_bits


def _quarantine(
    spec: CoresetTask, ds: VFLDataset, alive: Optional[list],
    degraded: Optional[DegradedBuild], offenders: Tuple[int, ...],
    tag: str = "dis/round1/G_j",
) -> Tuple[VFLDataset, list, DegradedBuild]:
    """Fold integrity offenders into the degrade machinery: map local
    offender indices back to original party ids, drop them, and extend the
    :class:`DegradedBuild` receipt with the quarantine reason.  The label
    party is irreplaceable and losing every party is unrecoverable — both
    raise instead of degrading, mirroring :func:`_faulted_round1`."""
    orig = list(alive) if alive is not None else list(range(ds.T))
    bad = sorted(orig[j] for j in offenders)
    survivors = [p for p in orig if p not in set(bad)]
    if not survivors:
        raise IntegrityError(bad[0], "every party quarantined; no feature "
                                     "slices left to build from", tag=tag)
    if spec.needs_labels and (ds.T - 1) in bad:
        raise IntegrityError(
            ds.T - 1, "label party failed integrity validation; labels "
                      "live only at party T-1, the build cannot continue",
            tag=tag)
    dropped = tuple(degraded.dropped if degraded is not None else ()) + tuple(
        DroppedParty(p, f"quarantine/{tag}", 1) for p in bad)
    reason = (f"part{'y' if len(bad) == 1 else 'ies'} {bad} quarantined "
              f"for integrity violations at {tag!r}")
    receipt = DegradedBuild(
        dropped=tuple(sorted(dropped, key=lambda d: d.party)),
        surviving=tuple(survivors), total_parties=ds.T, reason=reason)
    return ds.select_parties(survivors), survivors, receipt


def _round2_wire(plan, alive: Optional[list], T: int, codec: str):
    """Pre-encode the round-2 index uploads ONCE: the returned payload
    descriptors (aligned with ``plan.counts``) carry the measured packed
    bits for :meth:`CommSchedule.dis_rounds23`, and the returned blobs are
    handed to :meth:`Transport.ship` via ``encoded=`` — bits billed equal
    bytes sealed by construction (delta-varint uploads are value-dependent,
    so the bound-only descriptor would over-bill)."""
    counts = np.asarray(plan.counts)
    ups = split_uploads(np.asarray(plan.indices), counts)
    orig = list(alive) if alive is not None else list(range(T))
    c = get_codec(codec)
    payloads: list = [None] * len(ups)
    blobs: dict = {}
    for j in range(len(ups)):
        if counts[j] <= 0:
            continue
        arr = np.asarray(ups[j])
        blob = c.encode(arr)
        blobs[orig[j]] = blob
        payloads[j] = WirePayload.measured(
            arr.shape, str(arr.dtype), codec, 8 * len(blob))
    return payloads, blobs


def _ship_round2(
    transport: Transport, ledger: Optional[CommLedger], fault_policy: str,
    plan, alive: Optional[list], T: int, codec: str = "raw_fp32",
    blobs: Optional[dict] = None,
):
    """Ship the round-2 index uploads under envelopes.  Units per party are
    the realized a_j — the exact sizes ``CommSchedule.dis_rounds23`` billed,
    so envelope-detected retransmissions land under ``retry/dis/round2/S_up``
    at the message's true cost (measured packed bits in the bits column).
    ``blobs`` are the pre-encoded uploads from :func:`_round2_wire`, sealed
    as-is.  Returns the (possibly corrupted, if the transport does not
    verify) realized index vector plus the retry units and bits billed, and
    raises through the weight validator when the policy defends."""
    counts = np.asarray(plan.counts)
    ups = split_uploads(np.asarray(plan.indices), counts)
    orig = list(alive) if alive is not None else list(range(T))
    payloads = {orig[j]: ups[j] for j in range(len(ups)) if counts[j] > 0}
    units = {orig[j]: int(counts[j]) for j in range(len(ups)) if counts[j] > 0}
    r0 = transport.stats.units_retried
    b0 = transport.stats.bits_retried
    delivered, _ = transport.ship(
        "dis/round2/S_up", payloads, ledger, units=units,
        max_retries=_policy_retries(fault_policy), drop_on_exhaust=False,
        codec=codec, encoded=blobs)
    retry_units = transport.stats.units_retried - r0
    retry_bits = transport.stats.bits_retried - b0
    if _validators_on(fault_policy):
        why = check_weights(plan.weights)
        if why is not None:
            raise IntegrityError(None, f"realized coreset weights: {why}",
                                 tag="dis/round3/g_scores")
    changed = any(delivered[p] is not payloads[p] for p in payloads)
    if not changed:
        return plan.indices, retry_units, retry_bits
    parts = [np.asarray(delivered.get(orig[j], ups[j]))
             for j in range(len(ups))]
    out = jnp.asarray(np.concatenate(parts)) if parts else plan.indices
    return out, retry_units, retry_bits


def _require_positive(totals) -> None:
    """DIS needs a positive total score; the read blocks on the device."""
    with trace.span("wait", of="totals"):
        ok = bool(totals.sum() > 0)
    if not ok:
        raise ValueError("DIS requires a positive total score")


def _bill_dis(T: int, m: int, plan, ledger: Optional[CommLedger],
              round1_payload: WirePayload) -> CommSchedule:
    """Record the DIS bill of a realised plan (the transportless path)."""
    with trace.span("bill"):
        schedule = CommSchedule.dis(T, m, counts=np.asarray(plan.counts),
                                    round1_payload=round1_payload)
        schedule.record(ledger)
    return schedule


def _sharded_mass_table(task_name: str, key, ds: VFLDataset,
                        block_size: int, backend: str, params: dict):
    """Compute the (T, nb) block-mass table data-parallel over a one-axis
    mesh spanning every local device (shard_map + two psums — see
    :mod:`repro.core.streaming`).  The per-row scores the sampler later
    recomputes come from the scorer's own block path; ``backend`` is
    forwarded so vkmc's iterated center solve runs the SAME kernels as the
    scorer (a mismatch would build the table from different centers), and
    the table matches the scorer's up to fp reduction order."""
    from repro.core.streaming import (
        vkmc_block_masses_sharded,
        vrlr_block_masses_sharded,
    )

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    if task_name == "vrlr":
        kw = {k: v for k, v in params.items() if k == "rcond"}
        return vrlr_block_masses_sharded(mesh, ds, block_size, **kw)
    if task_name == "vkmc":
        kw = {k: v for k, v in params.items()
              if k in ("k", "alpha", "local_iters", "center_sample")}
        return vkmc_block_masses_sharded(mesh, ds, block_size, key=key,
                                         use_kernel=_use_kernel(backend),
                                         **kw)
    raise ValueError(
        f"sharded_masses supports tasks ('vrlr', 'vkmc'), got {task_name!r}"
    )


@dataclasses.dataclass
class _Engine:
    """What a single-cell engine contributes to :func:`_build_one`; the
    protocol rounds around it are the same for every engine.  A subclass
    supplies ``cells(ds)`` (round-1 table entries per party),
    ``score(ds)`` (a scoring state), ``table(state)`` (the round-1 mass
    table it ships), ``with_table(state, table)`` (the same state drawing
    from a delivered table), ``draw(state, m)`` (a DIS plan, positivity
    checked) and ``health(state)``."""

    task: CoresetTask
    key: jax.Array
    backend: str
    params: dict


class _MaterializedEngine(_Engine):
    """Per-row scores: the task's (T, n) score table, drawn from by ONE
    compiled dispatch (:func:`dis_plan_compiled`, bit-identical to an eager
    :func:`dis_plan_full`).  The state is ``(scores, dis_key)``.  The draw
    returns its counts on the host, read inside ``repro.dis`` so the span
    carries ``draw_rows`` (:func:`repro.core.dis.round2_rows`)."""

    def cells(self, ds: VFLDataset) -> int:
        return ds.n

    def score(self, ds: VFLDataset):
        return self.task.score_fn(self.key, ds, backend=self.backend,
                                  **self.params)

    def table(self, state):
        return state[0]

    def with_table(self, state, table):
        return jnp.asarray(table), state[1]

    def draw(self, state, m: int):
        scores, dis_key = state
        with trace.span("dis"):
            plan = dis_plan_compiled(dis_key, scores, m, core=dis_plan_full)
            with trace.span("wait", of="counts"):
                counts = np.asarray(plan.counts)
            trace.add(draw_rows=round2_rows(dis_key, counts, scores.shape[1],
                                            m))
        _require_positive(plan.totals)
        return plan._replace(counts=counts)

    def health(self, state) -> HealthReport:
        return health_from_masses(np.asarray(state[0]))


@dataclasses.dataclass
class _StreamedEngine(_Engine):
    """Block-scan scoring and the hierarchical (party, block) draw: the
    state is a :class:`~repro.core.streaming.StreamScorer` whose (T, nb)
    block-mass table is the round-1 payload.  ``pipelined`` selects the
    superchunk-grouped redraw
    (:func:`repro.core.streaming.dis_plan_streamed_batched`) — the same
    draws as the block-at-a-time reference, fewer dispatches.  All knobs
    arrive RESOLVED (validated by :class:`CoresetSpec`, clamped by the
    planner).

    ``checkpoint`` makes the scorer's scan passes resumable per
    superchunk: a crashed build rerun with the same arguments restores the
    last completed superchunk's accumulators and finishes
    draw-identically."""

    m: int
    probe: Optional[Callable[[], None]]
    block_size: int
    chunk_blocks: int
    prefetch: bool
    pipelined: bool
    sharded_masses: bool
    checkpoint: Optional[StreamCheckpoint]

    def cells(self, ds: VFLDataset) -> int:
        return ds.block_geometry(int(self.block_size))[0]

    def score(self, ds: VFLDataset):
        from repro.core.streaming import make_stream_scorer

        masses = None
        if self.sharded_masses:
            # task/backend compatibility was validated by compile_plan —
            # every path into this engine goes through the planner
            masses = _sharded_mass_table(self.task.name, self.key, ds,
                                         self.block_size, self.backend,
                                         self.params)
        if self.checkpoint is not None:
            self.checkpoint.bind((
                self.task.name, ds.n, ds.dims, ds.y is not None,
                int(self.block_size), int(self.chunk_blocks),
                bool(self.prefetch), self.backend,
                tuple(sorted(self.params.items())), int(self.m),
                tuple(np.asarray(_key_data(self.key)).ravel().tolist()),
            ))
        return make_stream_scorer(self.task.name, self.key, ds,
                                  int(self.block_size), self.backend,
                                  probe=self.probe,
                                  chunk_blocks=self.chunk_blocks,
                                  prefetch=self.prefetch, masses=masses,
                                  ckpt=self.checkpoint, **self.params)

    def table(self, scorer):
        return scorer.masses

    def with_table(self, scorer, table):
        from repro.core.streaming import with_masses

        return with_masses(scorer, table)

    def draw(self, scorer, m: int):
        from repro.core.streaming import (
            dis_plan_streamed,
            dis_plan_streamed_batched,
        )

        _require_positive(scorer.masses)
        with trace.span("dis"):
            if self.pipelined:
                plan = dis_plan_streamed_batched(scorer, m, probe=self.probe)
            else:
                plan = dis_plan_streamed(scorer, m, probe=self.probe)
        if self.checkpoint is not None:
            self.checkpoint.clear()            # the build completed
        return plan

    def health(self, scorer) -> HealthReport:
        return health_from_masses(np.asarray(scorer.masses),
                                  gram_conds=scorer.gram_conds)


def _build_one(
    engine: _Engine, ds: VFLDataset, m: int, ledger: Optional[CommLedger],
    transport: Optional[Transport], fault_policy: str, codec: str,
) -> Coreset:
    """Algorithm 1 for one coreset on a single-cell engine.

    Without a ``transport`` the DIS rounds are RECORDED on ``ledger`` from
    the realised plan.  With one they are DELIVERED: round 1 before
    scoring, where ``degrade`` can still drop a party (sensitivities are
    then computed over the surviving feature slices); then the scored mass
    table crosses the wire under envelopes, and what was delivered drives
    the draw; rounds 2-3 after the draw.  With a null fault plan the draws
    and ledger entries are bit-identical to the recorded path.

    The health copy of the mass table runs after the draw is dispatched,
    so on the materialized engine it overlaps the draw on the device.
    """
    task = engine.task
    if task.needs_labels and ds.y is None:
        raise ValueError(f"{task.name} requires labels at party T")
    retries = _policy_retries(fault_policy)
    if task.score_fn is None:
        S, w = uniform_plan(engine.key, ds.n, m)
        schedule = CommSchedule.uniform(ds.T, m)
        if transport is None:
            schedule.record(ledger)
            return Coreset(S, w, schedule.total,
                           comm_bits=schedule.total_bits)
        rep = transport.deliver(schedule, ledger, max_retries=retries,
                                drop_on_exhaust=(fault_policy == "degrade"))
        degraded = None
        if rep.failed:
            dropped = tuple(sorted(rep.failed.values(), key=lambda d: d.party))
            alive = sorted(set(range(ds.T)) - set(rep.failed))
            degraded = DegradedBuild(dropped=dropped, surviving=tuple(alive),
                                     total_parties=ds.T)
        return Coreset(S, w, rep.units, comm_bits=rep.bits,
                       degraded=degraded)

    if transport is None and codec != "raw_fp32":
        raise ValueError(
            f"codec={codec!r} quantizes what crosses the wire; without a "
            f"transport nothing crosses it — the recorded path supports "
            f"codec='raw_fp32' only"
        )
    # the round-1 G_j upload physically carries the party's mass table —
    # one float32 entry per scoring cell (a row, or a block on the streaming
    # engines); one descriptor for the recorded and the delivered path so
    # their bits columns agree
    r1_payload = WirePayload.of((engine.cells(ds),), "float32", codec)
    eff_ds, alive, degraded, units, bits = ds, None, None, 0, 0
    if transport is not None:
        eff_ds, alive, degraded, units, bits = _faulted_round1(
            task, ds, transport, ledger, fault_policy, payload=r1_payload)
    with trace.span("score"):
        state = engine.score(eff_ds)
    if transport is not None:
        # integrity seam: ship the mass table under envelopes, validate
        # what arrived
        delivered, offenders, ship_units, ship_bits = _integrity_round1(
            task, eff_ds, transport, ledger, fault_policy,
            np.asarray(engine.table(state)), engine.backend, engine.params,
            codec=codec)
        units, bits = units + ship_units, bits + ship_bits
        if offenders:
            eff_ds, alive, degraded = _quarantine(task, ds, alive, degraded,
                                                  offenders)
            # rescore the survivors; their tables already validated clean
            with trace.span("score"):
                state = engine.score(eff_ds)
        elif delivered is not None:
            # what crossed the wire drives the draw: a lossy codec's
            # quantized table on the clean path, or — with verification
            # off — corrupted masses, exactly the undefended blow-up the
            # integrity benchmark measures
            state = engine.with_table(state, delivered)
    plan = engine.draw(state, m)
    if transport is None:
        schedule = _bill_dis(ds.T, m, plan, ledger, r1_payload)
        indices, units, bits = plan.indices, schedule.total, schedule.total_bits
    else:
        # rounds 2-3 exhaust hard even under degrade: by now the scores
        # exist and dropping a party would orphan its drawn rows
        up_payloads, up_blobs = _round2_wire(plan, alive, ds.T, codec)
        rep23 = transport.deliver(
            CommSchedule.dis_rounds23(ds.T, m, counts=np.asarray(plan.counts),
                                      parties=alive,
                                      upload_payloads=up_payloads),
            ledger, max_retries=retries, drop_on_exhaust=False,
        )
        indices, r2_units, r2_bits = _ship_round2(
            transport, ledger, fault_policy, plan, alive, ds.T,
            codec=codec, blobs=up_blobs)
        units += rep23.units + r2_units
        bits += rep23.bits + r2_bits
    with trace.span("health"):
        health = engine.health(state)
    return Coreset(indices, plan.weights, units, comm_bits=bits,
                   degraded=degraded, health=health)


# --------------------------------------------------------------------------
# Batched multi-seed / multi-budget engine (one compilation)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchedCoresets:
    """A (num_seeds, num_budgets) grid of coresets from ONE compiled call.

    ``indices``/``weights`` are ``(R, M, m_cap)`` with the valid-prefix
    convention: cell (r, i) holds ``ms[i]`` real samples; the padded tail has
    weight 0.  ``counts`` carries the realised round-2 a_j per cell so the
    exact CommSchedule can be derived lazily, after the fact — accounting
    never touched the compiled path.
    """

    indices: jax.Array            # (R, M, m_cap) int
    weights: jax.Array            # (R, M, m_cap) float
    counts: Optional[jax.Array]   # (R, M, T) int; None for the uniform task
    ms: Tuple[int, ...]
    T: int
    #: Round-1 mass-table cells per party (n on this engine); 0 on legacy
    #: grids predating the bits column — their schedules then bill the
    #: scalar-only convention.  The batched engine is raw_fp32-only (it
    #: never transports), so no codec field is needed.
    cells: int = 0

    @property
    def num_seeds(self) -> int:
        return int(self.indices.shape[0])

    def schedule(self, seed_idx: int, m_idx: int) -> CommSchedule:
        m = self.ms[m_idx]
        if self.counts is None:
            return CommSchedule.uniform(self.T, m)
        r1 = (WirePayload.of((self.cells,), "float32", "raw_fp32")
              if self.cells else None)
        return CommSchedule.dis(
            self.T, m, counts=np.asarray(self.counts[seed_idx, m_idx]),
            round1_payload=r1,
        )

    def coreset(
        self, seed_idx: int, m_idx: int = 0,
        ledger: Optional[CommLedger] = None,
    ) -> Coreset:
        """Extract cell (seed_idx, m_idx) as a plain :class:`Coreset`."""
        m = self.ms[m_idx]
        schedule = self.schedule(seed_idx, m_idx).record(ledger)
        return Coreset(
            self.indices[seed_idx, m_idx, :m],
            self.weights[seed_idx, m_idx, :m],
            schedule.total,
            comm_bits=schedule.total_bits,
        )


def _exec_batched(
    spec: CoresetTask, ds: VFLDataset, ms: Tuple[int, ...], keys,
    backend: str, m_cap: int, params: dict,
) -> BatchedCoresets:
    """The batched engine: every (seed, budget) cell in one compiled
    ``jit(vmap(vmap(dis_plan_full)))`` call over the pure DIS core, using
    the ``m_cap`` prefix-masking convention for the budget grid.  For ``m
    == m_cap`` each cell is exactly the materialized engine's result
    for that key (eager hoisted totals keep the weight arithmetic
    bit-identical for deterministic-score tasks).
    """
    if spec.needs_labels and ds.y is None:
        raise ValueError(f"{spec.name} requires labels at party T")
    ms_arr = jnp.asarray(ms, jnp.int32)

    def _cells(dis_key, sc, totals=None):
        """All budget cells for one seed (scores computed once per seed)."""
        def cell(m):
            plan = dis_plan_full(dis_key, sc, m, m_cap=m_cap, totals=totals)
            return plan.indices, plan.weights, plan.counts
        return jax.vmap(cell)(ms_arr)

    if spec.score_fn is None:
        def per_seed(k):
            def cell(m):
                S, w = uniform_plan(k, ds.n, m, m_cap=m_cap)
                return S, w, jnp.zeros((ds.T,), jnp.int32)
            return jax.vmap(cell)(ms_arr)
    else:
        hoisted = None
        if spec.deterministic_scores:
            # scores are seed-independent: compute once on the host and
            # share across the whole grid — but only if the score_fn honours
            # the deterministic contract (key passed through unchanged);
            # otherwise fall back to per-seed scoring so sequential and
            # batched builds keep sampling with the same dis_key.
            sc0, dk0 = spec.score_fn(keys[0], ds, backend=backend, **params)
            if np.array_equal(_key_data(dk0), _key_data(keys[0])):
                hoisted = sc0
        if hoisted is not None:
            if not bool(hoisted.sum() > 0):
                raise ValueError("DIS requires a positive total score")
            # eager per-party totals: same reduction kernel as the sequential
            # path, so w = G/(m g) matches sequential builds bit for bit.
            hoisted_totals = jnp.sum(hoisted.astype(_float_dtype()), axis=1)

            def per_seed(k):
                return _cells(k, hoisted, totals=hoisted_totals)
        else:
            def per_seed(k):
                sc, dis_key = spec.score_fn(k, ds, backend=backend, **params)
                return _cells(dis_key, sc)

    S, w, counts = jax.jit(jax.vmap(per_seed))(keys)
    if spec.score_fn is not None and not bool(jnp.all(w[..., 0] > 0)):
        # w[r, i, 0] = G / (m * g) is positive iff the realised total score
        # G was — the traced core can't raise, so validate post hoc.
        raise ValueError("DIS requires a positive total score")
    return BatchedCoresets(
        indices=S, weights=w,
        counts=None if spec.score_fn is None else counts,
        ms=ms, T=ds.T, cells=ds.n,
    )


# --------------------------------------------------------------------------
# CoresetPipeline: spec in, plan-dispatched build out
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CoresetPipeline:
    """The declarative entry point: ``build(spec)`` compiles the spec into
    an :class:`~repro.core.plan.ExecutionPlan` and dispatches to the named
    engine.

    ``plan(spec)`` exposes the compiled plan without running anything
    (``plan.describe()`` prints engine, resolved knobs, the full memory
    model, and the exact predicted communication bill); ``build`` also
    accepts a pre-compiled plan so introspect-then-run costs one
    compilation.  A forced-engine spec reproduces the corresponding legacy
    entry point draw for draw — the legacy functions ARE such specs.

    ``plan_cache`` (a :class:`~repro.core.plan.PlanCache`) memoizes
    ``plan(spec)`` by ``(task, geometry, knobs)`` — the serving layer's
    seam: one cache shared across tenants makes repeat shapes skip
    compilation (the same signature also keys the executors' jit caches,
    so a hit implies the engine's compiled programs are warm too).
    """

    ds: VFLDataset
    plan_cache: Optional[PlanCache] = None

    def plan(self, spec: CoresetSpec) -> ExecutionPlan:
        if self.plan_cache is not None:
            return self.plan_cache.get(spec, self.ds)
        return compile_plan(spec, self.ds)

    def build(
        self,
        spec: Union[CoresetSpec, ExecutionPlan],
        *,
        key: Optional[jax.Array] = None,
        keys: Optional[jax.Array] = None,
        ledger: Optional[CommLedger] = None,
        probe: Optional[Callable[[], None]] = None,
        transport: Optional[Transport] = None,
        checkpoint: Optional[StreamCheckpoint] = None,
    ) -> Union[Coreset, BatchedCoresets]:
        """Build per the (compiled) spec.

        Returns a :class:`Coreset` for single-cell plans and a
        :class:`BatchedCoresets` grid for the batched engine.  ``keys``
        (a stacked key array) overrides ``key`` + ``spec.num_seeds`` for
        the batched engine; ``probe`` is the streaming engines'
        per-superchunk instrumentation hook.  The batched engine derives
        its bills lazily per cell (``grid.coreset(..., ledger=...)``), so
        ``ledger`` applies to single-cell engines only.

        ``transport`` (a :class:`~repro.core.faults.Transport`) delivers
        the protocol rounds through the party fault seam, honouring
        ``spec.fault_policy``; with no transport — or a null fault plan —
        every engine's draws AND ledger entries are bit-identical to a
        transportless build (pinned in ``tests/test_faults.py``).
        ``checkpoint`` (a :class:`~repro.core.faults.StreamCheckpoint`)
        makes the streamed/pipelined engines' passes resumable per
        superchunk.
        """
        if isinstance(spec, ExecutionPlan):
            ep = spec
            if (ep.n, ep.dims) != (self.ds.n, self.ds.dims):
                raise ValueError(
                    f"plan was compiled for a dataset with n={ep.n}, "
                    f"dims={ep.dims}; this pipeline's dataset has "
                    f"n={self.ds.n}, dims={self.ds.dims} — recompile with "
                    f"plan(spec)"
                )
        else:
            ep = self.plan(spec)
        with trace.span("build", build=next(trace.BUILDS), engine=ep.engine):
            return self._dispatch(ep, key, keys, ledger, probe, transport,
                                  checkpoint)

    def _dispatch(self, ep: ExecutionPlan, key, keys, ledger, probe,
                  transport, checkpoint) -> Union[Coreset, BatchedCoresets]:
        cspec = ep.spec
        task = get_task(cspec.task)

        if ep.engine == "batched":
            if transport is not None or checkpoint is not None:
                raise ValueError(
                    "the batched engine bills its cells lazily; transport "
                    "delivery and checkpointed resume apply to single-cell "
                    "engines only"
                )
            if keys is None:
                if key is None:
                    raise ValueError("pass either `key` (+ num_seeds) or `keys`")
                keys = jax.random.split(key, cspec.num_seeds)
            return _exec_batched(task, self.ds, cspec.budgets, keys,
                                 ep.backend, ep.m_cap, cspec.params)

        if key is None:
            raise ValueError(f"the {ep.engine} engine requires `key`")
        m = cspec.budget
        if ep.engine == "materialized":
            if checkpoint is not None:
                raise ValueError(
                    "checkpointed resume is a streamed/pipelined-engine "
                    "feature; the materialized engine has no superchunk "
                    "passes to checkpoint"
                )
            engine = _MaterializedEngine(task, key, ep.backend, cspec.params)
        else:
            engine = _StreamedEngine(
                task, key, ep.backend, cspec.params, m, probe,
                cspec.block_size, ep.chunk_blocks, ep.prefetch,
                pipelined=(ep.engine == "pipelined"),
                sharded_masses=cspec.sharded_masses, checkpoint=checkpoint)
        return _build_one(engine, self.ds, m, ledger, transport=transport,
                          fault_policy=cspec.fault_policy, codec=ep.codec)

    def build_failover(
        self,
        spec: CoresetSpec,
        *,
        key: jax.Array,
        ledger: Optional[CommLedger] = None,
        probe: Optional[Callable[[], None]] = None,
        transport: Optional[Transport] = None,
        checkpoint: Optional[StreamCheckpoint] = None,
        memory_budget_bytes: Optional[int] = None,
    ) -> "FailoverOutcome":
        """:meth:`build` with the plan's engine failover ladder armed.

        Runs the compiled plan's engine under a live-bytes
        :class:`~repro.core.plan.MemoryWatchdog` (when
        ``memory_budget_bytes`` is given, checked at every superchunk probe
        and once after the build); a watchdog breach or an engine crash
        retries once per remaining rung of ``plan.fallback_chain``
        (materialized -> pipelined -> streamed).  The LAST rung runs
        without the watchdog — streamed is the minimum-footprint engine,
        there is nothing left to fall back to.

        Errors that are engine-INDEPENDENT propagate instead of burning
        ladder rungs: :class:`DeadlineExceeded` (caller's time budget),
        :class:`PartyUnavailable` / :class:`IntegrityError` (party-side —
        the circuit breaker's domain, a cheaper engine talks to the same
        parties), and ``ValueError`` (spec/geometry validation).

        The billing contract the acceptance test pins: each failed attempt
        is rolled back to a ``ledger.mark()``, then a zero-unit
        ``fallback/<from>-><to>`` entry attributes the switch — the final
        total equals the successful engine's bill exactly, plus the tagged
        zero-cost marker.  The winning plan is returned with the decision
        appended to ``plan.notes``.
        """
        first = self.plan(spec)
        chain = (first.engine,) + first.fallback_chain
        watchdog = (None if memory_budget_bytes is None
                    else MemoryWatchdog(memory_budget_bytes))
        attempts = []
        tried = set()
        ep = first
        for rung, engine in enumerate(chain):
            if engine in tried:
                continue
            if rung > 0:
                fb_spec = dataclasses.replace(spec, engine=engine)
                ep = self.plan(fb_spec)
                if ep.engine in tried:   # pipelined may lower to streamed
                    continue
            tried.add(ep.engine)
            last_rung = (rung == len(chain) - 1) or all(
                e in tried for e in chain[rung + 1:]
            )
            wd = None if (watchdog is None or last_rung) else watchdog
            eff_probe = _compose_probes(probe, wd)
            mark = None if ledger is None else ledger.mark()
            # checkpoints only exist on the streaming engines; the bind
            # signature changes with the engine's knobs, so reusing one
            # store across rungs auto-discards the failed rung's state
            ckpt = (checkpoint if ep.engine in ("streamed", "pipelined")
                    else None)
            try:
                cs = self.build(ep, key=key, ledger=ledger, probe=eff_probe,
                                transport=transport, checkpoint=ckpt)
                if wd is not None:
                    wd.check()   # materialized has no probes; final census
            except (DeadlineExceeded, PartyUnavailable, IntegrityError,
                    ValueError):
                if ledger is not None:
                    ledger.rollback(mark)
                raise
            except Exception as e:
                if ledger is not None:
                    ledger.rollback(mark)
                attempts.append(FailoverAttempt(
                    engine=ep.engine,
                    error=f"{type(e).__name__}: {e}",
                ))
                if last_rung:
                    raise
                continue
            if attempts:
                trail = " -> ".join([a.engine for a in attempts]
                                    + [ep.engine])
                ep = dataclasses.replace(
                    ep, notes=ep.notes + (
                        f"failover: {trail} "
                        f"({attempts[-1].error})",
                    ))
                if ledger is not None:
                    ledger.send(
                        f"fallback/{attempts[-1].engine}->{ep.engine}",
                        "server", "server", 0)
            return FailoverOutcome(coreset=cs, plan=ep,
                                   attempts=tuple(attempts))
        raise RuntimeError("unreachable: failover chain exhausted silently")


def _compose_probes(*fns) -> Optional[Callable[[], None]]:
    """Chain per-superchunk probes (caller's deadline check, the memory
    watchdog) into one hook; None entries drop out."""
    live = [f for f in fns if f is not None]
    if not live:
        return None
    if len(live) == 1:
        return live[0]

    def probe() -> None:
        for f in live:
            f()
    return probe


@dataclasses.dataclass(frozen=True)
class FailoverAttempt:
    """One failed rung of the ladder: which engine, what killed it."""

    engine: str
    error: str


@dataclasses.dataclass(frozen=True)
class FailoverOutcome:
    """Result of :meth:`CoresetPipeline.build_failover`: the coreset, the
    plan that produced it (with any failover note appended), and the failed
    attempts in ladder order (empty when the first engine succeeded)."""

    coreset: Coreset
    plan: ExecutionPlan
    attempts: Tuple[FailoverAttempt, ...] = ()

    @property
    def engine(self) -> str:
        return self.plan.engine

    @property
    def fallback(self) -> Optional[str]:
        """``"<first-failed>-><winner>"`` when the ladder fired, else None."""
        if not self.attempts:
            return None
        return f"{self.attempts[0].engine}->{self.plan.engine}"


# --------------------------------------------------------------------------
# Legacy entry points — thin shims over forced-engine specs.
# --------------------------------------------------------------------------

def build_coreset(
    task: Union[str, CoresetTask],
    ds: VFLDataset,
    budget: int,
    *,
    key: jax.Array,
    backend: str = "auto",
    ledger: Optional[CommLedger] = None,
    **params,
) -> Coreset:
    """Build one coreset of ``budget`` rows for ``task`` on ``ds`` — the
    MATERIALIZED engine (shim over ``CoresetSpec(engine="materialized")``).

    Task-specific knobs (vkmc's ``k``/``alpha``/``local_iters``) pass through
    ``**params`` to the task's score function.  ``backend`` defaults to
    ``"auto"`` (:func:`resolve_backend`: kernels on TPU, jnp refs
    elsewhere).  The exact per-round communication bill is derived from the
    realised plan and recorded on ``ledger`` (when given);
    ``Coreset.comm_units`` is always this construction's own total.
    """
    spec = CoresetSpec(task=task, budgets=int(budget),
                       engine="materialized", backend=backend, params=params)
    return CoresetPipeline(ds).build(spec, key=key, ledger=ledger)


def build_coreset_streaming(
    task: Union[str, CoresetTask],
    ds: VFLDataset,
    budget: int,
    *,
    key: jax.Array,
    block_size: int = 65536,
    chunk_blocks: Optional[int] = None,
    prefetch: Optional[bool] = None,
    backend: str = "auto",
    ledger: Optional[CommLedger] = None,
    probe: Optional[Callable[[], None]] = None,
    **params,
) -> Coreset:
    """Build one coreset with n as a STREAMING dimension — the streamed /
    pipelined engines (shim over ``CoresetSpec(engine="pipelined")``; the
    planner lowers ``chunk_blocks=1, prefetch=False`` to the strictly
    block-at-a-time streamed engine, same draws either way).

    Block-scan scoring plus the hierarchical (party, block)-cell DIS
    sampler keep peak device memory O(chunk_blocks * block_size * d) — the
    (T, n) score matrix and the (n, d) design are never materialized (pass
    a numpy-backed ``VFLDataset`` to keep the raw data off-device too).

    ``chunk_blocks`` (default :data:`repro.core.plan.DEFAULT_CHUNK_BLOCKS`)
    sets the pipelined dispatch granularity; ``prefetch`` (default
    :data:`repro.core.plan.PREFETCH_DEFAULT` — the measured winner per
    backend: off on CPU, where the staging thread competes with compute
    for the same cores and costs ~25% throughput, on for TPU/GPU, where
    the transfer engine overlaps for free) double-buffers the superchunk
    staging.  Knob validation is centralized in
    :class:`~repro.core.plan.CoresetSpec` (non-positive / non-integral
    values raise ``ValueError`` before any work); ``chunk_blocks`` above
    the block count is clamped by the PLANNER — an explicit decision
    surfaced in ``CoresetPipeline(ds).plan(spec).describe()``.

    The sampled marginal is exactly the flat plan's g_i/G (the two-level
    sampling telescopes — :func:`repro.core.dis.dis_plan_blocked`), and
    with ``block_size >= ds.n`` the draws coincide with
    :func:`build_coreset` bit for bit when the blockwise scores do (e.g.
    the row-local ``norm`` backend).  ``probe`` (if given) is invoked once
    per superchunk step — instrumentation hook for the memory benchmark.
    The communication bill is unchanged: blocking is server-side
    bookkeeping.
    """
    spec = CoresetSpec(task=task, budgets=int(budget),
                       engine="pipelined", backend=backend,
                       block_size=block_size, chunk_blocks=chunk_blocks,
                       prefetch=prefetch, params=params)
    return CoresetPipeline(ds).build(spec, key=key, ledger=ledger,
                                     probe=probe)


def build_coresets_batched(
    task: Union[str, CoresetTask],
    ds: VFLDataset,
    ms,
    *,
    key: Optional[jax.Array] = None,
    num_seeds: int = 1,
    keys: Optional[jax.Array] = None,
    backend: str = "ref",
    m_cap: Optional[int] = None,
    **params,
) -> BatchedCoresets:
    """Construct coresets for every (seed, budget) pair in one compiled call
    — the BATCHED engine (shim over ``CoresetSpec(engine="batched")``).

    ``ms`` is the budget grid (any iterable of ints); seeds come either from
    ``keys`` (a stacked ``(R, ...)`` key array) or ``jax.random.split(key,
    num_seeds)``.  Budgets below ``max(ms)`` use the prefix-masking
    convention (draws are iid, so a prefix of the capacity draw is a valid
    m-sample); for ``m == max(ms)`` each cell is exactly the sequential
    :func:`build_coreset` result for that key.

    ``backend`` defaults to ``"ref"`` (the pure-jnp scores are cheapest on
    a CPU container); ``"pallas"`` also vmaps — the kernels fold the seed
    batch into their grid via the native pallas batching rule — and
    ``"auto"`` resolves per :func:`resolve_backend`.  ``m_cap`` overrides
    the draw capacity (defaults to ``max(ms)``); every budget must lie in
    [1, m_cap] or the spec raises before tracing.
    """
    ms = tuple(int(m) for m in ms)       # the legacy coercion, pre-validation
    if keys is not None:
        num_seeds = int(keys.shape[0])
    spec = CoresetSpec(task=task, budgets=ms, num_seeds=num_seeds,
                       engine="batched", backend=backend, m_cap=m_cap,
                       params=params)
    return CoresetPipeline(ds).build(spec, key=key, keys=keys)
