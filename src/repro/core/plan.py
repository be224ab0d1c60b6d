"""CoresetSpec -> ExecutionPlan: the declarative layer over every engine.

One spec compiles to one engine, in the declarative-launcher idiom:

  * :class:`CoresetSpec` — ONE frozen description of a construction: task,
    budgets, seeds, backend, engine preference, streaming knobs
    (``block_size``/``chunk_blocks``/``prefetch``), ``memory_budget_bytes``
    and the ``sharded_masses`` toggle.  ALL knob validation lives in its
    ``__post_init__`` with uniform ``ValueError`` messages — no entry point
    validates anything on its own anymore.
  * :class:`ExecutionPlan` — the compiled plan: ONE concrete engine
    (``materialized | batched | streamed | pipelined``), resolved backend
    and knobs (the ``chunk_blocks > nb`` clamp is an explicit, recorded
    planner decision, not a silent coercion), the full memory model, the
    predicted communication bill (via :class:`repro.core.comm.CommSchedule`
    — the total is count-independent, so it is exact before any draw), and
    ``describe()`` introspection.
  * :func:`compile_plan` — the auto-planner.  Engine selection is driven by
    a MEMORY MODEL calibrated against the measured yardsticks in
    BENCH_kernels.json: the materialized path holds the (T, n, s) stacked
    design plus the (T, n) score matrix; the streamed path holds one
    (T, bs, s) block (measured peak within ~2% of ``block_bytes``); the
    pipelined path holds up to 2.5x one (C, T, bs, s) superchunk (two
    double-buffered staging slots + the live compute residency — measured
    peaks are <= 2.01x ``chunk_bytes``).  Given ``memory_budget_bytes`` the
    planner picks the FASTEST engine whose predicted peak fits:
    materialized when everything fits, pipelined when a superchunk pipeline
    fits, streamed otherwise (the minimum-footprint engine; if even that
    exceeds the budget the plan is still streamed, flagged
    ``budget_exceeded``).  Grids (num_seeds > 1 or multiple budgets) always
    compile to the batched engine.

The executors themselves live in :mod:`repro.core.api`
(:class:`~repro.core.api.CoresetPipeline` dispatches on the plan); this
module stays import-light so the spec can be constructed anywhere.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Mapping, Optional, Tuple, Union

import numpy as np

from repro.core.comm import CommSchedule
from repro.core.vfl import VFLDataset, block_geometry
from repro.core.wire import (
    CODEC_LADDER,
    SPEC_CODECS,
    choose_codec,
    fmt_bits,
    predict_dis_bits,
    predict_uniform_bits,
)
from repro.utils import trace

SCORE_BACKENDS = ("pallas", "ref", "norm")

ENGINES = ("materialized", "batched", "streamed", "pipelined")

#: Failover order, most capable to minimum footprint.  A build that crashes
#: or breaches its runtime memory budget retries on the next engine in this
#: ladder (pipelined -> streamed is bit-identical by the PR 4 contract).
FAILOVER_LADDER = ("materialized", "pipelined", "streamed")

FAULT_POLICIES = ("fail", "retry", "degrade", "quarantine")

# superchunk width when chunk_blocks is not given: deep enough to amortise
# the per-dispatch overhead, shallow enough that two prefetch slots + one
# resident superchunk stay a small multiple of the single-block footprint
DEFAULT_CHUNK_BLOCKS = 8

#: Measured-winner prefetch default per backend.  BENCH_kernels.json's
#: streaming sweep: on CPU the host thread that feeds the prefetch slot
#: competes with the compute it overlaps — noprefetch wins (918,245 rows/s
#: vs 690,124 with prefetch on).  On accelerators the staging copy runs on
#: the transfer engine while compute owns the cores, so prefetch wins.
#: Backends outside the table default to prefetching (accelerator-like).
PREFETCH_DEFAULT = {"cpu": False, "tpu": True, "gpu": True}

# pipelined peak model: two double-buffered staging slots + the live compute
# residency of one superchunk.  BENCH_kernels.json's streaming_pipelined
# sweep measures every peak <= 2.01x chunk_bytes; 2.5x is the documented
# bound the benchmark asserts against.
PIPELINED_PEAK_FACTOR = 2.5

_FLOAT_BYTES = 4        # every engine scores in float32


def _is_int(x) -> bool:
    return isinstance(x, (int, np.integer)) and not isinstance(x, bool)


@dataclasses.dataclass(frozen=True)
class CoresetSpec:
    """Frozen declarative description of one coreset construction.

    ``budgets`` accepts a single int or any iterable of ints; a grid
    (``num_seeds > 1`` or multiple budgets) compiles to the batched engine.
    ``engine="auto"`` lets the planner choose from the memory model;
    forcing an engine pins the exact legacy code path (the thin shims
    ``build_coreset`` / ``build_coreset_streaming`` /
    ``build_coresets_batched`` are precisely such forced specs, and stay
    draw-identical).  ``params`` carries task-specific score knobs (vkmc's
    ``k``/``alpha``/``local_iters``, vrlr's ``rcond``) verbatim.

    All validation is centralized HERE — uniform ``ValueError`` messages,
    raised at spec construction before any work happens.  The one knob
    that is *coerced* rather than rejected, ``chunk_blocks`` above the
    block count, is clamped by the PLANNER (an explicit decision recorded
    in ``ExecutionPlan.notes`` and ``describe()``), never silently here.
    """

    task: Union[str, Any] = "vrlr"
    budgets: Union[int, Tuple[int, ...]] = (512,)
    num_seeds: int = 1
    engine: str = "auto"
    backend: str = "auto"
    block_size: int = 65536
    chunk_blocks: Optional[int] = None    # None -> DEFAULT_CHUNK_BLOCKS (planner)
    prefetch: Optional[bool] = None       # None -> backend-aware (planner)
    memory_budget_bytes: Optional[int] = None
    sharded_masses: bool = False          # mass table via shard_map over `data`
    m_cap: Optional[int] = None           # batched draw capacity override
    fault_policy: str = "fail"            # fail | retry | degrade | quarantine
    codec: str = "raw_fp32"               # wire codec, or "auto" (planner)
    comm_budget_bits: Optional[int] = None
    params: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        if not (isinstance(self.task, str) or hasattr(self.task, "score_fn")):
            raise ValueError(
                f"task must be a registry name or CoresetTask, got {self.task!r}"
            )
        budgets = self.budgets
        if _is_int(budgets):
            budgets = (int(budgets),)
        else:
            budgets = tuple(budgets)
        if not budgets:
            raise ValueError("budgets must be a non-empty tuple of positive ints")
        bad = [b for b in budgets if not _is_int(b) or b < 1]
        if bad:
            raise ValueError(
                f"budgets must be positive ints, got {bad} in {budgets}"
            )
        budgets = tuple(int(b) for b in budgets)
        object.__setattr__(self, "budgets", budgets)
        if not _is_int(self.num_seeds) or self.num_seeds < 1:
            raise ValueError(
                f"num_seeds must be a positive int, got {self.num_seeds!r}"
            )
        if self.engine not in ("auto",) + ENGINES:
            raise ValueError(
                f"engine must be 'auto' or one of {ENGINES}, got {self.engine!r}"
            )
        if self.backend not in ("auto",) + SCORE_BACKENDS:
            raise ValueError(
                f"backend must be 'auto' or one of {SCORE_BACKENDS}, "
                f"got {self.backend!r}"
            )
        if not _is_int(self.block_size) or self.block_size < 1:
            raise ValueError(
                f"block_size must be a positive int, got {self.block_size!r}"
            )
        if self.chunk_blocks is not None and (
                not _is_int(self.chunk_blocks) or self.chunk_blocks < 1):
            raise ValueError(
                f"chunk_blocks must be a positive int, got {self.chunk_blocks!r}"
            )
        if self.prefetch is not None and not isinstance(self.prefetch, bool):
            raise ValueError(f"prefetch must be a bool, got {self.prefetch!r}")
        if self.memory_budget_bytes is not None and (
                not _is_int(self.memory_budget_bytes)
                or self.memory_budget_bytes < 1):
            raise ValueError(
                f"memory_budget_bytes must be a positive int, "
                f"got {self.memory_budget_bytes!r}"
            )
        if not isinstance(self.sharded_masses, bool):
            raise ValueError(
                f"sharded_masses must be a bool, got {self.sharded_masses!r}"
            )
        if self.sharded_masses and self.engine in ("materialized", "batched"):
            raise ValueError(
                f"sharded_masses computes the streaming block-mass table; it "
                f"cannot combine with engine={self.engine!r}"
            )
        if self.m_cap is not None:
            if not _is_int(self.m_cap) or self.m_cap < 1:
                raise ValueError(
                    f"m_cap must be a positive int, got {self.m_cap!r}"
                )
            over = [b for b in budgets if b > self.m_cap]
            if over:
                raise ValueError(
                    f"budgets {over} outside [1, m_cap={self.m_cap}]; every "
                    f"budget must be >= 1 and <= the draw capacity"
                )
        if self.fault_policy not in FAULT_POLICIES:
            raise ValueError(
                f"fault_policy must be one of {FAULT_POLICIES}, "
                f"got {self.fault_policy!r}"
            )
        if self.fault_policy != "fail" and self.engine == "batched":
            raise ValueError(
                f"fault_policy={self.fault_policy!r} delivers per-round "
                f"schedules through a transport; the batched engine bills "
                f"its cells lazily and cannot combine with it"
            )
        if self.codec not in SPEC_CODECS:
            raise ValueError(
                f"codec must be one of {SPEC_CODECS}, got {self.codec!r}"
            )
        if (self.codec not in ("auto", "raw_fp32")
                and self.engine == "batched"):
            raise ValueError(
                f"codec={self.codec!r} quantizes per-round payloads; the "
                f"batched engine bills its cells lazily and cannot combine "
                f"with it"
            )
        if self.comm_budget_bits is not None and (
                not _is_int(self.comm_budget_bits)
                or self.comm_budget_bits < 1):
            raise ValueError(
                f"comm_budget_bits must be a positive int, "
                f"got {self.comm_budget_bits!r}"
            )
        object.__setattr__(self, "params", dict(self.params))

    # -- conveniences --------------------------------------------------------

    @property
    def is_grid(self) -> bool:
        return self.num_seeds > 1 or len(self.budgets) > 1

    @property
    def budget(self) -> int:
        """The single budget of a non-grid spec."""
        if self.is_grid:
            raise ValueError(
                f"spec is a {self.num_seeds}x{len(self.budgets)} grid; "
                f"use .budgets"
            )
        return self.budgets[0]

    def replace(self, **kw) -> "CoresetSpec":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Memory model (bytes) — calibrated against BENCH_kernels.json yardsticks
# --------------------------------------------------------------------------

def block_bytes(T: int, bs: int, s: int) -> int:
    """One (T, bs, s) stacked row block — the streaming yardstick (measured
    streamed peaks sit within ~2% of this)."""
    return T * bs * s * _FLOAT_BYTES


def memory_model(
    T: int, n: int, s: int, bs: int, chunk_blocks: int,
    num_seeds: int = 1, num_budgets: int = 1, m_cap: int = 512,
    scored: bool = True,
) -> dict:
    """Predicted peak live device bytes per engine.

    materialized: the (T, n, s) stacked design + the (T, n) score matrix.
    batched:      materialized + the (R, M, m_cap) result grid.
    streamed:     one (T, bs, s) block + its transient (T, bs) scores.
    pipelined:    PIPELINED_PEAK_FACTOR x one (C, T, bs, s) superchunk
                  (two double-buffered staging slots + compute residency).

    ``scored=False`` (the uniform task — no scores, no design on device)
    collapses every engine to the tiny sample buffers.
    """
    if not scored:
        tiny = num_seeds * num_budgets * m_cap * 2 * _FLOAT_BYTES
        return {e: tiny for e in ENGINES}
    design = T * n * s * _FLOAT_BYTES
    scores = T * n * _FLOAT_BYTES
    blk = block_bytes(T, bs, s)
    grid = num_seeds * num_budgets * m_cap * 3 * _FLOAT_BYTES
    return {
        "materialized": design + scores,
        "batched": design + scores + grid,
        "streamed": blk + T * bs * _FLOAT_BYTES,
        "pipelined": int(PIPELINED_PEAK_FACTOR * chunk_blocks * blk),
    }


def _fmt_bytes(b: int) -> str:
    if b >= 1 << 20:
        return f"{b / (1 << 20):.1f}MB"
    if b >= 1 << 10:
        return f"{b / (1 << 10):.1f}KB"
    return f"{b}B"


# --------------------------------------------------------------------------
# Runtime memory watchdog — the benchmarks/streaming.py dedup census,
# productionized: the planner PREDICTS peaks from the calibrated model, the
# watchdog MEASURES them, and the failover ladder reacts when the model was
# wrong (ROADMAP item 2 shows it already is on CPU).
# --------------------------------------------------------------------------

def live_bytes() -> int:
    """Total bytes of live device arrays right now, deduped by underlying
    buffer so donated/aliased views (e.g. the pipelined engine's staging
    slots) count once, not per ``jax.Array`` object.  Process-wide: in a
    multi-tenant service this measures the whole device residency, which is
    exactly the number an OOM cares about."""
    import jax

    seen, total = set(), 0
    for a in jax.live_arrays():
        try:
            key = a.unsafe_buffer_pointer()
        except Exception:
            key = id(a)
        if key in seen:
            continue
        seen.add(key)
        total += int(np.prod(a.shape)) * a.dtype.itemsize
    return total


class MemoryBudgetExceeded(RuntimeError):
    """The live-bytes census breached the build's ``memory_budget_bytes``.

    Raised by :class:`MemoryWatchdog` at a probe boundary (between
    superchunk dispatches / after a build) — the failover ladder catches it
    and retries on the next-cheaper engine."""

    def __init__(self, observed: int, budget: int) -> None:
        super().__init__(
            f"live device bytes {observed} exceed memory_budget_bytes="
            f"{budget} ({_fmt_bytes(observed)} > {_fmt_bytes(budget)})"
        )
        self.observed = int(observed)
        self.budget = int(budget)


class MemoryWatchdog:
    """Runtime guard: compare the live-bytes census against a budget at
    every check.  Callable, so it plugs directly into the streaming
    engines' per-superchunk ``probe`` hook; ``peak``/``checks`` are the
    census the receipts and benchmarks read back."""

    def __init__(self, budget_bytes: int) -> None:
        if not _is_int(budget_bytes) or budget_bytes < 1:
            raise ValueError(
                f"budget_bytes must be a positive int, got {budget_bytes!r}"
            )
        self.budget_bytes = int(budget_bytes)
        self.checks = 0
        self.peak = 0

    def check(self) -> int:
        b = live_bytes()
        self.checks += 1
        if b > self.peak:
            self.peak = b
        if b > self.budget_bytes:
            raise MemoryBudgetExceeded(b, self.budget_bytes)
        return b

    __call__ = check


# --------------------------------------------------------------------------
# ExecutionPlan
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The compiled execution of a :class:`CoresetSpec` on one dataset.

    ``engine`` is concrete (one of :data:`ENGINES`); every knob is resolved
    (``backend`` never ``"auto"``, ``chunk_blocks`` clamped to the block
    count with the clamp recorded in ``notes``).  ``predicted_comm_units``
    is exact, not an estimate: Algorithm 1's total is independent of the
    realised round-2 counts (2T + m + 2mT per DIS cell, mT per uniform
    cell), so the bill is known before any draw.  ``memory_model`` keeps
    every engine's predicted peak so tests can pin the selection
    thresholds; ``predicted_peak_bytes`` is the chosen engine's entry.
    """

    spec: CoresetSpec
    engine: str
    backend: str
    task_name: str
    n: int
    T: int
    dims: Tuple[int, ...]          # per-party feature widths (sans label col)
    stacked_width: int
    nb: int
    bs: int
    chunk_blocks: int
    prefetch: bool
    grid: Tuple[int, int]                  # (num_seeds, num_budgets)
    m_cap: int
    memory_model: Mapping[str, int]
    predicted_peak_bytes: int
    predicted_comm_units: int
    #: Resolved wire codec (never ``"auto"``) and its predicted bill in
    #: bits.  Exact for ``raw_fp32`` (32 bits/unit); a certified upper
    #: bound for codecs with varint index uploads.  ``comm_budget_exceeded``
    #: flags a plan whose cheapest admissible codec still overshoots
    #: ``spec.comm_budget_bits`` — recorded, never silently dropped.
    codec: str = "raw_fp32"
    predicted_wire_bits: int = 0
    comm_budget_exceeded: bool = False
    budget_exceeded: bool = False
    notes: Tuple[str, ...] = ()
    #: Ordered engines to retry on if this plan's engine crashes or breaches
    #: its runtime memory budget — the cheaper tail of the failover ladder
    #: materialized -> pipelined -> streamed.  Empty for batched (grid
    #: semantics don't failover) and for streamed (already the
    #: minimum-footprint engine).  PR 5's executor contract makes
    #: pipelined -> streamed draw-identical; materialized -> pipelined
    #: switches to the streaming draw path (each engine's own canonical
    #: draw, same Thm 2.5 guarantee).
    fallback_chain: Tuple[str, ...] = ()

    @property
    def is_grid(self) -> bool:
        return self.grid[0] > 1 or self.grid[1] > 1

    def describe(self) -> str:
        """Human-readable plan: engine, geometry, memory table, comm bill,
        and every planner decision (clamps, lowerings, budget verdict)."""
        spec = self.spec
        lines = [
            f"ExecutionPlan: engine={self.engine}"
            + (" +sharded_masses" if spec.sharded_masses else ""),
            f"  task={self.task_name} backend={self.backend} "
            f"grid={self.grid[0]}x{self.grid[1]} budgets={spec.budgets} "
            f"m_cap={self.m_cap} fault_policy={spec.fault_policy}",
            f"  data: n={self.n} T={self.T} s={self.stacked_width} "
            f"blocks: {self.nb} x {self.bs} rows "
            f"(block_size={spec.block_size})",
        ]
        if self.engine in ("streamed", "pipelined"):
            lines.append(
                f"  streaming knobs: chunk_blocks={self.chunk_blocks} "
                f"prefetch={'on' if self.prefetch else 'off'}"
            )
        validators = ("on" if spec.fault_policy in ("fail", "quarantine")
                      else "off")
        lines.append(
            f"  integrity: wire envelopes on transported rounds 1-2; "
            f"value validators {validators} "
            f"(policy={spec.fault_policy})"
        )
        mm = ", ".join(f"{e}={_fmt_bytes(self.memory_model[e])}"
                       for e in ENGINES)
        lines.append(f"  memory model: {mm}")
        if spec.memory_budget_bytes is None:
            lines.append(
                f"  budget: none -> {self.engine} "
                f"(predicted peak {_fmt_bytes(self.predicted_peak_bytes)})"
            )
        else:
            verdict = ("EXCEEDS budget — streamed is the minimum-footprint "
                       "engine" if self.budget_exceeded else "fits")
            lines.append(
                f"  budget: {_fmt_bytes(spec.memory_budget_bytes)} -> "
                f"{self.engine} (predicted peak "
                f"{_fmt_bytes(self.predicted_peak_bytes)}, {verdict})"
            )
        lines.append(
            f"  predicted comm: {self.predicted_comm_units} units "
            f"({fmt_bits(self.predicted_wire_bits)} on the wire, "
            f"codec={self.codec})"
        )
        if spec.comm_budget_bits is not None:
            verdict = ("EXCEEDS budget — no admissible codec fits"
                       if self.comm_budget_exceeded else "fits")
            lines.append(
                f"  comm budget: {fmt_bits(spec.comm_budget_bits)} -> "
                f"{self.codec} ({fmt_bits(self.predicted_wire_bits)}, "
                f"{verdict})"
            )
        for note in self.notes:
            lines.append(f"  note: {note}")
        return "\n".join(lines)


# --------------------------------------------------------------------------
# Plan cache — the serving layer's compile-once seam
# --------------------------------------------------------------------------

#: CoresetSpec fields folded verbatim into the plan-cache key, in key
#: order.  ``task`` and ``params`` are encoded specially (registry name;
#: sorted item tuple).  The key-audit test asserts every CoresetSpec field
#: appears here, in the special pair, or on PLAN_KEY_EXEMPT — so a new
#: knob (fault_policy in PR 7, the integrity policy now) can never
#: silently alias cached plans.
PLAN_KEY_FIELDS = (
    "engine", "backend", "budgets", "num_seeds", "block_size",
    "chunk_blocks", "prefetch", "memory_budget_bytes", "sharded_masses",
    "m_cap", "fault_policy", "codec", "comm_budget_bits",
)

#: Spec fields deliberately excluded from the cache key, each with the
#: reason it cannot alias a cached plan.  Currently empty: every knob
#: influences planning or execution.
PLAN_KEY_EXEMPT: Tuple[str, ...] = ()


class PlanCache:
    """Memoized :func:`compile_plan`, keyed by ``(task, dataset geometry,
    resolved knobs)``.

    A long-lived service compiles the SAME plan over and over: every tenant
    streaming fixed-size superchunks presents the same ``(task, shapes,
    knobs)`` signature, and — because the executors' jit caches key on the
    same shapes — a plan-cache hit also means every jitted program the
    engine dispatches is already compiled.  That is the warm/cold latency
    story BENCH_kernels.json measures (warm 690k rows/s vs cold 240k on the
    pipelined engine): the plan itself is cheap, the warmup it signals is
    not.  ``hits``/``misses`` are exposed so the serving benchmark can
    report the ratio.

    A cached plan is geometry-checked at dispatch time
    (:meth:`CoresetPipeline.build` rejects a plan whose ``(n, dims)`` do
    not match the dataset), so sharing one cache across tenants/datasets is
    safe: different shapes occupy different keys.  ``spec.params`` values
    must be hashable (the shipped task knobs — ints/floats — are).

    ``max_entries`` bounds the cache LRU-style: a long-lived service seeing
    an unbounded variety of shapes (many tenants, many chunk sizes) evicts
    the least-recently-USED plan instead of growing forever.  Evicting a
    plan only costs a recompile on the next miss — correctness is
    unaffected.  ``evictions`` counts them; :meth:`stats` is the
    one-call census the serving layer surfaces.
    """

    DEFAULT_MAX_ENTRIES = 256

    def __init__(self, max_entries: Optional[int] = None, *,
                 time_fn=None) -> None:
        from collections import OrderedDict

        if max_entries is None:
            max_entries = self.DEFAULT_MAX_ENTRIES
        if not _is_int(max_entries) or max_entries < 1:
            raise ValueError(
                f"max_entries must be a positive int, got {max_entries!r}"
            )
        self.max_entries = int(max_entries)
        self._plans: "OrderedDict[tuple, ExecutionPlan]" = OrderedDict()
        # last_used ages entries so a long-lived service can shed stale
        # shape signatures (prune) instead of waiting for LRU pressure;
        # time_fn is injectable for deterministic aging tests.
        self._time_fn = time.monotonic if time_fn is None else time_fn
        self._last_used: dict = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @staticmethod
    def key(spec: CoresetSpec, ds: VFLDataset) -> tuple:
        task = spec.task if isinstance(spec.task, str) else spec.task.name
        return (
            (task, ds.n, ds.dims, ds.y is not None)
            + tuple(getattr(spec, f) for f in PLAN_KEY_FIELDS)
            + (tuple(sorted(spec.params.items())),)
        )

    def get(self, spec: CoresetSpec, ds: VFLDataset) -> "ExecutionPlan":
        k = self.key(spec, ds)
        plan = self._plans.get(k)
        if plan is None:
            self.misses += 1
            plan = compile_plan(spec, ds)
            self._plans[k] = plan
            if len(self._plans) > self.max_entries:
                old, _ = self._plans.popitem(last=False)  # least recently used
                self._last_used.pop(old, None)
                self.evictions += 1
        else:
            self.hits += 1
            self._plans.move_to_end(k)
        self._last_used[k] = self._time_fn()
        return plan

    def __len__(self) -> int:
        return len(self._plans)

    def clear(self) -> None:
        self._plans.clear()
        self._last_used.clear()

    def prune(self, max_idle_s: float) -> int:
        """Evict every entry unused for more than ``max_idle_s`` seconds.
        Returns the number evicted (also added to ``evictions``).  Cheap to
        call periodically — correctness is unaffected; a pruned plan just
        recompiles on its next miss."""
        if not (isinstance(max_idle_s, (int, float)) and max_idle_s >= 0):
            raise ValueError(
                f"max_idle_s must be a non-negative number, got {max_idle_s!r}"
            )
        now = self._time_fn()
        stale = [k for k, t in self._last_used.items()
                 if now - t > max_idle_s]
        for k in stale:
            self._plans.pop(k, None)
            self._last_used.pop(k, None)
        self.evictions += len(stale)
        return len(stale)

    def stats(self) -> dict:
        now = self._time_fn()
        ages = [now - t for t in self._last_used.values()]
        return {
            "size": len(self._plans),
            "max_entries": self.max_entries,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "oldest_idle_s": max(ages) if ages else 0.0,
            "newest_idle_s": min(ages) if ages else 0.0,
        }


# --------------------------------------------------------------------------
# The planner
# --------------------------------------------------------------------------

def _cell_comm(T: int, m: int, uniform: bool) -> int:
    """Exact per-cell bill via CommSchedule — the DIS total is independent
    of the realised a_j split (:meth:`CommSchedule.dis_total`)."""
    if uniform:
        return CommSchedule.uniform(T, m).total
    return CommSchedule.dis_total(T, m)


def compile_plan(spec: CoresetSpec, ds: VFLDataset) -> ExecutionPlan:
    """Compile ``spec`` against ``ds``'s geometry into an ExecutionPlan.

    Pure planning — no scoring work, no draws; the only jax state consulted
    is the default backend (for ``backend="auto"`` and the prefetch
    default).  Raises the task's label requirement eagerly so a bad spec
    fails before any engine runs.
    """
    with trace.span("plan"):
        return _compile_plan(spec, ds)


def _compile_plan(spec: CoresetSpec, ds: VFLDataset) -> ExecutionPlan:
    import jax

    from repro.core.api import get_task, resolve_backend

    task = get_task(spec.task)
    backend = resolve_backend(spec.backend)
    if task.needs_labels and ds.y is None:
        raise ValueError(f"{task.name} requires labels at party T")

    uniform = task.score_fn is None
    with_labels = task.needs_labels and ds.y is not None
    if uniform:
        s = 0
    else:
        _, s = ds.stacked_widths(with_labels=with_labels)
    n, T = ds.n, ds.T
    nb, bs = block_geometry(n, spec.block_size)
    R, M = spec.num_seeds, len(spec.budgets)
    m_cap = max(spec.budgets) if spec.m_cap is None else spec.m_cap

    notes = []

    # -- streaming knob resolution (explicit planner decisions) --------------
    chunk_req = (DEFAULT_CHUNK_BLOCKS if spec.chunk_blocks is None
                 else int(spec.chunk_blocks))
    chunk = min(chunk_req, nb)
    # prefetch default = the measured winner per backend, NOT "accelerator
    # => on" folklore.  BENCH_kernels.json streaming sweep on CPU:
    # 918,245 rows/s without prefetch vs 690,124 with it — the host thread
    # feeding the staging slot steals the cores the compute needs.
    if spec.prefetch is None:
        prefetch = PREFETCH_DEFAULT.get(jax.default_backend(), True)
    else:
        prefetch = bool(spec.prefetch)

    mm = memory_model(T, n, s, bs, chunk, R, M, m_cap, scored=not uniform)

    # -- engine selection ----------------------------------------------------
    budget_exceeded = False
    if spec.is_grid:
        if spec.engine not in ("auto", "batched"):
            raise ValueError(
                f"engine={spec.engine!r} builds one coreset per call; a "
                f"{R}x{M} grid requires engine='batched' (or 'auto')"
            )
        if spec.fault_policy != "fail":
            raise ValueError(
                f"fault_policy={spec.fault_policy!r} delivers per-round "
                f"schedules through a transport; the batched engine bills "
                f"its cells lazily and cannot combine with it"
            )
        engine = "batched"
        if spec.engine == "auto":
            notes.append(f"{R}x{M} grid -> batched (one compiled call)")
    elif spec.engine != "auto":
        engine = spec.engine
    elif spec.memory_budget_bytes is None:
        engine = "materialized"
    else:
        B = spec.memory_budget_bytes
        if mm["materialized"] <= B:
            engine = "materialized"
        elif mm["pipelined"] <= B:
            engine = "pipelined"
        else:
            engine = "streamed"
            budget_exceeded = mm["streamed"] > B
        notes.append(
            f"auto-selected {engine} for memory_budget_bytes={B} "
            f"(materialized needs {mm['materialized']}, pipelined "
            f"{mm['pipelined']}, streamed {mm['streamed']})"
        )

    # the streamed engine IS the pipelined engine at C=1 without prefetch —
    # normalize both directions so dispatch is unambiguous
    lowered_from_pipelined = False
    if engine == "streamed":
        chunk, prefetch = 1, False
    elif engine == "pipelined" and chunk == 1 and not prefetch:
        engine = "streamed"
        lowered_from_pipelined = True
        notes.append(
            "pipelined at chunk_blocks=1 without prefetch IS the "
            "block-at-a-time engine -> lowered to streamed"
        )
    if chunk_req > nb and (engine == "pipelined" or lowered_from_pipelined):
        # the documented planner clamp (NOT silent coercion: recorded here,
        # printed by describe()) — a superchunk cannot span more than nb
        # blocks, so chunk_blocks >= nb means one full-span superchunk.
        # Forced-streamed plans ignore chunk_blocks entirely (chunk = 1), so
        # no clamp note there.
        notes.append(
            f"chunk_blocks clamped {chunk_req} -> {nb}: n={n} at "
            f"block_size={spec.block_size} has only {nb} blocks "
            f"(one full-span superchunk)"
        )

    # a flag that only makes sense on SOME engines must not be dropped
    # silently when the auto-planner picks another one — mirror the forced
    # combination CoresetSpec.__post_init__ already rejects
    if spec.sharded_masses:
        if engine not in ("streamed", "pipelined"):
            raise ValueError(
                f"sharded_masses computes the streaming block-mass table, "
                f"but the planner selected engine {engine!r} — force a "
                f"streaming engine or drop the toggle"
            )
        if backend == "norm":
            raise ValueError(
                "sharded_masses computes the task's real score masses; it "
                "cannot combine with backend='norm'"
            )
        if task.name not in ("vrlr", "vkmc"):
            raise ValueError(
                f"sharded_masses supports tasks ('vrlr', 'vkmc'), got "
                f"{task.name!r}"
            )
        D = jax.device_count()
        if not uniform and (n % D != 0 or (n // D) % bs != 0):
            # the shard-grid requirement _check_shard_grid enforces at run
            # time, surfaced at PLAN time so a bad spec fails before work
            raise ValueError(
                f"sharded_masses needs n divisible by the device count and "
                f"the per-device shard divisible by the block size: n={n}, "
                f"devices={D}, bs={bs}"
            )

    comm = R * sum(_cell_comm(T, m, uniform) for m in spec.budgets)

    # -- wire codec resolution (the comm-budget axis) ------------------------
    # The round-1 mass table a party uploads has one entry per scoring cell:
    # the full n-row table on the materialized/batched paths, the nb
    # block-mass table on the streaming engines.  Bits are exact for every
    # shape-determined message; varint uploads contribute their certified
    # upper bound, so the prediction is a ceiling the realized bill never
    # crosses.
    cells = n if engine in ("materialized", "batched") else nb
    lossless_only = engine == "batched"
    if spec.codec not in ("auto", "raw_fp32") and lossless_only:
        raise ValueError(
            f"codec={spec.codec!r} quantizes per-round payloads, but the "
            f"planner selected the batched path — use codec='raw_fp32' or "
            f"a transported engine"
        )

    def _predict(name: str) -> int:
        if uniform:
            return R * sum(predict_uniform_bits(T, m) for m in spec.budgets)
        return R * sum(predict_dis_bits(T, m, cells, name)
                       for m in spec.budgets)

    if spec.codec == "auto" and lossless_only:
        # the only admissible codec on a never-leaves-device path
        codec, wire_bits = "raw_fp32", _predict("raw_fp32")
        comm_budget_exceeded = (
            spec.comm_budget_bits is not None
            and wire_bits > spec.comm_budget_bits
        )
        if comm_budget_exceeded:
            notes.append(
                f"comm budget {spec.comm_budget_bits}b unmeetable: the "
                f"batched path admits only raw_fp32 ({wire_bits}b predicted)"
            )
    else:
        bits_by_codec = {name: _predict(name) for name in CODEC_LADDER}
        codec, comm_budget_exceeded, codec_note = choose_codec(
            spec.codec, spec.comm_budget_bits, bits_by_codec
        )
        wire_bits = bits_by_codec[codec]
        if codec_note:
            notes.append(codec_note)

    # failover ladder: the cheaper engines after the chosen one.
    # sharded_masses binds the spec to the streaming engines (validated
    # above), so those plans pin their engine and never failover.
    if engine in FAILOVER_LADDER and not spec.sharded_masses:
        fallback = FAILOVER_LADDER[FAILOVER_LADDER.index(engine) + 1:]
    else:
        fallback = ()

    return ExecutionPlan(
        spec=spec,
        engine=engine,
        backend=backend,
        task_name=task.name,
        n=n, T=T, dims=ds.dims, stacked_width=s, nb=nb, bs=bs,
        chunk_blocks=chunk, prefetch=prefetch,
        grid=(R, M), m_cap=m_cap,
        memory_model=mm,
        predicted_peak_bytes=mm[engine],
        predicted_comm_units=comm,
        codec=codec,
        predicted_wire_bits=wire_bits,
        comm_budget_exceeded=comm_budget_exceeded,
        budget_exceeded=budget_exceeded,
        notes=tuple(notes),
        fallback_chain=fallback,
    )
