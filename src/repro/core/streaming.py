"""Streaming block-scan scoring + hierarchical DIS: million-row coreset
construction on fixed device memory, at pipeline speed.

The materialized pipeline (:mod:`repro.core.api`) holds the full (T, n, s)
stacked design and a (T, n) score matrix on device — its memory scales with
n even though the protocol's *communication* scales with m.  This module
makes n a streaming dimension end to end:

  * **Block-scan scoring** — every score path is restructured into passes
    over (T, bs, s) row blocks (``VFLDataset.blocks``), with only ONE block
    device-resident at a time.  VRLR: pass 1 accumulates the per-party
    (s, s) Gram across blocks (the d x d sufficient statistic — the same
    VMEM-scratch accumulation pattern the Pallas ``weighted_gram`` /
    ``kmeans_assign_update`` kernels use across their sequential grid, here
    lifted to HBM-block granularity), then the eigen-pseudo-inverse is
    computed ONCE and pass 2 emits leverage scores block by block.  VKMC:
    local k-means runs on a bounded uniform row subsample, pass 2
    accumulates global cluster sizes/costs via the fused assign-update
    kernel per block, pass 3 emits sensitivities block by block.
  * **Pipelined superchunks** — the per-block Python dispatch loop is the
    throughput ceiling at large n (one host->device copy + one XLA launch
    per (T, bs, s) block).  With ``chunk_blocks=C > 1`` every scan pass
    instead consumes (C, T, bs, s) superchunks staged by
    ``VFLDataset.blocks_prefetched`` (double-buffered: the async transfer
    of superchunk c+1 is issued while c computes; each chunk's fresh
    staging buffer is aliased by the zero-copy CPU ``device_put``, and
    prompt reference dropping caps live slots at two) and runs the
    per-block step as a
    ``jax.lax.scan`` inside ONE jitted dispatch per superchunk — nb Python
    dispatches become nb/C.  The scan body is the *same* per-block
    computation in the same order, so Gram/stats accumulation and the mass
    table stay draw-identical to the per-block path.
  * **Hierarchical DIS** (:func:`repro.core.dis.dis_plan_blocked`) — round 1
    samples (party, block) cells from the (T, nb) block-mass table, round 2
    samples rows within only the *touched* blocks (scores recomputed on
    demand), so the (T, n) score matrix never exists.  The induced marginal
    telescopes to exactly the flat plan's g_i/G.
    :func:`dis_plan_streamed` recomputes touched blocks one dispatch per
    block; :func:`dis_plan_streamed_batched` gathers touched blocks in
    superchunk-sized groups and scores + draws each group in single
    vmapped dispatches (the one-dispatch redraw), bit-for-bit the same
    draws.
  * **Data-parallel masses** (:func:`vrlr_block_masses_sharded` /
    :func:`vkmc_block_masses_sharded`) — rows sharded over the mesh's
    ``data`` axis via ``shard_map``; each device scores its row shard and
    the block-mass table is combined with one psum (plus one sufficient-
    statistic psum: the (T, s, s) Gram for VRLR, the (T, 2k) cluster
    size/cost table for VKMC — the mesh analogue of DIS round 1's T
    scalars).  Communication stays the DIS bill; compute scales with
    devices.

With a numpy-backed :class:`~repro.core.vfl.VFLDataset` the dataset lives in
host memory and peak *device* memory is O(chunk_blocks * block_size * d) at
any n — measured by ``benchmarks/streaming.py`` and recorded in
BENCH_kernels.json (``streaming`` and ``streaming_pipelined`` sections).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dis import (
    DisPlan,
    _categorical_head,
    _float_dtype,
    _head_draws_ok,
    _key_chain,
)
from repro.core.faults import StreamCheckpoint
from repro.core.sensitivity import (
    HIGHEST,
    batched_gram_pinv,
    kmeans_update,
    norm_scores,
)
from repro.core.vfl import VFLDataset
from repro.core.vkmc import kmeans
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.utils import trace


@dataclasses.dataclass(frozen=True)
class StreamScorer:
    """Block-granular view of one task's party-local scores.

    ``masses[j, b]`` is the block mass G^(j,b) = sum_{i in block b} g_i^(j)
    (the round-1 table of the hierarchical sampler); ``score_block(b)``
    recomputes the (T, bs) scores of block ``b`` on demand, with padded rows
    exactly 0; ``score_blocks(ids)`` recomputes a whole GROUP of blocks as
    one (len(ids), T, bs) batch in a single vmapped dispatch (the
    one-dispatch redraw path), block i bitwise equal to
    ``score_block(ids[i])``.  ``chunk_blocks`` is the superchunk width the
    scorer was built with (the redraw groups touched blocks at the same
    granularity).  ``data_passes`` counts full passes over the dataset the
    scorer spent building its state + mass table (the streamed analogue of
    ``fused_lloyd``'s passes-over-X census).
    """

    T: int
    n: int
    nb: int
    bs: int
    masses: jax.Array                       # (T, nb) float32
    dis_key: jax.Array
    score_block: Callable[[int], jax.Array]
    data_passes: int
    score_blocks: Optional[Callable[[Sequence[int]], jax.Array]] = None
    chunk_blocks: int = 1
    # (T,) retained condition numbers of the accumulated party Grams (VRLR
    # scorers only; None elsewhere) — feeds the build's HealthReport
    gram_conds: Optional[jax.Array] = None


# (task name) -> factory(key, ds, block_size, backend, probe, **params)
STREAM_SCORERS: Dict[str, Callable[..., StreamScorer]] = {}


def register_stream_scorer(name: str):
    """Decorator: register a :class:`StreamScorer` factory for task ``name``."""

    def deco(fn):
        if name in STREAM_SCORERS:
            raise KeyError(f"stream scorer for {name!r} already registered")
        STREAM_SCORERS[name] = fn
        return fn

    return deco


def make_stream_scorer(
    name: str,
    key: jax.Array,
    ds: VFLDataset,
    block_size: int,
    backend: str,
    probe: Optional[Callable[[], None]] = None,
    chunk_blocks: int = 1,
    prefetch: bool = False,
    masses: Optional[jax.Array] = None,
    ckpt: Optional[StreamCheckpoint] = None,
    **params,
) -> StreamScorer:
    """Build the task's :class:`StreamScorer`.  ``masses`` (a precomputed
    (T, nb) block-mass table, e.g. from :func:`vrlr_block_masses_sharded`)
    skips the factory's own mass pass — the ``sharded_masses`` plan toggle:
    round 1 samples from the supplied table while per-row scores still come
    from the scorer's block recomputation.  ``ckpt`` (a bound
    :class:`~repro.core.faults.StreamCheckpoint`) makes every data pass
    resumable: the accumulator + completed-chunk counter is saved after
    each superchunk (or block), and a restarted build with the same ckpt
    continues the fold where it died, draw-identical to an uninterrupted
    run.  ``ckpt=None`` leaves the scan paths untouched."""
    factory = STREAM_SCORERS.get(name)
    if factory is None:
        raise ValueError(
            f"no streaming scorer registered for task {name!r}; "
            f"available: {sorted(STREAM_SCORERS)}"
        )
    return factory(key, ds, block_size, backend, probe=probe,
                   chunk_blocks=chunk_blocks, prefetch=prefetch,
                   masses=masses, ckpt=ckpt, **params)


def with_masses(scorer: StreamScorer, masses) -> StreamScorer:
    """``scorer`` with its block-mass table swapped for the DELIVERED one.

    The wire seam's hook: when the round-1 table crossed a transport — a
    lossy codec's quantized copy, or a corrupted one an unverifying
    transport let through — the hierarchical sampler must draw from what
    arrived, not the honest host table.  The per-row scores the redraw
    recomputes are untouched; only the block-selection marginals change.
    The table is cast to the scorer's mass dtype so downstream weight
    arithmetic keeps its precision contract."""
    tbl = jnp.asarray(
        np.asarray(masses).astype(np.asarray(scorer.masses).dtype))
    if tbl.shape != scorer.masses.shape:
        raise ValueError(
            f"delivered mass table has shape {tbl.shape}; the scorer's "
            f"is {scorer.masses.shape}"
        )
    return dataclasses.replace(scorer, masses=tbl)


def _noop() -> None:
    return None


def _ckpt_load(ckpt: Optional[StreamCheckpoint], phase: str):
    """(resume chunk counter, restored carry-or-None) for one scan phase.
    A completed phase resumes past the end of the traversal, so its loop
    body never re-runs and the carry is the pass's final accumulator."""
    if ckpt is None:
        return 0, None
    saved = ckpt.load(phase)
    return (0, None) if saved is None else saved


def _ckpt_save(ckpt: Optional[StreamCheckpoint], phase: str, done: int,
               carry) -> None:
    if ckpt is not None:
        ckpt.save(phase, done, carry)


def _row_valid(bs: int, nvalid) -> jax.Array:
    return (jnp.arange(bs) < nvalid).astype(jnp.float32)


# --------------------------------------------------------------------------
# VRLR: Gram block-scan -> one pinv -> blockwise leverage
# --------------------------------------------------------------------------

def _gram_body(G, blk, nvalid, use_kernel: bool):
    """G += blk^T diag(valid) blk, batched over the party axis — the ONE
    per-block Gram step shared verbatim by the per-block jit, the superchunk
    scan, and (einsum form) the sharded mass table, so every granularity
    accumulates bit-identically."""
    T, bs, _ = blk.shape
    f = blk.astype(jnp.float32)
    wv = jnp.broadcast_to(_row_valid(bs, nvalid), (T, bs))
    if use_kernel:
        Gb = kops.weighted_gram(f, wv)
    else:
        Gb = jnp.einsum("tns,tn,tnu->tsu", f, wv, f)
    return G + Gb


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _gram_step(G, blk, nvalid, *, use_kernel: bool):
    """Padded rows are zero so the mask is belt-and-braces; the kernel path
    streams the block through the Pallas ``weighted_gram`` grid
    accumulator."""
    return _gram_body(G, blk, nvalid, use_kernel)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _gram_chunk(G, chunk, nvalids, *, use_kernel: bool):
    """The Gram pass over one (C, T, bs, s) superchunk as a ``lax.scan`` —
    C per-block :func:`_gram_body` steps in block order inside ONE
    dispatch (zero-padded trailing blocks contribute exactly 0)."""

    def body(g, xs):
        blk, nv = xs
        return _gram_body(g, blk, nv, use_kernel), None

    G, _ = jax.lax.scan(body, G, (chunk, nvalids))
    return G


def _vrlr_score_body(blk, M, nvalid, n, use_kernel: bool):
    """clip(x_i^T M x_i, 0, 1) + 1/n per party; 0 on padded rows."""
    f = blk.astype(jnp.float32)
    if use_kernel:
        lev = kops.leverage(f, M)
    else:
        lev = jnp.einsum("tns,tsr,tnr->tn", f, M, f, precision=HIGHEST)
    sc = jnp.clip(lev, 0.0, 1.0) + 1.0 / n
    ok = jnp.arange(f.shape[1]) < nvalid
    return jnp.where(ok[None, :], sc, 0.0)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vrlr_score_block(blk, M, nvalid, n, *, use_kernel: bool):
    return _vrlr_score_body(blk, M, nvalid, n, use_kernel)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vrlr_mass_chunk(chunk, M, nvalids, n, *, use_kernel: bool):
    """(T, C) block masses of one superchunk: the per-block score + sum in a
    single scanned dispatch."""

    def body(carry, xs):
        blk, nv = xs
        return carry, jnp.sum(_vrlr_score_body(blk, M, nv, n, use_kernel),
                              axis=1)

    _, mm = jax.lax.scan(body, 0, (chunk, nvalids))        # (C, T)
    return mm.T


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vrlr_score_batch(batch, M, nvalids, n, *, use_kernel: bool):
    """(nt, T, bs) scores of a gathered block batch in ONE vmapped dispatch."""
    return jax.vmap(
        lambda blk, nv: _vrlr_score_body(blk, M, nv, n, use_kernel)
    )(batch, nvalids)


def _norm_score_body(blk, nvalid, n):
    """Row-norm^2 ablation scores, blockwise.  Row-local, so each row's value
    is bitwise identical to the materialized ``norm`` backend's."""
    sc = norm_scores(blk) + 1.0 / n
    ok = jnp.arange(blk.shape[1]) < nvalid
    return jnp.where(ok[None, :], sc, 0.0)


@jax.jit
def _norm_score_block(blk, nvalid, n):
    return _norm_score_body(blk, nvalid, n)


@jax.jit
def _norm_mass_chunk(chunk, nvalids, n):
    def body(carry, xs):
        blk, nv = xs
        return carry, jnp.sum(_norm_score_body(blk, nv, n), axis=1)

    _, mm = jax.lax.scan(body, 0, (chunk, nvalids))
    return mm.T


@jax.jit
def _norm_score_batch(batch, nvalids, n):
    return jax.vmap(lambda blk, nv: _norm_score_body(blk, nv, n))(
        batch, nvalids)


def _mass_table(ds, block_size, score_block, probe, ckpt=None):
    """One pass over the blocks collecting the (T, nb) block-mass table."""
    nb, _ = ds.block_geometry(block_size)
    start, saved = _ckpt_load(ckpt, "mass")
    masses = list(saved) if saved is not None else []
    with trace.span("score.mass"):
        for b in range(start, nb):
            masses.append(jnp.sum(score_block(b), axis=1))
            _ckpt_save(ckpt, "mass", b + 1, tuple(masses))
            probe()
        return jnp.stack(masses, axis=1)                   # (T, nb)


def _chunked_mass_table(ds, block_size, chunk_blocks, prefetch, probe,
                        with_labels, mass_chunk, ckpt=None):
    """The mass-table pass at superchunk granularity: one jitted scan
    dispatch per (C, T, bs, s) superchunk, blocks prefetched double-buffered.
    Column b is bitwise :func:`_mass_table`'s column b (same per-block score
    + sum, same order); trailing zero-padded blocks are sliced away."""
    nb, _ = ds.block_geometry(block_size)
    start, saved = _ckpt_load(ckpt, "mass")
    cols = list(saved) if saved is not None else []
    with trace.span("score.mass"):
        for b0, chunk, nvalids in ds.blocks_prefetched(
                block_size, with_labels, chunk_blocks, prefetch,
                start_chunk=start):
            cols.append(mass_chunk(chunk, jnp.asarray(nvalids)))   # (T, C)
            del chunk        # drop the slot before the next one is staged
            _ckpt_save(ckpt, "mass", b0 // chunk_blocks + 1, tuple(cols))
            probe()
        return jnp.concatenate(cols, axis=1)[:, :nb]


@register_stream_scorer("vrlr")
def vrlr_stream_scorer(
    key, ds: VFLDataset, block_size: int, backend: str,
    probe: Optional[Callable[[], None]] = None, rcond: float = 1e-6,
    chunk_blocks: int = 1, prefetch: bool = False,
    masses: Optional[jax.Array] = None,
    ckpt: Optional[StreamCheckpoint] = None,
) -> StreamScorer:
    """Algorithm 2's scores without ever holding (n, d): one block-scan pass
    accumulates each party's (s, s) Gram, the eigen-pseudo-inverse is taken
    once, and scores are re-emitted per block from (block, M) alone.  The
    key passes through untouched, matching the materialized ``vrlr`` task's
    deterministic-score contract.

    ``chunk_blocks=C > 1`` (or ``prefetch=True``) switches both passes to
    the pipelined engine: double-buffered (C, T, bs, s) superchunks, the
    per-block step run as a ``lax.scan`` inside one dispatch per superchunk
    — same accumulation order, same mass table, nb/C dispatches.
    """
    probe = probe or _noop
    use_kernel = backend == "pallas"
    nb, bs = ds.block_geometry(block_size)
    widths, s = ds.stacked_widths(with_labels=True)
    n = ds.n
    C = max(1, min(int(chunk_blocks), nb))
    pipelined = C > 1 or prefetch
    gram_conds = None

    if backend == "norm":
        def score_block(b: int) -> jax.Array:
            blk, nvalid = ds.block(b, block_size, with_labels=True)
            return _norm_score_block(blk, nvalid, float(n))

        def score_blocks(ids) -> jax.Array:
            batch, nvalids = ds.gather_blocks(ids, block_size,
                                              with_labels=True)
            return _norm_score_batch(batch, jnp.asarray(nvalids), float(n))

        if masses is None:
            if pipelined:
                masses = _chunked_mass_table(
                    ds, block_size, C, prefetch, probe, True,
                    lambda chunk, nv: _norm_mass_chunk(chunk, nv, float(n)),
                    ckpt=ckpt)
            else:
                masses = _mass_table(ds, block_size, score_block, probe,
                                     ckpt=ckpt)
            passes = 1
        else:
            passes = 0
    else:
        start, saved = _ckpt_load(ckpt, "gram")
        G = saved if saved is not None else jnp.zeros((ds.T, s, s),
                                                      jnp.float32)
        with trace.span("score.gram"):
            if pipelined:
                for b0, chunk, nvalids in ds.blocks_prefetched(
                        block_size, True, C, prefetch, start_chunk=start):
                    G = _gram_chunk(G, chunk, jnp.asarray(nvalids),
                                    use_kernel=use_kernel)
                    del chunk    # drop the slot before the next is staged
                    _ckpt_save(ckpt, "gram", b0 // C + 1, G)
                    probe()
            else:
                for b, blk, nvalid in ds.blocks(block_size, with_labels=True):
                    if b < start:
                        continue
                    G = _gram_step(G, blk, nvalid, use_kernel=use_kernel)
                    _ckpt_save(ckpt, "gram", b + 1, G)
                    probe()
        with trace.span("score.pinv"):
            M, gram_conds = batched_gram_pinv(G, rcond, return_cond=True,
                                              expected_rank=widths)

        def score_block(b: int) -> jax.Array:
            blk, nvalid = ds.block(b, block_size, with_labels=True)
            return _vrlr_score_block(blk, M, nvalid, float(n),
                                     use_kernel=use_kernel)

        def score_blocks(ids) -> jax.Array:
            batch, nvalids = ds.gather_blocks(ids, block_size,
                                              with_labels=True)
            return _vrlr_score_batch(batch, M, jnp.asarray(nvalids), float(n),
                                     use_kernel=use_kernel)

        if masses is None:
            if pipelined:
                masses = _chunked_mass_table(
                    ds, block_size, C, prefetch, probe, True,
                    lambda chunk, nv: _vrlr_mass_chunk(chunk, M, nv, float(n),
                                                       use_kernel=use_kernel),
                    ckpt=ckpt)
            else:
                masses = _mass_table(ds, block_size, score_block, probe,
                                     ckpt=ckpt)
            passes = 2
        else:
            passes = 1           # the Gram pass still ran; the mass pass didn't

    return StreamScorer(T=ds.T, n=n, nb=nb, bs=bs, masses=masses,
                        dis_key=key, score_block=score_block,
                        data_passes=passes, score_blocks=score_blocks,
                        chunk_blocks=C, gram_conds=gram_conds)


# --------------------------------------------------------------------------
# VKMC: subsampled local k-means -> stats block-scan -> blockwise scores
# --------------------------------------------------------------------------

def _vkmc_key_chain(key, T: int):
    """One split per party + one for DIS — the materialized ``vkmc`` task's
    exact key consumption, shared by the scorer and the sharded mass table
    so the same seed drives comparable constructions everywhere."""
    subs = []
    for _ in range(T):
        key, sub = jax.random.split(key)
        subs.append(sub)
    key, dis_key = jax.random.split(key)
    return subs, dis_key


def vkmc_local_centers(
    key, ds: VFLDataset, k: int = 10, local_iters: int = 15,
    center_sample: int = 16384, use_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Party-local alpha-approximate k-means centers from a bounded uniform
    row subsample, padded to the common stacked width: (T, k, s) centers +
    the downstream DIS key.  O(center_sample * d_j) memory per party; the
    subsample's solution is still an alpha'-approximation absorbed by the
    ``alpha`` knob."""
    widths, s = ds.stacked_widths(with_labels=False)
    subs, dis_key = _vkmc_key_chain(key, ds.T)
    centers = []
    for j, sub in enumerate(subs):
        k_smp, k_km = jax.random.split(sub)
        if ds.n > center_sample:
            idx = np.asarray(jax.random.randint(k_smp, (center_sample,), 0,
                                                ds.n))
            Xj = jnp.asarray(ds.parts[j][idx])
        else:
            Xj = jnp.asarray(ds.parts[j])
        c = kmeans(k_km, Xj, k, iters=local_iters, use_kernel=use_kernel)
        centers.append(jnp.pad(c, ((0, 0), (0, s - widths[j]))))
    return jnp.stack(centers), dis_key                     # (T, k, s)


def _vkmc_stats_body(blk, centers, nvalid, use_kernel: bool):
    """(cluster sizes (T, k), cluster costs (T, k)) of one block — the fused
    assign-update pass with validity weights, batched over parties."""
    T, bs, _ = blk.shape
    wv = jnp.broadcast_to(_row_valid(bs, nvalid), (T, bs))
    _, _, _, wsum, ccost = kmeans_update(blk, centers, wv,
                                         use_kernel=use_kernel)
    return wsum, ccost


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vkmc_stats_step(blk, centers, nvalid, *, use_kernel: bool):
    return _vkmc_stats_body(blk, centers, nvalid, use_kernel)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vkmc_stats_chunk(csize, ccost, chunk, centers, nvalids,
                      *, use_kernel: bool):
    """The stats pass over one superchunk as a scan of per-block
    :func:`_vkmc_stats_body` steps — one dispatch, same accumulation order
    as the per-block loop."""

    def body(carry, xs):
        cs, cc = carry
        blk, nv = xs
        ws, c2 = _vkmc_stats_body(blk, centers, nv, use_kernel)
        return (cs + ws, cc + c2), None

    (csize, ccost), _ = jax.lax.scan(body, (csize, ccost), (chunk, nvalids))
    return csize, ccost


def _vkmc_score_body(blk, centers, csize, ccost, nvalid, alpha,
                     use_kernel: bool):
    """Algorithm 3 lines 3-11 for one block, given the GLOBAL per-party
    cluster sizes/costs from the stats pass; 0 on padded rows."""
    # kops/kref directly: both batch over the leading party axis (the
    # inline fallback in sensitivity.kmeans_assignment is 2-D only)
    if use_kernel:
        assign, d2 = kops.kmeans_assign(blk, centers)
    else:
        assign, d2 = kref.kmeans_assign(blk, centers)
    cost = jnp.maximum(ccost.sum(axis=1), 1e-30)[:, None]      # (T, 1)
    cs = jnp.maximum(csize, 1.0)                               # (T, k)
    cc_a = jnp.take_along_axis(ccost, assign, axis=1)          # (T, bs)
    cs_a = jnp.take_along_axis(cs, assign, axis=1)
    sc = alpha * d2 / cost + alpha * cc_a / (cs_a * cost) + 2.0 * alpha / cs_a
    ok = jnp.arange(blk.shape[1]) < nvalid
    return jnp.where(ok[None, :], sc, 0.0)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vkmc_score_block(blk, centers, csize, ccost, nvalid, alpha,
                      *, use_kernel: bool):
    return _vkmc_score_body(blk, centers, csize, ccost, nvalid, alpha,
                            use_kernel)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vkmc_mass_chunk(chunk, centers, csize, ccost, nvalids, alpha,
                     *, use_kernel: bool):
    def body(carry, xs):
        blk, nv = xs
        sc = _vkmc_score_body(blk, centers, csize, ccost, nv, alpha,
                              use_kernel)
        return carry, jnp.sum(sc, axis=1)

    _, mm = jax.lax.scan(body, 0, (chunk, nvalids))
    return mm.T


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _vkmc_score_batch(batch, centers, csize, ccost, nvalids, alpha,
                      *, use_kernel: bool):
    return jax.vmap(
        lambda blk, nv: _vkmc_score_body(blk, centers, csize, ccost, nv,
                                         alpha, use_kernel)
    )(batch, nvalids)


@register_stream_scorer("vkmc")
def vkmc_stream_scorer(
    key, ds: VFLDataset, block_size: int, backend: str,
    probe: Optional[Callable[[], None]] = None,
    k: int = 10, alpha: float = 2.0, local_iters: int = 15,
    center_sample: int = 16384,
    chunk_blocks: int = 1, prefetch: bool = False,
    masses: Optional[jax.Array] = None,
    ckpt: Optional[StreamCheckpoint] = None,
) -> StreamScorer:
    """Algorithm 3's sensitivities with only one superchunk resident.

    Party j's local alpha-approximate k-means runs on a uniform row
    subsample (:func:`vkmc_local_centers`), then ONE block-scan pass
    accumulates the global cluster sizes/costs through the fused
    assign-update kernel, and scores are re-emitted per block from (block,
    centers, stats).  The key chain (one split per party, one for DIS)
    matches the materialized ``vkmc`` task, so the same seed drives
    comparable constructions.  ``chunk_blocks``/``prefetch`` select the
    pipelined superchunk engine exactly as in :func:`vrlr_stream_scorer`.
    """
    probe = probe or _noop
    use_kernel = backend == "pallas"
    nb, bs = ds.block_geometry(block_size)
    n, T = ds.n, ds.T
    C = max(1, min(int(chunk_blocks), nb))
    pipelined = C > 1 or prefetch

    if backend == "norm":
        _, dis_key = _vkmc_key_chain(key, T)   # the task's exact key budget

        def score_block(b: int) -> jax.Array:
            blk, nvalid = ds.block(b, block_size, with_labels=False)
            return _norm_score_block(blk, nvalid, float(n))

        def score_blocks(ids) -> jax.Array:
            batch, nvalids = ds.gather_blocks(ids, block_size,
                                              with_labels=False)
            return _norm_score_batch(batch, jnp.asarray(nvalids), float(n))

        if masses is None:
            if pipelined:
                masses = _chunked_mass_table(
                    ds, block_size, C, prefetch, probe, False,
                    lambda chunk, nv: _norm_mass_chunk(chunk, nv, float(n)),
                    ckpt=ckpt)
            else:
                masses = _mass_table(ds, block_size, score_block, probe,
                                     ckpt=ckpt)
            passes = 1
        else:
            passes = 0
        return StreamScorer(T=T, n=n, nb=nb, bs=bs, masses=masses,
                            dis_key=dis_key, score_block=score_block,
                            data_passes=passes, score_blocks=score_blocks,
                            chunk_blocks=C)

    centers, dis_key = vkmc_local_centers(
        key, ds, k=k, local_iters=local_iters, center_sample=center_sample,
        use_kernel=use_kernel)

    start, saved = _ckpt_load(ckpt, "stats")
    if saved is not None:
        csize, ccost = saved
    else:
        csize = jnp.zeros((T, k), jnp.float32)
        ccost = jnp.zeros((T, k), jnp.float32)
    if pipelined:
        for b0, chunk, nvalids in ds.blocks_prefetched(
                block_size, False, C, prefetch, start_chunk=start):
            csize, ccost = _vkmc_stats_chunk(csize, ccost, chunk, centers,
                                             jnp.asarray(nvalids),
                                             use_kernel=use_kernel)
            del chunk        # drop the slot before the next one is staged
            _ckpt_save(ckpt, "stats", b0 // C + 1, (csize, ccost))
            probe()
    else:
        for b, blk, nvalid in ds.blocks(block_size, with_labels=False):
            if b < start:
                continue
            ws, cc = _vkmc_stats_step(blk, centers, nvalid,
                                      use_kernel=use_kernel)
            csize = csize + ws
            ccost = ccost + cc
            _ckpt_save(ckpt, "stats", b + 1, (csize, ccost))
            probe()

    def score_block(b: int) -> jax.Array:
        blk, nvalid = ds.block(b, block_size, with_labels=False)
        return _vkmc_score_block(blk, centers, csize, ccost, nvalid,
                                 float(alpha), use_kernel=use_kernel)

    def score_blocks(ids) -> jax.Array:
        batch, nvalids = ds.gather_blocks(ids, block_size, with_labels=False)
        return _vkmc_score_batch(batch, centers, csize, ccost,
                                 jnp.asarray(nvalids), float(alpha),
                                 use_kernel=use_kernel)

    if masses is None:
        if pipelined:
            masses = _chunked_mass_table(
                ds, block_size, C, prefetch, probe, False,
                lambda chunk, nv: _vkmc_mass_chunk(chunk, centers, csize,
                                                   ccost, nv, float(alpha),
                                                   use_kernel=use_kernel),
                ckpt=ckpt)
        else:
            masses = _mass_table(ds, block_size, score_block, probe,
                                 ckpt=ckpt)
        passes = 3
    else:
        passes = 2               # centers + stats passes ran; masses supplied
    return StreamScorer(T=T, n=n, nb=nb, bs=bs, masses=masses,
                        dis_key=dis_key, score_block=score_block,
                        data_passes=passes, score_blocks=score_blocks,
                        chunk_blocks=C)


# --------------------------------------------------------------------------
# Streamed hierarchical DIS: masses + on-demand block recomputation
# --------------------------------------------------------------------------

def dis_plan_streamed(
    scorer: StreamScorer, m: int,
    probe: Optional[Callable[[], None]] = None,
) -> DisPlan:
    """Run the hierarchical sampler against a :class:`StreamScorer` —
    draw-identical to :func:`repro.core.dis.dis_plan_blocked` on the same
    scores, but only the *touched* blocks' scores are ever materialized.

    Round 1 samples m (party, block) cells from ``scorer.masses``; round 2
    recomputes scores for each touched block once and draws the within-block
    rows (per-cell candidate streams and the cell-ordered union match the
    in-memory plan exactly); round 3 gathers the sampled rows' combined
    scores from the same recomputed blocks, accumulated in party order so
    the weight arithmetic matches the flat plan's scan.

    This is the one-dispatch-per-touched-block reference;
    :func:`dis_plan_streamed_batched` produces the same draws with one
    dispatch per touched-block *group*.
    """
    probe = probe or _noop
    T, nb, bs, n = scorer.T, scorer.nb, scorer.bs, scorer.n
    cap = int(m)
    ncells = T * nb
    subs = _key_chain(scorer.dis_key, ncells + 1)
    masses = scorer.masses.astype(_float_dtype())
    G = masses.sum()

    # ---- round 1: cells ~ Multinomial(m, G_jb/G) ----------------------------
    draws = jax.random.categorical(
        subs[0], jnp.log(jnp.maximum(masses.reshape(-1), 1e-30)), shape=(cap,)
    )
    with trace.span("wait", of="draws"):
        draws = np.asarray(draws)
    a_cells = np.bincount(draws, minlength=ncells)

    # ---- rounds 2+3: recompute each touched block ONCE, draw its cells' rows
    # and gather their combined scores, then DISCARD the block's scores — at
    # no point is more than one block's score matrix live, so peak memory is
    # O(bs * T) regardless of how many blocks the m draws touch.
    occupied = np.flatnonzero(a_cells)
    touched = sorted({int(c) % nb for c in occupied})
    per_cell: Dict[int, tuple] = {}
    for b in touched:
        sc_b = scorer.score_block(b).astype(_float_dtype())    # (T, bs)
        # party-ordered combined row scores: gather commutes with the adds,
        # so g_b[cand] is bitwise the flat plan's per-party gather scan
        g_b = jnp.zeros((bs,), sc_b.dtype)
        for j in range(T):
            g_b = g_b + sc_b[j]
        row_ok = (b * bs + jnp.arange(bs)) < n
        for j in range(T):
            c = j * nb + b
            if a_cells[c] == 0:
                continue
            lg = jnp.where(row_ok, jnp.log(jnp.maximum(sc_b[j], 1e-30)),
                           -jnp.inf)
            # full-capacity candidate stream, first a_c taken — the
            # iid-prefix convention keeping draws identical to the
            # in-memory plan
            cand = jax.random.categorical(subs[1 + c], lg, shape=(cap,))
            cand = cand[: int(a_cells[c])]
            per_cell[c] = (b * bs + cand, g_b[cand])
        del sc_b, g_b
        probe()
    trace.add(draw_rows=len(per_cell) * cap)     # a whole stream per cell
    # server union in cell order — matches the in-memory plan's stable
    # taken-slots-first selection exactly
    cells = sorted(per_cell)
    S = (jnp.concatenate([per_cell[c][0] for c in cells]) if cells
         else jnp.zeros((0,), jnp.int32))                      # (m,)
    g_sum = (jnp.concatenate([per_cell[c][1] for c in cells]) if cells
             else jnp.zeros((0,), masses.dtype))
    w = G / (m * jnp.maximum(g_sum, 1e-30))

    a = jnp.asarray(a_cells.reshape(T, nb).sum(axis=1), jnp.int32)
    return DisPlan(S, w, a, masses.sum(axis=1))


@functools.partial(jax.jit, static_argnames=("cap", "take", "head"))
@jax.named_scope("dis_redraw")
def _group_candidates(sc_g, subs, cells, gidx, jidx, bids, n,
                      *, cap: int, take: int, head: bool):
    """Rounds 2+3 for every occupied cell of one touched-block group in ONE
    dispatch.

    ``sc_g`` is the group's (ng, T, bs) scores; ``cells``/``gidx``/``jidx``/
    ``bids`` index the nc occupied cells (global cell id, group-local block
    index, party, global block index).  Returns (rows (nc, take), combined
    scores (nc, take)) — the first ``take`` entries of each cell's
    full-capacity candidate stream and their party-ordered g gathers,
    bitwise the per-block path's (vmapped draws consume the same per-cell
    subkeys; gather commutes with the party-ordered adds).  ``head``
    selects the counter-sliced replay (:func:`_categorical_head`); off, the
    full (cap,)-stream is drawn and its head sliced.
    """
    ng, T, bs = sc_g.shape
    g = jnp.zeros((ng, bs), sc_g.dtype)
    for j in range(T):                     # party order — the flat plan's scan
        g = g + sc_g[:, j]
    sel = sc_g[gidx, jidx]                                     # (nc, bs)
    row_ok = (bids[:, None] * bs + jnp.arange(bs)[None, :]) < n
    lg = jnp.where(row_ok, jnp.log(jnp.maximum(sel, 1e-30)), -jnp.inf)
    keys = subs[1 + cells]                                     # (nc,) subkeys
    if head:
        if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
            keys = jax.random.key_data(keys)
        cand = jax.vmap(
            lambda k, l: _categorical_head(k, l, cap, take)
        )(keys, lg)                                            # (nc, take)
    else:
        # full-capacity fallback: draw the cells SEQUENTIALLY (lax.map) so
        # only one (cap, bs) gumbel tensor is transient at a time — the
        # per-block oracle's memory profile, same bits per cell
        cand = jax.lax.map(
            lambda kl: jax.random.categorical(kl[0], kl[1], shape=(cap,)),
            (keys, lg))[:, :take]                              # (nc, take)
    rows = bids[:, None] * bs + cand
    gath = jnp.take_along_axis(g[gidx], cand, axis=1)          # (nc, take)
    return rows, gath


def dis_plan_streamed_batched(
    scorer: StreamScorer, m: int,
    probe: Optional[Callable[[], None]] = None,
) -> DisPlan:
    """:func:`dis_plan_streamed` with the ONE-DISPATCH redraw: touched
    blocks are gathered in ``scorer.chunk_blocks``-sized groups, each group
    scored by a single vmapped dispatch (``scorer.score_blocks``) and all of
    its cells' candidate streams drawn by a single vmapped categorical
    (:func:`_group_candidates`) — 2 dispatches per group instead of
    1 + #cells per block.  Draws, weights, counts, and totals are
    bit-identical to :func:`dis_plan_streamed` for the same scorer and m
    (pinned by ``tests/test_streaming_pipelined.py``); peak score memory is
    one (C, T, bs) group instead of one block.
    """
    probe = probe or _noop
    T, nb, bs, n = scorer.T, scorer.nb, scorer.bs, scorer.n
    if scorer.score_blocks is None:
        return dis_plan_streamed(scorer, m, probe=probe)
    cap = int(m)
    ncells = T * nb
    subs = _key_chain(scorer.dis_key, ncells + 1)
    masses = scorer.masses.astype(_float_dtype())
    G = masses.sum()

    # ---- round 1: cells ~ Multinomial(m, G_jb/G) ----------------------------
    if cap > 0:
        draws = jax.random.categorical(
            subs[0], jnp.log(jnp.maximum(masses.reshape(-1), 1e-30)),
            shape=(cap,))
        with trace.span("wait", of="draws"):
            draws = np.asarray(draws)
        a_cells = np.bincount(draws, minlength=ncells)
    else:
        a_cells = np.zeros((ncells,), np.int64)

    # ---- rounds 2+3, grouped: score C touched blocks per dispatch, draw all
    # of the group's cells per dispatch, then host-slice the realised prefixes
    occupied = np.flatnonzero(a_cells)
    touched = sorted({int(c) % nb for c in occupied})
    C = max(1, int(scorer.chunk_blocks))
    per_cell: Dict[int, tuple] = {}
    for g0 in range(0, len(touched), C):
        group = touched[g0:g0 + C]
        # pad the trailing group to the full C blocks (repeats of the last
        # block — same scores, ignored below) so every group shares ONE
        # compiled score/draw shape instead of recompiling per remainder
        padded = group + [group[-1]] * (C - len(group))
        sc_g = scorer.score_blocks(padded).astype(_float_dtype())
        cells: List[int] = []
        gidx: List[int] = []
        jidx: List[int] = []
        bids: List[int] = []
        for gi, b in enumerate(group):
            for j in range(T):
                c = j * nb + b
                if a_cells[c]:
                    cells.append(c)
                    gidx.append(gi)
                    jidx.append(j)
                    bids.append(b)
        nc = len(cells)
        # every cell consumes only the first a_c entries of its cap-capacity
        # stream, so the group draws max(a_c) rows per cell — counter-sliced
        # when the replay is provably exact, full-capacity otherwise.  Both
        # the cell count and the head length are bucketed (multiple of 8 /
        # next power of two, via duplicate cells and extra rows that are
        # sliced away) to bound the number of compiled shape variants.
        take = int(max(a_cells[c] for c in cells))
        pad_nc = -(-nc // 8) * 8
        cells += [cells[0]] * (pad_nc - nc)
        gidx += [gidx[0]] * (pad_nc - nc)
        jidx += [jidx[0]] * (pad_nc - nc)
        bids += [bids[0]] * (pad_nc - nc)
        take_pow2 = 1
        while take_pow2 < take:
            take_pow2 *= 2
        if _head_draws_ok(subs, cap, bs, take_pow2):
            take_eff, head = take_pow2, True
        elif _head_draws_ok(subs, cap, bs, take):
            take_eff, head = take, True
        else:
            take_eff, head = min(take_pow2, cap), False
        trace.add(draw_rows=pad_nc * (take_eff if head else cap))
        rows, gath = _group_candidates(
            sc_g, subs, jnp.asarray(cells), jnp.asarray(gidx),
            jnp.asarray(jidx), jnp.asarray(bids), n,
            cap=cap, take=take_eff, head=head)
        with trace.span("wait", of="rows"):
            rows = np.asarray(rows)
            gath = np.asarray(gath)
        for i, c in enumerate(cells[:nc]):
            a_c = int(a_cells[c])
            per_cell[c] = (rows[i, :a_c], gath[i, :a_c])
        probe()

    # server union in cell order — identical to the per-block path
    cells_sorted = sorted(per_cell)
    S = (jnp.asarray(np.concatenate([per_cell[c][0] for c in cells_sorted]))
         if cells_sorted else jnp.zeros((0,), jnp.int32))
    g_sum = (jnp.asarray(np.concatenate([per_cell[c][1]
                                         for c in cells_sorted]))
             if cells_sorted else jnp.zeros((0,), masses.dtype))
    w = G / (m * jnp.maximum(g_sum, 1e-30))
    a = jnp.asarray(a_cells.reshape(T, nb).sum(axis=1), jnp.int32)
    return DisPlan(S, w, a, masses.sum(axis=1))


# --------------------------------------------------------------------------
# Data-parallel block masses over the mesh (rows over the `data` axis)
# --------------------------------------------------------------------------

def _stacked_rows(ds: VFLDataset, lo: int, hi: int, widths, s: int,
                  with_labels: bool = True) -> np.ndarray:
    """Host-side (T, hi-lo, s) stacked slice — the layout of
    ``VFLDataset.stacked(with_labels).blocks[:, lo:hi]``, built from the
    host representation of the parts so only this slice is allocated."""
    parts = []
    for j, p in enumerate(ds.parts):
        seg = np.asarray(p[lo:hi], dtype=np.float32)
        if with_labels and j == ds.T - 1:
            yseg = np.asarray(ds.y[lo:hi], dtype=np.float32)
            seg = np.concatenate([seg, yseg[:, None]], axis=1)
        parts.append(np.pad(seg, ((0, 0), (0, s - widths[j]))))
    return np.stack(parts)


def _sharded_stacked(mesh, ds: VFLDataset, widths, s: int, axis: str,
                     with_labels: bool):
    """The (T, n, s) stacked design sharded over ``axis``, each shard built
    straight from the host dataset (``jax.make_array_from_callback``) — the
    full array never lands on one device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = ds.n
    sharding = NamedSharding(mesh, P(None, axis, None))
    return jax.make_array_from_callback(
        (ds.T, n, s), sharding,
        lambda idx: _stacked_rows(ds, idx[1].start or 0,
                                  n if idx[1].stop is None else idx[1].stop,
                                  widths, s, with_labels),
    )


def _check_shard_grid(n: int, D: int, bs: int, axis: str):
    if n % D != 0 or (n // D) % bs != 0:
        raise ValueError(
            f"n={n} must shard evenly over {axis}={D} into bs={bs} blocks"
        )


def vrlr_block_masses_sharded(
    mesh, ds: VFLDataset, block_size: int,
    *, rcond: float = 1e-6, axis: str = "data",
):
    """VRLR block-mass table with rows sharded over ``axis``.

    Each device computes its shard's (T, s, s) partial Gram — combined with
    ONE psum (the mesh analogue of DIS round 1: O(T s^2) scalars, no row
    data moves) — then scores its own rows and emits its slice of the
    (T, nb) mass table; a second psum unions the disjoint slices.  This is
    the selector's psum idiom (:mod:`repro.core.selector`) applied to the
    streaming sampler's round-1 table: compute scales with the ``data``
    axis, communication stays the DIS bill.  Per-device memory is
    O(n/D * d).

    Requires n divisible by the axis size and the per-device shard
    divisible by ``bs`` (block grid aligned to shards).  Returns the same
    (T, nb) table as ``vrlr_stream_scorer(...).masses`` up to fp reduction
    order.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    nb, bs = ds.block_geometry(block_size)
    T, n = ds.T, ds.n
    if ds.y is None:
        raise ValueError("vrlr requires labels at party T")
    D = mesh.shape[axis]
    _check_shard_grid(n, D, bs, axis)
    nb_local = (n // D) // bs
    widths, s = ds.stacked_widths(with_labels=True)
    blocks = _sharded_stacked(mesh, ds, widths, s, axis, with_labels=True)

    def _inner(blk):                                           # (T, n/D, s)
        f = blk.astype(jnp.float32)
        Gm = jax.lax.psum(jnp.einsum("tns,tnu->tsu", f, f), axis)
        M = batched_gram_pinv(Gm, rcond)
        lev = jnp.einsum("tns,tsr,tnr->tn", f, M, f, precision=HIGHEST)
        sc = jnp.clip(lev, 0.0, 1.0) + 1.0 / n
        masses_loc = sc.reshape(T, nb_local, bs).sum(axis=2)
        i = jax.lax.axis_index(axis)
        full = jnp.zeros((T, nb), masses_loc.dtype)
        full = jax.lax.dynamic_update_slice(full, masses_loc, (0, i * nb_local))
        return jax.lax.psum(full, axis)

    fn = shard_map(_inner, mesh=mesh, in_specs=P(None, axis, None),
                   out_specs=P(), check_rep=False)
    return fn(blocks)


def vkmc_block_masses_sharded(
    mesh, ds: VFLDataset, block_size: int,
    *, key, k: int = 10, alpha: float = 2.0, local_iters: int = 15,
    center_sample: int = 16384, axis: str = "data",
    use_kernel: bool = False,
):
    """VKMC block-mass table with rows sharded over ``axis`` — the mirror of
    :func:`vrlr_block_masses_sharded` for Algorithm 3.

    The party-local centers come from the same bounded-subsample k-means
    (and the same key chain) as :func:`vkmc_stream_scorer`, computed once at
    the server side of the simulation.  Each device then assigns its row
    shard, and the GLOBAL per-party cluster size/cost table — VKMC's
    sufficient statistic, O(T k) scalars — is combined with ONE psum (the
    (T, 2k) stack of sizes and costs); scores follow locally and a second
    psum unions the disjoint (T, nb) mass-table slices.  ``use_kernel``
    MUST match the consuming scorer's backend: the centers come from an
    iterated Lloyd solve whose fp accumulation order differs between the
    Pallas kernels and the jnp refs, so a mismatch yields a mass table
    built from *different centers* than the per-row scores the sampler
    recomputes — not an fp-tolerance drift.  With it matched, the table
    equals ``vkmc_stream_scorer(key, ...).masses`` up to fp reduction
    order.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    nb, bs = ds.block_geometry(block_size)
    T, n = ds.T, ds.n
    D = mesh.shape[axis]
    _check_shard_grid(n, D, bs, axis)
    nb_local = (n // D) // bs
    widths, s = ds.stacked_widths(with_labels=False)
    centers, _ = vkmc_local_centers(
        key, ds, k=k, local_iters=local_iters, center_sample=center_sample,
        use_kernel=use_kernel)
    blocks = _sharded_stacked(mesh, ds, widths, s, axis, with_labels=False)
    assign_fn = kops.kmeans_assign if use_kernel else kref.kmeans_assign

    def _inner(blk):                                           # (T, n/D, s)
        f = blk.astype(jnp.float32)
        assign, d2 = assign_fn(f, centers)                     # (T, n/D)
        onehot = (assign[..., None] ==
                  jnp.arange(k)[None, None, :]).astype(jnp.float32)
        stats_loc = jnp.concatenate(
            [onehot.sum(axis=1), (onehot * d2[..., None]).sum(axis=1)],
            axis=1)                                            # (T, 2k)
        stats = jax.lax.psum(stats_loc, axis)                  # ONE stats psum
        csize, ccost = stats[:, :k], stats[:, k:]
        cost = jnp.maximum(ccost.sum(axis=1), 1e-30)[:, None]
        cs = jnp.maximum(csize, 1.0)
        cc_a = jnp.take_along_axis(ccost, assign, axis=1)
        cs_a = jnp.take_along_axis(cs, assign, axis=1)
        sc = (alpha * d2 / cost + alpha * cc_a / (cs_a * cost)
              + 2.0 * alpha / cs_a)
        masses_loc = sc.reshape(T, nb_local, bs).sum(axis=2)
        i = jax.lax.axis_index(axis)
        full = jnp.zeros((T, nb), masses_loc.dtype)
        full = jax.lax.dynamic_update_slice(full, masses_loc, (0, i * nb_local))
        return jax.lax.psum(full, axis)                        # ONE mass psum

    fn = shard_map(_inner, mesh=mesh, in_specs=P(None, axis, None),
                   out_specs=P(), check_rep=False)
    return fn(blocks)
