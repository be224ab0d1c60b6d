"""Algorithm 1 of the paper: the unified Distributed Importance Sampling
(DIS) scheme for coreset construction in the VFL model.

Three communication rounds (star topology, unit accounting per
:mod:`repro.core.comm`):

  round 1:  party j -> server: scalar G^(j) = sum_i g_i^(j)            (T units)
            server samples multiset A ~ Multinomial(m, G^(j)/G)
            server -> party j: a_j = #{j in A}                          (T units)
  round 2:  party j -> server: multiset S^(j) of a_j indices,
            i sampled w.p. g_i^(j)/G^(j)                               (m units)
            server -> all parties: S = union_j S^(j)                 (mT units)
  round 3:  party j -> server: {g_i^(j) : i in S}                     (mT units)
            server: w(i) = G / (|S| * sum_j g_i^(j))

The induced marginal of every sample is exactly g_i/G with
g_i = sum_j g_i^(j) (proof of Thm 3.1), i.e. DIS *simulates* the
Feldman-Langberg importance-sampling framework without any party ever
revealing a raw feature.  Tests verify both the marginal and the ledger
against ``theoretical_dis_cost``.

Layering (post api_redesign):

  * :func:`dis_plan` / :func:`dis_plan_full` — the PURE protocol core.  The
    party scores enter stacked as one ``(T, n)`` array, there are no Python
    party loops and no ledger mutation, so the function jit-compiles and
    vmaps (over seeds and over a budget grid via the ``m_cap`` masking
    convention).  Accounting is derived afterwards by
    :class:`repro.core.comm.CommSchedule` from ``(T, m)`` and the realised
    round-2 counts ``a_j`` the plan returns.
  * :func:`dis_plan_compiled` — :func:`dis_plan_full` for concrete inputs,
    as one jitted dispatch cached per shape.  The materialized engine and
    :func:`dis_sample` call it; engines that are already inside a trace
    (fused, batched, streamed, the service's merge tree) call
    :func:`dis_plan_full` itself.
  * :func:`server_plan` — the one-round server-side variant used when the
    combined scores already live on every shard (the mesh selector's psum
    path: :mod:`repro.core.selector`).
  * :func:`dis_sample` / :func:`uniform_sample` — back-compat wrappers with
    the seed API (list-of-scores in, ledger recorded in place); they produce
    bit-identical ``(S, w)`` for the same PRNG key.

:data:`GUMBEL_CHUNK_BYTES`, ``jax_enable_x64``, ``jax_threefry_partitionable``
and ``jax_default_prng_impl`` are read when a function here is traced, not
when it runs.  :func:`dis_plan_compiled` therefore fixes them per compiled
program: its cache is keyed by the x64 and partitionable flags (JAX's own
jit key) and by the chunk size (a static argument), but not by the default
PRNG implementation, so change that one before the first draw.
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import CommLedger, CommSchedule
from repro.utils import trace

try:  # the head-draw replay reaches for the threefry primitive directly
    from jax._src.prng import threefry2x32_p as _threefry2x32_p
except ImportError:  # pragma: no cover - jax moved the internal; fall back
    _threefry2x32_p = None


def _float_dtype() -> jnp.dtype:
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _key_chain(key: jax.Array, num: int) -> jax.Array:
    """``num`` subkeys from the sequential ``key, sub = split(key)`` chain.

    Matches the seed's per-party key consumption exactly (sub_0 for the
    round-1 counts, sub_1..sub_T for the party draws) while staying a single
    scan — no Python loop, traceable, vmap-able.
    """

    def body(k, _):
        nxt, sub = jax.random.split(k)
        return nxt, sub

    _, subs = jax.lax.scan(body, key, None, length=num)
    return subs


def _gumbel_from_bits(bits, rows: int, n: int):
    """(rows, n) float32 gumbel noise from raw uint32 uniform bits —
    ``jax.random._uniform``'s mantissa trick and ``gumbel``'s double-log,
    replayed verbatim."""
    float_bits = jax.lax.bitwise_or(
        jax.lax.shift_right_logical(bits, np.uint32(9)),
        np.array(1.0, np.float32).view(np.uint32))
    floats = (jax.lax.bitcast_convert_type(float_bits, jnp.float32)
              - np.float32(1.0))
    tiny = np.float32(np.finfo(np.float32).tiny)
    u = jax.lax.max(tiny, floats * (np.float32(1.0) - tiny) + tiny)
    return -jnp.log(-jnp.log(u)).reshape(rows, n)


def _partitionable_rows(key_data, lg, first, rows: int):
    """Rows ``[first, first + rows)`` of ``jax.random.categorical(key, lg,
    shape=(cap,))`` in the partitionable threefry layout (JAX's default),
    for any ``cap`` past them.  There the uniform bits at flat position p
    of the draw are ``threefry_2x32(key, (0, p))`` xor-folded, a function
    of p alone, so these rows come from counters ``[first*n, (first +
    rows)*n)`` and nothing else.  ``first`` is a uint32 row index."""
    n = lg.shape[-1]
    lo = jax.lax.iota(jnp.uint32, rows * n) + first * np.uint32(n)
    # key words batched as the counters are: under nested vmaps the chunk
    # index can be batched over an axis the key is not, and threefry's
    # rolled-loop lowering (the CPU's) needs key words of equal shapes
    k1, k2 = (lo * np.uint32(0) + k for k in key_data)
    b1, b2 = _threefry2x32_p.bind(k1, k2, jnp.zeros_like(lo), lo)
    return jnp.argmax(_gumbel_from_bits(b1 ^ b2, rows, n) + lg[None, :],
                      axis=-1)


def _categorical_head(key_data, lg, cap: int, take: int):
    """The first ``take`` entries of ``jax.random.categorical(key, lg,
    shape=(cap,))`` WITHOUT materializing the (cap, bs) gumbel tensor.

    Every DIS round-2 sampler in this codebase follows the full-capacity
    candidate-stream convention — draw ``cap`` iid candidates per cell, use
    the first a_c — because static shapes demand it inside jit/vmap.  In
    the partitionable layout the head is the plain prefix of the stream
    (:func:`_partitionable_rows` from row 0).  In the legacy layout the
    full draw's uniform bits come from ``threefry_2x32(key, iota(cap*bs))``,
    which pairs counter p with counter p + cap*bs/2 and keeps lane 1 for
    flat positions below the midpoint — so rows [0, take) (flat positions
    [0, take*bs), all below the midpoint when take <= cap//2) are
    reproducible bit for bit from exactly those counter pairs.  This is
    what makes the convention affordable at streaming scale: a cell that
    uses a_c of its cap candidates only ever *computes* max(a_c) rows
    (:func:`repro.core.streaming.dis_plan_streamed_batched`).
    """
    if getattr(jax.config, "jax_threefry_partitionable", False):
        return _partitionable_rows(key_data, lg, np.uint32(0), take)
    bs = lg.shape[-1]
    half = (cap * bs) // 2
    x1 = jax.lax.iota(jnp.uint32, take * bs)
    x2 = x1 + jnp.uint32(half)
    bits, _ = _threefry2x32_p.bind(key_data[0], key_data[1], x1, x2)
    return jnp.argmax(_gumbel_from_bits(bits, take, bs) + lg[None, :], axis=-1)


#: Per-party gumbel bytes above which round 2 draws its candidates in
#: row chunks (the paper's YearPredictionMSD at m=2048 would otherwise
#: hold 4.2 GB of noise per party).  Read at trace time;
#: :func:`dis_plan_compiled` keys its cache on it.
GUMBEL_CHUNK_BYTES = 1 << 28


def _is_threefry(key) -> bool:
    """True when ``key`` (typed, or raw under the default implementation)
    is a threefry key."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return "threefry" in str(jax.random.key_impl(key)).lower()
    return getattr(jax.config, "jax_default_prng_impl",
                   "threefry2x32") == "threefry2x32"


def _threefry_key_data(key):
    """The raw (..., 2) uint32 threefry key data behind ``key``, or None
    when the key is of another PRNG implementation."""
    if not _is_threefry(key):
        return None
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def _chunk_rows(key, n: int, cap: int) -> Optional[int]:
    """Rows per chunk of round 2's partial draw over ``n`` logits, or None
    where every party draws its whole ``cap`` stream: any layout, dtype or
    key implementation but partitionable float32 threefry, an empty
    capacity, or a stream past 2**32 counters."""
    if (cap <= 0 or _threefry2x32_p is None or not _is_threefry(key)
            or _float_dtype() != jnp.float32
            or not getattr(jax.config, "jax_threefry_partitionable", False)):
        return None
    rows = min(cap, max(1, GUMBEL_CHUNK_BYTES // (4 * n)))
    if -(-cap // rows) * rows * n >= 2 ** 32:
        return None
    return rows


def _round2_candidates(keys, logits, counts, cap: int):
    """Round 2's (T, cap) candidate streams: entry r < ``counts[j]`` of
    row j is entry r of ``jax.random.categorical(keys[j], logits[j],
    shape=(cap,))``, bit for bit.  The entries past ``counts[j]``, which
    round 2 never takes, are unspecified.

    Where :func:`_chunk_rows` allows, party j computes only its first
    ceil(a_j / rows) chunks of ``rows`` rows (:func:`_partitionable_rows`),
    a loop whose trip count is the realised a_j; the parties run one after
    another (``lax.map``), since a ``vmap`` would run every party to the
    largest a_j.  Under an outer ``vmap`` the loop runs to the batch's
    largest a_j.  Otherwise every party draws its whole stream.
    """
    n = logits.shape[-1]
    rows = _chunk_rows(keys, n, cap)
    if rows is None:
        return jax.vmap(
            lambda k, lg: jax.random.categorical(k, lg, shape=(cap,))
        )(keys, logits)
    nchunks = -(-cap // rows)

    def party(args):
        kd, lg, a_j = args

        def chunk(c, buf):
            first = (c * rows).astype(jnp.uint32)
            return jax.lax.dynamic_update_slice(
                buf, _partitionable_rows(kd, lg, first, rows), (c * rows,))

        return jax.lax.fori_loop(0, (a_j + rows - 1) // rows, chunk,
                                 jnp.zeros((nchunks * rows,), jnp.int32))

    cand = jax.lax.map(party, (_threefry_key_data(keys), logits, counts))
    return cand[:, :cap]


def round2_rows(key, counts, n: int, cap: int) -> int:
    """The candidate rows :func:`dis_plan_full`'s round 2 computes for the
    realised host ``counts`` a_j over ``n`` logits at capacity ``cap``:
    sum_j ceil(a_j / rows) * rows in the partial draw, T * cap where every
    party draws its whole stream.  Reads the flags as the draw's trace
    does."""
    rows = _chunk_rows(key, n, cap)
    if rows is None:
        return len(counts) * cap
    return sum(-(-int(a) // rows) for a in counts) * rows


def _head_draws_ok(subs, cap: int, bs: int, take: int) -> bool:
    """True when :func:`_categorical_head` provably replays the full draw:
    float32 sampling dtype, threefry keys, and in the partitionable layout
    any ``0 < take <= cap``; in the legacy layout an even counter stream
    and a head strictly inside the first threefry lane (take <= cap // 2).
    Anything else falls back to the full-capacity draw — still one
    dispatch per group, just cap rows instead of take."""
    if _threefry2x32_p is None or _float_dtype() != jnp.float32:
        return False
    if getattr(jax.config, "jax_threefry_partitionable", False):
        if not 0 < take <= cap or take * bs >= 2 ** 32:
            return False
    elif cap <= 0 or take > cap // 2 or (cap * bs) % 2:
        return False
    return _is_threefry(subs)


class DisPlan(NamedTuple):
    """The result of one DIS execution, accounting-free.

    With ``m_cap`` masking (``m`` traced < ``m_cap``), ``indices``/``weights``
    hold the m real samples as a prefix; the padded tail has weight 0 and
    index 0.
    """

    indices: jax.Array    # (m_cap,) int   — the sampled multiset S
    weights: jax.Array    # (m_cap,) float — w(i) = G / (m * g_i)
    counts: jax.Array     # (T,) int       — realised round-1 a_j (sums to m)
    totals: jax.Array     # (T,) float     — per-party score mass G^(j)


def dis_plan_full(
    key: jax.Array,
    scores: jax.Array,
    m: Union[int, jax.Array],
    m_cap: Optional[int] = None,
    totals: Optional[jax.Array] = None,
) -> DisPlan:
    """Run Algorithm 1 purely: scores ``(T, n)`` in, :class:`DisPlan` out.

    Args:
      key: PRNG key.
      scores: stacked party-local scores g^(j), shape (T, n), entries >= 0
        with a positive total (NOT checked here — the core stays trace-safe;
        wrappers validate host-side).
      m: number of samples (with replacement).  May be a traced int32 scalar
        when ``m_cap`` is given.
      m_cap: static draw capacity for the masked/batched path.  When None
        (or equal to a static ``m``) the plan is bit-identical to the seed's
        ``dis_sample`` for the same key.
      totals: optional precomputed per-party mass ``sum_i g_i^(j)`` (T,).
        The batched builder passes the eagerly-reduced totals of hoisted
        scores here: XLA lowers the (T, n) -> (T,) reduction with a
        different accumulation order inside a vmapped program than in the
        standalone eager kernel, and since every weight carries G = sum_j
        G^(j), reusing the eager reduction keeps batched cells bit-identical
        to sequential builds.

    Returns:
      DisPlan — no ledger is touched; derive the bill afterwards with
      ``CommSchedule.dis(T, m, counts=plan.counts)``.
    """
    T, _ = scores.shape
    scores = scores.astype(_float_dtype())
    static_m = m_cap is None or (isinstance(m, int) and int(m) == int(m_cap))
    cap = int(m) if m_cap is None else int(m_cap)
    valid = jnp.arange(cap) < m                                # all True if static

    # ---- round 1: a ~ Multinomial(m, G_j/G), realised as m iid draws --------
    with jax.named_scope("dis_round1"):
        subs = _key_chain(key, T + 1)
        G_j = (jnp.sum(scores, axis=1) if totals is None
               else totals.astype(_float_dtype()))             # (T,)
        G = G_j.sum()
        draws = jax.random.categorical(
            subs[0], jnp.log(jnp.maximum(G_j, 1e-30)), shape=(cap,)
        )
        a = jnp.zeros((T,), jnp.int32).at[draws].add(valid.astype(jnp.int32))

    # ---- round 2: party-local index sampling, then server union -------------
    # Party j draws a_j iid indices ~ g_i^(j)/G^(j).  To keep everything
    # static-shape each party has a `cap`-candidate stream and the first a_j
    # of each are selected via a mask when concatenating — statistically
    # identical because draws are iid; only those first a_j are computed
    # (rounded up to whole chunks) where the PRNG layout allows.
    with jax.named_scope("dis_round2"):
        logits = jnp.log(jnp.maximum(scores, 1e-30))           # (T, n)
        cand = _round2_candidates(subs[1:], logits, a, cap)    # (T, cap)
        take = jnp.arange(cap)[None, :] < a[:, None]           # (T, cap) bool
        # stable selection of exactly m entries (sum(a) = m by construction)
        order = jnp.argsort(~take.reshape(-1), stable=True)    # taken slots first
        S = cand.reshape(-1)[order][:cap]                      # (cap,)

    # ---- round 3: per-sample local scores up, weights at server -------------
    # Sequential per-party accumulation (scan) keeps the float addition order
    # identical to the seed's Python loop.
    def add_party(acc, g_row):
        return acc + g_row[S], None

    with jax.named_scope("dis_round3"):
        g_sum_S, _ = jax.lax.scan(add_party, jnp.zeros((cap,), scores.dtype),
                                  scores)
        w = G / (m * jnp.maximum(g_sum_S, 1e-30))
    if not static_m:
        S = jnp.where(valid, S, 0)
        w = jnp.where(valid, w, 0.0)
    return DisPlan(S, w, a, G_j)


def _dis_core(core, key, scores, totals, m, chunk_bytes):
    """The body :func:`dis_plan_compiled` jits.  ``chunk_bytes`` only keys
    the cache: :func:`_round2_candidates` reads :data:`GUMBEL_CHUNK_BYTES`
    while this traces.  ``dis_traces`` counts the traces on the open span,
    so a warm build's ``repro.dis`` carries none."""
    del chunk_bytes
    trace.add(dis_traces=1)
    return core(key, scores, m, totals=totals)


_dis_plan_jit = jax.jit(_dis_core, static_argnames=("core", "m", "chunk_bytes"))


def dis_plan_compiled(key: jax.Array, scores: jax.Array, m: int,
                      core: Optional[Callable[..., DisPlan]] = None) -> DisPlan:
    """:func:`dis_plan_full` at a static ``m`` as one jitted dispatch,
    compiled once per shape, dtype and ``m``.

    The per-party totals are reduced eagerly and passed in, as the batched
    engine does, so G comes from the same reduction kernel as an eager
    :func:`dis_plan_full` and the plan is bit-identical to it.  ``core`` is
    the function traced, :func:`dis_plan_full` as this module binds it at
    call time by default: a caller that binds its own name for the DIS core
    passes what that name holds, and a substitute gets a program of its own.
    """
    totals = jnp.sum(scores.astype(_float_dtype()), axis=1)
    return _dis_plan_jit(core or dis_plan_full, key, scores, totals, m=int(m),
                         chunk_bytes=GUMBEL_CHUNK_BYTES)


def split_uploads(indices, counts):
    """Recover the round-2 per-party uploads from a realized plan.

    The realized sample ``S`` is party-major (round 2 concatenates party
    j's a_j draws in party order — in :func:`dis_plan_full` the stable
    argsort keeps taken slots in row-major (party, slot) order), so party
    j's upload is the j-th contiguous slice of length ``counts[j]``.  These
    are exactly the payloads the integrity envelopes seal on the
    ``dis/round2/S_up`` message.  Host-side numpy; returns a list of
    (a_j,) arrays whose concatenation is ``indices``."""
    idx = np.asarray(indices)
    c = np.asarray(counts, dtype=np.int64)
    if int(c.sum()) != idx.shape[0]:
        raise ValueError(
            f"counts sum to {int(c.sum())} but the plan realized "
            f"{idx.shape[0]} indices; uploads cannot be attributed")
    return np.split(idx, np.cumsum(c)[:-1])


def blocked_geometry(n: int, block_size: int) -> Tuple[int, int]:
    """(num_blocks nb, rows-per-block bs) for a ``block_size`` row chunking —
    delegates to the canonical :func:`repro.core.vfl.block_geometry`, so the
    sampler's cell grid and ``VFLDataset.block``'s chunking can never drift.
    ``block_size >= n`` degenerates to ONE unpadded block — the regime where
    :func:`dis_plan_blocked` is bit-identical to :func:`dis_plan_full`.
    """
    from repro.core.vfl import block_geometry

    return block_geometry(n, block_size)


def dis_plan_blocked(
    key: jax.Array,
    scores: jax.Array,
    m: Union[int, jax.Array],
    block_size: int,
    m_cap: Optional[int] = None,
) -> DisPlan:
    """Hierarchical (two-level) DIS: Algorithm 1 applied recursively to
    (party, row-block) cells.

    Round 1 samples *cells* (j, b) from the block masses
    G^(j,b) = sum_{i in block b} g_i^(j); round 2 samples a row within the
    chosen cell ~ g_i^(j)/G^(j,b).  The induced marginal telescopes,

        P(i via j) = (G^(j,b(i))/G) * (g_i^(j)/G^(j,b(i))) = g_i^(j)/G,

    i.e. EXACTLY the flat plan's marginal (:func:`dis_blocked_marginals`
    verifies this cancellation numerically) — the blocking is invisible to
    Theorem 3.1.  What it buys: the sampler only ever needs block masses
    (T, nb) plus the scores of *touched* blocks, so the streaming builder
    (:mod:`repro.core.streaming`) never materializes the (T, n) score
    matrix.  This in-memory variant takes the full scores (it is the
    semantic oracle the streamed path is tested against) and consumes a
    ``T*nb + 1``-subkey chain; with ``block_size >= n`` that chain, the cell
    masses, and every draw coincide with :func:`dis_plan_full` bit for bit.
    """
    T, n = scores.shape
    scores = scores.astype(_float_dtype())
    nb, bs = blocked_geometry(n, block_size)
    static_m = m_cap is None or (isinstance(m, int) and int(m) == int(m_cap))
    cap = int(m) if m_cap is None else int(m_cap)
    valid = jnp.arange(cap) < m

    npad = nb * bs
    sp = jnp.pad(scores, ((0, 0), (0, npad - n))).reshape(T, nb, bs)
    row_ok = (jnp.arange(npad) < n).reshape(nb, bs)            # (nb, bs)

    ncells = T * nb
    subs = _key_chain(key, ncells + 1)
    masses = jnp.sum(sp, axis=2)                               # (T, nb)
    G = masses.sum()

    # ---- round 1: cells ~ Multinomial(m, G_jb/G) ----------------------------
    draws = jax.random.categorical(
        subs[0], jnp.log(jnp.maximum(masses.reshape(-1), 1e-30)), shape=(cap,)
    )
    a_cells = jnp.zeros((ncells,), jnp.int32).at[draws].add(valid.astype(jnp.int32))

    # ---- round 2: within-cell row sampling, then server union ---------------
    # Padded rows get -inf logits (probability exactly 0); valid rows keep the
    # flat plan's 1e-30 floor.  Cells are ordered party-major (j*nb + b), so
    # nb == 1 reproduces dis_plan_full's per-party candidate streams.
    cell_logits = jnp.where(
        row_ok[None, :, :], jnp.log(jnp.maximum(sp, 1e-30)), -jnp.inf
    ).reshape(ncells, bs)
    cand_local = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg, shape=(cap,))
    )(subs[1:], cell_logits)                                   # (ncells, cap)
    offsets = jnp.tile(jnp.arange(nb) * bs, T)                 # cell -> row base
    cand = cand_local + offsets[:, None]
    take = jnp.arange(cap)[None, :] < a_cells[:, None]
    order = jnp.argsort(~take.reshape(-1), stable=True)        # taken slots first
    S = cand.reshape(-1)[order][:cap]

    # ---- round 3: per-sample combined scores, weights at server -------------
    def add_party(acc, g_row):
        return acc + g_row[S], None

    g_sum_S, _ = jax.lax.scan(add_party, jnp.zeros((cap,), scores.dtype), scores)
    w = G / (m * jnp.maximum(g_sum_S, 1e-30))
    if not static_m:
        S = jnp.where(valid, S, 0)
        w = jnp.where(valid, w, 0.0)
    a = a_cells.reshape(T, nb).sum(axis=1)                     # per-party a_j
    return DisPlan(S, w, a, masses.sum(axis=1))


def dis_blocked_marginals(
    local_scores: List[jax.Array], block_size: int
) -> np.ndarray:
    """The exact per-index marginal induced by :func:`dis_plan_blocked`,
    computed WITHOUT algebraic simplification (float64): sum over cells of
    P(cell) * P(i | cell).  Tests assert this telescopes back to the flat
    :func:`dis_marginals` — the hierarchical sampler's correctness claim."""
    g = np.stack([np.asarray(x, np.float64) for x in local_scores])  # (T, n)
    T, n = g.shape
    nb, bs = blocked_geometry(n, block_size)
    gp = np.pad(g, ((0, 0), (0, nb * bs - n))).reshape(T, nb, bs)
    masses = gp.sum(axis=2)                                    # (T, nb)
    G = masses.sum()
    within = gp / np.maximum(masses[:, :, None], np.finfo(np.float64).tiny)
    per_cell = (masses[:, :, None] / G) * within               # (T, nb, bs)
    return per_cell.reshape(T, -1)[:, :n].sum(axis=0)


def dis_plan(
    key: jax.Array,
    scores: jax.Array,
    m: Union[int, jax.Array],
    m_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pure DIS core: ``(key, scores (T, n), m) -> (S, w)``.

    jit with ``static_argnums=2`` (or pass a traced ``m`` plus static
    ``m_cap``), vmap over keys and/or budgets freely.
    """
    plan = dis_plan_full(key, scores, m, m_cap=m_cap)
    return plan.indices, plan.weights


def server_plan(
    key: jax.Array, g: jax.Array, m: int
) -> Tuple[jax.Array, jax.Array]:
    """One-round server-side DIS: m categorical draws ~ g/G with importance
    weights G/(m*g_S).

    This is the degenerate T=1 view of Algorithm 1, used when the combined
    scores g already live at the sampler — the mesh selector after its psum
    (rounds 1+3 collapse into the all-reduce, round 2's broadcast into the
    shared key).
    """
    G = jnp.sum(g)
    S = jax.random.categorical(key, jnp.log(jnp.maximum(g, 1e-30)), shape=(m,))
    w = G / (m * jnp.maximum(g[S], 1e-30))
    return S, w


def uniform_plan(
    key: jax.Array,
    n: int,
    m: Union[int, jax.Array],
    m_cap: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pure uniform baseline: m server-side uniform indices, weight n/m."""
    static_m = m_cap is None or (isinstance(m, int) and int(m) == int(m_cap))
    cap = int(m) if m_cap is None else int(m_cap)
    S = jax.random.randint(key, (cap,), 0, n)
    if static_m:
        return S, jnp.full((cap,), n / m)
    valid = jnp.arange(cap) < m
    return jnp.where(valid, S, 0), jnp.where(valid, n / m, 0.0)


# --------------------------------------------------------------------------
# Back-compat wrappers (seed API): list-of-scores in, ledger recorded here
# --------------------------------------------------------------------------

def dis_sample(
    key: jax.Array,
    local_scores: List[jax.Array],
    m: int,
    ledger: Optional[CommLedger] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run Algorithm 1 (DIS) — seed-compatible wrapper over
    :func:`dis_plan_compiled`, the materialized engine's compiled draw.

    Args:
      key: PRNG key.
      local_scores: list over parties; party j's vector g^(j) of shape (n,),
        entries >= 0 with a positive total.
      m: number of samples (with replacement — a multiset, as in the paper).
      ledger: optional CommLedger to account the protocol's traffic.

    Returns:
      (indices, weights): both shape (m,).  ``weights[i] = G/(m * g_{S_i})``.
    """
    T = len(local_scores)
    scores = jnp.stack([jnp.asarray(g) for g in local_scores])
    plan = dis_plan_compiled(key, scores, int(m))
    if not bool(plan.totals.sum() > 0):
        raise ValueError("DIS requires a positive total score")
    CommSchedule.dis(T, int(m), counts=np.asarray(plan.counts)).record(ledger)
    return plan.indices, plan.weights


def dis_marginals(local_scores: List[jax.Array]) -> jax.Array:
    """The exact per-index sampling marginal g_i/G (used by tests)."""
    g = jnp.sum(jnp.stack(local_scores), axis=0)
    return g / g.sum()


def uniform_sample(
    key: jax.Array, n: int, m: int, T: int, ledger: Optional[CommLedger] = None
) -> Tuple[jax.Array, jax.Array]:
    """Uniform-sampling baseline (the paper's U-*): the server draws m indices
    itself and broadcasts them; weight n/m each.  Cost: mT (broadcast only —
    no scores ever travel, which is why U-* is slightly cheaper)."""
    S, w = uniform_plan(key, n, int(m))
    CommSchedule.uniform(T, int(m)).record(ledger)
    return S, w
