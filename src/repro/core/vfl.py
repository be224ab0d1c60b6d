"""Vertical-federated dataset model: one dataset, feature columns split
across T parties; labels (if any) live at party T-1 (0-indexed; paper's
"party T").

This is the faithful, protocol-level simulation substrate used by the
paper-reproduction benchmarks.  The mesh/shard_map execution of the same
geometry (model axis = party axis) lives in :mod:`repro.core.selector`.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import trace


class StackedParts(NamedTuple):
    """Padded party-major view of a :class:`VFLDataset`.

    ``blocks`` is (T, n, s) with party j's block left-aligned and
    zero-padded to the common width s = max_j d_j (+1 when labels are
    stacked in); ``mask`` is (T, s) bool marking the valid columns.  Zero
    padding is score-transparent: distances, Grams, row norms and
    quadratic forms over the padded axis all equal their unpadded values,
    so one vmap over axis 0 scores every party in a single dispatch.
    """

    blocks: jnp.ndarray            # (T, n, s) float
    mask: jnp.ndarray              # (T, s) bool
    dims: Tuple[int, ...]          # valid width per party (incl. label col)

    @property
    def T(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def n(self) -> int:
        return int(self.blocks.shape[1])


def block_geometry(n: int, block_size: int) -> Tuple[int, int]:
    """(num_blocks nb, rows-per-block bs) for a ``block_size`` row chunking
    of n rows — the canonical geometry shared by ``VFLDataset.block`` and
    the hierarchical DIS sampler (``repro.core.dis.blocked_geometry``
    delegates here, so the two can never drift apart).

    bs clamps to n, so ``block_size >= n`` is exactly one unpadded block —
    the flat-plan degeneration the bit-identity tests rely on; the last
    block is zero-padded up to bs.
    """
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    bs = min(int(block_size), int(n))
    return -(-int(n) // bs), bs


def split_columns(d: int, T: int, sizes: Optional[Sequence[int]] = None) -> List[slice]:
    """Column slices for T parties. ``sizes`` overrides the near-even split."""
    if sizes is None:
        base, rem = divmod(d, T)
        sizes = [base + (1 if j < rem else 0) for j in range(T)]
    if len(sizes) != T or sum(sizes) != d:
        raise ValueError(f"bad sizes {sizes} for d={d}, T={T}")
    out, start = [], 0
    for s in sizes:
        out.append(slice(start, start + s))
        start += s
    return out


@dataclasses.dataclass
class VFLDataset:
    """X (n, d) vertically partitioned; y optional, held by the last party.

    ``parts`` may be jnp arrays (device-resident) or plain numpy arrays.
    Numpy-backed datasets are the host-resident substrate of the streaming
    path (:mod:`repro.core.streaming`): :meth:`block` slices on the host and
    only the requested (T, bs, s) chunk ever becomes a device array, so
    device memory stays O(block_size * d) at any n.
    """

    parts: List[jnp.ndarray]            # party j's local block (n, d_j)
    y: Optional[jnp.ndarray] = None     # (n,), stored at party T-1
    validate: bool = True               # NaN/Inf screen at construction

    def __post_init__(self) -> None:
        if not self.parts:
            raise ValueError(
                "VFLDataset needs at least one party (parts is empty)"
            )
        n = self.parts[0].shape[0]
        if n == 0:
            raise ValueError(
                "VFLDataset needs at least one row (n=0); every protocol "
                "downstream scores and samples rows"
            )
        for j, p in enumerate(self.parts):
            if p.ndim != 2 or p.shape[0] != n:
                raise ValueError(f"party {j}: bad shape {p.shape}")
        if self.y is not None and self.y.shape[0] != n:
            raise ValueError("label length mismatch")
        if self.validate:
            self._validate_values()

    def _validate_values(self) -> None:
        """NaN/Inf screen: a single non-finite cell poisons every Gram /
        distance it touches downstream, so fail loudly at ingest and name
        the offender.  Skipped for traced arrays (a dataset built inside a
        jitted function has no values to read) and via ``validate=False``
        when non-finite values are intentional (e.g. corruption-injection
        tests)."""
        named = [(f"party {j}", p) for j, p in enumerate(self.parts)]
        if self.y is not None:
            named.append((f"labels (party {self.T - 1})", self.y))
        for name, a in named:
            if isinstance(a, jax.core.Tracer):
                continue
            vals = np.asarray(a)
            if not np.issubdtype(vals.dtype, np.inexact):
                continue
            finite = np.isfinite(vals)
            if finite.all():
                continue
            loc = np.argwhere(~finite)[0]
            where = (f"row {loc[0]}, column {loc[1]}" if loc.size == 2
                     else f"row {loc[0]}")
            bad = vals[tuple(loc)]
            kind = "NaN" if np.isnan(bad) else "Inf"
            raise ValueError(
                f"non-finite value ({kind}) in {name} at {where}; "
                f"clean the feed or construct with validate=False to "
                f"bypass the ingest screen"
            )

    @property
    def n(self) -> int:
        return int(self.parts[0].shape[0])

    @property
    def T(self) -> int:
        return len(self.parts)

    @property
    def d(self) -> int:
        return int(sum(p.shape[1] for p in self.parts))

    @property
    def dims(self) -> Tuple[int, ...]:
        return tuple(int(p.shape[1]) for p in self.parts)

    def full(self) -> jnp.ndarray:
        """Server-side concatenation — ONLY for evaluation/tests, never used
        inside communication-accounted protocols."""
        return jnp.concatenate(self.parts, axis=1)

    def stacked_widths(self, with_labels: bool = False) -> Tuple[Tuple[int, ...], int]:
        """(per-party valid widths, common padded width s) of the stacked
        view — the geometry shared by :meth:`stacked` and :meth:`block`."""
        if with_labels and self.y is None:
            raise ValueError("with_labels requires labels at party T")
        widths = list(self.dims)
        if with_labels:
            widths[-1] += 1
        return tuple(widths), max(widths)

    def stacked(self, with_labels: bool = False) -> StackedParts:
        """Padded (T, n, s) stacking of the party blocks for single-dispatch
        scoring (one vmap over the party axis instead of a Python loop).

        With ``with_labels=True`` party T's labels are appended as one extra
        column of its block (the [X^(T), y] basis of Algorithm 2); the
        common width s grows accordingly.  Each party only ever touches its
        own slice, so the view is a layout change, not a protocol change.
        """
        widths, s = self.stacked_widths(with_labels)
        blocks, mask = [], []
        for j, p in enumerate(self.parts):
            b = jnp.asarray(p)
            if with_labels and j == self.T - 1:
                b = jnp.concatenate([b, jnp.asarray(self.y)[:, None].astype(b.dtype)],
                                    axis=1)
            pad = s - widths[j]
            if pad:
                b = jnp.pad(b, ((0, 0), (0, pad)))
            blocks.append(b)
            mask.append(np.arange(s) < widths[j])
        return StackedParts(jnp.stack(blocks), jnp.asarray(np.stack(mask)),
                            tuple(widths))

    # -- chunked row-block view (the streaming substrate) ---------------------

    def block_geometry(self, block_size: int) -> Tuple[int, int]:
        """:func:`block_geometry` of this dataset's n rows."""
        return block_geometry(self.n, block_size)

    def block(
        self, b: int, block_size: int, with_labels: bool = False
    ) -> Tuple[jnp.ndarray, int]:
        """Padded (T, bs, s) stacked view of row block ``b`` + its valid-row
        count.

        Rows [b*bs, b*bs + bs) of every party, laid out exactly as the
        corresponding slice of :meth:`stacked` (labels appended to party T,
        columns zero-padded to the common width); rows past n are zero.
        Slicing happens on the host representation of ``parts`` (numpy or
        jnp), so with numpy-backed parts only this one block is ever
        transferred to the device.
        """
        widths, s = self.stacked_widths(with_labels)
        nb, bs = self.block_geometry(block_size)
        if not 0 <= b < nb:
            raise IndexError(f"block {b} out of range [0, {nb})")
        lo = b * bs
        hi = min(lo + bs, self.n)
        nvalid = hi - lo
        blocks = []
        for j, p in enumerate(self.parts):
            seg = jnp.asarray(p[lo:hi])
            if with_labels and j == self.T - 1:
                seg = jnp.concatenate(
                    [seg, jnp.asarray(self.y[lo:hi])[:, None].astype(seg.dtype)],
                    axis=1)
            seg = jnp.pad(seg, ((0, bs - nvalid), (0, s - widths[j])))
            blocks.append(seg)
        return jnp.stack(blocks), nvalid

    def blocks(self, block_size: int, with_labels: bool = False):
        """Iterate ``(b, block (T, bs, s), nvalid)`` over the row chunking —
        the one-block-resident traversal the streaming scorers consume."""
        nb, _ = self.block_geometry(block_size)
        for b in range(nb):
            blk, nvalid = self.block(b, block_size, with_labels)
            yield b, blk, nvalid

    # -- pipelined superchunk view (the prefetched streaming substrate) -------

    def _staging_dtype(self, with_labels: bool) -> np.dtype:
        """Canonical dtype of the stacked device blocks (what :meth:`block`
        yields after jnp's dtype canonicalization) — the staging buffers must
        match it so the superchunk path sees the exact same values."""
        arrs = [p[0:0] for p in self.parts]
        if with_labels:
            arrs.append(self.y[0:0])
        dt = np.result_type(*[np.asarray(a).dtype for a in arrs])
        return np.dtype(jax.dtypes.canonicalize_dtype(dt))

    def _fill_superchunk(
        self, out: np.ndarray, b0: int, block_size: int, with_labels: bool,
        widths: Tuple[int, ...], bs: int, nb: int,
    ) -> np.ndarray:
        """Host-side assembly of blocks [b0, b0 + C) into the (C, T, bs, s)
        numpy staging buffer ``out`` (zeroed first; blocks past nb stay
        all-zero with 0 valid rows).  One contiguous host slice per party per
        superchunk — no device dispatches happen here at all; the single
        ``device_put`` of ``out`` is the only transfer.  Returns the (C,)
        per-block valid-row counts."""
        C = out.shape[0]
        out[...] = 0.0
        count = max(0, min(C, nb - b0))
        lo = b0 * bs
        hi = min(lo + count * bs, self.n)
        nvalids = np.clip(self.n - (b0 + np.arange(C)) * bs, 0, bs)
        nvalids[count:] = 0
        for j, p in enumerate(self.parts):
            seg = np.asarray(p[lo:hi])
            if with_labels and j == self.T - 1:
                yseg = np.asarray(self.y[lo:hi])
                seg = np.concatenate([seg, yseg[:, None].astype(seg.dtype)],
                                     axis=1)
            w = widths[j]
            for i in range(count):
                r0 = i * bs
                nv = int(nvalids[i])
                out[i, j, :nv, :w] = seg[r0:r0 + nv]
        return nvalids

    def blocks_prefetched(
        self, block_size: int, with_labels: bool = False,
        chunk_blocks: int = 1, prefetch: bool = True,
        start_chunk: int = 0,
    ) -> Iterator[Tuple[int, jnp.ndarray, np.ndarray]]:
        """Iterate ``(b0, chunk (C, T, bs, s) device array, nvalids (C,))``
        over superchunks of ``chunk_blocks`` row blocks — the double-buffered
        staging layer of the pipelined streaming engine.

        With ``prefetch=True`` the async ``jax.device_put`` of superchunk
        c+1 is issued BEFORE superchunk c is yielded, so the staging of the
        next chunk overlaps with whatever the consumer computes on the
        current one.  Each superchunk gets a FRESH staging buffer that the
        device array aliases (CPU ``device_put`` is zero-copy: the staging
        buffer IS the device buffer, so assembly writes double as the
        transfer and nothing is ever copied twice; on an accelerator it
        becomes a real async H2D copy of an immutable source — safe either
        way because a staged buffer is never written again).  The consumed
        chunk's reference is dropped as soon as the next one is yielded, so
        at most two slots are live regardless of n.  Block contents and
        ordering are identical to :meth:`blocks`; only the transfer
        granularity and overlap change.

        ``start_chunk`` skips the first superchunks entirely (no staging, no
        transfer) — the checkpointed-resume entry point: a restored scan
        continues at the first unprocessed superchunk and sees exactly the
        buffers a full traversal would have yielded from there.
        """
        widths, s = self.stacked_widths(with_labels)
        nb, bs = self.block_geometry(block_size)
        if chunk_blocks < 1:
            raise ValueError(f"chunk_blocks must be >= 1, got {chunk_blocks}")
        nchunks = -(-nb // chunk_blocks)
        if not 0 <= start_chunk <= nchunks:
            raise ValueError(
                f"start_chunk {start_chunk} out of range [0, {nchunks}]"
            )
        dt = self._staging_dtype(with_labels)

        def stage(c: int):
            with trace.span("stage"):
                buf = np.empty((chunk_blocks, self.T, bs, s), dt)
                nvalids = self._fill_superchunk(buf, c * chunk_blocks,
                                                block_size, with_labels,
                                                widths, bs, nb)
                trace.add(bytes=buf.nbytes)
                return jax.device_put(buf), nvalids      # async: returns now

        if not prefetch:
            for c in range(start_chunk, nchunks):
                dev, nvalids = stage(c)
                yield c * chunk_blocks, dev, nvalids
                del dev                       # drop the slot before restaging
            return
        if start_chunk >= nchunks:
            return
        nxt = stage(start_chunk)
        for c in range(start_chunk, nchunks):
            cur = nxt
            # issue the NEXT transfer before handing the current chunk to the
            # consumer — the copy proceeds while the consumer's dispatch runs
            nxt = stage(c + 1) if c + 1 < nchunks else None
            yield c * chunk_blocks, cur[0], cur[1]
            del cur

    def gather_blocks(
        self, block_ids, block_size: int, with_labels: bool = False,
    ) -> Tuple[jnp.ndarray, np.ndarray]:
        """One (len(ids), T, bs, s) device batch of arbitrary row blocks plus
        their valid-row counts — the gather feeding the one-dispatch
        touched-block redraw (scores for ALL touched cells from a single
        vmapped dispatch instead of one per block)."""
        widths, s = self.stacked_widths(with_labels)
        nb, bs = self.block_geometry(block_size)
        ids = [int(b) for b in block_ids]
        for b in ids:
            if not 0 <= b < nb:
                raise IndexError(f"block {b} out of range [0, {nb})")
        with trace.span("stage"):
            out = np.empty((len(ids), self.T, bs, s),
                           self._staging_dtype(with_labels))
            nvalids = np.zeros((len(ids),), np.int64)
            for i, b in enumerate(ids):
                nvalids[i:i + 1] = self._fill_superchunk(
                    out[i:i + 1], b, block_size, with_labels, widths, bs, nb)
            trace.add(bytes=out.nbytes)
            return jax.device_put(out), nvalids

    def rows(self, idx: jnp.ndarray) -> "VFLDataset":
        y = None if self.y is None else self.y[idx]
        return VFLDataset([p[idx] for p in self.parts], y)

    def select_parties(self, parties: Sequence[int]) -> "VFLDataset":
        """The SAME rows restricted to a party subset — the surviving
        federation of a degraded build (:mod:`repro.core.faults`).  Labels
        survive only if the label holder (party T-1) is among ``parties``;
        order follows ``parties`` (keep it sorted to preserve the paper's
        party numbering)."""
        ids = [int(j) for j in parties]
        if not ids:
            raise ValueError("select_parties needs at least one party")
        bad = [j for j in ids if not 0 <= j < self.T]
        if bad:
            raise ValueError(f"parties {bad} out of range [0, {self.T})")
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate parties in {ids}")
        y = self.y if (self.T - 1) in ids else None
        return VFLDataset([self.parts[j] for j in ids], y)

    @staticmethod
    def from_dense(X, y=None, T: int = 3, sizes: Optional[Sequence[int]] = None) -> "VFLDataset":
        X = jnp.asarray(X)
        slices = split_columns(X.shape[1], T, sizes)
        return VFLDataset([X[:, s] for s in slices], None if y is None else jnp.asarray(y))


def standardize(ds: VFLDataset, eps: float = 1e-8) -> VFLDataset:
    """Per-feature mean-0 / std-1 normalisation, computed party-locally
    (no cross-party stats needed — matches the paper's preprocessing)."""
    parts = []
    for p in ds.parts:
        mu = p.mean(axis=0, keepdims=True)
        sd = p.std(axis=0, keepdims=True)
        parts.append((p - mu) / jnp.maximum(sd, eps))
    return VFLDataset(parts, ds.y)


def as_numpy(ds: VFLDataset) -> Tuple[List[np.ndarray], Optional[np.ndarray]]:
    return [np.asarray(p) for p in ds.parts], (None if ds.y is None else np.asarray(ds.y))
