"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Busy time is the union of the intervals in which an operation ran on the
device (the per-op line of each device plane), clipped to the window, the
benchmark's own ``bench.window`` host span.  Pallas kernels are the ops
that lowered to a Mosaic ``tpu_custom_call``.  Each idle gap on the device
is named by the innermost ``bench.*`` host span around its middle: what
the host was doing while the chip waited.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINES = ("XLA Ops",)
KERNEL_MARKS = ("tpu_custom_call", "mosaic", "pallas_call")
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_events: int
    kernel_union_s: float
    kernel_sum_s: float
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    devices: int

    def breakdown(self) -> Dict[str, List]:
        return {"device_ops": [[n, s] for n, s in self.top_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def is_kernel(event) -> bool:
    text = event.name.lower()
    if any(m in text for m in KERNEL_MARKS):
        return True
    for _, v in _stats(event):
        if isinstance(v, str) and any(m in v.lower() for m in KERNEL_MARKS):
            return True
    return False


def _stats(event) -> Iterable[Tuple[str, object]]:
    try:
        return list(event.stats)
    except (TypeError, AttributeError):
        return []


def reduce_planes(planes, window: str = "bench.window") -> Summary:
    """``planes``: objects with ``name`` and ``lines``; lines with ``name``
    and ``events``; events with ``name``, ``start_ns``, ``duration_ns`` and
    ``stats`` (``jax.profiler.ProfileData``'s shape)."""
    host: List[Tuple[str, float, float]] = []
    device_ops: List[List[Tuple[str, float, float, bool]]] = []
    seen: Dict[str, List[str]] = {}          # device planes and their lines
    for plane in planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/device"):
            ops = []
            seen[plane.name] = []
            for line in plane.lines:
                seen[plane.name].append(line.name)
                if line.name in OPS_LINES:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns, is_kernel(e))
                            for e in line.events]
            if ops:
                device_ops.append(ops)
    if seen and not device_ops:
        raise ValueError(f"no device plane holds an op line {OPS_LINES}; "
                         f"device planes and lines: {seen}")
    win = [(s, e) for n, s, e in host if n == window]
    if not win:
        raise ValueError(f"the trace holds no {window!r} host span")
    lo, hi = win[0]
    span_ns = hi - lo
    busy = kernel_union = kernel_sum = 0.0
    kernel_events = 0
    per_op: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[float, float]] = []
    for ops in device_ops:
        iv = _union(_clip([(s, e) for _, s, e, _ in ops], lo, hi))
        busy += _length(iv)
        kin = [(s, e) for _, s, e, k in ops if k]
        kin = _clip(kin, lo, hi)
        kernel_union += _length(_union(kin))
        kernel_sum += _length(kin)
        kernel_events += len(kin)
        for name, s, e, _ in ops:
            if e > lo and s < hi:
                per_op[name] += min(e, hi) - max(s, lo)
        edges = [lo] + [x for se in iv for x in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(device_ops), 1)             # a CPU trace has no device plane
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = 0.5 * (s + e)
        around = [(hs, he, hn) for hn, hs, he in host
                  if hn != window and hs <= mid <= he]
        name = min(around, key=lambda x: x[1] - x[0])[2] if around else "no span"
        named.append((name, (e - s) * 1e-9))
    return Summary(
        window_s=span_ns * 1e-9,
        busy_s=busy / n * 1e-9,
        kernel_events=kernel_events,
        kernel_union_s=kernel_union / n * 1e-9,
        kernel_sum_s=kernel_sum / n * 1e-9,
        top_ops=[(k, v * 1e-9) for k, v in per_op.most_common(TOP)],
        idle_gaps=named,
        devices=len(device_ops),
    )


def reduce(path: str, window: str = "bench.window") -> Summary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window)


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and the costliest event names with their stats: the
    look at a trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name!r}: {[(l.name, len(list(l.events))) for l in lines]}")
        if not plane.name.startswith("/device"):
            out += [f"  span {e.name!r} {e.start_ns} +{e.duration_ns}"
                    for line in lines for e in line.events if e.name == "bench.window"]
            continue
        for line in lines:
            agg: Dict[str, float] = collections.Counter()
            first: Dict[str, object] = {}
            for e in line.events:
                agg[e.name] += e.duration_ns
                if e.name not in first:
                    first[e.name] = (e.start_ns, dict(_stats(e)))
            out.append(f"  line {line.name!r}")
            for name, ns in agg.most_common(limit):
                out.append(f"    {ns * 1e-6:12.3f} ms  {name!r}  {first[name]}")
    return "\n".join(out)
