"""The program's own host spans (``repro.*``, from ``repro.utils.trace``)
in a traced run's ``.xplane.pb``, reduced for the per-layer readers.

A reader finds its run's trace under ``.bench_trace/<workload>``, the
workload found by matching the run's configuration name and traffic
against ``BENCHMARK.json``.  The file is parsed once per run and clipped
to the benchmark's ``bench.window`` span.  Per ``repro.*`` name it holds
the count, the summed seconds and the self seconds (less the child
``repro.*`` spans on the same thread), and the device idle under it: at
each instant the device ran no op, the innermost ``repro.*`` span open on
the host.  Each ``repro.compile`` marker stands for the interval
``[end - secs, end]`` of one compile or compilation-cache read.  A trace
of a program without these spans reduces to ``None``.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as tr

PREFIX = "repro."
COMPILE = "repro.compile"

_CACHE: Dict[Tuple[str, int], Optional["ProgramSpans"]] = {}


@dataclasses.dataclass
class ProgramSpans:
    window_s: float
    count: Dict[str, int]
    total_s: Dict[str, float]
    self_s: Dict[str, float]
    idle_s: Dict[Optional[str], float]   # device idle by innermost span; None: no span
    compiles: int                        # repro.compile markers in the window
    compile_idle_s: float                # device idle inside a compile interval


def _self_times(spans: Sequence[Tuple[str, float, float]], lo: float, hi: float):
    """Summed and self ns per name of one thread's (name, start, end)
    spans, clipped to [lo, hi]; a span's parent is the innermost span that
    holds it."""
    total: Dict[str, float] = collections.Counter()
    own: Dict[str, float] = collections.Counter()
    stack: List[Tuple[str, float]] = []                 # (name, end)
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        d = max(0.0, min(e, hi) - max(s, lo))
        total[name] += d
        own[name] += d
        if stack:
            own[stack[-1][0]] -= d
        stack.append((name, e))
    return total, own


def _innermost(spans: Sequence[Tuple[str, float, float]], lo: float, hi: float):
    """[lo, hi) cut into (start, end, name) pieces, ``name`` the innermost
    span open there (the latest started; None where none is)."""
    cuts = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e) if lo < x < hi})
    starts = sorted(spans, key=lambda x: x[1])
    out, i, open_ = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while i < len(starts) and starts[i][1] <= a:
            open_.append(starts[i])
            i += 1
        open_ = [x for x in open_ if x[2] > a]
        name = max(open_, key=lambda x: (x[1], -x[2]))[0] if open_ else None
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _overlap(iv, pieces):
    """ns of the sorted disjoint intervals ``iv`` inside each name of the
    sorted disjoint (start, end, name) ``pieces``."""
    got: Dict[Optional[str], float] = collections.Counter()
    j = 0
    for s, e in iv:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            got[name] += max(0.0, min(b, e) - max(a, s))
            k += 1
    return got


def reduce_planes(planes, window: str = "bench.window") -> Optional[ProgramSpans]:
    """``planes`` as :func:`bench.trace.reduce_planes` takes them."""
    threads: List[List[Tuple[str, float, float]]] = []
    markers: List[Tuple[float, float]] = []          # (end, secs)
    win = None
    device_ops = []
    for plane in planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    name, s = ev.name, float(ev.start_ns)
                    if name == window and win is None:
                        win = (s, s + ev.duration_ns)
                    elif name == COMPILE:
                        markers.append((s, float(dict(tr._stats(ev)).get("secs", 0.0))))
                    elif name.startswith(PREFIX):
                        spans.append((name, s, s + ev.duration_ns))
                if spans:
                    threads.append(spans)
        elif plane.name.startswith("/device"):
            ops = [ev for line in plane.lines if line.name in tr.OPS_LINES
                   for ev in line.events]
            if ops:
                device_ops.append(ops)
    if win is None or not (threads or markers):
        return None
    lo, hi = win
    count: Dict[str, int] = collections.Counter()
    total: Dict[str, float] = collections.Counter()
    own: Dict[str, float] = collections.Counter()
    for spans in threads:
        t, o = _self_times(spans, lo, hi)
        total.update(t)
        own.update(o)
        count.update(n for n, s, e in spans if e > lo and s < hi)
    pieces = _innermost([x for spans in threads for x in spans], lo, hi)
    ends = [(end, secs) for end, secs in markers if lo <= end <= hi]
    compile_iv = tr._union(tr._clip([(end - secs * 1e9, end) for end, secs in ends],
                                    lo, hi))
    idle: Dict[Optional[str], float] = collections.Counter()
    compile_idle = 0.0
    for ops in device_ops or [[]]:
        busy = tr._union(tr._clip([(e.start_ns, e.start_ns + e.duration_ns)
                                   for e in ops], lo, hi))
        edges = [lo] + [x for se in busy for x in se] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle.update(_overlap(gaps, pieces))
        compile_idle += sum(_overlap(gaps, [(s, e, 0) for s, e in compile_iv]).values())
    n = max(len(device_ops), 1)
    return ProgramSpans(
        window_s=(hi - lo) * 1e-9,
        count=dict(count),
        total_s={k: v * 1e-9 for k, v in total.items()},
        self_s={k: v * 1e-9 for k, v in own.items()},
        idle_s={k: v / n * 1e-9 for k, v in idle.items()},
        compiles=len(ends),
        compile_idle_s=compile_idle / n * 1e-9,
    )


def reduce(path: str, window: str = "bench.window") -> Optional[ProgramSpans]:
    """:func:`reduce_planes` of one ``.xplane.pb``, parsed once per file."""
    st = os.stat(path)
    key = (path, st.st_mtime_ns)
    if key not in _CACHE:
        from jax.profiler import ProfileData

        _CACHE[key] = reduce_planes(ProfileData.from_file(path).planes, window)
    return _CACHE[key]


def workload_of(config: Dict, traffic: Dict, root: Path) -> Optional[str]:
    """The cell of ``BENCHMARK.json`` whose configuration is named
    ``config["name"]`` and whose traffic file holds ``traffic``."""
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["config"] != config.get("name"):
            continue
        with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
            if json.load(f) == traffic:
                return w["name"]
    return None


def for_ctx(ctx, root: Path) -> Optional[ProgramSpans]:
    """The program spans of the run a reader sees, or ``None`` where the
    run was not traced or its trace holds no ``repro.*`` span."""
    if ctx.trace is None:
        return None
    name = workload_of(ctx.config, ctx.traffic, root)
    if name is None:
        return None
    try:
        path = tr.find_xplane(root / ".bench_trace" / name)
    except FileNotFoundError:
        return None
    return reduce(path)
