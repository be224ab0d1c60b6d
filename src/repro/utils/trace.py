"""Host spans of the coreset build on the profiler's own timeline.

``span(name)`` marks a step of the build where the device can wait on the
host: staging, a blocking read, the health copy, Algorithm 1 on the host.
While a profiler session is active (``jax.profiler.trace``,
``start_trace`` or a connected ``start_server``) each span is a
``TraceAnnotation`` named ``repro.<name>``, on the same clock as the device
ops of the ``.xplane.pb``.  With no session, ``span`` returns one shared
null context: nothing is recorded and nothing is kept.

``add(**counts)`` sums counts into the innermost open span; they land on
the span as stats when it closes (``bytes`` on ``repro.stage``).  Every
XLA compile or compilation-cache read while a session is active adds
``compiles`` and ``compile_s`` to the innermost open span and leaves a
``repro.compile`` marker at its end carrying ``secs``, so a reader can
rebuild the interval ``[end - secs, end]``.  Spans nest on their thread's
timeline; the spans of one build sit inside its ``repro.build``, which
carries ``build`` (a process-wide sequence number) and ``engine``.
"""

from __future__ import annotations

import contextlib
import itertools
import threading

import jax
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: Sequence numbers of ``repro.build`` spans, process-wide.
BUILDS = itertools.count(1)

_NULL = contextlib.nullcontext()
_listening = False
_listen_lock = threading.Lock()


class _Open(threading.local):
    """The thread's open spans, innermost last."""

    def __init__(self) -> None:
        self.spans = []


_open = _Open()


class _Span:
    __slots__ = ("name", "stats", "_ann")

    def __init__(self, name: str, stats: dict) -> None:
        self.name = name
        self.stats = stats

    def __enter__(self):
        self._ann = TraceAnnotation(f"repro.{self.name}")
        self._ann.__enter__()
        _open.spans.append(self)
        return self

    def __exit__(self, *exc):
        _open.spans.pop()
        if self.stats:
            self._ann.set_metadata(**self.stats)
        return self._ann.__exit__(*exc)


def span(name: str, **stats):
    """A ``repro.<name>`` span with ``stats`` while the profiler records,
    else the shared null context."""
    if not TraceAnnotation.is_enabled():
        return _NULL
    _listen()
    return _Span(name, dict(stats))


def add(**counts) -> None:
    """Sum ``counts`` into the innermost open span of this thread; a no-op
    where none is open."""
    spans = _open.spans
    if spans:
        stats = spans[-1].stats
        for k, v in counts.items():
            stats[k] = stats.get(k, 0) + v


def _on_duration(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT or not TraceAnnotation.is_enabled():
        return
    add(compiles=1, compile_s=secs)
    with TraceAnnotation("repro.compile", secs=secs):
        pass


def _listen() -> None:
    global _listening
    if _listening:
        return
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
