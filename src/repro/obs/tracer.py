"""Per-query span trees and batch phases for the serving stack.

A ``TraceContext`` rides on ``SearchRequest.trace`` through the whole
request path — admission, queue wait, batch assembly, executable lookup
(hit/miss/retrace), device dispatch and read-back, grow-segment merge,
per-replica scatter fan-out, fusion re-score, delivery — and each stage
appends a ``Span``. Stages usually record retrospectively
(``add_span(name, t0, t1)``) with timestamps they measured anyway: a batch
phase is timed ONCE and attributed to every query in the batch, instead of
each query carrying live span objects across the pump/submit thread
boundary. ``span()`` is the live context-manager form for single-owner
phases.

``phase`` times one batch phase: it records the interval for those
per-request trees, observes it in a per-phase histogram, and holds a
``jax.profiler.TraceAnnotation`` named ``serving.<phase>`` open for the
same interval. The annotation is a host event in the profiler's own trace,
so a batch phase sits on the device trace's clock, beside the device
operations it waited for or enqueued.

Timestamps are ``time.perf_counter()`` seconds (monotonic, sub-µs), so a
span tree is internally ordered but not wall-clock anchored. ``Tracer`` is
the factory plus a bounded ring of finished traces. Everything is
lock-protected: spans are appended from submitter, pump, and scatter-pool
threads concurrently.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Iterator, Optional

try:  # the profiler's host annotations; obs itself needs no JAX
    from jax.profiler import TraceAnnotation
except ImportError:  # pragma: no cover - JAX is a dependency of the package
    TraceAnnotation = None


class Span:
    """One named interval with attributes and children. ``t1`` is None
    while open; ``annotate`` merges attributes at any point."""

    __slots__ = ("name", "t0", "t1", "attrs", "children")

    def __init__(self, name: str, t0: float, attrs: Optional[dict] = None):
        self.name = name
        self.t0 = t0
        self.t1: Optional[float] = None
        self.attrs: dict = dict(attrs) if attrs else {}
        self.children: list[Span] = []

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def annotate(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self, t: Optional[float] = None) -> "Span":
        if self.t1 is None:
            self.t1 = time.perf_counter() if t is None else t
        return self

    def walk(self) -> Iterator["Span"]:
        yield self
        for child in list(self.children):
            yield from child.walk()

    def __repr__(self) -> str:
        return (
            f"Span({self.name!r}, dur={self.duration * 1e3:.3f}ms, "
            f"attrs={self.attrs}, children={len(self.children)})"
        )


class TraceContext:
    """The span tree of one query (or one background operation). Carried on
    ``SearchRequest.trace``; every instrumented stage hangs spans off the
    root. Thread-safe: the serving path appends from several threads."""

    _next_id = [0]
    _id_lock = threading.Lock()

    def __init__(self, name: str, tracer: Optional["Tracer"] = None, **attrs):
        with self._id_lock:
            self._next_id[0] += 1
            self.trace_id = self._next_id[0]
        self.name = name
        self.root = Span(name, time.perf_counter(), attrs)
        self._tracer = tracer
        self._lock = threading.Lock()

    # -- recording ----------------------------------------------------------

    def add_span(
        self,
        name: str,
        t0: float,
        t1: float,
        parent: Optional[Span] = None,
        **attrs,
    ) -> Span:
        """Retrospective span from timestamps the caller already measured
        (the batch-phase pattern: time once, attribute to every query)."""
        span = Span(name, t0, attrs)
        span.t1 = max(t1, t0)  # clamp: a span is never negative-length
        with self._lock:
            (parent or self.root).children.append(span)
        return span

    def span(self, name: str, parent: Optional[Span] = None, **attrs):
        """Live span as a context manager (single-owner phases)."""
        return _LiveSpan(self, name, parent, attrs)

    def annotate(self, **attrs) -> "TraceContext":
        with self._lock:
            self.root.attrs.update(attrs)
        return self

    def end(self) -> "TraceContext":
        self.root.end()
        if self._tracer is not None:
            self._tracer._finish(self)
        return self

    def __enter__(self) -> "TraceContext":
        return self

    def __exit__(self, *exc) -> None:
        self.end()

    # -- inspection ---------------------------------------------------------

    def spans(self) -> list[Span]:
        """Every span of the tree, pre-order (root first)."""
        with self._lock:
            return list(self.root.walk())

    def find(self, name: str) -> list[Span]:
        return [s for s in self.spans() if s.name == name]

    def span_names(self) -> list[str]:
        return [s.name for s in self.spans()]


class _LiveSpan:
    def __init__(self, ctx: TraceContext, name, parent, attrs):
        self._ctx = ctx
        self._name = name
        self._parent = parent
        self._attrs = attrs
        self.span: Optional[Span] = None

    def __enter__(self) -> Span:
        self.span = Span(self._name, time.perf_counter(), self._attrs)
        with self._ctx._lock:
            (self._parent or self._ctx.root).children.append(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.span.annotate(error=repr(exc))
        self.span.end()


class Tracer:
    """TraceContext factory + bounded ring of finished traces. ``keep``
    bounds memory: a service tracing every query forever retains only the
    most recent ``keep`` trees."""

    def __init__(self, keep: int = 256):
        self._lock = threading.Lock()
        self._finished: deque[TraceContext] = deque(maxlen=keep)

    def trace(self, name: str, **attrs) -> TraceContext:
        return TraceContext(name, tracer=self, **attrs)

    def _finish(self, ctx: TraceContext) -> None:
        with self._lock:
            self._finished.append(ctx)

    @property
    def finished(self) -> list[TraceContext]:
        with self._lock:
            return list(self._finished)


class phase:
    """Context manager timing one batch phase ``name``; yields the phase's
    attribute dict, which the body may extend.

    On exit the interval is observed in ``hist`` under the label
    ``phase=name`` and, when ``phases`` is a list, appended to it as
    ``(name, t0, t1, attrs)`` for attribution to the batch's requests. For
    the same interval a ``jax.profiler.TraceAnnotation("serving.<name>")``
    is open: without a running profiler that costs well under a
    microsecond. Phases of one batch follow each other; batches on other
    threads overlap them."""

    __slots__ = ("name", "hist", "phases", "attrs", "t0", "_ann")

    def __init__(self, name: str, hist, phases: Optional[list] = None, **attrs):
        self.name = name
        self.hist = hist
        self.phases = phases
        self.attrs = attrs

    def __enter__(self) -> dict:
        self._ann = (
            TraceAnnotation(f"serving.{self.name}")
            if TraceAnnotation is not None else None
        )
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.hist.observe(t1 - self.t0, phase=self.name)
        if self.phases is not None:
            self.phases.append((self.name, self.t0, t1, self.attrs))
