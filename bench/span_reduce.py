"""The program's batch phases in a profiler trace.

``HybridSearchService`` holds a ``jax.profiler.TraceAnnotation`` named
``serving.<phase>`` open over each phase of a batch
(``repro.obs.tracer.phase``), so each phase is a host event on the device
trace's clock. Batches run on the pump thread and on the clients' threads,
so spans of different batches overlap across host lines. A trace of a
program without these spans holds none, and every reduction here then reads
nothing.
"""

from __future__ import annotations

from bench import trace_reduce

PREFIX = "serving."


def spans(planes, phase: str | None = None) -> list:
    """Host events of one phase (``serving.<phase>``), or of every phase."""
    events = [e for p in trace_reduce.host_planes(planes) for ln in p.lines for e in ln.events]
    if phase is None:
        return [e for e in events if e.name.startswith(PREFIX)]
    return [e for e in events if e.name == PREFIX + phase]


def mean_ms(planes, phase: str, lo: float, hi: float) -> float | None:
    """Mean duration in ms of the phase's spans that start in [lo, hi]."""
    durs = [e.dur_ns for e in spans(planes, phase) if lo <= e.start_ns <= hi]
    return 1e-6 * sum(durs) / len(durs) if durs else None


def _overlap(a: list, b: list) -> float:
    """Total length shared by two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def host_bound_idle_seconds(planes, lo: float, hi: float) -> float | None:
    """Seconds in [lo, hi] in which the first device plane runs no operation
    while at least one batch phase is open on some host thread; None where
    the trace holds no device plane or no phase span in the window."""
    devs = trace_reduce.device_planes(planes)
    open_ = trace_reduce._merged(spans(planes), lo, hi)
    if not devs or not open_:
        return None
    busy = trace_reduce._merged(trace_reduce.op_events(devs[0]), lo, hi)
    return (sum(t - s for s, t in open_) - _overlap(open_, busy)) / 1e9
