"""Reduce a JAX profiler trace to device busy time, kernel time and the
longest idle gaps.

A trace is read once into plain ``Plane``/``Line``/``Event`` records
(``load`` for an ``.xplane.pb`` file, ``from_text_proto`` for a hand-written
XSpace), and every reduction works on those, so a test can check the
arithmetic on a small trace whose answers are known.

* Device planes are the planes named ``/device:TPU:<n>``; their device
  operations are the events of the ``XLA Ops`` line.
* Busy time is the length of the union of those events' intervals inside
  the window, averaged over the device planes; idle share is one minus
  busy over the window.
* A kernel's time is the summed duration of its operations, found by name:
  an operation ``%fused_topk.3 = ...`` (a TPU trace names each operation by
  its HLO instruction's text) is the kernel ``fused_topk``. Operations nest
  (a ``while`` loop holds the operations of its body), so per-kernel times
  overlap and do not add up to the busy time.
* Each idle gap is labelled with the host event that overlaps it most, so
  the gap says what the host was doing while the device waited.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_EVENT = "bench_window"
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _convert(profile) -> list:
    return [
        Plane(
            p.name,
            [
                Line(ln.name, [Event(e.name, float(e.start_ns), float(e.duration_ns)) for e in ln.events])
                for ln in p.lines
            ],
        )
        for p in profile.planes
    ]


def load(trace_dir: str) -> list:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return _convert(ProfileData.from_file(max(files, key=os.path.getmtime)))


def from_text_proto(text: str) -> list:
    from jax.profiler import ProfileData

    return _convert(ProfileData.from_text_proto(text))


def device_planes(planes) -> list:
    return [p for p in planes if DEVICE_PLANE.match(p.name)]


def host_planes(planes) -> list:
    return [p for p in planes if p.name.startswith("/host:")]


def op_events(plane) -> list:
    return [e for ln in plane.lines if ln.name == OPS_LINE for e in ln.events]


def window(planes) -> tuple:
    """(start_ns, end_ns) of the host's ``bench_window`` annotation."""
    for p in host_planes(planes):
        for ln in p.lines:
            for e in ln.events:
                if e.name == WINDOW_EVENT:
                    return e.start_ns, e.end_ns
    raise ValueError(f"trace has no {WINDOW_EVENT!r} host event")


def _merged(events, lo: float, hi: float) -> list:
    """Union of the events' intervals clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    spans = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events)
    out = []
    for s, t in spans:
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def busy_seconds(planes, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which a device operation ran, averaged over
    the device planes."""
    devs = device_planes(planes)
    if not devs:
        return 0.0
    total = sum(t - s for d in devs for s, t in _merged(op_events(d), lo, hi))
    return total / len(devs) / 1e9


def kernel_name(op: str) -> str:
    """``fused_topk`` for a TPU operation event, whose name is the HLO
    instruction's text (``%fused_topk.3 = (f32[...]) custom-call(...)``),
    or for a bare operation name (``fused_topk.3``)."""
    head = op.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def kernel_seconds(planes, kernels, lo: float, hi: float) -> float:
    """Summed device seconds of the operations of the named kernels whose
    start lies in [lo, hi], over all device planes."""
    names = set(kernels)
    return sum(
        e.dur_ns
        for d in device_planes(planes)
        for e in op_events(d)
        if kernel_name(e.name) in names and lo <= e.start_ns <= hi
    ) / 1e9


def top_ops(planes, lo: float, hi: float, n: int = 10) -> list:
    """[(kernel name, seconds)] of the device operations that took most
    time in [lo, hi], summed over the device planes."""
    acc: dict = {}
    for d in device_planes(planes):
        for e in op_events(d):
            if lo <= e.start_ns <= hi:
                k = kernel_name(e.name)
                acc[k] = acc.get(k, 0.0) + e.dur_ns / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(planes, lo: float, hi: float, n: int = 10) -> list:
    """[(host label, seconds)] of the longest device idle gaps in [lo, hi]
    on the first device plane, each labelled with the host event (other
    than the window itself) that overlaps it most, or ``host idle``."""
    devs = device_planes(planes)
    if not devs:
        return []
    busy = _merged(op_events(devs[0]), lo, hi)
    edges = [lo] + [x for s, t in busy for x in (s, t)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [
        e for p in host_planes(planes) for ln in p.lines for e in ln.events
        if e.name != WINDOW_EVENT and e.dur_ns > 0
    ]
    out = []
    for s, t in gaps:
        best, best_ov = "host idle", 0.0
        for e in host:
            ov = min(e.end_ns, t) - max(e.start_ns, s)
            if ov > best_ov:
                best, best_ov = e.name, ov
        out.append((best, (t - s) / 1e9))
    return out
