"""The readers of the service's batch-phase spans on a small hand-written
trace whose answers are known. Times in µs:

    window             [1000, 11000]  (bench_window, host thread 1)
    TPU:0 ops          [2000, 4000], [7000, 8000]
    host thread 1      serving.batch_assembly [500, 1500]
                       serving.device_readback [3000, 5000]
    host thread 2      serving.batch_assembly [4500, 5500]
                       np.asarray [6000, 7000]  (not a phase)
                       serving.device_readback [8000, 8500]
                       serving.deliver [10500, 11500]

The device idles over [1000, 2000], [4000, 7000] and [8000, 11000]: 7000 of
the 10000 in the window. Some phase is open over [1000, 1500],
[3000, 5500] (the two threads overlap), [8000, 8500] and [10500, 11000].
Idle while a phase is open: 500 + 1500 (half of the [4000, 7000] gap) +
500 + 500 = 3000, so 30% host-bound and 40% waiting for requests.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import harness, span_reduce, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
NEW = ("batch_assembly_ms.steady", "device_readback_ms.steady", "host_bound_idle_share.steady")


def _plane(pid, name, lines, names):
    """A plane of ``lines`` [(line name, [(metadata id, start µs, end µs)])]."""
    out = [f'planes {{\n  id: {pid}\n  name: "{name}"']
    for lid, (lname, events) in enumerate(lines, 1):
        evs = "\n".join(
            f"    events {{ metadata_id: {m} offset_ps: {s * 10**6} duration_ps: {(t - s) * 10**6} }}"
            for m, s, t in events
        )
        out.append(f'  lines {{\n    id: {lid}\n    name: "{lname}"\n    timestamp_ns: 0\n{evs}\n  }}')
    out += [f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}' for k, v in names.items()]
    return "\n".join(out) + "\n}\n"


DEVICE = _plane(1, "/device:TPU:0", [("XLA Ops", [(1, 2000, 4000), (2, 7000, 8000)])],
                {1: "fused_topk.3", 2: "while.1"})
HOST = _plane(
    2, "/host:CPU",
    [("python3", [(1, 1000, 11000), (2, 500, 1500), (3, 3000, 5000)]),
     ("client-1", [(2, 4500, 5500), (4, 6000, 7000), (3, 8000, 8500), (5, 10500, 11500)])],
    {1: "bench_window", 2: "serving.batch_assembly", 3: "serving.device_readback",
     4: "np.asarray", 5: "serving.deliver"},
)


def _record(text):
    planes = trace_reduce.from_text_proto(text)
    lo, hi = trace_reduce.window(planes)
    return harness.RunRecord({}, {}, "TPU v5 lite", planes, lo, hi, {})


def _read(name, rec):
    return harness.metric_reader(ROOT, name)(rec)


def test_phase_spans_on_every_host_thread():
    planes = trace_reduce.from_text_proto(DEVICE + HOST)
    assert len(span_reduce.spans(planes)) == 5
    assert [e.start_ns for e in span_reduce.spans(planes, "batch_assembly")] == [500e3, 4500e3]


def test_phase_means_count_the_spans_that_start_in_the_window():
    rec = _record(DEVICE + HOST)
    # thread 1's assembly starts before the window: only thread 2's counts
    assert _read("batch_assembly_ms.steady", rec) == pytest.approx(1.0)
    assert _read("device_readback_ms.steady", rec) == pytest.approx((2.0 + 0.5) / 2)


def test_host_bound_idle_is_the_idle_time_under_an_open_phase():
    rec = _record(DEVICE + HOST)
    assert _read("device_idle_share.steady", rec) == pytest.approx(70.0)
    assert _read("host_bound_idle_share.steady", rec) == pytest.approx(30.0)
    assert span_reduce.host_bound_idle_seconds(rec.planes, rec.lo_ns, rec.hi_ns) == pytest.approx(3e-3)


def test_a_program_without_phase_spans_reads_nothing():
    bare = _plane(2, "/host:CPU", [("python3", [(1, 1000, 11000), (2, 3000, 5000)])],
                  {1: "bench_window", 2: "np.asarray"})
    rec = _record(DEVICE + bare)
    for name in NEW:
        assert _read(name, rec) is None, name
    # and a record with no trace at all
    empty = harness.RunRecord({}, {}, "TPU v5 lite", [], 0.0, 0.0, {})
    for name in NEW:
        assert _read(name, empty) is None, name
