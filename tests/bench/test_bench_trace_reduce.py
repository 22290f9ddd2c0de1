"""The trace reduction on a small hand-written trace whose answers are known.

Two device planes, each with an ``XLA Ops`` line, and a host plane with the
``bench_window`` annotation and two host events. Times in ns:

    window            [0, 10_000]
    TPU:0 ops         fused_topk.3 [1_000, 3_000], hybrid_scores.1 [2_000, 4_000],
                      fused_topk.7 [6_000, 7_000], fused_topk.9 [9_500, 11_000]
    TPU:1 ops         fused_topk.1 [0, 5_000]
    host              submit [4_000, 6_000], merge [7_000, 9_000]

TPU:0 is busy over [1000, 4000] + [6000, 7000] + [9500, 10000] = 4_500 ns
inside the window; TPU:1 over 5_000 ns; the mean is 4_750 ns.
"""

from __future__ import annotations

import pytest

from bench import trace_reduce as tr


def _plane(pid, name, line, t0, events, names):
    evs = "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {off * 1000} duration_ps: {dur * 1000} }}"
        for m, off, dur in events
    )
    meta = "\n".join(
        f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}' for k, v in names.items()
    )
    return (
        f'planes {{\n  id: {pid}\n  name: "{name}"\n  lines {{\n    id: 1\n    name: "{line}"\n'
        f"    timestamp_ns: {t0}\n{evs}\n  }}\n{meta}\n}}\n"
    )


TRACE = (
    _plane(
        1, "/device:TPU:0", "XLA Ops", 1000,
        [(1, 0, 2000), (2, 1000, 2000), (3, 5000, 1000), (4, 8500, 1500)],
        {1: "fused_topk.3", 2: "hybrid_scores.1", 3: "fused_topk.7", 4: "fused_topk.9"},
    )
    + _plane(2, "/device:TPU:1", "XLA Ops", 0, [(1, 0, 5000)], {1: "fused_topk.1"})
    + _plane(
        3, "/host:CPU", "python3", 0,
        [(1, 0, 10000), (2, 4000, 2000), (3, 7000, 2000)],
        {1: "bench_window", 2: "submit", 3: "merge"},
    )
)


@pytest.fixture(scope="module")
def planes():
    return tr.from_text_proto(TRACE)


def test_window_and_planes(planes):
    assert tr.window(planes) == (0.0, 10000.0)
    assert [p.name for p in tr.device_planes(planes)] == ["/device:TPU:0", "/device:TPU:1"]
    assert [p.name for p in tr.host_planes(planes)] == ["/host:CPU"]


def test_busy_is_the_union_inside_the_window_averaged_over_devices(planes):
    assert tr.busy_seconds(planes, 0.0, 10000.0) == pytest.approx(4750e-9)
    # a window that cuts an operation counts only its inside part
    assert tr.busy_seconds(planes, 2500.0, 3500.0) == pytest.approx(1000e-9)


def test_kernel_time_by_name_and_start_in_window(planes):
    # fused_topk.3, .7 and .9 on TPU:0 (9 starts inside) and .1 on TPU:1
    assert tr.kernel_seconds(planes, ("fused_topk",), 0.0, 10000.0) == pytest.approx(9500e-9)
    assert tr.kernel_seconds(planes, ("hybrid_scores",), 0.0, 10000.0) == pytest.approx(2000e-9)
    assert tr.kernel_seconds(planes, ("pairwise_tile",), 0.0, 10000.0) == 0.0
    assert tr.kernel_name("fused_topk.12") == "fused_topk"
    # a TPU trace names each operation by its HLO instruction's text
    assert tr.kernel_name("%fused_topk.3 = (f32[8,1,128]{2,1,0}) custom-call(%x)") == "fused_topk"
    assert tr.kernel_name("%while.24 = (s32[]) while(%t), body=%b") == "while"


def test_top_ops_sums_by_kernel(planes):
    assert tr.top_ops(planes, 0.0, 10000.0) == [
        ("fused_topk", pytest.approx(9500e-9)),
        ("hybrid_scores", pytest.approx(2000e-9)),
    ]


def test_idle_gaps_are_labelled_by_the_host(planes):
    # TPU:0 idles over [0, 1000], [4000, 6000] and [7000, 9500]
    gaps = tr.idle_gaps(planes, 0.0, 10000.0)
    assert gaps == [
        ("merge", pytest.approx(2500e-9)),
        ("submit", pytest.approx(2000e-9)),
        ("host idle", pytest.approx(1000e-9)),
    ]


def test_no_device_plane_reads_nothing():
    host_only = tr.from_text_proto(
        _plane(1, "/host:CPU", "python3", 0, [(1, 0, 100)], {1: "bench_window"})
    )
    assert tr.busy_seconds(host_only, 0.0, 100.0) == 0.0
    assert tr.idle_gaps(host_only, 0.0, 100.0) == []
