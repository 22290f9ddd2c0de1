"""Each per-layer metric reader on a record whose answer is known, and the
byte counts its roofline rests on."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import harness, kernel_bytes, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MSMARCO = json.loads((ROOT / "bench" / "configs" / "msmarco-splade.json").read_text())

# one device plane: fused_topk ops for 2 ms and another op for 1 ms inside a
# 10 ms window
TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 1500000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 500000000 }
    events { metadata_id: 3 offset_ps: 5000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fused_topk.1 = (f32[64,1,128]{2,1,0}, s32[64,1,128]{2,1,0}) custom-call(f32[64,768]{1,0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "fused_topk.2" } }
  event_metadata { key: 3 value { id: 3 name: "gather.4" } }
}
"""


def _record(cfg, counters, planes=None):
    planes = trace_reduce.from_text_proto(TRACE) if planes is None else planes
    return harness.RunRecord(cfg, {}, "TPU v5 lite", planes, 0.0, 10e6, counters)


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert callable(harness.metric_reader(ROOT, m["name"])), m["name"]
    for f in (ROOT / "bench" / "metrics").glob("*.py"):
        assert callable(harness.metric_reader(ROOT, f.stem)), f.name


def test_row_bytes_at_stored_widths():
    assert kernel_bytes.row_bytes(MSMARCO) == 768 * 4 + 128 * 8 + 64 * 8 == 4608


def test_readers():
    read = lambda name, rec: harness.metric_reader(ROOT, name)(rec)
    rec = _record(MSMARCO, {"requests": 640, "padded_slots": 0, "queue_wait_s_sum": 1.5,
                            "queue_wait_count": 100})
    assert read("device_idle_share.steady", rec) == pytest.approx(70.0)
    assert read("queue_wait_ms.steady", rec) == pytest.approx(15.0)
    need = 640 * 128 * 16 * 4608  # requests x rounds x degree x row bytes
    assert read("fused_topk_roofline.steady", rec) == pytest.approx(
        100 * need / 819e9 / 2e-3
    )
    # nothing to read: no trace, no requests
    empty = _record(MSMARCO, {}, planes=[])
    for f in (ROOT / "bench" / "metrics").glob("*.py"):
        assert read(f.stem, empty) is None, f.stem
