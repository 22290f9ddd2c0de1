"""Exposition glue: merged metric snapshots for the METRICS_snapshot.json
artifact. Spans need no exporter of their own: batch phases are profiler
annotations (``obs.tracer.phase``), written into the profiler's trace on
the device trace's clock."""

from __future__ import annotations

import json
import pathlib
from typing import Optional

from repro.obs.metrics import MetricsRegistry, merged_snapshot


def write_metrics_snapshot(
    path, *registries: MetricsRegistry, extra: Optional[dict] = None
) -> dict:
    """Merged JSON snapshot of several registries (service + GLOBAL is the
    usual pair) — the METRICS_snapshot.json CI artifact. ``extra`` merges
    top-level context keys (bench config, backend)."""
    doc = merged_snapshot(*registries)
    if extra:
        doc = {**extra, **doc}
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_suffix(p.suffix + ".tmp")
    tmp.write_text(json.dumps(doc, indent=2) + "\n")
    tmp.replace(p)
    return doc
