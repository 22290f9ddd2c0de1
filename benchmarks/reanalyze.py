"""Re-run the HLO analysis over saved .hlo.gz artifacts (no recompilation)
and refresh the dry-run JSON records in place."""

from __future__ import annotations

import argparse
import gzip
import json
import pathlib

from repro.launch.hlo_analysis import (
    MODELED_CHIP,
    analyze_hlo,
    chip_peaks,
    roofline_terms,
)


def reanalyze(json_path: pathlib.Path) -> bool:
    hlo_path = json_path.with_suffix(".hlo.gz")
    if not hlo_path.exists():
        return False
    record = json.loads(json_path.read_text())
    if str(record.get("status", "")).startswith("SKIP"):
        return False
    text = gzip.open(hlo_path, "rt").read()
    hlo = analyze_hlo(text)
    record["hlo"] = hlo
    if record.get("model_flops_per_device") and hlo["dot_flops"] > 0:
        record["useful_flops_ratio"] = (
            record["model_flops_per_device"] / hlo["dot_flops"]
        )
    record["roofline"] = roofline_terms(
        hlo_flops=hlo["dot_flops"],
        hlo_bytes=hlo["hbm_bytes"],
        coll_bytes_per_device=hlo["collective_bytes"],
        n_chips=record["n_devices"],
        peaks=chip_peaks(MODELED_CHIP),
    )
    json_path.write_text(json.dumps(record, indent=1))
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    args = ap.parse_args()
    n = 0
    for f in sorted(pathlib.Path(args.dir).glob("*.json")):
        if f.name == "summary.json":
            continue
        if reanalyze(f):
            n += 1
    print(f"reanalyzed {n} records")


if __name__ == "__main__":
    main()
