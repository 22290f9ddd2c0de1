"""Roofline report generator: reads results/dryrun/*.json plus the fused-
kernel sweep (results/BENCH_kernel.json) into the EXPERIMENTS.md §Roofline
table (single-pod baselines per the assignment) and ranks cells for the perf
hillclimb."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

if __package__ in (None, ""):  # script mode: python benchmarks/roofline.py
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

from repro.launch.hlo_analysis import MODELED_CHIP, chip_peaks

KERNEL_BENCH = "results/BENCH_kernel.json"


def load(out_dir: str, mesh: str = "16x16") -> list[dict]:
    rows = []
    for f in sorted(pathlib.Path(out_dir).glob("*.json")):
        if f.name == "summary.json":
            continue
        r = json.loads(f.read_text())
        if r.get("mesh") == mesh:
            rows.append(r)
    return rows


def load_kernel_rows(path: str = KERNEL_BENCH) -> list[dict]:
    """Map the fused-selection sweep (kernel_bench.py) into table rows.

    Each (c_tile, k, expand) pair becomes one row: the analytic roofline of
    the fused kernel on its padded candidate grid; ``useful_flops_ratio`` is
    the candidate-lane utilization (live candidates / padded lanes), so the
    roofline fraction reflects padding waste exactly like the training rows.
    """
    p = pathlib.Path(path)
    if not p.exists():
        return []
    bench = json.loads(p.read_text())
    rows = []
    hbm_bw = chip_peaks(MODELED_CHIP).hbm_bw
    for name, row in bench.get("sweep", {}).items():
        rl = row.get("roofline", {})
        util = row.get("model", {}).get("lane_util_candidates", 1.0)
        rows.append({
            "arch": "allanpoe-retrieval",
            "shape": name,
            "status": "OK",
            "roofline": {
                "compute_s": rl.get("compute_s", 0.0),
                "memory_s": rl.get("memory_s", 0.0),
                "collective_s": rl.get("collective_s", 0.0),
                "dominant": rl.get("dominant", "?"),
            },
            "model_flops_per_device": rl.get("model_flops", 0) * util,
            "useful_flops_ratio": util,
        })
        # the quantized twin of the same grid point: identical flops,
        # memory term re-derived from the int8 bytes model, so the table
        # shows how far dequant-in-tile moves the memory bound
        bq = row.get("model", {}).get("bytes_quantized")
        if bq is not None:
            mem_q = bq / hbm_bw
            comp = rl.get("compute_s", 0.0)
            rows.append({
                "arch": "allanpoe-retrieval",
                "shape": name + "_q",
                "status": "OK",
                "roofline": {
                    "compute_s": comp,
                    "memory_s": mem_q,
                    "collective_s": rl.get("collective_s", 0.0),
                    "dominant": "memory" if mem_q > comp else "compute",
                },
                "model_flops_per_device": rl.get("model_flops", 0) * util,
                "useful_flops_ratio": util,
            })
    return rows


def step_time_and_fraction(r: dict) -> tuple[float, float]:
    """Bound step time = max of terms (idealized overlap); roofline fraction =
    ideal compute time on *useful* (model) flops / bound time."""
    rl = r.get("roofline", {})
    bound = max(rl.get("compute_s", 0), rl.get("memory_s", 0), rl.get("collective_s", 0))
    useful = r.get("model_flops_per_device", 0) / chip_peaks(MODELED_CHIP).flops_bf16
    frac = useful / bound if bound > 0 else 0.0
    return bound, frac


def table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute_s | memory_s | collective_s | dominant | "
           "MODEL/HLO flops | roofline frac | what would move the bound |")
    sep = "|" + "---|" * 9
    lines = [hdr, sep]
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        if str(r.get("status", "")).startswith("SKIP"):
            lines.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | SKIP(full-attn) | — | — | "
                f"O(L²) attention at 524k tokens; run on ssm/hybrid archs only |"
            )
            continue
        rl = r.get("roofline", {})
        bound, frac = step_time_and_fraction(r)
        dom = rl.get("dominant", "?").replace("_s", "")
        fix = {
            "compute": "more chips or lower-precision matmuls",
            "memory": "fuse attention (avoid L×S materialization), better remat policy",
            "collective": "sequence-parallel activations / larger per-device batch / compressed DP reduce",
        }.get(dom, "")
        if r.get("arch") == "allanpoe-retrieval":
            fix = {
                "compute": "bf16 candidate tiles / larger C_TILE on the MXU",
                "memory": "fused selection removes the score round-trip; "
                          "int8 corpus storage (the _q rows) shrinks the "
                          "candidate stream itself",
            }.get(dom, fix)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {rl.get('compute_s', 0):.4f} | "
            f"{rl.get('memory_s', 0):.4f} | {rl.get('collective_s', 0):.4f} | "
            f"{dom} | {r.get('useful_flops_ratio', 0):.2f} | {frac:.3f} | {fix} |"
        )
    return "\n".join(lines)


def pick_hillclimb(rows: list[dict]) -> dict:
    ok = [r for r in rows if r.get("status") == "OK" and "roofline" in r]
    if not ok:
        return {}
    worst_frac = min(ok, key=lambda r: step_time_and_fraction(r)[1])
    coll_bound = max(
        ok,
        key=lambda r: r["roofline"]["collective_s"]
        / max(r["roofline"]["compute_s"], 1e-12),
    )
    return {"worst_fraction": worst_frac, "most_collective_bound": coll_bound}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--kernel-bench", default=KERNEL_BENCH)
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args()
    rows = load(args.dir, args.mesh) + load_kernel_rows(args.kernel_bench)
    if not rows:
        print(f"SKIP: no results under {args.dir} and no {args.kernel_bench} — "
              "run the dryrun launcher or benchmarks/kernel_bench.py first")
        return
    print(table(rows))
    picks = pick_hillclimb(rows)
    if not picks:
        print("\nhillclimb picks: SKIP (no OK rows with a roofline)")
        return
    print("\nhillclimb picks:")
    for k, r in picks.items():
        bound, frac = step_time_and_fraction(r)
        print(f"  {k}: {r['arch']} x {r['shape']} (frac={frac:.3f}, "
              f"dominant={r['roofline']['dominant']})")


if __name__ == "__main__":
    main()
