"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig8,table2,...]

Prints ``name,us_per_call,derived`` CSV rows (and writes
results/benchmarks.csv)."""

from __future__ import annotations

import argparse
import pathlib
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller corpora")
    ap.add_argument("--only", default=None, help="comma list of bench names")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        fig8_overall,
        fig10_pruning,
        fig11_keyword,
        fig12_weights,
        fig14_scale,
        kernel_bench,
        serving_bench,
        table2_build,
        table3_kg,
        table5_insert,
    )

    q = args.quick
    benches = {
        "fig8": lambda: fig8_overall.run(*((2048, 32) if q else (8192, 64))),
        "table2": lambda: table2_build.run(2048 if q else 8192),
        "table3": lambda: table3_kg.run(*((2048, 32) if q else (4096, 64))),
        "fig10": lambda: fig10_pruning.run(*((2048, 32) if q else (4096, 64))),
        "fig11": lambda: fig11_keyword.run(*((2048, 32) if q else (4096, 64))),
        "fig12": lambda: fig12_weights.run(*((2048, 32) if q else (4096, 64))),
        "table5": lambda: table5_insert.run(*((2048, 32) if q else (4096, 64))),
        "fig14": lambda: fig14_scale.run(
            n_docs=2048 if q else 10_000,
            replicas=(1, 2) if q else (1, 2, 4),
            n_requests=64 if q else 256,
            segment_docs=256,
        ),
        "kernel": kernel_bench.run,
        "serving": lambda: serving_bench.run(*((1024, 64) if q else (4096, 256))),
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    all_rows = []
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        t0 = time.time()
        try:
            rows = fn()
        except Exception:
            traceback.print_exc()
            rows = [(f"{name}.ERROR", 0.0, "failed")]
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}", flush=True)
            all_rows.append(r)
        print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr, flush=True)

    out = pathlib.Path("results")
    out.mkdir(exist_ok=True)
    with open(out / "benchmarks.csv", "w") as f:
        f.write("name,us_per_call,derived\n")
        for r in all_rows:
            f.write(f"{r[0]},{r[1]:.1f},{r[2]}\n")


if __name__ == "__main__":
    main()
