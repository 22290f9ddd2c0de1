#!/usr/bin/env python3
"""Find the highest rate a cell sustains, by a sweep on the chip.

    python3 bench/sweep.py --workload <open cell> --seed <n> --seconds <s> \
        --rates 200 400 800 ...

One process sets the cell up once (as ``bench/run.py`` does) and offers its
traffic at each rate in turn. Per rate it prints the offered and completed
rates, p50 and p99 latency from each request's due time, and the p50 of
the first and last fifth of the requests: a backlog that grows through the
run shows as a last fifth far slower than the first. The cell's traffic
file then fixes its rate at about four fifths of the highest sustained one.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.entry import prepare

    prepare(ROOT)
    import numpy as np
    from bench import harness
    from bench import traffic as traffic_mod

    cell = harness.load_cell(args.workload, ROOT, args.rehearse)
    dev = harness._device_check(cell, args.rehearse)[0]
    clock = harness.CompileClock()
    corpus, entries, _, requests = harness.make_data(cell, args.seed)
    service = harness.ready_service(cell, ROOT, args.seed, corpus, entries, requests, clock,
                                    dev.device_kind)
    print(f"set-up {time.time() - T_START:.1f} s", flush=True)

    rows = []
    for rate in args.rates:
        due = traffic_mod.arrivals(rate, args.seconds, args.seed)
        order = traffic_mod.request_order(len(entries), len(due), args.seed)
        t0 = time.perf_counter()
        ans, late = harness.drive_open(service, requests, order, due, t0,
                                       int(cell.traffic["clients"]))
        rs = sorted(ans.due)
        lat = np.asarray([
            (ans.done[r] if r in ans.done and r not in ans.failed else np.inf) - ans.due[r]
            for r in rs
        ])
        done = [ans.done[r] for r in rs if r in ans.done and r not in ans.failed]
        fifth = max(len(lat) // 5, 1)
        row = {
            "rate": rate,
            "requests": len(rs),
            "failed": len(ans.failed) + ans.unanswered(),
            "completed_qps": len(done) / (max(done) - t0) if done else 0.0,
            "p50_ms": 1e3 * harness.percentile(lat, 50),
            "p99_ms": 1e3 * harness.percentile(lat, 99),
            "first_fifth_p50_ms": 1e3 * harness.percentile(lat[:fifth], 50),
            "last_fifth_p50_ms": 1e3 * harness.percentile(lat[-fifth:], 50),
            "sender_late_p99_ms": 1e3 * harness.percentile(late, 99),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    service.stop_pump()
    return 0


if __name__ == "__main__":
    sys.exit(main())
