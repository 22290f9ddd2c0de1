#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and per-layer metrics are found by
name from ``BENCHMARK.json`` at the repository root. The last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (with ``--trace 1`` also
``breakdown``), and last the ``checks`` each compared number was held to;
the same numbers close standard error. A run that finds no TPU, or fewer
chips than the cell asks for, exits 2 and prints no result. ``--rehearse``
runs the cell at its configuration's tiny ``rehearse`` sizes and allows the
CPU: it checks the harness end to end and reports no device metric.

JAX's persistent compilation cache is kept in ``bench/.cache/jax`` inside
the checkout, and built indexes in ``bench/.cache/index``.
"""

import time

T_START = time.time()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes; the CPU is allowed")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.entry import prepare

    prepare(ROOT)
    from bench import harness

    return harness.main(args, T_START, ROOT)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # every thread the run started has been joined; skip the interpreter's
    # teardown, where the TPU runtime's objects have been seen to crash it
    os._exit(code)
