#!/usr/bin/env python3
"""The control of ``correct``: the readings that set the limits
(``bench/check.py``).

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> <n> ... \
        [--fault int8_corpus|shuffled_neighbours|half_rounds]

For each seed, in one process, the cell is set up and run as
``bench/run.py`` runs it, and one JSON line gives the numbers compared for
four sets of answers to the same requests:

* ``program``: the answers the program served;
* ``control``: the plain reference one precision below what the
  configuration states on each path, rescoring the served passages: the
  dense passage rows as per-row symmetric int8 (the configuration states
  bfloat16 operands with float32 accumulation, the TPU's default matrix
  precision), the sparse values rounded to bfloat16 (it states float32);
* ``bf16``: the plain reference with every operand rounded to bfloat16,
  which the configuration states for the dense path: a witness that the
  program's dense scores are what that precision gives;
* ``altered``: the served answers with the first passage of each altered
  where it is produced (the next id), a fault every gap has to see.

With ``--fault`` the run serves its index with that fault or control
planted (``bench/faults.py``), and the ``program`` numbers are its
readings: ``int8_corpus`` is the program's own int8 storage path, the
control proper; a traversal fault's recall is the upper reading of
recall's floor.

The lower reading of each gap is the largest the program gives over a dozen
seeds or more; the upper is the smallest the control gives. The
benchmark's own runs never run the control.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _round(a, how: str):
    import ml_dtypes
    import numpy as np

    a = np.asarray(a, np.float32)
    if how == "bfloat16":
        return a.astype(ml_dtypes.bfloat16).astype(np.float32)
    if how == "int8":  # symmetric per row, as an int8 store keeps it
        scale = np.abs(a).max(axis=-1, keepdims=True) / 127.0
        scale = np.where(scale > 0, scale, 1.0)
        return np.clip(np.round(a / scale), -127, 127) * scale
    return a


def lowered_answers(arr: dict, host: dict, stats: dict, dense: str, sparse: str) -> dict:
    """The program's answers with every score recomputed by the reference
    from operands rounded as ``dense`` (the passages' dense rows, and the
    queries' too under ``bfloat16``) and ``sparse`` (all sparse values) say."""
    import numpy as np

    from bench import reference

    qd, qsi, qsv, qfi, qfv = host["queries"]
    pd, psi, psv, pfi, pfv = host["passages"]
    q = (_round(qd, "bfloat16" if dense == "bfloat16" else "none"), qsi,
         _round(qsv, sparse), qfi, _round(qfv, sparse))
    p = (_round(pd, dense), psi, _round(psv, sparse), pfi, _round(pfv, sparse))
    path, scale = reference.pair_path_scores(q, p, arr["qrow"], arr["ids"])
    scores = arr["scores"].copy()
    for mode in (reference.WEIGHTED_SUM, reference.ZSCORE):
        rows = arr["mode"] == mode
        if rows.any():
            scores[rows] = reference.fused_scores(
                path[rows], scale[rows], mode, arr["weights"][rows][:, None, :], stats
            )[0]
    return dict(arr, path=path, scores=scores)


def readings(m) -> dict:
    """The program's, the control's and the bfloat16 witness's numbers on
    the measured run ``m``, and those of its answers with the first
    passage of each altered where it is produced (the next id)."""
    import numpy as np

    from bench import check, harness

    host, stats = harness.host_data(m)
    arr = m.result["answers"]

    def numbers(a):
        return check.compare(a, host, (0, m.corpus.n), stats, 0)

    altered = dict(arr, ids=arr["ids"].copy())
    first = altered["ids"][:, 0]
    altered["ids"][:, 0] = np.where(first >= 0, (first + 1) % m.corpus.n, first)
    return {
        "program": harness.compared(m),
        "control": numbers(lowered_answers(arr, host, stats, "int8", "bfloat16")),
        "bf16": numbers(lowered_answers(arr, host, stats, "bfloat16", "bfloat16")),
        "altered": numbers(altered),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.entry import prepare

    prepare(ROOT)
    from bench import check, faults, harness

    if args.fault:
        faults.plant(args.fault, harness)
    for seed in args.seeds:
        m = harness.measure(args.workload, seed, args.seconds, False, args.rehearse,
                            time.time(), ROOT)
        line = {"workload": args.workload, "seed": seed, "fault": args.fault, **readings(m),
                "limits": dict(check.LIMITS, recall_at_10_min=m.cell.cfg["check"]["recall_at_10_min"])}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
