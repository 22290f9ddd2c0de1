"""What decides ``correct`` fails what it must.

* each compared number catches its own fault in a hand-made answer;
* the control, the program's own int8 storage path, and the reference one
  precision lower in the program's place, read above a gap's limit while
  the program reads below it;
* a whole run with the timed path broken underneath comes out not correct:
  an answer altered where it is produced, half of each batch left
  unanswered, and a broken neighbour list under the search traversal. The
  harness's look for a chip is skipped (``--rehearse`` sizes on the CPU).
"""

from __future__ import annotations

import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from bench import check, control, faults, harness, reference

from repro.serving.hybrid_service import HybridSearchService

ROOT = Path(__file__).resolve().parents[2]
OPEN = "msmarco-splade.open-steady"


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    """The benchmark's files in a directory of their own, so that what a
    run caches stays out of the repository."""
    dst = tmp_path_factory.mktemp("bench_root")
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(ROOT / "bench", dst / "bench", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    return dst


@pytest.fixture(scope="module")
def measured(root):
    return harness.measure(OPEN, 2**32 + 5, 2.0, False, True, time.time(), root)


def test_a_sound_run_passes_every_number(measured):
    ok, checks = check.verdict(harness.compared(measured), measured.cell.cfg["check"])
    assert ok, checks
    assert set(checks) == set(check.LIMITS) | {"recall_at_10"}
    assert len(measured.result["answers"]["ids"]) > 0


def test_each_number_catches_its_fault(measured):
    host, stats = harness.host_data(measured)
    arr = measured.result["answers"]
    lo, hi = 0, measured.corpus.n

    def numbers(a, unanswered=0):
        return check.compare(a, host, (lo, hi), stats, unanswered)

    # an altered id: the reference scores the passage the client got
    bad = {k: v.copy() for k, v in arr.items()}
    bad["ids"][0, 0] = (bad["ids"][0, 0] + 1) % hi
    assert numbers(bad)["dense_gap"] > check.LIMITS["dense_gap"]
    assert numbers(bad)["sparse_gap"] > check.LIMITS["sparse_gap"]
    # a passage without the request's required keyword
    j = int(np.flatnonzero((arr["keywords"] >= 0).any(axis=1))[0])
    kw = {k: v.copy() for k, v in arr.items()}
    kw["keywords"][j] = [hi + 7, -1]  # a keyword no passage holds
    assert numbers(kw)["keyword_misses"] > 0
    # an id twice, and scores out of order
    dup = {k: v.copy() for k, v in arr.items()}
    dup["ids"][1, 1] = dup["ids"][1, 0]
    assert numbers(dup)["bad_answers"] == 1
    order = {k: v.copy() for k, v in arr.items()}
    order["scores"][2, :2] = order["scores"][2, 1::-1] + [0.0, 1.0]
    assert numbers(order)["bad_answers"] == 1
    floor = measured.cell.cfg["check"]
    assert check.verdict(dict(numbers(arr, unanswered=1), recall_at_10=1.0), floor)[0] is False
    # recall under its floor, or not a number
    for recall in (floor["recall_at_10_min"] / 2, float("nan")):
        assert check.verdict(dict(numbers(arr), recall_at_10=recall), floor)[0] is False


def test_the_control_fails_the_limit_the_program_passes(measured):
    got = control.readings(measured)
    assert all(got["program"][k] <= v for k, v in check.LIMITS.items()), got["program"]
    assert got["control"]["sparse_gap"] > check.LIMITS["sparse_gap"]
    assert got["control"]["dense_gap"] > got["bf16"]["dense_gap"] > got["program"]["dense_gap"]


def test_the_programs_int8_path_fails_what_the_program_passes(monkeypatch, root):
    monkeypatch.setattr(harness, "make_service", harness.make_service)
    faults.plant("int8_corpus", harness)
    m = harness.measure(OPEN, 2**32 + 5, 2.0, False, True, time.time(), root)
    got = harness.compared(m)
    assert got["sparse_gap"] > 3 * check.LIMITS["sparse_gap"]
    assert got["dense_gap"] > 3 * check.LIMITS["dense_gap"]
    assert check.verdict(got, m.cell.cfg["check"])[0] is False


def _run(cell, root, seed=2**31 + 3):
    return harness.run(cell, seed, 2.0, False, True, time.time(), root)


def _after_warm_up(monkeypatch, name, fault):
    """Break ``HybridSearchService.<name>`` once set-up has warmed it up:
    the fault is on the timed path."""
    real_warm_up = harness.warm_up

    def warm_up(*args, **kw):
        n = real_warm_up(*args, **kw)
        monkeypatch.setattr(HybridSearchService, name, fault)
        return n

    monkeypatch.setattr(harness, "warm_up", warm_up)


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch, root):
    real = HybridSearchService._run_pool

    def altered(self, pool, bucket, args, phases=None):
        ids, scores, ps, expanded = real(self, pool, bucket, args, phases)
        ids = ids.copy()
        ids[:, 0] = np.where(ids[:, 0] >= 0, (ids[:, 0] + 1) % pool.max_global_id(), ids[:, 0])
        return ids, scores, ps, expanded

    _after_warm_up(monkeypatch, "_run_pool", altered)
    out = _run(OPEN, root)
    assert out["correct"] is False
    assert out["checks"]["dense_gap"]["value"] > out["checks"]["dense_gap"]["limit"]


def test_half_of_each_batch_left_unanswered_is_not_correct(monkeypatch, root):
    real = HybridSearchService._run_batch

    def half(self, bucket, entries):
        return real(self, bucket, entries[: max(len(entries) // 2, 1)])

    _after_warm_up(monkeypatch, "_run_batch", half)
    monkeypatch.setattr(harness, "DRAIN_S", 2.0)
    out = _run(OPEN, root)
    assert out["correct"] is False
    assert out["failed"] > 0 and out["checks"]["unanswered"]["value"] > 0


def test_a_broken_neighbour_list_is_not_correct(monkeypatch, root):
    monkeypatch.setattr(harness, "make_service", harness.make_service)
    faults.plant("shuffled_neighbours", harness)
    out = _run(OPEN, root)
    assert out["correct"] is False
    recall = out["checks"]["recall_at_10"]
    assert recall["fails"] == "below" and recall["value"] < recall["limit"]
    # every score it returns is still right: only recall sees the fault
    assert all(c["value"] <= c["limit"] for k, c in out["checks"].items() if k != "recall_at_10")


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(reference))
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names if m and m.startswith("repro")]
