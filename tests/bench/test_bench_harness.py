"""The benchmark harness is driven by data.

* every cell of ``BENCHMARK.json`` runs for 2 s under ``--rehearse`` on the
  CPU and prints a last line of the result's shape, with no device metric;
* a cell added as files only (a configuration, a traffic mix and a metric
  reader in a directory of their own) is found by name and run;
* a run without ``--rehearse`` and without a TPU exits non-zero and prints
  nothing on standard output, and so does a run from a directory that holds
  only the benchmark's own files.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _env(tmp_path: Path) -> dict:
    # one compute thread: the suite's other workers share the machine
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path), TMPDIR=str(tmp_path),
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1")
    env.pop("PYTHONPATH", None)  # the command finds the program by itself
    return env


def _run(args, cwd: Path, tmp_path: Path, timeout: float = 600):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args],
        cwd=cwd, env=_env(tmp_path), capture_output=True, text=True, timeout=timeout,
    )


@pytest.fixture(scope="module")
def checkout(tmp_path_factory) -> Path:
    """A copy of the benchmark and the program, so that rehearsal caches land
    outside the repository."""
    dst = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for d in ("bench", "src"):
        shutil.copytree(ROOT / d, dst / d, ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    return dst


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_rehearses_and_prints_the_result_line(cell, checkout, tmp_path):
    proc = _run(
        ["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "0",
         "--rehearse"],
        checkout, tmp_path,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want
    # a CPU rehearsal reports no device metric
    assert all(m["value"] is None for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["memory_peak_bytes"] is None
    # each compared number beside its limit, last on standard error too
    tail = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    for (name, c), err_line in zip(line["checks"].items(), tail):
        assert c["value"] <= c["limit"] if c["fails"] == "above" else c["value"] >= c["limit"], name
        assert err_line.startswith(f"check {name}: "), err_line


def test_a_cell_added_as_files_only_is_found_by_name(tmp_path):
    root = tmp_path / "repo"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "metrics").mkdir()
    cfg = json.loads((ROOT / "bench" / "configs" / "msmarco-splade.json").read_text())
    cfg = {**cfg, **cfg.pop("rehearse"), "name": "tiny", "n_docs": 512}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench" / "traffic" / "open-steady.json").read_text())
    mix.update(rate_qps=30, clients=2, query_pool=64)
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "requests_seen.tiny.py").write_text(
        "def read(run):\n    return float(run.counters['requests'])\n"
    )
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny", "traffic": "tiny-mix", "chips": 1}],
        "end_to_end": [{"name": "latency_p50_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "requests_seen.tiny", "unit": "requests",
                       "moves": "latency_p50_ms", "workloads": ["tiny.mix"]}],
    }))
    cell = harness.load_cell("tiny.mix", root)
    assert cell.cfg["name"] == "tiny" and cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["requests_seen.tiny"]
    out = harness.run("tiny.mix", 5, 1.0, True, True, time.time(), root)
    assert out["correct"] is True, out["checks"]
    assert list(out["metrics"]) == ["requests_seen.tiny"]
    assert (root / "bench" / ".cache" / "index").is_dir()


def test_no_tpu_and_no_rehearse_exits_nonzero_without_a_result(checkout, tmp_path):
    proc = _run(
        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"], checkout, tmp_path,
        timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_the_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, alone / p, ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _run(
        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        alone, tmp_path, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
