"""``chip_smoke.py`` keeps working: every phase of it, rehearsed on the CPU
at a tiny size, and its refusal to run without a TPU.

Each run is a subprocess: the four-chip rehearsal needs fake CPU devices,
which only a fresh process can be given, and a run must not change this
process's JAX configuration (the smoke turns the compile cache on)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _smoke(tmp_path, *args, devices: int = 1) -> subprocess.CompletedProcess:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
    )
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )


def _last_line(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rehearse_one_chip(tmp_path):
    got = _last_line(_smoke(tmp_path, "--rehearse"))
    assert got == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_rehearse_four_chips(tmp_path):
    got = _last_line(_smoke(tmp_path, "--four-chips", "--rehearse", devices=4))
    assert got["ok"] is True and got["device"]["count"] == 4


def test_refuses_to_run_without_a_tpu(tmp_path):
    proc = _smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
