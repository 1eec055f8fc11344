"""The benchmark's command refuses to run without the system under test or
without a TPU, and prints no result line then."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import _bench_cells

ROOT = _bench_cells.ROOT
ARGS = ["--workload", "whisper-tiny.fl-paper", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    got = _run(tmp_path)
    assert got.returncode != 0
    assert got.stdout.strip() == ""


def test_refuses_without_a_tpu():
    got = _run(ROOT)
    assert got.returncode == 3
    assert "needs 1 TPU" in got.stderr
    assert got.stdout.strip() == ""
