"""``run.py`` prints no result and exits non-zero where it cannot measure:
without a TPU, and in a directory that holds only the benchmark's own
files."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import chipfixtures  # noqa: F401  (the benchmark on the path)

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP))

ARGS = ["--workload", "mamba-chat", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "chip", "run.py")]
        + ARGS, cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip")
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""
