"""The measurement path refuses what is not the chip, and a directory that
holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import harness

ROOT = Path(harness.ROOT)
CELL = "qwen2-0.5b-bf16.decode-backlog"


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_run_exits_nonzero_naming_the_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "tpu" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
