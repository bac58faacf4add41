"""run.py needs a TPU: anywhere else it exits non-zero and prints no
result line, also from a directory that holds only the benchmark."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen2-0.5b.chat", "--seed", "4000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "'cpu'" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
