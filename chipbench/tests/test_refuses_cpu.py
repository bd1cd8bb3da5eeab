"""A run without a TPU, or without the program, exits non-zero and
prints no result."""
import os
import shutil
import subprocess
import sys

from chipbench.lib.registry import ROOT

ARGS = ["--workload", "serve64_live", "--seed", "5", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cpu_run_is_refused():
    p = _run(ROOT, "chipbench/run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "chipbench/run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
