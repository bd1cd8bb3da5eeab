"""``chip_smoke.py``'s control flow at tiny size on the CPU, and the
compile-cache placement its entry points share.

The script itself refuses to run off a TPU; its phase functions take
their sizes as arguments, so every phase is rehearsed here at 8 cells and
one stream epoch, with kernels interpreted because the backend is the
CPU.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _last_json(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_kernels_phase_tiny(smoke, capsys):
    line = smoke.phase_kernels(n_cells=64, n_lanes=40)
    assert line == _last_json(capsys)
    assert line["phase"] == "kernels" and line["admitted"] > 0
    # interpreted on the CPU backend: no Mosaic call in the program
    assert line["tpu_custom_calls"] == {"group_occupancy": 0,
                                        "queue_admit": 0}
    assert line["memory_source"] == "host_rss"


def test_serve_bench_phase_tiny(smoke):
    line = smoke.phase_serve_bench(n_cells=8, rounds=4, n_epochs=1)
    for name in ("greedy", "cost_greedy_spot"):
        assert line[name]["audit"]["ok"]
        assert line[name]["served_requests"] > 0
    assert line["cost_greedy_spot"]["cost_per_1k_requests"] > 0


def test_large_fleet_phase_tiny(smoke):
    line = smoke.phase_large_fleet(n_cells=8, horizon_ms=500.0, n_epochs=1)
    assert line["audit"]["ok"] and line["served_requests"] > 0
    assert line["epoch_tpu_custom_calls"] == 0


def test_hltrain_phase_tiny(smoke):
    line = smoke.phase_hltrain(n_cells=8, n_max=3, epochs=1, chunk=1,
                               serve_cells=8, rounds=4, n_epochs=1)
    assert line["steps"] > 0
    assert line["serve"]["audit"]["ok"]


def test_cells_mesh_phase_tiny(smoke):
    line = smoke.phase_cells_mesh(n_chips=1, n_cells=8, horizon_ms=500.0,
                                  n_epochs=1)
    assert line["records_max_abs_diff"] <= 1e-5
    assert line["audit"]["ok"]


def test_main_refuses_off_tpu(smoke, capsys, monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_script_alone_fails_without_result(tmp_path):
    """Outside a checkout the script has no program to run."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""


def test_imports_leave_xla_flags_alone():
    """Nothing chip_smoke.py imports may rewrite XLA_FLAGS (the LM
    dry-run launcher forces host devices when imported)."""
    code = ("import importlib.util, os, sys\n"
            f"spec = importlib.util.spec_from_file_location('s', "
            f"{str(ROOT / 'chip_smoke.py')!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "print(os.environ.get('XLA_FLAGS', ''))\n"
            "print('repro.launch.dryrun' in sys.modules)\n")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.splitlines()[-2:] == ["", "False"]


# ------------------------------------------------------ compile cache
def test_compile_cache_env_dir_is_used_as_is(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_lands_in_env_dir(tmp_path):
    """A process started with JAX_COMPILATION_CACHE_DIR writes its
    compiled programs there."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "use_compile_cache()\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0.0)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert any((tmp_path / "cache").iterdir())
