"""Ahead-of-time compiles of the main path for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a ``v5e:2x2`` topology that is described, not present.  That refuses
what interpret-mode tests cannot see — blocks not aligned to the tiling,
layouts Mosaic does not accept, fast memory over budget — at no chip
time.  Nothing here runs; results are the CPU tests' business.

The topology is described inside a module-scoped fixture (never at
import), so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.  The persistent compilation
cache is off around these compiles: an entry written for a described chip
cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import registry
from repro.kernels import orchestration
from repro.kernels.orchestration import (group_occupancy_pallas,
                                         queue_admit_pallas)

CELLS = (32, 4096, 65536)
# per-tick arrival lanes: a small burst, and the fleet's real burst at
# 3 requests per cell per 250 ms round (~0.6 per cell per 50 ms tick)
LANES = {32: 16, 4096: 2600, 65536: 40000}
QUEUE_CAP = 64


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("c", CELLS)
def test_group_occupancy_compiles(one_chip, c):
    fn = jax.jit(lambda own, g: group_occupancy_pallas(own, g,
                                                       interpret=False))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one_chip)).compile()
    assert _custom_calls(compiled) == 1


@pytest.mark.parametrize("c", CELLS)
def test_queue_admit_compiles(one_chip, c):
    a = LANES[c]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    fn = jax.jit(lambda *args: queue_admit_pallas(*args, interpret=False))
    compiled = fn.lower(
        i32(c, QUEUE_CAP), i32(c), i32(c), i32(a), i32(a),
        jax.ShapeDtypeStruct((a,), jnp.bool_, sharding=one_chip)).compile()
    assert _custom_calls(compiled) == 1


def test_serve_epoch_compiles_with_mosaic_kernels(one_chip, monkeypatch):
    """The benchmark's coupled serve epoch at 256 cells: on a TPU backend
    the tick calls both kernels compiled, never interpreted.  This
    process's backend is the CPU, so the test steers the backend check."""
    monkeypatch.setattr(orchestration, "interpret_mode", lambda: False)
    fn, args, kwargs = registry._serve_build(registry._SERVE_SHARDED_CFG,
                                             n_cells=256)
    compiled = fn.lower(*_abstract(args, one_chip), **kwargs).compile()
    assert _custom_calls(compiled) >= 1


def test_serve_epoch_kernels_carry_names_and_stage_tags(one_chip,
                                                        monkeypatch):
    """Compiled for the chip, the tick's kernel custom calls keep their
    names and stage tags (XLA frontend attributes, which a profiler trace
    prints with each operation): edge-group occupancy ``stage=
    "occupancy"`` over the enclosing observe/step stage, queue admission
    ``stage="admit"``."""
    monkeypatch.setattr(orchestration, "interpret_mode", lambda: False)
    fn, args, kwargs = registry._serve_build(registry._SERVE_SHARDED_CFG,
                                             n_cells=256)
    text = fn.lower(*_abstract(args, one_chip), **kwargs).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    occupancy = [ln for ln in calls if ln.startswith("%group_occupancy")]
    admit = [ln for ln in calls if ln.startswith("%queue_admit")]
    assert occupancy and admit
    assert len(occupancy) + len(admit) == len(calls)
    assert all('stage="occupancy"' in ln for ln in occupancy)
    assert all('stage="admit"' in ln for ln in admit)


def test_hltrain_run_compiles(one_chip):
    fn, (state, scenario, start), kwargs = registry._hltrain_build()
    compiled = fn.lower(_abstract(state, one_chip),
                        _abstract(scenario, one_chip), start,
                        **kwargs).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
