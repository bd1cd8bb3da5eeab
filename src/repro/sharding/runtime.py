"""Process-global mesh registry for modules that need explicit shard_map.

Two consumers today:

- the seed LM stack (MoE dispatch, where GSPMD replicates the scatter
  operands) — meshes with ``data``/``pod``/``model`` axes;
- the fleet serving engine (``repro.serve``) — a one-axis ``cells`` mesh
  built by :func:`cells_mesh`, over which ``serve_stream`` shard_maps
  the per-tick loop.

Launchers (dryrun/train/serve_fleet) call ``set_mesh_info(mesh)`` before
building the step function; model/engine code queries ``get_mesh_info()``
and falls back to the mesh-free path when None (single-device tests).
The two axis vocabularies never mix: a ``cells`` mesh carries no dp/tp
axes and vice versa, so ``dp_spec``/``tp_size`` keep their seed LM
semantics untouched.

Every reduction across the ``cells`` axis goes through
:func:`exchange_psum`, which tags it for a profiler trace.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import Mesh

CELLS_AXIS = "cells"


def exchange_psum(x, axis: str):
    """``psum`` of ``x`` over the mesh axis ``axis``, its device
    operations tagged ``stage="exchange"`` (over any enclosing stage), so
    a profiler trace names the cross-shard exchange."""
    with set_xla_metadata(stage="exchange"):
        return jax.lax.psum(x, axis)


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    mesh: Mesh
    dp_axes: tuple[str, ...]   # ("data",) or ("pod", "data"); () for cells
    tp_axis: str = "model"
    cells_axis: Optional[str] = None   # set iff this is a serving mesh

    @property
    def dp_spec(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def cells_size(self) -> int:
        if self.cells_axis is None:
            return 1
        return self.mesh.shape[self.cells_axis]


_CURRENT: Optional[MeshInfo] = None


def set_mesh_info(mesh: Optional[Mesh]) -> None:
    global _CURRENT
    if mesh is None:
        _CURRENT = None
        return
    if CELLS_AXIS in mesh.axis_names:
        _CURRENT = MeshInfo(mesh, dp_axes=(), cells_axis=CELLS_AXIS)
        return
    dp = (("pod", "data") if "pod" in mesh.axis_names else ("data",))
    _CURRENT = MeshInfo(mesh, dp)


def get_mesh_info() -> Optional[MeshInfo]:
    return _CURRENT


def cells_mesh(n_devices: Optional[int] = None) -> Mesh:
    """One-axis ``("cells",)`` mesh over the first ``n_devices`` devices
    (all of them when None).  On a CPU box, more than one device requires
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    import — the error message says so because it is the only way this
    can fail in CI."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if n > len(devices):
        raise ValueError(
            f"cells_mesh: asked for {n} devices but only {len(devices)} "
            f"visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before importing "
            f"jax")
    import numpy as np
    return Mesh(np.asarray(devices[:n]), (CELLS_AXIS,))
