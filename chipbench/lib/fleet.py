"""The fleet generator: a copy of ``random_fleet`` in
``repro.fleet.workload``, kept here so that the benchmark's fleets do not
move when the program's generator does.

Each cell draws a weak-link probability p ~ U(0, weak_s_prob_max),
Bernoulli(p) weak end-node flags for every slot, a weak-edge flag, a user
count in [n_users_min, n_max], an accuracy constraint from the Table V
levels and a latency target from the pool; ``cells_per_edge`` consecutive
cells share one edge server.  One jitted call on the device draws it all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FIELDS = ("weak_s", "weak_e", "n_users", "constraint", "latency_target",
          "edge_group")


@functools.partial(jax.jit, static_argnames=(
    "n_cells", "n_max", "n_users_min", "weak_s_prob_max", "weak_e_prob",
    "cells_per_edge"))
def _draw(key, constraint_pool, latency_pool, *, n_cells, n_max,
          n_users_min, weak_s_prob_max, weak_e_prob, cells_per_edge):
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    p_cell = jax.random.uniform(k1, (n_cells, 1)) * weak_s_prob_max
    weak_s = jax.random.uniform(k2, (n_cells, n_max)) < p_cell
    weak_e = jax.random.uniform(k3, (n_cells,)) < weak_e_prob
    n_users = jax.random.randint(k4, (n_cells,), n_users_min, n_max + 1,
                                 jnp.int32)
    constraint = constraint_pool[jax.random.randint(
        k5, (n_cells,), 0, constraint_pool.shape[0])]
    latency = latency_pool[jax.random.randint(
        k6, (n_cells,), 0, latency_pool.shape[0])]
    edge_group = (jnp.arange(n_cells, dtype=jnp.int32)
                  // max(1, cells_per_edge))
    return weak_s, weak_e, n_users, constraint, latency, edge_group


def draw_fleet(key, spec: dict) -> dict:
    """``spec`` is the ``fleet`` block of a configuration file.  Returns
    the per-cell arrays named in ``FIELDS``, on the device."""
    out = _draw(jnp.asarray(key),
                jnp.asarray(np.asarray(spec["constraint_pool"], np.float32)),
                jnp.asarray(np.asarray(spec["latency_pool"], np.float32)),
                n_cells=int(spec["n_cells"]), n_max=int(spec["n_max"]),
                n_users_min=int(spec["n_users_min"]),
                weak_s_prob_max=float(spec["weak_s_prob_max"]),
                weak_e_prob=float(spec["weak_e_prob"]),
                cells_per_edge=int(spec["cells_per_edge"]))
    return dict(zip(FIELDS, out))


def to_host(fleet: dict) -> dict:
    return {k: np.asarray(v) for k, v in fleet.items()}
