"""jax.numpy port of ``repro.env.latency_model`` — vmap/jit-compatible.

Single source of truth: all constants (model pool, anchored times, weak /
busy penalties) are imported from the numpy reference module; nothing is
re-derived here.  The functions below reproduce the reference element for
element (test-enforced to 1e-5 over randomized actions / backgrounds /
weak-link patterns) while being traceable: every input, including the
``weak_e`` / ``busy_m_e`` / ``busy_m_c`` scalars, may be a traced JAX value,
so the whole thing can be ``vmap``-ed over a leading cell axis and stepped
inside ``lax.scan``.

One extension over the reference: an optional boolean ``mask`` marks which
of the (padded, fixed-width) user slots are real.  Masked-out slots
contribute neither contention nor response time, which is what lets one
stacked array hold cells with heterogeneous user counts (2–32 users in the
same fleet).  ``mask=None`` is exactly the reference semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro.analysis import envflags
from repro.env import latency_model as lm
from repro.sharding.runtime import exchange_psum

N_MODELS = lm.N_MODELS
N_ACTIONS = lm.N_ACTIONS
A_EDGE, A_CLOUD = lm.A_EDGE, lm.A_CLOUD

# The fused Pallas group-occupancy kernel is the default path; set
# REPRO_ORCH_KERNELS=0 to fall back to the segment_sum reference
# (diagnostic escape hatch, parity-tested identical).  Strictly parsed:
# only "0"/"1" are accepted — a typoed value raises at import instead of
# silently picking a kernel path.
USE_KERNELS = envflags.bool_flag(envflags.ORCH_KERNELS, True)


def group_slot_mask(groups: jnp.ndarray) -> jnp.ndarray:
    """(C, C) bool — ``mask[i, j]`` iff cells i and j share an edge group.

    The dense membership mask of the ``shared_edge`` coupling: row i
    selects exactly the slots whose occupancy cell i's edge server sees.
    Tests use it to assert occupancy conservation; the env uses the
    segment-sum form (:func:`group_occupancy`) which is O(C), not O(C²).
    """
    groups = jnp.asarray(groups)
    return groups[:, None] == groups[None, :]


def group_occupancy_ref(own: jnp.ndarray, groups: jnp.ndarray,
                        num_segments: int | None = None) -> jnp.ndarray:
    """Unfused reference: one ``segment_sum`` + gather."""
    groups = jnp.asarray(groups)
    n = groups.shape[0] if num_segments is None else num_segments
    totals = jax.ops.segment_sum(own, groups, num_segments=n)
    return totals[groups]


def group_occupancy(own: jnp.ndarray, groups: jnp.ndarray, *,
                    axis: str | None = None,
                    num_segments: int | None = None) -> jnp.ndarray:
    """(C,) total occupancy of each cell's group (own contribution
    included): ``out[i] = sum_j own[j] · [groups[j] == groups[i]]``.

    Equivalent to ``group_slot_mask(groups) @ own``.  Group ids must lie
    in [0, num_segments) (defaults to the local cell count).

    Two execution paths:

    - ``axis`` set (inside ``shard_map`` over a cell axis): groups may
      span shards, so per-shard segment totals over the *global* id
      space (``num_segments``) are ``psum``-reduced across ``axis``
      before the gather — exact cross-shard group occupancy.
    - otherwise: the fused Pallas kernel from
      ``repro.kernels.orchestration`` (default; ``REPRO_ORCH_KERNELS=0``
      falls back to :func:`group_occupancy_ref`).

    Whichever path runs, its device operations carry the XLA frontend
    attribute ``stage="occupancy"`` (over any enclosing stage), so a
    profiler trace names edge-group occupancy whatever implements it;
    the cross-shard ``psum`` is tagged ``stage="exchange"`` instead.
    """
    with set_xla_metadata(stage="occupancy"):
        if axis is not None:
            groups = jnp.asarray(groups)
            n = groups.shape[0] if num_segments is None else num_segments
            totals = jax.ops.segment_sum(own, groups, num_segments=n)
            totals = exchange_psum(totals, axis)
            return totals[groups]
        if USE_KERNELS:
            from repro.kernels.orchestration import group_occupancy_pallas
            return group_occupancy_pallas(own, jnp.asarray(groups))
        return group_occupancy_ref(own, groups, num_segments)


def group_coupling(own: jnp.ndarray, groups: jnp.ndarray, *,
                   axis: str | None = None,
                   num_segments: int | None = None) -> jnp.ndarray:
    """(C,) extra occupancy each cell sees from *co-located* cells (its
    edge group minus its own contribution).  Singleton groups → zero,
    which is the uncoupled-env parity guarantee."""
    return group_occupancy(own, groups, axis=axis,
                           num_segments=num_segments) - own


def action_accuracy(actions: jnp.ndarray) -> jnp.ndarray:
    """Per-request accuracy (%) for an action vector (any shape)."""
    accuracy = jnp.asarray(lm.ACCURACY)
    return jnp.where(actions < N_MODELS,
                     accuracy[jnp.minimum(actions, N_MODELS - 1)],
                     accuracy[0])


def response_times(actions, weak_s, weak_e,
                   busy_p_s=None, busy_m_s=None,
                   busy_m_e=False, busy_m_c=False,
                   bg_edge=0, bg_cloud=0, mask=None) -> jnp.ndarray:
    """Response time (ms) per user slot for one round of requests.

    actions: (n,) ints in [0, 10); weak_s: (n,) bool; weak_e: scalar bool;
    busy_*: background flags ((n,) or scalar; None → quiet); bg_edge /
    bg_cloud: background occupancy; mask: (n,) bool of real slots (None →
    all real).  All arguments may be traced.
    """
    actions = jnp.asarray(actions)
    n = actions.shape[-1]
    if busy_p_s is None:
        busy_p_s = jnp.zeros(n, bool)
    if busy_m_s is None:
        busy_m_s = jnp.zeros(n, bool)
    if mask is None:
        mask = jnp.ones(n, bool)
    t_local = jnp.asarray(lm.T_LOCAL)

    is_local = (actions < N_MODELS) & mask
    is_edge = (actions == A_EDGE) & mask
    is_cloud = (actions == A_CLOUD) & mask
    k_edge = is_edge.sum(-1) + bg_edge
    k_cloud = is_cloud.sum(-1) + bg_cloud

    tl = t_local[jnp.minimum(actions, N_MODELS - 1)]
    tl = tl * jnp.where(busy_p_s, lm.BUSY_CPU_LOCAL, 1.0)
    tl = tl * jnp.where(busy_m_s, lm.BUSY_MEM, 1.0)
    te = (lm.T_EDGE_D0 * jnp.maximum(1, k_edge)
          * jnp.where(busy_m_e, lm.BUSY_MEM, 1.0)
          + jnp.where(weak_e, lm.WEAK_E_EDGE, 0.0))
    tc = (lm.T_CLOUD_D0 * jnp.maximum(1, k_cloud)
          * jnp.where(busy_m_c, lm.BUSY_MEM, 1.0)
          + jnp.where(weak_e, lm.WEAK_E_CLOUD, 0.0))

    t = jnp.where(is_local, tl, 0.0)
    t = jnp.where(is_edge, te, t)
    t = jnp.where(is_cloud, tc, t)
    t = t + jnp.where(weak_s & mask, lm.WEAK_S_PENALTY, 0.0)
    return t


def round_metrics(actions, weak_s, weak_e, mask=None, **bg):
    """(average response time ms, average accuracy %) over the real slots."""
    t = response_times(actions, weak_s, weak_e, mask=mask, **bg)
    acc = action_accuracy(actions)
    if mask is None:
        return t.mean(-1), acc.mean(-1)
    denom = jnp.maximum(1, mask.sum(-1))
    return ((t * mask).sum(-1) / denom,
            (acc * mask).sum(-1) / denom)
