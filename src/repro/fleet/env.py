"""Functional, fully vectorized FleetEnv.

The numpy ``EdgeCloudEnv`` steps one user of one cell per Python call; this
module steps *every cell of a fleet at once* inside jit.  All per-cell
state — background flags, the partially-built action vector, charged
reward, the PRNG key — lives in a ``FleetState`` of stacked arrays, so one
``lax.scan`` over round positions simulates an entire fleet of rounds.

Semantics match ``EdgeCloudEnv`` exactly (test-enforced at n_max=5): the
same observation spec (layout owned by ``repro.specs.observation`` — both
envs encode through it), the same dense-shaping reward with terminal
contention settlement and graded accuracy penalty, and auto-reset on round
completion (fresh background, cleared actions).  Cells with fewer than
``n_max`` users simply complete (and reset) earlier, so every cell issues
one orchestration decision per step — heterogeneous fleets keep the
accelerator fully busy.

API (all functions returned by ``make_fleet_env`` are pure and jitted):

    env = make_fleet_env(FleetConfig(n_max=5))
    state = env.init(key, scenario)            # scenario: FleetScenario
    obs = env.observe(scenario, state)         # (C, cfg.state_dim) float32
    state, obs, reward, done, info = env.step(scenario, state, actions)
    state, traj = env.rollout(scenario, state, actions_TC)  # (T, C) scan

The scenario is an *argument*, not a closure constant, so the same jitted
step serves any fleet of the same (C, n_max) shape.  User-count swaps (for
Poisson trace replay) are only well-defined at round boundaries: call
``reset_rounds`` before stepping under a new ``n_users`` vector, otherwise
a cell mid-round would settle its reward against the wrong round total.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.economy.tiers import (EconomyProfile, TierEconomyState,
                                 init_economy, ticks_to_warm)
from repro.env.edge_cloud import (PENALTY_BASE, PENALTY_PER_PCT,
                                  REWARD_SCALE)
from repro.fleet import latency
from repro.fleet.workload import FleetScenario
from repro.sharding.runtime import exchange_psum
from repro.specs.observation import ObsInputs, ObservationSpec, make_spec


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    n_max: int = 5
    bg_busy_prob: float = 0.1
    quiet: bool = False  # disable background fluctuations (for eval)
    # Cross-cell contention: when True the cloud tier is one shared pool —
    # the cloud occupancy every cell sees is the *fleet-wide* sum of
    # assigned cloud requests, so offloading in one cell raises cloud
    # queueing latency in every other.  Off by default; with a single cell
    # the coupling term is identically zero (parity test-enforced).
    shared_cloud: bool = False
    # Shared-edge coupling: cells with the same ``scenario.edge_group`` id
    # co-locate on one edge server, so each cell's edge occupancy includes
    # its group peers' assigned edge requests.  Singleton groups (the
    # scenario default) make the coupling identically zero.
    shared_edge: bool = False
    # Observation layout variant (repro.specs.observation.SPEC_NAMES);
    # "base" is bit-compatible with the pre-spec Table-II layout.
    obs_spec: str = "base"
    # Set when the env runs *inside* a ``shard_map`` over a mesh axis of
    # cells: ``cell_axis`` names the axis and ``cell_axis_size`` its size.
    # The env then treats ``scenario.n_cells`` as the per-shard count and
    # reduces the cross-cell couplings (shared cloud occupancy, edge-group
    # occupancy, fleet-wide load aggregates) with ``psum`` over that axis
    # (``exchange_psum``: tagged ``stage="exchange"``), so a sharded fleet
    # is numerically identical to the same fleet on one device
    # (background draws are keyed per *global* cell id).
    cell_axis: str | None = None
    cell_axis_size: int = 1
    # Optional tier economics (repro.economy): when set, ``init`` seeds a
    # per-cell ``TierEconomyState`` on ``FleetState.econ`` and ``observe``
    # feeds the spec's ``economy`` block from it.  The env itself never
    # advances the state machine — the serve engine does, per tick —
    # and ``economy=None`` leaves every compiled program unchanged.
    economy: EconomyProfile | None = None

    def spec(self) -> ObservationSpec:
        return make_spec(self.obs_spec, self.n_max)

    @property
    def state_dim(self) -> int:
        return self.spec().dim


class FleetBackground(NamedTuple):
    busy_p_s: jnp.ndarray  # (C, n_max) bool
    busy_m_s: jnp.ndarray  # (C, n_max) bool
    busy_m_e: jnp.ndarray  # (C,) bool
    busy_m_c: jnp.ndarray  # (C,) bool
    bg_edge: jnp.ndarray   # (C,) int32
    bg_cloud: jnp.ndarray  # (C,) int32


class FleetState(NamedTuple):
    key: jnp.ndarray       # PRNG key for background resampling
    actions: jnp.ndarray   # (C, n_max) int32, -1 = undecided
    user: jnp.ndarray      # (C,) int32 — requesting-user cursor
    charged: jnp.ndarray   # (C,) float32 — dense reward charged so far
    bg: FleetBackground
    # tier-economy state (None unless FleetConfig.economy is set — the
    # trailing default keeps every existing constructor/pytree unchanged)
    econ: TierEconomyState | None = None


class FleetEnvFns(NamedTuple):
    init: callable
    observe: callable
    step: callable
    reset_rounds: callable
    rollout: callable


def make_fleet_env(cfg: FleetConfig) -> FleetEnvFns:
    n_max = cfg.n_max
    spec = cfg.spec()

    def _cell0(n_cells: int):
        """Global id of this shard's first cell (0 off-mesh)."""
        if cfg.cell_axis is None:
            return 0
        return jax.lax.axis_index(cfg.cell_axis) * n_cells

    def sample_background(key, n_cells: int) -> FleetBackground:
        """Background flags keyed per *global* cell id (``fold_in``), so
        the draws a cell sees are a function of (key, its id) only — the
        sharded env reproduces the single-device background bit-exactly
        from the same replicated key."""
        if cfg.quiet:
            zc = jnp.zeros((n_cells, n_max), bool)
            z = jnp.zeros((n_cells,), bool)
            zi = jnp.zeros((n_cells,), jnp.int32)
            return FleetBackground(zc, zc, z, z, zi, zi)
        p = cfg.bg_busy_prob

        def one_cell(cid):
            ks = jax.random.split(jax.random.fold_in(key, cid), 6)
            u = lambda k, shape: jax.random.uniform(k, shape)
            return FleetBackground(
                u(ks[0], (n_max,)) < p,
                u(ks[1], (n_max,)) < p,
                u(ks[2], ()) < p,
                u(ks[3], ()) < p,
                (u(ks[4], ()) < p / 2).astype(jnp.int32),
                (u(ks[5], ()) < p / 2).astype(jnp.int32),
            )

        return jax.vmap(one_cell)(_cell0(n_cells) + jnp.arange(n_cells))

    def init(key, scenario: FleetScenario) -> FleetState:
        n_cells = scenario.n_cells
        key, sub = jax.random.split(key)
        return FleetState(
            key=key,
            actions=jnp.full((n_cells, n_max), -1, jnp.int32),
            user=jnp.zeros((n_cells,), jnp.int32),
            charged=jnp.zeros((n_cells,), jnp.float32),
            bg=sample_background(sub, n_cells),
            econ=(init_economy(cfg.economy, n_cells, n_max)
                  if cfg.economy is not None else None),
        )

    def reset_rounds(state: FleetState) -> FleetState:
        """Abort any in-flight rounds: clear actions/cursor/charged but keep
        the PRNG key and background.  Required before swapping a scenario's
        ``n_users`` (e.g. per Poisson-trace row) so no cell settles a round
        against a user count it did not start with."""
        return state._replace(
            actions=jnp.full_like(state.actions, -1),
            user=jnp.zeros_like(state.user),
            charged=jnp.zeros_like(state.charged))

    def _n_cells_global(n_cells: int) -> int:
        return n_cells * cfg.cell_axis_size

    def _fleet_sum(x):
        """Sum over all cells of the fleet, across shards when sharded."""
        total = x.sum()
        if cfg.cell_axis is not None:
            total = exchange_psum(total, cfg.cell_axis)
        return total

    def _cloud_coupling(actions, mask):
        """(C,) extra cloud occupancy each cell sees from *other* cells'
        assigned cloud requests (zero unless cfg.shared_cloud)."""
        own = ((actions == latency.A_CLOUD) & mask).sum(-1)
        return _fleet_sum(own) - own

    def _edge_coupling(scenario, actions, mask):
        """(C,) extra edge occupancy from co-located cells' assigned edge
        requests (zero unless cfg.shared_edge / non-singleton groups)."""
        own = ((actions == latency.A_EDGE) & mask).sum(-1)
        return latency.group_coupling(
            own, scenario.edge_groups(), axis=cfg.cell_axis,
            num_segments=_n_cells_global(scenario.n_cells))

    def _round_times(scenario, state, actions):
        """Per-slot response times under the partial assignment (undecided
        slots run the d7 placeholder, exactly like the numpy env)."""
        a_eff = jnp.where(actions >= 0, actions, latency.N_MODELS - 1)
        mask = scenario.user_mask()
        bg_cloud = state.bg.bg_cloud
        if cfg.shared_cloud:
            bg_cloud = bg_cloud + _cloud_coupling(a_eff, mask)
        bg_edge = state.bg.bg_edge
        if cfg.shared_edge:
            bg_edge = bg_edge + _edge_coupling(scenario, a_eff, mask)
        return jax.vmap(latency.response_times)(
            a_eff, scenario.weak_s, scenario.weak_e,
            state.bg.busy_p_s, state.bg.busy_m_s,
            state.bg.busy_m_e, state.bg.busy_m_c,
            bg_edge, bg_cloud, mask)

    def observe(scenario: FleetScenario, state: FleetState) -> jnp.ndarray:
        """Observation under ``cfg.obs_spec`` — layout owned by
        ``repro.specs.observation``; this function only computes the
        semantic inputs (occupancies incl. couplings, committed accuracy,
        fleet/group load aggregates, constraint targets)."""
        mask = scenario.user_mask()
        own_edge = ((state.actions == latency.A_EDGE) & mask).sum(-1)
        own_cloud = ((state.actions == latency.A_CLOUD) & mask).sum(-1)
        k_edge = own_edge + state.bg.bg_edge
        k_cloud = own_cloud + state.bg.bg_cloud
        if cfg.shared_cloud:
            k_cloud = k_cloud + _cloud_coupling(state.actions, mask)
        if cfg.shared_edge:
            k_edge = k_edge + _edge_coupling(scenario, state.actions, mask)
        decided = (state.actions >= 0) & mask
        acc_sum = (latency.action_accuracy(jnp.maximum(state.actions, 0))
                   * decided).sum(-1)
        n_cells = scenario.n_cells
        # fleet-wide mean cloud occupancy (cloud_load block input):
        # every cell sees the same scalar — the cloud is one tier
        cloud_fleet = jnp.broadcast_to(
            _fleet_sum(own_cloud + state.bg.bg_cloud)
            / _n_cells_global(n_cells), (n_cells,))
        # per-group mean edge occupancy (edge_load block input)
        groups = scenario.edge_groups()
        edge_occ = own_edge + state.bg.bg_edge
        go = lambda v: latency.group_occupancy(
            v, groups, axis=cfg.cell_axis,
            num_segments=_n_cells_global(n_cells))
        group_sz = go(jnp.ones_like(groups))
        edge_group = go(edge_occ) / jnp.maximum(1, group_sz)
        eco = {}
        if cfg.economy is not None and state.econ is not None:
            price = jnp.asarray(cfg.economy.route_price(), jnp.float32)
            eco = dict(
                econ_state=state.econ.tier_state,
                econ_warm_ticks=ticks_to_warm(cfg.economy, state.econ),
                econ_price=jnp.broadcast_to(price[None, :],
                                            (n_cells, price.shape[0])))
        return spec.encode_jnp(ObsInputs(
            user=state.user, n_users=scenario.n_users,
            busy_p_s=state.bg.busy_p_s, busy_m_s=state.bg.busy_m_s,
            weak_s=scenario.weak_s, weak_e=scenario.weak_e,
            busy_m_e=state.bg.busy_m_e, busy_m_c=state.bg.busy_m_c,
            k_edge=k_edge, k_cloud=k_cloud, acc_sum=acc_sum,
            cloud_fleet=cloud_fleet, edge_group=edge_group,
            constraint=scenario.constraint,
            latency_target=scenario.latency_targets(), **eco))

    def step(scenario: FleetScenario, state: FleetState, actions_in):
        """One orchestration decision per cell. Returns
        (state', obs', reward, done, info); done cells auto-reset and
        report their round's art/acc/violated in ``info``."""
        n_cells = scenario.n_cells
        cell = jnp.arange(n_cells)
        n = scenario.n_users
        u = jnp.minimum(state.user, n_max - 1)
        acts = state.actions.at[cell, u].set(actions_in.astype(jnp.int32))
        mask = scenario.user_mask()

        times = _round_times(scenario, state, acts)
        t_i = times[cell, u]
        charged = state.charged + t_i
        user2 = state.user + 1
        done = user2 >= n

        nf = n.astype(jnp.float32)
        total = (times * mask).sum(-1)
        art = total / nf
        acc = ((latency.action_accuracy(jnp.where(acts >= 0, acts, 0))
                * mask).sum(-1) / nf)
        violated = acc < scenario.constraint - 1e-9
        settle = total - charged
        penalty = jnp.where(
            violated,
            PENALTY_BASE + PENALTY_PER_PCT * (scenario.constraint - acc),
            0.0)
        r_dense = -t_i / (nf * REWARD_SCALE)
        r_term = -(t_i + settle) / (nf * REWARD_SCALE) - penalty
        reward = jnp.where(done, r_term, r_dense).astype(jnp.float32)

        # auto-reset finished cells: fresh background, cleared round
        key, sub = jax.random.split(state.key)
        bg_new = sample_background(sub, n_cells)
        pick = lambda new, old: jnp.where(
            done.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
        state2 = FleetState(
            key=key,
            actions=jnp.where(done[:, None], -1, acts),
            user=jnp.where(done, 0, user2),
            charged=jnp.where(done, 0.0, charged).astype(jnp.float32),
            bg=jax.tree.map(pick, bg_new, state.bg),
            econ=state.econ,  # advanced by the serve engine, not here
        )
        info = {"art": art, "acc": acc, "violated": violated,
                "t_ms": jnp.where(done, t_i + jnp.maximum(0.0, settle), t_i),
                # (C, n_max) per-slot response times under the current
                # assignment; at ``done`` this is the completed round's
                # final per-request service latency (padded slots zero) —
                # what the request-level serving engine records per request
                "times": times * mask,
                "actions": acts}
        return state2, observe(scenario, state2), reward, done, info

    def rollout(scenario: FleetScenario, state: FleetState, actions):
        """Scan-friendly multi-step rollout: apply a (T, C) action sequence
        in one ``lax.scan`` and return (state', trajectory) with every
        per-step output stacked on a leading T axis — the primitive the
        hltrain trainer, trace replay, and tests build on.

        trajectory = {"obs": (T, C, D), "reward": (T, C), "done": (T, C),
                      "art"/"acc"/"violated"/"t_ms"/"actions": per-step
                      info arrays}.
        """
        def body(st, a_t):
            st, obs, reward, done, info = step(scenario, st, a_t)
            return st, dict(info, obs=obs, reward=reward, done=done)

        return jax.lax.scan(body, state, actions)

    return FleetEnvFns(init=jax.jit(init),
                       observe=jax.jit(observe),
                       step=jax.jit(step),
                       reset_rounds=jax.jit(reset_rounds),
                       rollout=jax.jit(rollout))
