"""Tests for the repro.fleet vectorized simulation subsystem.

Covers the acceptance contract of the fleet PR:
  * fleet.latency ≡ env.latency_model to 1e-5 over ≥1000 randomized cases
  * fleet.solver ≡ brute_force_optimal on every scenario×constraint at n=5
  * fleet.solver handles n=32 instances in < 1 s each
  * FleetEnv step/observe/reward parity with the numpy EdgeCloudEnv
  * workload generators produce well-formed heterogeneous fleets
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.env import latency_model as lm
from repro.env.edge_cloud import (EdgeCloudEnv, EnvConfig,
                                  brute_force_optimal)
from repro.env.scenarios import SCENARIOS, CONSTRAINTS, Scenario
from repro.fleet import latency as fl
from repro.fleet import (FleetConfig, make_fleet_env, from_table4,
                         random_fleet, solve_optimal, make_greedy_evaluator)
from repro.fleet.workload import poisson_round_trace
from repro.core.networks import init_mlp_net


# ---------------------------------------------------------------- latency
def test_latency_matches_numpy_reference_1000_cases():
    """≥1000 randomized (actions, background, weak-link) cases, 1e-5."""
    with jax.enable_x64(True):
        fn = jax.jit(jax.vmap(fl.response_times))
        acc_fn = jax.jit(fl.action_accuracy)
        rng = np.random.default_rng(0)
        total = 0
        for n in (2, 3, 5, 8):
            B = 300
            a = rng.integers(0, lm.N_ACTIONS, (B, n))
            ws = rng.random((B, n)) < 0.35
            we = rng.random(B) < 0.5
            bps = rng.random((B, n)) < 0.3
            bms = rng.random((B, n)) < 0.3
            bme = rng.random(B) < 0.3
            bmc = rng.random(B) < 0.3
            be = rng.integers(0, 3, B)
            bc = rng.integers(0, 3, B)
            mask = np.ones((B, n), bool)
            got = np.asarray(fn(jnp.asarray(a), jnp.asarray(ws),
                                jnp.asarray(we), jnp.asarray(bps),
                                jnp.asarray(bms), jnp.asarray(bme),
                                jnp.asarray(bmc), jnp.asarray(be),
                                jnp.asarray(bc), jnp.asarray(mask)))
            ref = np.stack([
                lm.response_times(a[i], ws[i], bool(we[i]), bps[i], bms[i],
                                  bool(bme[i]), bool(bmc[i]), int(be[i]),
                                  int(bc[i]))
                for i in range(B)])
            np.testing.assert_allclose(got, ref, atol=1e-5, rtol=0)
            np.testing.assert_allclose(np.asarray(acc_fn(jnp.asarray(a))),
                                       lm.action_accuracy(a), atol=1e-5)
            total += B
        assert total >= 1000


def test_latency_mask_excludes_padded_slots():
    """Masked slots contribute neither contention nor time."""
    a = jnp.array([8, 8, 9, 0, 8])  # last slot padded away
    ws = jnp.zeros(5, bool)
    mask = jnp.array([True, True, True, True, False])
    t = np.asarray(fl.response_times(a, ws, False, mask=mask))
    # only 2 real edge users → each pays T_EDGE * 2
    np.testing.assert_allclose(t[0], lm.T_EDGE_D0 * 2)
    assert t[4] == 0.0


# ----------------------------------------------------------------- solver
def test_solver_matches_brute_force_every_cell_n5():
    for name in ("A", "B", "C", "D"):
        for cname, c in CONSTRAINTS.items():
            bf = brute_force_optimal(SCENARIOS[name], c, 5)
            sv = solve_optimal(SCENARIOS[name], c, 5)
            assert abs(bf["art"] - sv["art"]) < 1e-9, (name, cname)
            assert abs(bf["acc"] - sv["acc"]) < 1e-9, (name, cname)
            assert np.array_equal(bf["actions"], sv["actions"]), \
                (name, cname, bf["actions"], sv["actions"])


def test_solver_matches_brute_force_random_n4():
    rng = np.random.default_rng(7)
    for trial in range(5):
        sc = Scenario("rand", tuple(rng.random(4) < 0.4),
                      bool(rng.random() < 0.5))
        c = float(rng.choice(list(CONSTRAINTS.values())))
        bf = brute_force_optimal(sc, c, 4)
        sv = solve_optimal(sc, c, 4)
        assert abs(bf["art"] - sv["art"]) < 1e-9
        assert np.array_equal(bf["actions"], sv["actions"])


def test_solver_n32_under_one_second():
    rng = np.random.default_rng(3)
    for trial in range(3):
        sc = Scenario("big", tuple(rng.random(32) < 0.3),
                      bool(rng.random() < 0.5))
        c = float(rng.choice(list(CONSTRAINTS.values())))
        t0 = time.time()
        r = solve_optimal(sc, c, 32)
        assert time.time() - t0 < 1.0
        assert r["acc"] >= c - 1e-9
        assert len(r["actions"]) == 32


# ---------------------------------------------------------------- FleetEnv
def test_fleet_env_matches_numpy_env_quiet_rounds():
    cfg = FleetConfig(n_max=5, quiet=True)
    env = make_fleet_env(cfg)
    scn = from_table4(names=("B",), constraints=("85%",), n_users=5)
    state = env.init(jax.random.PRNGKey(0), scn)
    nenv = EdgeCloudEnv(EnvConfig(SCENARIOS["B"], CONSTRAINTS["85%"],
                                  n_users=5, seed=0, quiet=True))
    obs_n = nenv.reset()
    np.testing.assert_allclose(np.asarray(env.observe(scn, state))[0],
                               obs_n, atol=1e-5)
    rng = np.random.default_rng(42)
    for step in range(15):  # three full rounds incl. auto-reset boundaries
        a = int(rng.integers(lm.N_ACTIONS))
        obs_n, r_n, done_n, info_n = nenv.step(a)
        state, obs_f, r_f, done_f, info_f = env.step(scn, state,
                                                     jnp.array([a]))
        assert bool(done_f[0]) == done_n
        np.testing.assert_allclose(float(r_f[0]), r_n, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(obs_f)[0], obs_n, atol=1e-5)
        if done_n:
            np.testing.assert_allclose(float(info_f["art"][0]),
                                       info_n["art"], rtol=1e-5)
            np.testing.assert_allclose(float(info_f["acc"][0]),
                                       info_n["acc"], rtol=1e-5)
            assert bool(info_f["violated"][0]) == info_n["violated"]


def test_fleet_env_heterogeneous_user_counts():
    """Cells with 2..5 users complete rounds at their own cadence."""
    cfg = FleetConfig(n_max=5, quiet=True)
    env = make_fleet_env(cfg)
    scn = random_fleet(jax.random.PRNGKey(1), 64, n_max=5, n_users_min=2)
    state = env.init(jax.random.PRNGKey(2), scn)
    dones = []
    for _ in range(5):
        state, obs, r, done, info = env.step(
            scn, state, jnp.zeros(64, jnp.int32))
        assert obs.shape == (64, cfg.state_dim)
        dones.append(np.asarray(done))
    dones = np.stack(dones)  # (5, 64)
    n_users = np.asarray(scn.n_users)
    # first completion happens exactly at step n_users-1 for every cell
    np.testing.assert_array_equal(dones.argmax(axis=0), n_users - 1)


def test_greedy_evaluator_vs_solver_optimum():
    """No *feasible* policy round can beat the exact constrained optimum —
    the batched evaluator's ART may only undercut the solver's on cells
    where it violates the accuracy constraint."""
    cfg = FleetConfig(n_max=5, quiet=True)
    scn = random_fleet(jax.random.PRNGKey(5), 32, n_max=5)
    params = init_mlp_net(jax.random.PRNGKey(6),
                          (cfg.state_dim, 32, lm.N_ACTIONS))
    ev = make_greedy_evaluator(cfg)
    info = ev(params, scn, jax.random.PRNGKey(7))
    opt = np.array([solve_optimal(*scn.cell(i))["art"]
                    for i in range(scn.n_cells)])
    art = np.asarray(info["art"])
    violated = np.asarray(info["violated"])
    assert np.all(art[~violated] >= opt[~violated] - 1e-3)


# ----------------------------------------------------- shared-edge coupling
def test_group_occupancy_conservation():
    """Per-group occupancy is conserved: the segment-sum path equals the
    dense per-group slot mask, and own + coupling == group total."""
    rng = np.random.default_rng(0)
    groups = jnp.asarray(rng.integers(0, 5, 16), jnp.int32)
    own = jnp.asarray(rng.integers(0, 4, 16), jnp.int32)
    total = fl.group_occupancy(own, groups)
    dense = fl.group_slot_mask(groups) @ own
    np.testing.assert_array_equal(np.asarray(total), np.asarray(dense))
    np.testing.assert_array_equal(
        np.asarray(fl.group_coupling(own, groups) + own),
        np.asarray(total))
    # every group's total is the sum of its members' own occupancy
    for g in range(5):
        members = np.asarray(groups) == g
        if members.any():
            assert np.all(np.asarray(total)[members]
                          == np.asarray(own)[members].sum())


def test_shared_edge_singleton_groups_parity():
    """With singleton edge groups (the scenario default) the coupling is
    identically zero: trajectories match the uncoupled env bit-for-bit."""
    scn = random_fleet(jax.random.PRNGKey(4), 4, n_max=5, n_users_min=5)
    assert scn.edge_group is not None  # sampled, 1 cell per edge
    e0 = make_fleet_env(FleetConfig(n_max=5, quiet=True))
    e1 = make_fleet_env(FleetConfig(n_max=5, quiet=True, shared_edge=True))
    s0 = e0.init(jax.random.PRNGKey(0), scn)
    s1 = e1.init(jax.random.PRNGKey(0), scn)
    rng = np.random.default_rng(5)
    for _ in range(12):
        a = jnp.asarray(rng.integers(0, lm.N_ACTIONS, 4), jnp.int32)
        s0, o0, r0, d0, _ = e0.step(scn, s0, a)
        s1, o1, r1, d1, _ = e1.step(scn, s1, a)
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))


def test_shared_edge_couples_colocated_cells():
    """Two cells on one edge server see each other's edge occupancy."""
    scn = random_fleet(jax.random.PRNGKey(1), 2, n_max=5, n_users_min=5,
                       weak_s_prob_max=0.0, weak_e_prob=0.0,
                       cells_per_edge=2)
    a_edge = jnp.full(2, lm.A_EDGE, jnp.int32)
    for shared, expect_k in ((False, 1), (True, 2)):
        env = make_fleet_env(FleetConfig(n_max=5, quiet=True,
                                         shared_edge=shared))
        st = env.init(jax.random.PRNGKey(2), scn)
        st, _, _, _, info = env.step(scn, st, a_edge)
        np.testing.assert_allclose(np.asarray(info["t_ms"]),
                                   lm.T_EDGE_D0 * expect_k)


def test_shared_edge_off_by_default():
    assert FleetConfig().shared_edge is False


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, lm.N_ACTIONS - 1), min_size=10,
                    max_size=10),
           st.integers(0, 4), st.integers(0, 2 ** 31 - 1))
    def test_property_colocated_load_never_improves_latency(
            acts, flip_slot, seed):
        """Adding edge load to one cell never *improves* a co-located
        cell's latency: flipping any of cell A's decisions to the edge
        tier can only raise (never lower) cell B's round time."""
        scn = random_fleet(jax.random.PRNGKey(seed % 1000), 2, n_max=5,
                           n_users_min=5, cells_per_edge=2)
        env = make_fleet_env(FleetConfig(n_max=5, quiet=True,
                                         shared_edge=True))
        base = np.asarray(acts, np.int64).reshape(2, 5)
        more = base.copy()
        more[0, flip_slot] = lm.A_EDGE  # cell A pushes one request to edge
        arts = []
        for joint in (base, more):
            st_ = env.init(jax.random.PRNGKey(0), scn)
            _, traj = env.rollout(scn, st_,
                                  jnp.asarray(joint.T, jnp.int32))
            arts.append(float(np.asarray(traj["art"])[-1, 1]))  # cell B
        assert arts[1] >= arts[0] - 1e-6
except ImportError:  # pragma: no cover - hypothesis is optional
    pass


# ---------------------------------------------------------------- workload
def test_random_fleet_well_formed():
    scn = random_fleet(jax.random.PRNGKey(9), 128, n_max=32,
                       n_users_min=2, n_users_max=32)
    assert scn.weak_s.shape == (128, 32)
    n_users = np.asarray(scn.n_users)
    assert n_users.min() >= 2 and n_users.max() <= 32
    # weak flags exist beyond the current user count so Poisson replay can
    # activate extra users with realistic link quality
    assert np.asarray(scn.weak_s).any()
    assert np.all(np.isin(np.asarray(scn.constraint),
                          np.float32(list(CONSTRAINTS.values()))))


def test_poisson_round_trace_bounds():
    scn = random_fleet(jax.random.PRNGKey(10), 16, n_max=8)
    trace = poisson_round_trace(jax.random.PRNGKey(11), scn, 50, rate=3.0)
    assert trace.shape == (50, 16)
    t = np.asarray(trace)
    assert t.min() >= 1 and t.max() <= 8
