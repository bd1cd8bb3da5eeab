"""RL orchestrator training launcher (the paper's experiment driver).

Single-cell (the paper's testbed, Python env loop):

    PYTHONPATH=src python -m repro.launch.rl_train --algo HL --users 5 \
        --scenario A --constraint 89% [--ckpt results/hl_agent.msgpack]

Fleet-scale (jitted hltrain over repro.fleet; the default workload is a
user-count *curriculum* 2 → n_max of random topologies, one stage per
epoch chunk):

    PYTHONPATH=src python -m repro.launch.rl_train --algo HL --fleet \
        --cells 256 --n-max 8 --epochs 60 [--no-curriculum] \
        [--obs-spec base|contention|constraint|full] \
        [--shared-cloud] [--shared-edge] [--cells-per-edge 4]

``--ckpt`` (both paths) writes a versioned ``repro.policy`` PolicyBundle —
params + obs-spec + n_max + schema version — loadable by the trace-driven
serving gateway: ``python -m repro.launch.serve_fleet --bundle <path>``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.agent import HLAgent, HLHyperParams, ConvergenceTracker
from repro.core.baselines import DQLAgent, QLAgent
from repro.env.edge_cloud import (EdgeCloudEnv, EnvConfig,
                                  brute_force_optimal, decision_string)
from repro.env.scenarios import SCENARIOS, CONSTRAINTS
from repro.launch.compile_cache import use_compile_cache
from repro.policy.bundle import PolicyBundle, save_bundle
from repro.specs.observation import SPEC_NAMES


def run_fleet(args):
    """Fleet-scale HL training: curriculum-sampled random fleets through
    the fully-jitted repro.hltrain trainer, scored against fleet.solver."""
    from repro.fleet import (FleetConfig, random_fleet, curriculum_fleets)
    from repro.hltrain import (FleetHLParams, make_hl_trainer,
                               evaluate_vs_solver, run_curriculum)

    cfg = FleetConfig(n_max=args.n_max, shared_cloud=args.shared_cloud,
                      shared_edge=args.shared_edge,
                      obs_spec=args.obs_spec)
    fleet_kw = dict(cells_per_edge=args.cells_per_edge)
    # buffers must hold at least one fleet-wide batched write per step
    hp = FleetHLParams(seed=args.seed, epochs=args.epochs,
                       plan_cap=max(4096, args.cells),
                       direct_cap=max(65536, 8 * args.cells),
                       world_cap=max(65536, 8 * args.cells))
    trainer = make_hl_trainer(cfg, hp)
    key = jax.random.PRNGKey(args.seed)
    k_fleet, k_init, k_eval = jax.random.split(key, 3)

    chunk = max(1, args.chunk)
    n_stages = -(-args.epochs // chunk)  # ceil
    if args.curriculum:
        stages = curriculum_fleets(k_fleet, args.cells, n_stages,
                                   start=2, end=args.n_max, **fleet_kw)
    else:
        stages = [random_fleet(k_fleet, args.cells, n_max=args.n_max,
                               **fleet_kw)] * n_stages
    print(f"fleet training: {args.cells} cells × n_max={args.n_max}, "
          f"obs spec '{cfg.obs_spec}' ({cfg.spec().describe()}), "
          f"{args.epochs} epochs in {n_stages} stages "
          f"({'curriculum 2→' + str(args.n_max) if args.curriculum else 'fixed fleet'})")

    def on_stage(s, scn, state, m):
        start = s * chunk
        n = min(chunk, args.epochs - start)
        print(f"stage {s + 1}/{n_stages}: epochs {start}–{start + n - 1}, "
              f"users ≤ {int(np.asarray(scn.n_users).max())}, "
              f"mean_r {float(np.asarray(m['mean_reward'])[-1]):.4f}, "
              f"eps {float(np.asarray(m['epsilon'])[-1]):.3f}, "
              f"real_steps {int(state.real_steps):,}")

    t0 = time.time()
    state = run_curriculum(trainer, stages, args.epochs, chunk, k_init,
                           on_stage)
    wall = time.time() - t0
    print(f"\ntrained in {wall:.0f}s wall — "
          f"{int(state.real_steps):,} real interactions "
          f"({int(state.real_steps) / wall:,.0f} steps/s incl. compile)")

    if args.shared_cloud:
        print("note: the solver optimum is per-cell (ignores the shared-"
              "cloud coupling), so it is a lower bound and the gap below "
              "is structurally inflated")
    final = evaluate_vs_solver(state.dqn.params, stages[-1], cfg,
                               key=k_eval)
    print(f"final stage fleet: mean reward {final['mean_policy_reward']:.4f}"
          f" vs optimal {final['mean_opt_reward']:.4f} "
          f"(gap {final['mean_reward_gap']:.1%}, "
          f"violations {final['violation_rate']:.1%})")
    held = random_fleet(jax.random.PRNGKey(args.seed + 1234), args.cells,
                        n_max=args.n_max, **fleet_kw)
    gen = evaluate_vs_solver(state.dqn.params, held, cfg, key=k_eval)
    print(f"held-out fleet:   mean reward {gen['mean_policy_reward']:.4f} "
          f"vs optimal {gen['mean_opt_reward']:.4f} "
          f"(gap {gen['mean_reward_gap']:.1%}, "
          f"violations {gen['violation_rate']:.1%})")
    if args.ckpt:
        save_bundle(args.ckpt, PolicyBundle(
            kind="dqn", obs_spec=cfg.obs_spec, n_max=cfg.n_max,
            params=state.dqn.params,
            meta={"algo": "HL", "trainer": "hltrain-fleet",
                  "cells": args.cells, "epochs": args.epochs,
                  "curriculum": bool(args.curriculum),
                  "shared_cloud": bool(args.shared_cloud),
                  "shared_edge": bool(args.shared_edge),
                  "cells_per_edge": int(args.cells_per_edge),
                  "held_out_violation_rate": float(gen["violation_rate"]),
                  "system": state.sm.params}))
        print("saved PolicyBundle →", args.ckpt,
              f"(dqn, spec {cfg.obs_spec!r}, n_max={cfg.n_max})")


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", choices=("HL", "DQL", "QL"), default="HL")
    ap.add_argument("--users", type=int, default=5)
    ap.add_argument("--scenario", choices="ABCD", default="A")
    ap.add_argument("--constraint",
                    choices=tuple(CONSTRAINTS), default="89%")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-steps", type=int, default=None)
    ap.add_argument("--ckpt", default=None)
    # fleet-scale mode (jitted repro.hltrain over repro.fleet)
    ap.add_argument("--fleet", action="store_true",
                    help="train on a vectorized fleet via repro.hltrain")
    ap.add_argument("--cells", type=int, default=256)
    ap.add_argument("--n-max", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=60)
    ap.add_argument("--chunk", type=int, default=5,
                    help="epochs per curriculum stage / jitted run call")
    ap.add_argument("--no-curriculum", dest="curriculum",
                    action="store_false",
                    help="train on one fixed random fleet instead of the "
                         "2→n_max user-count curriculum")
    ap.add_argument("--shared-cloud", action="store_true",
                    help="couple cells through a shared cloud pool")
    ap.add_argument("--shared-edge", action="store_true",
                    help="couple co-located cells through shared edge "
                         "servers (see --cells-per-edge)")
    ap.add_argument("--cells-per-edge", type=int, default=1,
                    help="cells co-located per edge server group "
                         "(1 = every cell on its own edge)")
    ap.add_argument("--obs-spec", choices=SPEC_NAMES, default="base",
                    help="observation spec variant "
                         "(repro.specs.observation)")
    args = ap.parse_args()

    if args.fleet:
        if args.algo != "HL":
            ap.error("--fleet currently supports --algo HL only")
        if args.shared_edge and args.cells_per_edge <= 1:
            ap.error("--shared-edge needs --cells-per-edge > 1: with one "
                     "cell per edge server every group is a singleton and "
                     "the coupling is identically zero")
        return run_fleet(args)

    def env(seed):
        return EdgeCloudEnv(EnvConfig(SCENARIOS[args.scenario],
                                      CONSTRAINTS[args.constraint],
                                      n_users=args.users, seed=seed))

    opt = brute_force_optimal(SCENARIOS[args.scenario],
                              CONSTRAINTS[args.constraint], args.users)
    print(f"target optimum: ART={opt['art']:.1f} "
          f"{decision_string(opt['actions'])}")
    tracker = ConvergenceTracker(env(args.seed + 90), patience=4)
    t0 = time.time()
    if args.algo == "HL":
        agent = HLAgent(env(args.seed), HLHyperParams(
            seed=args.seed, epochs=400,
            eps_decay_steps=1000 * args.users, k_best=4,
            n_suggest=2 * args.users))
        res = agent.train(tracker=tracker)
        extra = {"system": agent.sm.params}
    elif args.algo == "DQL":
        agent = DQLAgent(env(args.seed), HLHyperParams(
            seed=args.seed, eps_decay_steps=6000 * args.users))
        res = agent.train(tracker=tracker,
                          max_steps=args.max_steps or 300_000,
                          eval_every=200)
        extra = {}
    else:
        agent = QLAgent(env(args.seed))
        res = agent.train(tracker=tracker,
                          max_steps=args.max_steps or 2_000_000,
                          eval_every=2000)
        extra = {}

    print(f"\n{args.algo}: converged@{res.steps_to_converge} "
          f"(total {res.real_steps} interactions, "
          f"{time.time() - t0:.0f}s wall)")
    print(f"final ART={res.final_art:.1f} "
          f"decisions={decision_string(res.final_actions)}")
    print(f"experience time {res.exp_time_ms / 60000:.1f} min (simulated), "
          f"compute time {res.comp_time_s / 60:.2f} min")
    if args.ckpt:
        save_bundle(args.ckpt, PolicyBundle(
            kind=agent.policy.kind, obs_spec="base", n_max=args.users,
            params=agent.policy_params,
            meta={"algo": args.algo, "trainer": "python-single-cell",
                  "scenario": args.scenario, "constraint": args.constraint,
                  "final_art_ms": float(res.final_art), **extra}))
        print(f"saved PolicyBundle → {args.ckpt} "
              f"({agent.policy.kind}, spec 'base', n_max={args.users})")


if __name__ == "__main__":
    main()
