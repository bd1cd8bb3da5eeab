"""Served-traffic benchmark: PolicyBundles through the serving stack.

    PYTHONPATH=src python -m benchmarks.serve [--smoke]
        [--cells 64] [--rounds 40] [--out BENCH_serve.json]

End-to-end exercise of the serving surface: train a fleet policy with
``repro.hltrain``, save it as a versioned PolicyBundle, load the bundle
back, and serve the *same* open-loop Poisson traffic through it twice —

* round replay (``repro.serve.compat.replay_trace``): the demoted
  round-synchronous gateway, round-mean metrics vs the exact solver
  oracle, labeled with the burst mass its ``[1, n_max]`` clipping
  discarded;
* request stream (``repro.serve.engine.serve_stream``): the
  event-driven request-level engine on an unclipped continuous-time
  trace of the same offered load, reporting per-request p50/p95/p99
  end-to-end latency, SLO attainment, and drop/defer counts —

alongside the parameter-free latency-greedy baseline bundle and the
hltrain bundle wrapped in the ``slo_guarded`` combinator
(``hltrain_guarded``), which trades tail latency for the greedy
baseline's zero accuracy-violation property.

A tier-economy matrix (``repro.economy``, spot profile) then serves the
same offered load twice more — cost-oblivious greedy vs the
cold-start-aware ``cost_greedy`` router — recording per-policy
``cost_per_1k_requests`` / ``joules_per_request`` next to p99/SLO under
``economy`` in the JSON, auditing the spend conservation law per run,
and failing unless the cost-aware router is cheaper at SLO attainment
within 0.02 of the baseline.  The greedy economy-on cost figure is
mirrored top-level and tier-1-gated via bench history.

Writes ``BENCH_serve.json`` with per-policy round-level figures
(``violation_rate``, request-weighted ART vs optimum, ``decisions_per_s``)
and request-level figures (``p50/p95/p99_latency_ms``, ``slo_attainment``,
``dropped_requests``, ``request_decisions_per_s``), plus the
``repro.telemetry.profiled`` compile-vs-run wall-clock split and peak
memory (``compile_time_s`` / ``run_time_s`` / ``peak_memory_mb``) — CI
gates on those fields being present.  ``--smoke`` shrinks training to a
minutes-scale CI job and marks the JSON ``smoke: true``.

``--cells-sweep`` adds a fleet-size scaling sweep of the request engine:
each size is served twice on the *same* stream — single-device, then
``shard_map``-sharded over every visible device (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to emulate a
mesh on CPU) — with record parity asserted to 1e-5 and per-size
throughput/p99/compile-run rows emitted as ``cells_sweep``.  The
sharded throughput at the largest size lands as the tier-1-gated
``sharded_request_decisions_per_s``.  ``--sweep-only`` skips training
and the per-policy serving matrix (the sharded CI job uses it).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import numpy as np

from benchmarks import history
from repro.economy import builtin_profile, cost_greedy_policy
from repro.fleet import FleetConfig, curriculum_fleets, random_fleet
from repro.fleet.workload import poisson_round_trace
from repro.hltrain import FleetHLParams, make_hl_trainer, run_curriculum
from repro.launch.serve_fleet import guarded_bundle_policy, replay_trace
from repro.policy import (PolicyBundle, heuristic_greedy_policy,
                          load_bundle, policy_from_bundle, save_bundle,
                          solve_oracle)
from repro.serve import ServeConfig, poisson_request_stream, serve_stream
from repro.specs.observation import make_spec
from repro.telemetry import (audit_serve_report, build_trace, profiled)

N_MAX = 5
OBS_SPEC = "full"
TICK_MS = 50.0
# tier-economy matrix: the spot profile exercises every state-machine
# feature (cheap preemptible edge with a slow cold start, scale-to-zero,
# expensive always-available cloud spill)
ECONOMY_PROFILE = "spot"
ECONOMY_SPEC = "full_economy"


def train_hltrain_bundle(path: str, cells: int, hp: FleetHLParams,
                         chunk: int) -> None:
    """Tiny curriculum training run -> PolicyBundle on disk."""
    cfg = FleetConfig(n_max=N_MAX, obs_spec=OBS_SPEC)
    trainer = make_hl_trainer(cfg, hp)
    n_stages = -(-hp.epochs // chunk)  # ceil
    stages = curriculum_fleets(jax.random.PRNGKey(7), cells, n_stages,
                               start=2, end=N_MAX)
    state = run_curriculum(trainer, stages, hp.epochs, chunk,
                           jax.random.PRNGKey(8))
    save_bundle(path, PolicyBundle(
        kind="dqn", obs_spec=OBS_SPEC, n_max=N_MAX,
        params=state.dqn.params,
        meta={"trainer": "hltrain-fleet", "cells": cells,
              "epochs": hp.epochs,
              "real_steps": int(state.real_steps)}))


def save_greedy_bundle(path: str) -> None:
    policy = heuristic_greedy_policy(N_MAX)
    save_bundle(path, PolicyBundle(
        kind="greedy", obs_spec=OBS_SPEC, n_max=N_MAX,
        params=policy.init(jax.random.PRNGKey(0))))


def run_cells_sweep(smoke: bool, rate: float) -> dict:
    """Fleet-size scaling sweep: serve the same stream single-device and
    sharded over every visible device, assert record parity ≤ 1e-5, and
    report per-size throughput rows.

    Single-device serving runs the Pallas group-occupancy kernel
    (interpreted on the CPU), whose cost grows with C²; the sharded path reduces the
    cross-cell couplings with ``segment_sum`` + ``psum`` per shard, so
    past the crossover fleet size the mesh wins even when the forced
    host devices share one physical core — the speedup is algorithmic
    (per-shard work), not parallel.
    """
    from repro.sharding.runtime import cells_mesh

    n_dev = jax.device_count()
    sizes = [32, 512, 4096] if smoke else [32, 512, 4096, 16384, 65536]
    sizes = [c for c in sizes if c % n_dev == 0]
    policy = heuristic_greedy_policy(N_MAX)
    params = policy.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(n_max=N_MAX, obs_spec=OBS_SPEC, tick_ms=TICK_MS,
                       shared_cloud=True, shared_edge=True)
    rnd = lambda v, d: None if v is None else round(v, d)

    rows = []
    with profiled("cells_sweep") as prof:
        for c in sizes:
            # rounds shrink with fleet size: decisions/s is a per-tick
            # steady-state figure, so fewer ticks at the big sizes keep
            # the sweep's wall clock bounded without moving the number
            rounds = 10 if smoke else (20 if c <= 1024 else
                                       10 if c <= 4096 else
                                       6 if c <= 16384 else 4)
            k = jax.random.fold_in(jax.random.PRNGKey(17), c)
            k_fleet, k_serve = jax.random.split(k)
            scn = random_fleet(k_fleet, c, n_max=N_MAX, cells_per_edge=4)
            horizon_ms = rounds * scfg.round_ms
            stream = poisson_request_stream(
                k_fleet, scn, horizon_ms, rate=rate,
                round_ms=scfg.round_ms,
                epoch_ms=horizon_ms / (4 if c <= 4096 else 2))
            r1 = serve_stream(policy, params, scn, stream, scfg,
                              key=k_serve)
            if prof._t_split is None:
                prof.split()  # the first run paid the XLA compiles
            row = {"cells": c, "rounds": rounds,
                   "n_requests": stream.n_requests,
                   "decisions_per_s_1dev": rnd(r1["decisions_per_s"], 1),
                   "compile_time_s_1dev": rnd(r1["compile_time_s"], 3),
                   "run_time_s_1dev": rnd(r1["run_time_s"], 3),
                   "p99_latency_ms": rnd(r1["p99_latency_ms"], 2)}
            if n_dev > 1:
                rS = serve_stream(policy, params, scn, stream, scfg,
                                  key=k_serve, mesh=cells_mesh())
                parity = max(
                    float(np.abs(np.asarray(r1["records"][f], np.float64)
                                 - np.asarray(rS["records"][f],
                                              np.float64)).max())
                    for f in r1["records"])
                if parity > 1e-5:
                    raise RuntimeError(
                        f"sharded/single-device record divergence at "
                        f"{c} cells: max abs diff {parity} > 1e-5")
                row.update({
                    "decisions_per_s_sharded":
                        rnd(rS["decisions_per_s"], 1),
                    "compile_time_s_sharded":
                        rnd(rS["compile_time_s"], 3),
                    "run_time_s_sharded": rnd(rS["run_time_s"], 3),
                    "speedup_x": rnd(rS["decisions_per_s"]
                                     / r1["decisions_per_s"], 3),
                    "parity_max_abs_diff": parity})
            rows.append(row)
            shard_txt = (f", {n_dev}dev "
                         f"{row['decisions_per_s_sharded']:,.0f} dec/s "
                         f"({row['speedup_x']:.2f}x, parity "
                         f"{row['parity_max_abs_diff']:g})"
                         if n_dev > 1 else "")
            print(f"— sweep {c:>6} cells: 1dev "
                  f"{row['decisions_per_s_1dev']:,.0f} dec/s"
                  f"{shard_txt} —")

    peak_1dev = max(r["decisions_per_s_1dev"] for r in rows)
    last = rows[-1]
    sharded_peak = (max(r["decisions_per_s_sharded"] for r in rows)
                    if n_dev > 1 else None)
    sweep = {
        "devices": n_dev,
        "rows": rows,
        "sharded_request_decisions_per_s":
            last.get("decisions_per_s_sharded"),
        # the ≥100x single-device target, with the honest gap: on this
        # host the forced devices share the physical cores, so the only
        # headroom is algorithmic — real meshes add compute per shard
        "target_100x": {
            "target_x": 100.0,
            "single_device_peak_decisions_per_s": peak_1dev,
            "sharded_peak_decisions_per_s": sharded_peak,
            "large_fleet_cells": last["cells"],
            "large_fleet_speedup_x": last.get("speedup_x"),
            "achieved_x_vs_single_device_peak":
                (None if sharded_peak is None
                 else round(sharded_peak / peak_1dev, 3)),
        },
        **{k: v for k, v in prof.report().items() if k != "label"},
    }
    return sweep


def run_economy_matrix(scenario, stream, key) -> dict:
    """Cost-oblivious greedy vs the cold-start-aware ``cost_greedy``
    router, both served on the *same* stream under the same tier-economy
    profile (``spot``), with telemetry on so the spend conservation laws
    are audited post-run.  Records per-policy ``cost_per_1k_requests``
    and ``joules_per_request`` next to p99/SLO, plus the paired
    comparison the acceptance gate reads: the cost-aware router must be
    cheaper at SLO attainment no worse than 0.02 below the baseline."""
    profile = builtin_profile(ECONOMY_PROFILE)
    spec = make_spec(ECONOMY_SPEC, N_MAX)
    ecfg = ServeConfig(n_max=N_MAX, obs_spec=ECONOMY_SPEC,
                       tick_ms=TICK_MS, telemetry=True, economy=profile)
    pols = {
        # the baseline sees the economy block but ignores it: pure
        # latency-greedy routing, priced after the fact
        "greedy": heuristic_greedy_policy(spec),
        "cost_greedy": cost_greedy_policy(spec, profile,
                                          tick_ms=TICK_MS),
    }
    rnd = lambda v, d: None if v is None else round(v, d)
    rows = {}
    for name, pol in pols.items():
        rep = serve_stream(pol, pol.init(key), scenario, stream, ecfg,
                           key=key)
        audit = audit_serve_report(rep, n_cells=scenario.n_cells,
                                   n_max=N_MAX,
                                   queue_cap=ecfg.queue_cap)
        audit.raise_on_failure()
        eco = rep["economy"]
        rows[name] = {
            "cost_per_1k_requests": rnd(eco["cost_per_1k_requests"], 6),
            "joules_per_request": rnd(eco["joules_per_request"], 4),
            "cost_usd_total": rnd(eco["cost_usd_total"], 6),
            "energy_j_total": rnd(eco["energy_j_total"], 1),
            "cold_starts": eco["cold_starts"],
            "preemptions": eco["preemptions"],
            "served_requests": rep["served_requests"],
            "p99_latency_ms": rnd(rep["p99_latency_ms"], 2),
            "slo_attainment": rnd(rep["slo_attainment"], 4),
            "violation_rate": rnd(rep["violation_rate"], 4),
            "audit": audit.summary(),
        }
        print(f"— economy[{ECONOMY_PROFILE}] {name}: "
              f"${rows[name]['cost_per_1k_requests'] or 0:.4f}/1k req, "
              f"{rows[name]['joules_per_request'] or 0:.2f} J/req, "
              f"{eco['cold_starts']} cold starts, "
              f"{eco['preemptions']} preemptions, p99 "
              f"{rows[name]['p99_latency_ms'] or 0:.0f} ms, SLO "
              f"{rows[name]['slo_attainment'] or 0:.1%} —")
    g, cg = rows["greedy"], rows["cost_greedy"]
    comparison = {
        "baseline": "greedy",
        "candidate": "cost_greedy",
        "cost_per_1k_delta": (None if None in (g["cost_per_1k_requests"],
                                               cg["cost_per_1k_requests"])
                              else round(cg["cost_per_1k_requests"]
                                         - g["cost_per_1k_requests"], 6)),
        "slo_delta": (None if None in (g["slo_attainment"],
                                       cg["slo_attainment"])
                      else round(cg["slo_attainment"]
                                 - g["slo_attainment"], 4)),
        "slo_tolerance": 0.02,
    }
    comparison["cost_lower"] = bool(
        comparison["cost_per_1k_delta"] is not None
        and comparison["cost_per_1k_delta"] < 0)
    comparison["slo_within_tolerance"] = bool(
        comparison["slo_delta"] is not None
        and comparison["slo_delta"] >= -comparison["slo_tolerance"])
    comparison["acceptance_met"] = (comparison["cost_lower"]
                                    and comparison["slo_within_tolerance"])
    if not comparison["acceptance_met"]:
        raise RuntimeError(
            f"economy acceptance gate: cost_greedy must beat the "
            f"cost-oblivious greedy on $/1k requests at SLO attainment "
            f"within {comparison['slo_tolerance']}: {comparison}")
    return {"profile": ECONOMY_PROFILE, "obs_spec": ECONOMY_SPEC,
            "policies": rows, "comparison": comparison}


def main(smoke: bool = False, cells: int = 64, rounds: int = 40,
         rate: float = 3.0, workdir: str = "results/serve",
         out: str = "BENCH_serve.json",
         check_regression: bool = False,
         history_path: str = history.DEFAULT_PATH,
         cells_sweep: bool = False, sweep_only: bool = False) -> dict:
    if sweep_only:
        # the sharded CI job: no training, no per-policy matrix — just
        # the scaling sweep (plus the greedy bundle, which the job's
        # serve_fleet --mesh-cells CLI step loads)
        os.makedirs(workdir, exist_ok=True)
        save_greedy_bundle(os.path.join(workdir, "greedy.bundle.msgpack"))
        sweep = run_cells_sweep(smoke, rate)
        result = {
            "smoke": smoke, "sweep_only": True, "rate": rate,
            "n_max": N_MAX, "obs_spec": OBS_SPEC, "tick_ms": TICK_MS,
            "cells_sweep": sweep,
            "sharded_request_decisions_per_s":
                sweep["sharded_request_decisions_per_s"],
        }
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
        print("wrote", out)
        history.record("serve", result, path=history_path,
                       check=check_regression)
        return result

    if smoke:
        cells, rounds = min(cells, 32), min(rounds, 25)
        hp = FleetHLParams(epochs=8, n_direct=4, t_direct=6, n_world=8,
                           n_suggest=2, t_suggest=3, n_plan=8, batch=64,
                           eps_decay_steps=300, updates_per_direct=4,
                           updates_per_plan=4)
        chunk = 4
    else:
        hp = FleetHLParams(epochs=60, eps_decay_steps=2000,
                           updates_per_direct=6, updates_per_plan=6)
        chunk = 10

    os.makedirs(workdir, exist_ok=True)
    bundles = {"greedy": os.path.join(workdir, "greedy.bundle.msgpack"),
               "hltrain": os.path.join(workdir, "hltrain.bundle.msgpack")}
    print(f"— training hltrain policy ({cells} cells, {hp.epochs} epochs, "
          f"obs spec {OBS_SPEC!r}) —")
    train_hltrain_bundle(bundles["hltrain"], cells, hp, chunk)
    save_greedy_bundle(bundles["greedy"])

    # one shared serving fleet + the SAME offered load in both
    # abstractions: a clipped round trace and an unclipped request stream
    k_fleet, k_trace, k_serve, k_guard = jax.random.split(
        jax.random.PRNGKey(42), 4)
    scenario = random_fleet(k_fleet, cells, n_max=N_MAX)
    trace, trace_stats = poisson_round_trace(k_trace, scenario, rounds,
                                             rate=rate, with_stats=True)
    oracle = solve_oracle(scenario)
    cfg = FleetConfig(n_max=N_MAX, obs_spec=OBS_SPEC)
    scfg = ServeConfig(n_max=N_MAX, obs_spec=OBS_SPEC, tick_ms=TICK_MS)
    horizon_ms = rounds * scfg.round_ms
    stream = poisson_request_stream(k_trace, scenario, horizon_ms,
                                    rate=rate, round_ms=scfg.round_ms,
                                    epoch_ms=horizon_ms / 5)

    loaded = {name: load_bundle(path, expect_spec=OBS_SPEC,
                                expect_n_max=N_MAX)
              for name, path in bundles.items()}
    served = {name: policy_from_bundle(b) for name, b in loaded.items()}
    served["hltrain_guarded"] = guarded_bundle_policy(loaded["hltrain"],
                                                      k_guard)

    # None-safe rounding: zero-served runs report None tails / ART, and a
    # bare round(None) would crash the benchmark after the work is done
    rnd = lambda v, d: None if v is None else round(v, d)
    policies = {}
    prof = None
    with profiled("serve_bench") as prof:
        for name, (policy, params) in served.items():
            rep = replay_trace(policy, params, scenario, trace, cfg,
                               key=k_serve, oracle=oracle)
            req = serve_stream(policy, params, scenario, stream, scfg,
                               key=k_serve)
            if prof._t_split is None:
                prof.split()  # the first policy paid the XLA compiles
            policies[name] = {
                # round-replay compat figures
                "violation_rate": rep["violation_rate"],
                "mean_art_ms": rnd(rep["mean_art_ms"], 2),
                "opt_art_ms": rnd(rep["opt_art_ms"], 2),
                "mean_reward": rnd(rep["mean_reward"], 4),
                "opt_reward": rnd(rep["opt_reward"], 4),
                "served_requests": rep["served_requests"],
                "decisions_per_s": rnd(rep["decisions_per_s"], 1),
                # request-level figures
                "p50_latency_ms": rnd(req["p50_latency_ms"], 2),
                "p95_latency_ms": rnd(req["p95_latency_ms"], 2),
                "p99_latency_ms": rnd(req["p99_latency_ms"], 2),
                "slo_attainment": rnd(req["slo_attainment"], 4),
                "request_violation_rate": rnd(req["violation_rate"], 4),
                "served_request_level": req["served_requests"],
                "dropped_requests": req["dropped_requests"],
                "deferred_requests": req["deferred_requests"],
                "request_decisions_per_s": rnd(req["decisions_per_s"], 1),
                # engine-measured compile/run split for this policy's
                # request-level run
                "compile_time_s": rnd(req.get("compile_time_s"), 3),
                "run_time_s": rnd(req.get("run_time_s"), 3),
            }
            print(f"— {name}: round replay {rep['served_requests']:,} req, "
                  f"ART {rep['mean_art_ms'] or 0:.1f} ms "
                  f"(opt {rep['opt_art_ms'] or 0:.1f}), violations "
                  f"{rep['violation_rate']:.1%}, "
                  f"{rep['decisions_per_s'] or 0:,.0f} dec/s —")
            print(f"  request level: {req['served_requests']:,}/"
                  f"{req['n_requests']:,} served "
                  f"({req['dropped_requests']} dropped), p50/p95/p99 "
                  f"{req['p50_latency_ms'] or 0:.0f}/"
                  f"{req['p95_latency_ms'] or 0:.0f}/"
                  f"{req['p99_latency_ms'] or 0:.0f} ms, SLO "
                  f"{req['slo_attainment'] or 0:.1%}, violations "
                  f"{req['violation_rate']:.1%}, "
                  f"{req['decisions_per_s'] or 0:,.0f} dec/s")

    # post-run invariant audit: re-serve the greedy baseline with the
    # telemetry buffer threaded through the tick scan and check the
    # conservation laws (admits == serves + drops + still-queued, window
    # sums == run totals, occupancy ≤ capacity) plus the lifecycle trace
    # — a silent metrics bug fails the benchmark, not just a dashboard
    tel_cfg = dataclasses.replace(scfg, telemetry=True)
    req_tel = serve_stream(*served["greedy"], scenario, stream, tel_cfg,
                           key=k_serve)
    audit = audit_serve_report(
        req_tel, trace=build_trace(stream, req_tel["records"], TICK_MS),
        n_cells=cells, n_max=N_MAX, queue_cap=tel_cfg.queue_cap)
    print(audit.render())
    audit.raise_on_failure()

    # tier-economy matrix: equal offered load, spot profile, spend
    # conservation audited per run; the greedy (economy-on) cost figure
    # is tier-1-gated via bench history
    economy = run_economy_matrix(scenario, stream, k_serve)

    result = {
        "smoke": smoke,
        "audit": audit.summary(),
        "n_cells": cells, "n_rounds": rounds, "rate": rate,
        "n_max": N_MAX, "obs_spec": OBS_SPEC, "tick_ms": TICK_MS,
        "trace_stats": trace_stats,
        "stream_requests": stream.n_requests,
        "policies": policies,
        "economy": economy,
        "cost_per_1k_requests":
            economy["policies"]["greedy"]["cost_per_1k_requests"],
        "joules_per_request":
            economy["policies"]["greedy"]["joules_per_request"],
        "decisions_per_s": max((p["decisions_per_s"]
                                for p in policies.values()
                                if p["decisions_per_s"] is not None),
                               default=None),
        "request_decisions_per_s": max(
            (p["request_decisions_per_s"] for p in policies.values()
             if p["request_decisions_per_s"] is not None),
            default=None),
        # profiled() split over the whole serving block: the first
        # policy's first calls carry every XLA compile
        **{k: v for k, v in prof.report().items() if k != "label"},
    }
    if cells_sweep:
        sweep = run_cells_sweep(smoke, rate)
        result["cells_sweep"] = sweep
        result["sharded_request_decisions_per_s"] = \
            sweep["sharded_request_decisions_per_s"]
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    print("wrote", out)
    history.record("serve", result, path=history_path,
                   check=check_regression)
    return result


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="minutes-scale CI config")
    p.add_argument("--cells", type=int, default=64)
    p.add_argument("--rounds", type=int, default=40)
    p.add_argument("--rate", type=float, default=3.0)
    p.add_argument("--workdir", default="results/serve",
                   help="where the trained bundles are written")
    p.add_argument("--out", default="BENCH_serve.json")
    p.add_argument("--check-regression", action="store_true",
                   help="fail if a tier-1 figure degrades beyond "
                        "tolerance vs the bench-history median")
    p.add_argument("--history", default=history.DEFAULT_PATH,
                   help="bench-history ledger (JSONL)")
    p.add_argument("--cells-sweep", action="store_true",
                   help="add the fleet-size scaling sweep (single-device "
                        "vs sharded over all visible devices)")
    p.add_argument("--sweep-only", action="store_true",
                   help="run only the scaling sweep (implies "
                        "--cells-sweep; skips training and the "
                        "per-policy matrix)")
    a = p.parse_args()
    main(a.smoke, a.cells, a.rounds, a.rate, a.workdir, a.out,
         check_regression=a.check_regression, history_path=a.history,
         cells_sweep=a.cells_sweep or a.sweep_only,
         sweep_only=a.sweep_only)
