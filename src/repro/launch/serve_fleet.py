"""Fleet serving CLI — request-level by default, round replay as compat.

    PYTHONPATH=src python -m repro.launch.serve_fleet \
        --bundle results/hl_fleet.bundle.msgpack --rounds 50 \
        [--cells 64] [--rate 3.0] [--seed 0] [--quiet] [--guard] \
        [--tick-ms 50] [--queue-cap 64] [--epochs 5] \
        [--telemetry] [--window-ms 1000] \
        [--trace-out trace.jsonl] [--trace-sample 1.0] \
        [--live] [--live-out live.ndjson] [--slo-target 0.9] \
        [--canary other.bundle.msgpack] [--mesh-cells N] \
        [--economy local|serverless|spot] \
        [--round-replay] [--out serve.json]

This module is a thin shell over ``repro.serve``: it loads a
PolicyBundle, builds a held-out random fleet at the bundle's recorded
(spec, n_max) — reproducing any shared-cloud / shared-edge coupling
regime its metadata records — and serves open-loop Poisson traffic
through the bundle's ``Policy``:

* default: a continuous-time ``RequestStream`` (per-request arrival
  timestamps, per-cell SLO deadlines, *no* ``[1, n_max]`` clipping —
  bursts queue, idle cells idle) through the jitted request-level engine,
  reporting p50/p95/p99 end-to-end latency, SLO attainment, and
  drop/defer counts.  ``--guard`` wraps the bundle in the
  ``slo_guarded`` combinator: any pick predicted to make the round's
  accuracy constraint unsatisfiable is replaced by the
  feasibility-preserving greedy action.
* ``--round-replay``: the demoted round-synchronous gateway
  (``repro.serve.compat.replay_trace``) with round-mean metrics vs the
  exact solver oracle, labeled with the fraction of burst mass the round
  abstraction clipped.

Observability: ``--telemetry`` threads a ``repro.telemetry`` metric
buffer through the engine's tick scan (per-``--window-ms`` queue depth /
backlog / occupancy / attainment series + latency histogram, in the
report under ``"telemetry"``); ``--trace-out`` writes a sampled
per-request lifecycle trace as JSONL (``--trace-sample`` is the
deterministic id-hash sampling rate) which
``python -m repro.telemetry.report`` renders into a run summary.

Live ops: ``--live`` (requires ``--telemetry``) streams each closed
telemetry window out of the running scan as NDJSON — to stdout, or to
``--live-out live.ndjson`` — with multi-window SLO burn-rate ``alert``
events inline (``--slo-target`` sets the attainment objective whose
error budget the burn rate is measured against).  ``--canary
other.bundle.msgpack`` serves a second bundle against the bit-identical
arrival stream (same fleet, same stream, same serving key) and attaches
a paired per-window diff — Δp99 / Δattainment / Δdrops plus sign-flip
windows — under ``"canary"`` in the report.

Economy: ``--economy <profile>`` (``local`` / ``serverless`` / ``spot``,
see ``repro.economy``) gives every tier a price, an energy cost, and a
warm/cold/warming startup state machine advanced inside the tick scan —
cold starts and spot preemptions delay recorded service, and the report
gains ``"economy"`` ($-spend, joules, ``cost_per_1k_requests``,
``joules_per_request``, cold-start / preemption counts).  With
``--telemetry`` the per-window spend/energy/cold-start counters ride in
the same metric buffer (and NDJSON stream), and
``repro.telemetry.audit`` checks the spend conservation law
Σ per-window spend == run spend.  Request-level only: the compat round
gateway has no tick clock, so ``--economy`` rejects ``--round-replay``.

Every run echoes its resolved seed and config in the output header (and
records them under ``"config"`` in the report), so any served run can be
reproduced bit-exactly from its printout alone.

The bundle's recorded observation spec decides the encoding end-to-end;
loading a bundle under a different spec/n_max raises before a single
request is served.
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.economy import PROFILE_NAMES, builtin_profile
from repro.fleet.env import FleetConfig
from repro.fleet.workload import poisson_round_trace, random_fleet
from repro.launch.compile_cache import use_compile_cache
from repro.policy.adapters import (heuristic_greedy_policy, slo_guarded,
                                   slo_guarded_params, solve_oracle)
from repro.policy.api import Policy
from repro.policy.bundle import load_bundle, policy_from_bundle
from repro.serve import (ServeConfig, poisson_request_stream, serve_stream)
from repro.serve.engine import (ECON_COUNTERS, ECON_GAUGES, TEL_COUNTERS,
                                TEL_GAUGES)
from repro.sharding.runtime import cells_mesh, set_mesh_info
from repro.telemetry import (BurnRateAlerter, BurnRateConfig, LiveEmitter,
                             build_trace, canary_diff, open_sink,
                             render_canary, write_trace)
# compat re-exports: tests and benchmarks historically import the round
# gateway from this module
from repro.serve.compat import make_gateway, replay_trace  # noqa: F401


def require_writable(path, flag: str) -> None:
    """Fail fast on an output path whose parent directory doesn't exist
    or isn't writable — *before* the expensive compile + serve, not
    after.  ``None`` and ``"-"`` (stdout) always pass."""
    if path is None or path == "-":
        return
    parent = os.path.dirname(os.path.abspath(path))
    if not os.path.isdir(parent):
        raise SystemExit(f"{flag} {path!r}: parent directory {parent!r} "
                         "does not exist")
    if not os.access(parent, os.W_OK):
        raise SystemExit(f"{flag} {path!r}: parent directory {parent!r} "
                         "is not writable")


def guarded_bundle_policy(bundle, key) -> tuple[Policy, object]:
    """Wrap a loaded bundle's (policy, params) in the ``slo_guarded``
    combinator with the greedy heuristic as fallback."""
    policy, params = policy_from_bundle(bundle)
    spec = bundle.spec()
    fallback = heuristic_greedy_policy(spec)
    return (slo_guarded(policy, spec, fallback),
            slo_guarded_params(params, fallback.init(key)))


def serve_bundle(bundle_path: str, *, rounds: int = 50, cells: int = 64,
                 rate: float = 3.0, seed: int = 0, quiet: bool = False,
                 guard: bool = False, tick_ms: float = 50.0,
                 queue_cap: int = 64, epochs: int = 5,
                 telemetry: bool = False, window_ms: float = 1000.0,
                 trace_out: str = None, trace_sample: float = 1.0,
                 live: bool = False, live_out: str = None,
                 slo_target: float = 0.9, canary: str = None,
                 round_replay: bool = False, mesh_cells: int = 0,
                 economy: str = None, verbose: bool = True) -> dict:
    """Load a PolicyBundle, build a held-out random fleet at the bundle's
    (spec, n_max), and serve ``rounds`` round-durations' worth of Poisson
    traffic through it — request-level by default, round replay with
    ``round_replay=True``.  The returned request-level report carries the
    raw per-request arrays under ``"records"`` (stripped before JSON).

    ``live`` streams closed telemetry windows as NDJSON (to ``live_out``
    or stdout) while the run executes; ``canary`` serves a second bundle
    against the bit-identical stream and attaches the paired per-window
    diff under ``"canary"``."""
    # fail fast on bad output paths and flag combinations — before the
    # bundle load and engine compile, not after
    require_writable(trace_out, "--trace-out")
    require_writable(live_out, "--live-out")
    if live and not telemetry:
        raise SystemExit("--live streams the telemetry windows; "
                         "add --telemetry")
    if round_replay and canary:
        raise SystemExit("--canary is a request-level feature; drop "
                         "--round-replay to use it")
    profile = None
    if economy:
        if round_replay:
            raise SystemExit("--economy prices the request-level tick "
                             "clock (cold starts, preemptions, per-tick "
                             "billing); the compat round gateway has "
                             "none — drop --round-replay to use it")
        try:
            profile = builtin_profile(economy)
        except ValueError as e:
            raise SystemExit(str(e))
    mesh = None
    if mesh_cells:
        if round_replay:
            raise SystemExit("--mesh-cells shards the request-level "
                             "engine; drop --round-replay to use it")
        if live:
            raise SystemExit("--live (io_callback) is not supported "
                             "under a cells mesh; drop --mesh-cells or "
                             "--live")
        if cells % mesh_cells:
            raise SystemExit(f"--cells {cells} must divide evenly over "
                             f"--mesh-cells {mesh_cells}")
        try:
            mesh = cells_mesh(mesh_cells)
        except ValueError as e:
            raise SystemExit(str(e))
        set_mesh_info(mesh)  # register for any nested serve_stream calls
    bundle = load_bundle(bundle_path)
    meta = bundle.meta
    k_fleet, k_trace, k_serve, k_guard = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    scenario = random_fleet(
        k_fleet, cells, n_max=bundle.n_max,
        cells_per_edge=int(meta.get("cells_per_edge", 1)))
    couplings = dict(shared_cloud=bool(meta.get("shared_cloud", False)),
                     shared_edge=bool(meta.get("shared_edge", False)))
    if guard:
        policy, params = guarded_bundle_policy(bundle, k_guard)
    else:
        policy, params = policy_from_bundle(bundle)

    # the resolved run config: echoed in the header and recorded in the
    # report so any served run is reproducible bit-exactly
    config = dict(bundle=bundle_path, seed=seed, cells=cells,
                  rounds=rounds, rate=rate, quiet=quiet, guard=guard,
                  tick_ms=tick_ms, queue_cap=queue_cap, epochs=epochs,
                  telemetry=telemetry, window_ms=window_ms,
                  trace_sample=trace_sample, round_replay=round_replay,
                  live=live, live_out=live_out, slo_target=slo_target,
                  canary=canary, mesh_cells=mesh_cells,
                  economy=economy,
                  obs_spec=bundle.obs_spec, n_max=bundle.n_max,
                  **couplings)
    if verbose:
        on = [c for c, v in couplings.items() if v] or ["uncoupled"]
        print(f"bundle {bundle_path}: kind {policy.kind!r}, obs spec "
              f"{bundle.obs_spec!r}, n_max={bundle.n_max} "
              f"(schema v{bundle.version})")
        print(f"serving fleet: {cells} cells ({', '.join(on)}), "
              f"Poisson(rate={rate}), background "
              f"{'quiet' if quiet else 'fluctuating'}, "
              f"{'round replay' if round_replay else 'request stream'}")
        print("config: " + " ".join(f"{k}={v}"
                                    for k, v in sorted(config.items())))

    if round_replay:
        if trace_out or telemetry:
            raise SystemExit("--telemetry/--trace-out are request-level "
                             "features; drop --round-replay to use them")
        cfg = FleetConfig(n_max=bundle.n_max, obs_spec=bundle.obs_spec,
                          quiet=quiet, **couplings)
        trace, stats = poisson_round_trace(k_trace, scenario, rounds,
                                           rate=rate, with_stats=True)
        report = replay_trace(policy, params, scenario, trace, cfg,
                              key=k_serve, oracle=solve_oracle(scenario),
                              trace_stats=stats)
        if verbose:
            for r in report["rounds"]:
                print(f"  round {r['round']:3d}: "
                      f"{r['served_requests']:4d} req, "
                      f"ART {r['mean_art_ms']:7.1f} ms "
                      f"(opt {r['opt_art_ms']:7.1f}), "
                      f"violations {r['violation_rate']:6.1%}")
            dps = report["decisions_per_s"]
            print(f"\nround replay served "
                  f"{report['served_requests']:,} requests "
                  f"({stats['clipped_fraction']:.1%} of raw burst mass "
                  f"clipped by the round abstraction): "
                  f"ART {report['mean_art_ms']:.1f} ms vs solver-optimal "
                  f"{report['opt_art_ms']:.1f} ms, violation rate "
                  f"{report['violation_rate']:.1%}"
                  + (f", {dps:,.0f} decisions/s" if dps else ""))
    else:
        cfg = ServeConfig(n_max=bundle.n_max, obs_spec=bundle.obs_spec,
                          quiet=quiet, tick_ms=tick_ms,
                          queue_cap=queue_cap, telemetry=telemetry,
                          window_ms=window_ms, economy=profile,
                          **couplings)
        horizon_ms = rounds * cfg.round_ms
        stream = poisson_request_stream(
            k_trace, scenario, horizon_ms, rate=rate,
            round_ms=cfg.round_ms,
            epoch_ms=horizon_ms / max(1, epochs))
        emitter = None
        if live:
            # metric names must match the engine's buffer layout: the
            # economy counters/gauges ride in the same windows
            counters = TEL_COUNTERS + (ECON_COUNTERS if profile else ())
            gauges = TEL_GAUGES + (ECON_GAUGES if profile else ())
            emitter = LiveEmitter(
                open_sink(live_out), counters, gauges,
                window_ms=window_ms,
                alerter=BurnRateAlerter(BurnRateConfig(target=slo_target)))
        report = serve_stream(policy, params, scenario, stream, cfg,
                              key=k_serve, verbose=verbose, live=emitter,
                              mesh=mesh)
        report["horizon_ms"] = horizon_ms
        if canary:
            c_bundle = load_bundle(canary, expect_spec=bundle.obs_spec,
                                   expect_n_max=bundle.n_max)
            if guard:
                c_policy, c_params = guarded_bundle_policy(c_bundle,
                                                           k_guard)
            else:
                c_policy, c_params = policy_from_bundle(c_bundle)
            c_report = serve_stream(c_policy, c_params, scenario, stream,
                                    cfg, key=k_serve, verbose=False,
                                    mesh=mesh)
            report["canary"] = dict(
                canary_diff(stream, report, c_report, window_ms),
                bundle=canary, kind=c_bundle.kind)
            if verbose:
                print("\n" + render_canary(report["canary"]))
        if trace_out:
            events = build_trace(stream, report["records"], tick_ms,
                                 sample=trace_sample)
            write_trace(trace_out, events)
            if verbose:
                print(f"wrote {len(events)} trace events "
                      f"(sample={trace_sample:g}) to {trace_out}")
        if verbose:
            dps = report["decisions_per_s"]
            tail = (f"latency p50/p95/p99 "
                    f"{report['p50_latency_ms']:.0f}/"
                    f"{report['p95_latency_ms']:.0f}/"
                    f"{report['p99_latency_ms']:.0f} ms, "
                    if report["served_requests"] else "")
            print(f"\nserved {report['served_requests']:,}/"
                  f"{report['n_requests']:,} requests over "
                  f"{horizon_ms:.0f} ms "
                  f"({report['dropped_requests']} dropped, "
                  f"{report['deferred_requests']} deferred): " + tail +
                  f"SLO attainment {report['slo_attainment']:.1%}, "
                  f"accuracy violations {report['violation_rate']:.1%}"
                  + (f", {dps:,.0f} decisions/s steady-state" if dps
                     else " (no steady-state window)"))
            if profile is not None:
                eco = report["economy"]
                c1k = eco["cost_per_1k_requests"]
                jpr = eco["joules_per_request"]
                print(f"economy [{eco['profile']}]: "
                      f"${eco['cost_usd_total']:.4f} total"
                      + (f" (${c1k:.4f}/1k req)" if c1k is not None
                         else "")
                      + f", {eco['energy_j_total']:.0f} J"
                      + (f" ({jpr:.2f} J/req)" if jpr is not None
                         else "")
                      + f", {eco['cold_starts']} cold starts, "
                      f"{eco['preemptions']} preemptions")

    report["bundle"] = {"path": bundle_path, "kind": bundle.kind,
                        "obs_spec": bundle.obs_spec,
                        "n_max": bundle.n_max,
                        "version": bundle.version,
                        "guarded": bool(guard)}
    report["config"] = config
    return report


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--bundle", required=True,
                    help="PolicyBundle checkpoint (see rl_train --ckpt)")
    ap.add_argument("--rounds", type=int, default=50,
                    help="traffic duration in round-durations "
                         "(horizon = rounds * n_max * tick_ms)")
    ap.add_argument("--cells", type=int, default=64)
    ap.add_argument("--rate", type=float, default=3.0,
                    help="Poisson mean arrivals per cell per round")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quiet", action="store_true",
                    help="disable background fluctuations")
    ap.add_argument("--guard", action="store_true",
                    help="wrap the bundle in slo_guarded: fall back to "
                         "the greedy action on picks predicted to "
                         "violate the accuracy constraint")
    ap.add_argument("--tick-ms", type=float, default=50.0)
    ap.add_argument("--queue-cap", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=5,
                    help="stream epochs (param-refresh / hot-swap "
                         "boundaries)")
    ap.add_argument("--telemetry", action="store_true",
                    help="thread a repro.telemetry metric buffer through "
                         "the tick scan (windowed series + latency "
                         "histogram under 'telemetry' in the report)")
    ap.add_argument("--window-ms", type=float, default=1000.0,
                    help="telemetry aggregation window")
    ap.add_argument("--trace-out", default=None,
                    help="write a sampled per-request lifecycle trace "
                         "as JSONL (render with repro.telemetry.report)")
    ap.add_argument("--trace-sample", type=float, default=1.0,
                    help="deterministic id-hash trace sampling rate")
    ap.add_argument("--live", action="store_true",
                    help="stream closed telemetry windows as NDJSON "
                         "while the run executes (requires --telemetry); "
                         "SLO burn-rate alerts are emitted inline")
    ap.add_argument("--live-out", default=None,
                    help="NDJSON sink for --live ('-' or unset: stdout)")
    ap.add_argument("--slo-target", type=float, default=0.9,
                    help="attainment objective for the burn-rate alerter")
    ap.add_argument("--canary", default=None,
                    help="second PolicyBundle to serve against the "
                         "bit-identical stream; attaches the paired "
                         "per-window diff under 'canary'")
    ap.add_argument("--mesh-cells", type=int, default=0,
                    help="shard_map the serving engine over an N-device "
                         "('cells',) mesh (request-level only; --cells "
                         "must divide by N; on CPU requires XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--economy", default=None, choices=PROFILE_NAMES,
                    help="tier-economy profile (repro.economy): per-tier "
                         "prices, energy, cold starts, preemption, "
                         "scale-to-zero — the report gains $-spend and "
                         "joules figures (request-level only)")
    ap.add_argument("--round-replay", action="store_true",
                    help="compat mode: round-synchronous trace replay "
                         "with round-mean metrics vs the solver oracle")
    ap.add_argument("--out", default=None,
                    help="write the serving report as JSON")
    args = ap.parse_args()
    require_writable(args.out, "--out")
    report = serve_bundle(args.bundle, rounds=args.rounds,
                          cells=args.cells, rate=args.rate,
                          seed=args.seed, quiet=args.quiet,
                          guard=args.guard, tick_ms=args.tick_ms,
                          queue_cap=args.queue_cap, epochs=args.epochs,
                          telemetry=args.telemetry,
                          window_ms=args.window_ms,
                          trace_out=args.trace_out,
                          trace_sample=args.trace_sample,
                          live=args.live, live_out=args.live_out,
                          slo_target=args.slo_target,
                          canary=args.canary,
                          round_replay=args.round_replay,
                          mesh_cells=args.mesh_cells,
                          economy=args.economy)
    if args.out:
        report.pop("records", None)  # raw numpy arrays, not JSON
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print("wrote", args.out)


if __name__ == "__main__":
    main()
