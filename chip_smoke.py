#!/usr/bin/env python3
"""Smoke run of the orchestrator's main path on a TPU.

    python chip_smoke.py             # one chip: every phase but the mesh
    python chip_smoke.py --chips 4   # four chips: the cells-mesh phase only

Everything runs in this one process (no child process touches JAX), and
any failing phase ends the run with a nonzero exit code.  The script
refuses to run (exit code 2, no result printed) when JAX's first device
is not a TPU.

One-chip phases:

``kernels``
    Both orchestration Pallas kernels, compiled by Mosaic, against their
    lax references on seeded random inputs at 4,096 cells: exact for
    queue admission, 1e-5 for edge-group occupancy.
``serve_bench``
    The ``BENCH_serve.json`` deployment: ``serve_stream`` at 32 cells,
    ``n_max`` 5, ``full`` spec, 50 ms ticks, 3 requests per cell per
    round.  The greedy bundle serves with telemetry on; ``cost_greedy``
    serves under the ``spot`` economy.  Each report passes the telemetry
    audit.
``large_fleet``
    65,536 cells, shared cloud and shared edge (4 cells per edge),
    2 s of simulated traffic (about 1.6M requests), audited, plus the
    count of ``tpu_custom_call`` ops in the compiled epoch program.
``hltrain``
    The Hybrid Learning trainer at 256 cells, ``n_max`` 8, ``full`` spec,
    for a few epochs; the result is saved as a PolicyBundle, loaded back,
    and serves a 32-cell stream of the ``serve_bench`` deployment.

``--chips 4`` runs ``cells_mesh`` alone: the 65,536-cell stream served on
one device and then over a 4-device ``("cells",)`` mesh; per-request
records must agree to 1e-5, and the donated epoch state must stay
sharded over all four devices after an epoch.

Each phase prints one JSON line (device kind, compile and run seconds,
requests or steps done, the device's ``peak_bytes_in_use`` so far).  The
last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
SRC = HERE / "src"
if not (SRC / "repro").is_dir():
    sys.exit(f"chip_smoke.py: no repro package under {SRC}; run it from "
             f"a checkout of the repository")
sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.economy import builtin_profile, cost_greedy_policy  # noqa: E402
from repro.fleet import FleetConfig, curriculum_fleets, random_fleet  # noqa: E402
from repro.hltrain import FleetHLParams, make_hl_trainer, run_curriculum  # noqa: E402
from repro.kernels.orchestration import (group_occupancy_lax,  # noqa: E402
                                         group_occupancy_pallas,
                                         interpret_mode, queue_admit_lax,
                                         queue_admit_pallas)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.policy import (PolicyBundle, heuristic_greedy_policy,  # noqa: E402
                          load_bundle, policy_from_bundle, save_bundle)
from repro.serve import ServeConfig, poisson_request_stream, serve_stream  # noqa: E402
from repro.serve.engine import first_epoch_args, make_serve_engine  # noqa: E402
from repro.sharding.runtime import cells_mesh  # noqa: E402
from repro.specs.observation import make_spec  # noqa: E402
from repro.telemetry import audit_serve_report, build_trace  # noqa: E402
from repro.telemetry.profiling import device_peak_memory_bytes  # noqa: E402

TICK_MS = 50.0
RATE = 3.0         # mean requests per cell per round
QUEUE_CAP = 64
SEED = 42


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _emit(phase: str, **fields) -> dict:
    """Print one phase line: the device, the phase's figures, and the
    device's peak allocation so far (None off a device backend)."""
    dev = jax.devices()[0]
    peak = device_peak_memory_bytes()
    line = {"phase": phase, "platform": dev.platform,
            "device_kind": dev.device_kind, **fields,
            "peak_bytes_in_use": peak,
            "memory_source": "host_rss" if peak is None else "device"}
    print(json.dumps(line), flush=True)
    return line


def _timed_compile(fn, *args):
    """(compiled, seconds) for ``jax.jit(fn)`` at ``args``."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _timed_run(compiled, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def _stream(scenario, cfg: ServeConfig, horizon_ms: float, n_epochs: int,
            key):
    return poisson_request_stream(key, scenario, horizon_ms, rate=RATE,
                                  round_ms=cfg.round_ms,
                                  epoch_ms=horizon_ms / n_epochs)


def _audited(report: dict, n_cells: int, cfg: ServeConfig, trace=None):
    audit = audit_serve_report(report, trace=trace, n_cells=n_cells,
                               n_max=cfg.n_max, queue_cap=cfg.queue_cap)
    audit.raise_on_failure()
    return audit.summary()


def _serve_fields(report: dict) -> dict:
    return {"compile_time_s": report["compile_time_s"],
            "run_time_s": report["run_time_s"],
            "requests": report["n_requests"],
            "served_requests": report["served_requests"],
            "dropped_requests": report["dropped_requests"],
            "p99_latency_ms": report["p99_latency_ms"],
            "slo_attainment": report["slo_attainment"]}


# ---------------------------------------------------------------- phases
def phase_kernels(n_cells: int = 4096, n_lanes: int = 4096,
                  queue_cap: int = QUEUE_CAP, seed: int = 0) -> dict:
    """Both kernels vs their lax references on seeded random inputs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    own = jax.random.uniform(ks[0], (n_cells,), jnp.float32, 0.0, 5.0)
    groups = jax.random.randint(ks[1], (n_cells,), 0, max(1, n_cells // 4))
    q_ids = jax.random.randint(ks[2], (n_cells, queue_cap), 0, 1 << 20)
    q_head = jax.random.randint(ks[3], (n_cells,), 0, queue_cap)
    q_len = jax.random.randint(ks[4], (n_cells,), 0, queue_cap + 1)
    cell = jax.random.randint(ks[5], (n_lanes,), 0, n_cells)
    valid = jax.random.bernoulli(ks[6], 0.7, (n_lanes,))
    rid = jnp.arange(n_lanes, dtype=jnp.int32) + (1 << 21)
    admit_args = (q_ids, q_head, q_len, rid, cell, valid)

    go, go_compile = _timed_compile(group_occupancy_pallas, own, groups)
    qa, qa_compile = _timed_compile(queue_admit_pallas, *admit_args)
    custom = {"group_occupancy": go.as_text().count("tpu_custom_call"),
              "queue_admit": qa.as_text().count("tpu_custom_call")}
    if not interpret_mode():
        _require(min(custom.values()) > 0,
                 f"a kernel did not lower to Mosaic: {custom}")
    got_go, go_run = _timed_run(go, own, groups)
    got_qa, qa_run = _timed_run(qa, *admit_args)
    want_go = jax.jit(group_occupancy_lax)(own, groups)
    want_qa = jax.jit(queue_admit_lax)(*admit_args)
    np.testing.assert_allclose(np.asarray(got_go), np.asarray(want_go),
                               atol=1e-5, rtol=1e-5)
    for g, w, name in zip(got_qa, want_qa, ("q_ids", "q_len", "admitted")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"queue_admit {name}")
    return _emit("kernels", cells=n_cells, lanes=n_lanes,
                 tpu_custom_calls=custom,
                 compile_time_s=go_compile + qa_compile,
                 run_time_s=go_run + qa_run,
                 admitted=int(np.asarray(got_qa[2]).sum()))


def phase_serve_bench(n_cells: int = 32, rounds: int = 25,
                      n_epochs: int = 5) -> dict:
    """The BENCH_serve.json deployment: greedy with telemetry, then
    cost_greedy under the spot economy; both audited."""
    k_fleet, k_trace, k_serve, _ = jax.random.split(
        jax.random.PRNGKey(SEED), 4)
    n_max = 5
    scenario = random_fleet(k_fleet, n_cells, n_max=n_max)
    cfg = ServeConfig(n_max=n_max, obs_spec="full", tick_ms=TICK_MS,
                      queue_cap=QUEUE_CAP, telemetry=True)
    stream = _stream(scenario, cfg, rounds * cfg.round_ms, n_epochs,
                     k_trace)
    greedy = heuristic_greedy_policy(make_spec("full", n_max))
    rep = serve_stream(greedy, greedy.init(k_serve), scenario, stream, cfg,
                       key=k_serve)
    audit = _audited(rep, n_cells, cfg,
                     trace=build_trace(stream, rep["records"], TICK_MS))
    out = {"greedy": {**_serve_fields(rep), "audit": audit}}

    profile = builtin_profile("spot")
    ecfg = ServeConfig(n_max=n_max, obs_spec="full_economy",
                       tick_ms=TICK_MS, queue_cap=QUEUE_CAP, telemetry=True,
                       economy=profile)
    router = cost_greedy_policy(make_spec("full_economy", n_max), profile,
                                tick_ms=TICK_MS)
    erep = serve_stream(router, router.init(k_serve), scenario, stream,
                        ecfg, key=k_serve)
    out["cost_greedy_spot"] = {
        **_serve_fields(erep), "audit": _audited(erep, n_cells, ecfg),
        "cost_per_1k_requests": erep["economy"]["cost_per_1k_requests"]}
    return _emit("serve_bench", cells=n_cells, **out)


def _large_fleet_case(n_cells: int, horizon_ms: float, n_epochs: int):
    k_fleet, k_trace, k_serve, _ = jax.random.split(
        jax.random.PRNGKey(SEED + 1), 4)
    n_max = 5
    scenario = random_fleet(k_fleet, n_cells, n_max=n_max,
                            cells_per_edge=4)
    cfg = ServeConfig(n_max=n_max, obs_spec="full", tick_ms=TICK_MS,
                      queue_cap=QUEUE_CAP, shared_cloud=True,
                      shared_edge=True, telemetry=True)
    stream = _stream(scenario, cfg, horizon_ms, n_epochs, k_trace)
    policy = heuristic_greedy_policy(make_spec("full", n_max))
    return scenario, cfg, stream, policy, policy.init(k_serve), k_serve


def phase_large_fleet(n_cells: int = 65536, horizon_ms: float = 2000.0,
                      n_epochs: int = 4) -> dict:
    """A large single-chip fleet with both couplings, audited; counts the
    Mosaic kernels in the compiled epoch program."""
    scenario, cfg, stream, policy, params, key = _large_fleet_case(
        n_cells, horizon_ms, n_epochs)
    rep = serve_stream(policy, params, scenario, stream, cfg, key=key)
    audit = _audited(rep, n_cells, cfg)
    engine = make_serve_engine(policy, cfg)
    args = first_epoch_args(engine, policy, params, scenario, stream, key)
    n_custom = engine.run_epoch.lower(*args).compile().as_text().count(
        "tpu_custom_call")
    if not interpret_mode():
        _require(n_custom > 0, "no Mosaic kernel in the compiled epoch")
    return _emit("large_fleet", cells=n_cells, horizon_ms=horizon_ms,
                 **_serve_fields(rep), audit=audit,
                 epoch_tpu_custom_calls=n_custom)


def phase_hltrain(n_cells: int = 256, n_max: int = 8, epochs: int = 4,
                  chunk: int = 2, serve_cells: int = 32, rounds: int = 25,
                  n_epochs: int = 5) -> dict:
    """Train with the HL trainer (the rl_train --fleet recipe), save and
    reload the PolicyBundle, and serve a 32-cell stream through it."""
    obs_spec = "full"
    fcfg = FleetConfig(n_max=n_max, obs_spec=obs_spec)
    hp = FleetHLParams(seed=0, epochs=epochs, plan_cap=max(4096, n_cells),
                       direct_cap=max(65536, 8 * n_cells),
                       world_cap=max(65536, 8 * n_cells))
    trainer = make_hl_trainer(fcfg, hp)
    k_fleet, k_init = jax.random.split(jax.random.PRNGKey(0))
    stages = curriculum_fleets(k_fleet, n_cells, -(-epochs // chunk),
                               start=2, end=n_max)
    marks = [time.perf_counter()]
    state = run_curriculum(trainer, stages, epochs, chunk, k_init,
                           lambda *_: marks.append(time.perf_counter()))
    real_steps = int(state.real_steps)
    _require(real_steps > 0, "the trainer took no real steps")
    _require(all(bool(jnp.isfinite(x).all())
                 for x in jax.tree.leaves(state.dqn.params)),
             "non-finite DQN parameters after training")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = os.path.join(tmp, "hltrain.bundle.msgpack")
        save_bundle(path, PolicyBundle(
            kind="dqn", obs_spec=obs_spec, n_max=n_max,
            params=state.dqn.params,
            meta={"algo": "HL", "trainer": "hltrain-fleet",
                  "cells": n_cells, "epochs": epochs}))
        bundle = load_bundle(path, expect_spec=obs_spec,
                             expect_n_max=n_max)
    policy, params = policy_from_bundle(bundle)
    k_fleet, k_trace, k_serve, _ = jax.random.split(
        jax.random.PRNGKey(SEED), 4)
    scenario = random_fleet(k_fleet, serve_cells, n_max=n_max)
    cfg = ServeConfig(n_max=n_max, obs_spec=obs_spec, tick_ms=TICK_MS,
                      queue_cap=QUEUE_CAP, telemetry=True)
    stream = _stream(scenario, cfg, rounds * cfg.round_ms, n_epochs,
                     k_trace)
    rep = serve_stream(policy, params, scenario, stream, cfg, key=k_serve)
    return _emit("hltrain", cells=n_cells, n_max=n_max, epochs=epochs,
                 compile_time_s=marks[1] - marks[0],
                 run_time_s=marks[-1] - marks[1], steps=real_steps,
                 serve={**_serve_fields(rep),
                        "audit": _audited(rep, serve_cells, cfg)})


def phase_cells_mesh(n_chips: int = 4, n_cells: int = 65536,
                     horizon_ms: float = 2000.0, n_epochs: int = 4) -> dict:
    """The large-fleet stream on one device, then over an ``n_chips``
    cells mesh: records agree to 1e-5 and the epoch state stays sharded
    over every device of the mesh."""
    scenario, cfg, stream, policy, params, key = _large_fleet_case(
        n_cells, horizon_ms, n_epochs)
    mesh = cells_mesh(n_chips)
    one = serve_stream(policy, params, scenario, stream, cfg, key=key)
    sharded = serve_stream(policy, params, scenario, stream, cfg, key=key,
                           mesh=mesh)
    diff = max(float(np.abs(np.asarray(one["records"][f], np.float64)
                            - np.asarray(sharded["records"][f],
                                         np.float64)).max())
               for f in one["records"])
    _require(diff <= 1e-5, f"sharded records diverge by {diff} > 1e-5")
    audit = _audited(sharded, n_cells, cfg)

    engine = make_serve_engine(policy, cfg, mesh=mesh)
    state, _ = jax.block_until_ready(engine.run_epoch(
        *first_epoch_args(engine, policy, params, scenario, stream, key)))
    mesh_devices = set(mesh.devices.flat)
    for name, leaf in (("q_ids", state.q_ids), ("q_len", state.q_len),
                       ("round_start", state.round_start),
                       ("rec.wait_ms", state.rec.wait_ms),
                       ("env.user", state.env.user)):
        shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
        _require(set(leaf.sharding.device_set) == mesh_devices
                 and shard_rows == {leaf.shape[0] // n_chips},
                 f"epoch state {name} is not sharded over the {n_chips} "
                 f"mesh devices: {leaf.sharding}, shard rows {shard_rows}")
    return _emit("cells_mesh", cells=n_cells, chips=n_chips,
                 requests=one["n_requests"],
                 records_max_abs_diff=diff, audit=audit,
                 single_device={"compile_time_s": one["compile_time_s"],
                                "run_time_s": one["run_time_s"]},
                 mesh={"compile_time_s": sharded["compile_time_s"],
                       "run_time_s": sharded["run_time_s"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the cells-mesh phase alone on four chips")
    args = ap.parse_args(argv)
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if args.chips == 1:
        phase_kernels()
        phase_serve_bench()
        phase_large_fleet()
        phase_hltrain()
    else:
        phase_cells_mesh(n_chips=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
