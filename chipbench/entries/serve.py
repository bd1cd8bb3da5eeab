"""Serve cells: ``repro.serve.engine.serve_stream`` on the run's seeded
fleet and stream.

A pass is one ``serve_stream`` call over the whole stream, the call that
``serve_fleet`` and every user makes: host bucketing, engine build,
state init, every epoch and the report.  Its ``on_epoch`` hook stamps
each epoch's start on the harness clock and returns the same params the
default hook returns.  ``WARM`` passes warm up: the first compiles the
epoch program or loads it from the persistent cache.  The window is the
whole passes that follow, each started while the elapsed time plus the
last pass's length stays within ``--seconds``; a compile inside the
window stays there, counted and printed.  A traced run warms up under
the same trace plan as its traced pass (the profiler changes the
compiled program's cache key) and traces one pass.

``correct`` compares every pass of the window with the plain reference
(``chipbench/reference/serve.py``) once the window has closed.
"""
from __future__ import annotations

import hashlib
import json
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import fleet as fleet_gen
from chipbench.lib import seeds, traffic, weights
from chipbench.lib import check as check_lib
from chipbench.reference import serve as ref


# Warm-up passes before the window.  After one pass every program the
# passes run is compiled or in the persistent cache, except in the first
# process on a cache without them, whose second ``serve_stream`` call
# compiles the epoch program once more (PERF.md); that compile falls
# inside the window, where it is counted and printed.
WARM = 1


class Pass(NamedTuple):
    t0: float           # entry into serve_stream
    stamps: list        # on_epoch stamps (one per epoch)
    t1: float           # return
    report: dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def epoch_seconds(self) -> np.ndarray:
        return np.diff(np.asarray(self.stamps + [self.t1]))


class Cell(NamedTuple):
    """Everything a pass needs, built once in set-up."""
    policy: object
    params: object
    scenario: object
    stream: object
    cfg: object
    key: object
    setup: ref.Setup
    n_requests: int
    live_ticks: int
    max_burst: int
    ticks_per_epoch: int


def build(config: dict, mix: dict, seed: int) -> Cell:
    from repro.fleet.env import FleetConfig
    from repro.fleet.workload import FleetScenario
    from repro.policy import dqn_policy, heuristic_greedy_policy
    from repro.serve import RequestStream, ServeConfig
    from repro.specs.observation import make_spec

    from repro.telemetry import metrics as tel_metrics

    fs, sv, pol = config["fleet"], config["serve"], config["policy"]
    if FleetConfig().bg_busy_prob != sv["bg_busy_prob"]:
        raise ValueError(f"the program's background busy probability is "
                         f"{FleetConfig().bg_busy_prob}, the configuration "
                         f"states {sv['bg_busy_prob']}")
    hist = None
    if sv["telemetry"]:
        h = sv["latency_hist"]
        hist = (float(h["lo_ms"]), float(h["hi_ms"]), int(h["bins"]))
        prog = (tel_metrics.LAT_LO_MS, tel_metrics.LAT_HI_MS,
                tel_metrics.LAT_BINS)
        if hist != prog:
            raise ValueError(f"the program's latency histogram is {prog} "
                             f"(lo, hi, bins), the configuration states "
                             f"{hist}")
    dev = fleet_gen.draw_fleet(seeds.raw_key(seed, "fleet"), fs)
    host = fleet_gen.to_host(dev)
    C, n_max, tick = int(fs["n_cells"]), int(fs["n_max"]), float(sv["tick_ms"])
    st = traffic.make_stream(mix, C, tick, host["latency_target"],
                             seeds.rng(seed, "traffic"))
    scenario = FleetScenario(**dev)
    stream = RequestStream(st["t_ms"], st["cell"], st["slo_ms"],
                           st["horizon_ms"], st["epoch_ms"], C)
    spec = make_spec(sv["obs_spec"], n_max)
    layers = None
    if pol["kind"] == "greedy":
        policy = heuristic_greedy_policy(spec)
        params = policy.init(jax.random.PRNGKey(0))
    elif pol["kind"] == "dqn":
        hidden = tuple(pol["hidden"])
        policy = dqn_policy(spec, hidden=hidden)
        params = weights.draw_mlp(seeds.raw_key(seed, "weights"),
                                  (spec.dim, *hidden, pol["n_actions"]))
        layers = weights.to_host(params)
    else:
        raise ValueError(f"unknown policy kind {pol['kind']!r}")
    cfg = ServeConfig(n_max=n_max, obs_spec=sv["obs_spec"], tick_ms=tick,
                      queue_cap=int(sv["queue_cap"]),
                      shared_cloud=bool(sv["shared_cloud"]),
                      shared_edge=bool(sv["shared_edge"]),
                      telemetry=bool(sv["telemetry"]),
                      window_ms=float(sv["window_ms"]))
    key = seeds.raw_key(seed, "serve")
    setup = ref.Setup(n_max=n_max, queue_cap=cfg.queue_cap, tick_ms=tick,
                      shared_cloud=cfg.shared_cloud,
                      shared_edge=cfg.shared_edge,
                      bg_busy_prob=float(sv["bg_busy_prob"]), fleet=host,
                      t_ms=st["t_ms"], cell=st["cell"], slo_ms=st["slo_ms"],
                      horizon_ms=st["horizon_ms"], serve_key=key,
                      policy=pol["kind"], dqn_layers=layers,
                      window_ms=cfg.window_ms if cfg.telemetry else None,
                      hist=hist)
    return Cell(policy, params, scenario, stream, cfg, jnp.asarray(key),
                setup, int(st["t_ms"].shape[0]),
                int(round(st["horizon_ms"] / tick)) + 1, st["max_burst"],
                int(round(st["epoch_ms"] / tick)))


class TracePlan(NamedTuple):
    """Which part of a pass the profiler records: from the pass's entry
    (``from_epoch`` 0, so the host's preparation is in the window) or from
    the start of epoch ``from_epoch``, for ``epochs`` epochs or (None) to
    the pass's end."""
    path: object
    from_epoch: int
    epochs: Optional[int]


def one_pass(cell: Cell, plan: Optional[TracePlan] = None) -> Pass:
    """One ``serve_stream`` call.  Under a trace plan the harness's own
    ``TraceAnnotation`` spans mark the host's phases in the profiler's
    trace: ``prep`` (entry to the first epoch: bucketing, stream arrays,
    engine build and init), ``epoch`` (one per epoch: host dispatch and
    the wait for the device; the last one holds the report too), and the
    seconds spent stopping the profiler inside the call are left out of
    the pass's length."""
    from chipbench.lib import trace as trace_lib
    from repro.policy.api import refresh_params
    from repro.serve import serve_stream

    stamps, span, tracing, stop_s = [], [], [False], [0.0]

    def enter(name=None):
        if span:
            span.pop().__exit__(None, None, None)
        if tracing[0] and name is not None:
            a = jax.profiler.TraceAnnotation(name)
            a.__enter__()
            span.append(a)

    def start():
        jax.profiler.start_trace(str(plan.path),
                                 profiler_options=trace_lib.options())
        tracing[0] = True

    def stop():
        enter()
        t = time.perf_counter()
        jax.profiler.stop_trace()
        tracing[0] = False
        stop_s[0] += time.perf_counter() - t

    def on_epoch(e, params_t):
        stamps.append(time.perf_counter())
        if plan is not None:
            if tracing[0] and plan.epochs is not None and (
                    e == plan.from_epoch + plan.epochs):
                stop()
            elif not tracing[0] and e == plan.from_epoch > 0:
                start()
        enter("epoch")
        return refresh_params(cell.policy, cell.params, cell.scenario)

    if plan is not None and plan.from_epoch == 0:
        start()
    t0 = time.perf_counter()
    enter("prep")
    rep = serve_stream(cell.policy, cell.params, cell.scenario, cell.stream,
                       cell.cfg, key=cell.key, on_epoch=on_epoch)
    t_ret = time.perf_counter()
    mid_pass_stops = stop_s[0]
    if tracing[0]:
        stop()
    return Pass(t0, stamps, t_ret - mid_pass_stops, rep)


def digest(report: dict) -> str:
    """What the check compares of a pass: its records and telemetry."""
    h = hashlib.sha1()
    records = report["records"]
    for k in sorted(records):
        h.update(np.ascontiguousarray(records[k]).tobytes())
    h.update(json.dumps(report.get("telemetry"), sort_keys=True).encode())
    return h.hexdigest()


def check_passes(cell: Cell, passes: list, limits: dict) -> dict:
    """Compare every pass's output with the reference; passes with the
    same records and telemetry share one comparison.  The worst reading of each number
    counts."""
    worst, failed = None, 0
    seen = {}
    for p in passes:
        d = digest(p.report)
        if d not in seen:
            seen[d] = check_lib.compare_serve(cell.setup, p.report)
        worst = check_lib.worst(worst, seen[d])
        failed += seen[d]["mismatched"]
    return dict(check_lib.judge(worst, limits), failed=failed)


def measure(config: dict, mix: dict, args, clock, limits: dict,
            trace_dir=None) -> dict:
    """The run: set-up, the window (or the traced pass), memory, check.
    A traced run warms up under the same trace plan as its traced pass,
    since the profiler changes the compiled program's cache key; only
    the last pass's trace is kept and read."""
    from chipbench.lib import trace as trace_lib

    cell = build(config, mix, args.seed)
    counter = clock.counter
    traced = None

    def passes_until(seconds, plan=None):
        """``WARM`` passes, then whole passes until the next would end
        past ``seconds`` (one under a trace plan)."""
        for _ in range(WARM):
            if plan is not None:
                trace_lib.discard(plan.path)  # keep the last pass's trace
            one_pass(cell, plan)
        passes, t_win, snap = [], time.perf_counter(), counter.snapshot()
        while True:
            if plan is not None:
                trace_lib.discard(plan.path)
            p = one_pass(cell, plan)
            passes.append(p)
            if plan is not None or (p.t1 - t_win) + p.seconds > seconds:
                return passes, t_win, snap

    if trace_dir is None:
        passes, t_win, snap = passes_until(args.seconds)
    else:
        tp = config["trace"]
        plan = TracePlan(trace_dir / "passes", int(tp["from_epoch"]),
                         tp["epochs"])
        passes, t_win, snap = passes_until(0.0, plan)
        traced = trace_lib.load(trace_dir / "passes")
        trace_lib.discard(trace_dir / "passes")
    setup_s = t_win - clock.t_start
    window_s = passes[-1].t1 - t_win
    compiles = counter.since(snap)
    info = {"setup_s": setup_s, "window_s": window_s, "passes": passes,
            "compiles": compiles, "peak_bytes": clock.peak_bytes(),
            "cell": cell, "traced": traced, "trace_plan": config["trace"]}
    info["check"] = check_passes(cell, passes, limits)
    info["attempted"] = cell.n_requests * len(passes)
    info["failed"] = info["check"]["failed"]
    return info


def end_to_end(info: dict, names: list) -> dict:
    passes = info["passes"]
    cell = info["cell"]
    out = {}
    span = passes[-1].t1 - passes[0].t0
    values = {
        "requests_per_s": (cell.n_requests * len(passes) / span, "requests/s"),
        "peak_hbm_mb": (info["peak_bytes"] / 1e6, "MB"),
        "setup_s": (info["setup_s"], "s"),
    }
    if cell.ticks_per_epoch == 1:
        ticks = np.concatenate([p.epoch_seconds() for p in passes])
        values["tick_p95_ms"] = (float(np.percentile(ticks, 95) * 1e3), "ms")
    for n in names:
        if n not in values:
            raise KeyError(f"the serve entry does not measure {n!r}")
        v, unit = values[n]
        out[n] = {"value": float(v), "unit": unit}
    return out


def layer_context(info: dict, bench, device_kind: str) -> dict:
    """What the per-layer readers read: the reduced trace of the traced
    pass, the pass's host stamps, its counts and shapes, the work
    functions and the chip's peaks."""
    cell = info["cell"]
    p = info["passes"][0]
    tp = info["trace_plan"]
    ticks = (cell.live_ticks - int(tp["from_epoch"]) * cell.ticks_per_epoch
             if tp["epochs"] is None
             else int(tp["epochs"]) * cell.ticks_per_epoch)
    return {"trace": info["traced"], "pass": p, "traced_ticks": ticks,
            "shapes": {"cells": int(cell.setup.fleet["weak_e"].shape[0]),
                       "queue_cap": cell.cfg.queue_cap,
                       "lanes": cell.max_burst, "n_max": cell.cfg.n_max,
                       "requests": cell.n_requests},
            "work": bench.work, "peaks": bench.peaks(device_kind)}
