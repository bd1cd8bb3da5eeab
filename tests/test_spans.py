"""Host spans, compile counters and device stage tags of the serve path.

* the span recorder: nesting, self time, first occurrence and median,
  innermost-record routing, JSON-safe summary
* ``serve_stream``: ``report["spans"]`` holds exactly the named spans,
  their self times fit in the call's wall time, ``compile_time_s`` /
  ``run_time_s`` come from them; ``report["counters"]``: the epoch
  program traced on the first call of an engine and reused after, and
  the backend compiles the benchmark's own counter sees over the same
  call
* the engine cache: reuse across calls, an engine per stream shape with
  the same results as a fresh engine, what bypasses it, its bounds, and
  the traced program a reused engine keeps
* the compiled epoch program carries a ``stage="…"`` frontend attribute
  for every tick stage
"""
import dataclasses
import io
import json
import re
import sys
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.fleet import FleetConfig, random_fleet
from repro.policy import heuristic_greedy_policy
from repro.serve import ServeConfig, poisson_request_stream, serve_stream
from repro.serve import engine as engine_mod
from repro.serve.engine import (TEL_COUNTERS, TEL_GAUGES, _tick_buckets,
                                first_epoch_args, make_serve_engine)
from repro.telemetry import LiveEmitter, NdjsonSink, profiling
from repro.telemetry.profiling import recording, span

REPO = Path(__file__).resolve().parents[1]

SPANS = {"serve.bucket", "serve.arrays", "serve.build", "serve.epoch",
         "serve.refresh", "serve.h2d", "serve.dispatch", "serve.wait",
         "serve.count", "serve.report", "serve.merge"}
STAGES = ("admit", "rounds", "observe", "act", "step", "scatter",
          "telemetry", "occupancy")


@pytest.fixture
def clock(monkeypatch):
    """A fake ``perf_counter`` for the recorder: each span entry or exit
    takes the next value of the returned list."""
    ticks = []
    fake = types.SimpleNamespace(perf_counter=lambda: ticks.pop(0))
    monkeypatch.setattr(profiling, "time", fake)
    return ticks


def test_span_nesting_self_first_and_median(clock):
    clock += [0, 1, 3, 4, 8, 9, 10, 11, 12, 13]
    with recording() as rec:
        with span("a") as outer:
            with span("b"):
                pass
            with span("b"):
                pass
            with span("b"):
                pass
        with span("a"):
            pass
    assert rec.spans == [["a", 0, 11, -1], ["b", 1, 3, 0], ["b", 4, 8, 0],
                         ["b", 9, 10, 0], ["a", 12, 13, -1]]
    assert (outer.start, outer.end) == (0, 11)
    s = rec.summary()
    assert s == {
        "a": {"n": 2, "total_s": 12, "self_s": 5, "first_s": 11,
              "p50_s": 6},
        "b": {"n": 3, "total_s": 7, "self_s": 7, "first_s": 2, "p50_s": 2}}
    json.dumps(s, allow_nan=False)


def test_spans_go_to_the_innermost_record_only(clock):
    clock += [0, 1, 2, 3, 4, 5]
    with span("outside"):          # no record: nothing kept
        pass
    with recording() as outer:
        with recording() as inner:
            with span("x"):
                pass
        with span("y"):
            pass
    assert [s[0] for s in inner.spans] == ["x"]
    assert [s[0] for s in outer.spans] == ["y"]


def test_open_span_left_out_of_summary(clock):
    clock += [0, 1, 2, 3]
    with recording() as rec:
        with span("done"):
            pass
        s = span("open")
        s.__enter__()
        summary = rec.summary()
        s.__exit__(None, None, None)
    assert set(summary) == {"done"}


# ------------------------------------------------------------ serve_stream
def _case(cells=8, horizon_ms=2000.0, telemetry=True, shared_edge=True):
    scn = random_fleet(jax.random.PRNGKey(0), cells, n_max=4,
                       cells_per_edge=2)
    cfg = ServeConfig(n_max=4, quiet=True, telemetry=telemetry,
                      shared_edge=shared_edge)
    pol = heuristic_greedy_policy(FleetConfig(n_max=4).spec())
    stream = poisson_request_stream(jax.random.PRNGKey(1), scn, horizon_ms,
                                    rate=1.0, round_ms=cfg.round_ms,
                                    epoch_ms=500.0)
    return pol, pol.init(jax.random.PRNGKey(2)), scn, stream, cfg


@pytest.mark.parametrize("verbose", [False, True])
def test_serve_stream_spans(verbose, capsys):
    pol, params, scn, stream, cfg = _case()
    t0 = time.perf_counter()
    rep = serve_stream(pol, params, scn, stream, cfg, verbose=verbose)
    wall = time.perf_counter() - t0
    spans = rep["spans"]
    want = SPANS | ({"serve.progress"} if verbose else set())
    assert set(spans) == want
    assert not {"prep", "epoch"} & set(spans)   # the harness's names
    n_epochs = rep["n_epochs"]
    for name in want - {"serve.bucket", "serve.arrays", "serve.build",
                        "serve.report", "serve.merge", "serve.count"}:
        assert spans[name]["n"] == n_epochs, name
    assert spans["serve.count"]["n"] == n_epochs - 1
    for name in ("serve.bucket", "serve.arrays", "serve.build",
                 "serve.report", "serve.merge"):
        assert spans[name]["n"] == 1, name
    assert all(v["self_s"] >= 0 for v in spans.values())
    assert sum(v["self_s"] for v in spans.values()) <= wall
    epoch = spans["serve.epoch"]
    assert rep["compile_time_s"] == epoch["first_s"]
    assert rep["run_time_s"] == pytest.approx(
        epoch["total_s"] - epoch["first_s"])
    # serve_fleet --out dumps the report's JSON-safe part
    json.dumps({"spans": spans, "counters": rep["counters"]},
               allow_nan=False)


def test_one_epoch_trace_per_call():
    """The first call builds the engine and traces its epoch program; a
    second call with the same policy and config reuses both."""
    pol, params, scn, stream, cfg = _case(telemetry=False)
    counters = [serve_stream(pol, params, scn, stream, cfg)["counters"]
                for _ in range(2)]
    assert [c["epoch_traces"] for c in counters] == [1, 0]
    assert [c["engine_reused"] for c in counters] == [0, 1]


def _burst(stream, cfg):
    tpe = round(stream.epoch_ms / cfg.tick_ms)
    return _tick_buckets(stream, cfg.tick_ms, tpe)[0].shape[-1]


def _assert_same_result(rep, want):
    assert rep["records"].keys() == want["records"].keys()
    for k, v in want["records"].items():
        np.testing.assert_array_equal(rep["records"][k], v, err_msg=k)
    assert rep.get("telemetry") == want.get("telemetry")


def test_new_stream_shape_gets_an_engine_of_its_own():
    """A stream of another request count N and burst A builds and traces
    an engine of its own, which serves it as a freshly built engine does,
    the same on every call; the engine of the first shape stays cached."""
    pol, params, scn, stream, cfg = _case()
    serve_stream(pol, params, scn, stream, cfg)
    other = poisson_request_stream(jax.random.PRNGKey(7), scn, 3000.0,
                                   rate=2.0, round_ms=cfg.round_ms,
                                   epoch_ms=500.0)
    assert other.n_requests != stream.n_requests
    assert _burst(other, cfg) != _burst(stream, cfg)
    key = jax.random.PRNGKey(8)
    reps = [serve_stream(pol, params, scn, other, cfg, key=key)
            for _ in range(2)]
    assert [r["counters"]["epoch_traces"] for r in reps] == [1, 0]
    assert [r["counters"]["engine_reused"] for r in reps] == [0, 1]
    back = serve_stream(pol, params, scn, stream, cfg)["counters"]
    assert (back["epoch_traces"], back["engine_reused"]) == (0, 1)
    fresh_pol = heuristic_greedy_policy(FleetConfig(n_max=4).spec())
    fresh = serve_stream(fresh_pol, fresh_pol.init(jax.random.PRNGKey(2)),
                         scn, other, cfg, key=key)
    assert fresh["counters"]["engine_reused"] == 0
    assert "telemetry" in fresh
    for rep in reps:
        _assert_same_result(rep, fresh)


def test_engine_cache_bounds_the_programs_kept():
    """Three streams of different N with one policy give three engines,
    and every cached engine has traced its epoch program once: one
    compiled program a shape, and at most ``_MAX_ENGINES`` of them."""
    pol, params, scn, _, cfg = _case(telemetry=False)
    for horizon_ms in (1000.0, 1500.0, 2500.0):
        stream = poisson_request_stream(jax.random.PRNGKey(3), scn,
                                        horizon_ms, rate=1.0,
                                        round_ms=cfg.round_ms,
                                        epoch_ms=500.0)
        c = serve_stream(pol, params, scn, stream, cfg)["counters"]
        assert (c["epoch_traces"], c["engine_reused"]) == (1, 0)
    mine = [e for k, e in engine_mod._ENGINES.items() if k[0] is pol]
    assert len(mine) == 3
    assert len(engine_mod._ENGINES) <= engine_mod._MAX_ENGINES
    assert all(e.epoch_traces() == 1
               for e in engine_mod._ENGINES.values())


@pytest.mark.parametrize("change", ["live", "cfg", "policy"])
def test_engine_not_reused(change):
    """A call with a live emitter, with another config, or with a policy
    built anew (equal in kind, new callables) builds its own engine."""
    pol, params, scn, stream, cfg = _case()
    serve_stream(pol, params, scn, stream, cfg)
    kw = {}
    if change == "live":
        kw["live"] = LiveEmitter(NdjsonSink(io.StringIO()), TEL_COUNTERS,
                                 TEL_GAUGES, window_ms=cfg.window_ms)
    elif change == "cfg":
        cfg = dataclasses.replace(cfg, queue_cap=32)
    else:
        pol = heuristic_greedy_policy(FleetConfig(n_max=4).spec())
    rep = serve_stream(pol, params, scn, stream, cfg, **kw)
    assert rep["counters"]["engine_reused"] == 0
    assert rep["counters"]["epoch_traces"] == 1


def test_reused_engine_keeps_the_program_it_traced(monkeypatch):
    """Module state read while the epoch program is traced is not in the
    cache key: after ``act_batch`` is patched, the same policy serves the
    program traced before, and a policy built anew traces the patch."""
    pol, params, scn, stream, cfg = _case(telemetry=False)
    base = serve_stream(pol, params, scn, stream, cfg)
    orig = engine_mod.act_batch

    def altered(policy, params, obs, key, n_users=None):
        a = orig(policy, params, obs, key, n_users=n_users)
        return a.at[0].set((a[0] + 1) % 10)
    monkeypatch.setattr(engine_mod, "act_batch", altered)
    same = serve_stream(pol, params, scn, stream, cfg)
    assert same["counters"]["engine_reused"] == 1
    _assert_same_result(same, base)
    new_pol = heuristic_greedy_policy(FleetConfig(n_max=4).spec())
    new = serve_stream(new_pol, params, scn, stream, cfg)
    assert new["counters"]["engine_reused"] == 0
    assert not np.array_equal(new["records"]["action"],
                              base["records"]["action"])


def test_engine_cache_keeps_the_most_recent_few():
    """The cache holds a bounded number of engines and lets the least
    recently used one go first."""
    _, params, scn, stream, cfg = _case(cells=4, horizon_ms=600.0,
                                        telemetry=False)
    spec = FleetConfig(n_max=4).spec()
    pols = [heuristic_greedy_policy(spec)
            for _ in range(engine_mod._MAX_ENGINES + 1)]
    serve = lambda p: serve_stream(p, params, scn, stream, cfg)[
        "counters"]["engine_reused"]
    assert [serve(p) for p in pols[:-1]] == [0] * (len(pols) - 1)
    assert serve(pols[0]) == 1          # now the most recently used
    assert serve(pols[-1]) == 0         # evicts pols[1]
    assert len(engine_mod._ENGINES) == engine_mod._MAX_ENGINES
    assert serve(pols[0]) == 1
    assert serve(pols[1]) == 0


def test_backend_compiles_match_the_benchmark_counter():
    """The program's compile count over a call equals what the chip
    benchmark's own listener (``chipbench/lib/compiles.py``) counts over
    the same call."""
    sys.path.insert(0, str(REPO))
    from chipbench.lib.compiles import CompileCounter
    counter = CompileCounter()
    # a horizon no other test serves, so the epoch program compiles here
    pol, params, scn, stream, cfg = _case(cells=6, horizon_ms=1700.0)
    snap = counter.snapshot()
    rep = serve_stream(pol, params, scn, stream, cfg)
    theirs = counter.since(snap)
    ours = rep["counters"]
    assert ours["backend_compiles"] >= 1
    assert ours["backend_compiles"] == theirs["backend_compiles"]
    assert ours["cache_hits"] == theirs["cache_hits"]
    assert ours["trace_s"] > 0 and ours["lower_s"] > 0
    assert ours["compile_s"] > 0


# ------------------------------------------------------------- stage tags
@pytest.mark.parametrize("economy", [None, "spot"])
def test_epoch_program_carries_stage_tags(economy):
    from repro.economy import builtin_profile, cost_greedy_policy
    from repro.specs.observation import make_spec
    cfg = ServeConfig(n_max=3, quiet=True, queue_cap=8, telemetry=True,
                      shared_edge=True,
                      obs_spec="full_economy" if economy else "base",
                      economy=builtin_profile(economy) if economy else None)
    spec = make_spec(cfg.obs_spec, cfg.n_max)
    pol = (cost_greedy_policy(spec, cfg.economy, tick_ms=cfg.tick_ms)
           if economy else heuristic_greedy_policy(spec))
    scn = random_fleet(jax.random.PRNGKey(0), 4, n_max=3, cells_per_edge=2)
    stream = poisson_request_stream(jax.random.PRNGKey(1), scn, 400.0,
                                    rate=1.0, round_ms=cfg.round_ms,
                                    epoch_ms=200.0)
    engine = make_serve_engine(pol, cfg)
    args = first_epoch_args(engine, pol, pol.init(jax.random.PRNGKey(2)),
                            scn, stream, jax.random.PRNGKey(3))
    text = engine.run_epoch.lower(*args).compile().as_text()
    found = set(re.findall(r'stage="(\w+)"', text))
    want = set(STAGES) | ({"economy"} if economy else set())
    assert found == want
