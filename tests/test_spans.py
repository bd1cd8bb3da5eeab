"""Host spans, compile counters and device stage tags of the serve path.

* the span recorder: nesting, self time, first occurrence and median,
  innermost-record routing, JSON-safe summary
* ``serve_stream``: ``report["spans"]`` holds exactly the named spans,
  their self times fit in the call's wall time, ``compile_time_s`` /
  ``run_time_s`` come from them; ``report["counters"]``: one epoch-program
  trace per call and the backend compiles the benchmark's own counter
  sees over the same call
* the compiled epoch program carries a ``stage="…"`` frontend attribute
  for every tick stage
"""
import json
import re
import sys
import time
import types
from pathlib import Path

import jax
import pytest

from repro.fleet import FleetConfig, random_fleet
from repro.policy import heuristic_greedy_policy
from repro.serve import ServeConfig, poisson_request_stream, serve_stream
from repro.serve.engine import first_epoch_args, make_serve_engine
from repro.telemetry import profiling
from repro.telemetry.profiling import recording, span

REPO = Path(__file__).resolve().parents[1]

SPANS = {"serve.bucket", "serve.arrays", "serve.build", "serve.epoch",
         "serve.refresh", "serve.h2d", "serve.dispatch", "serve.wait",
         "serve.count", "serve.report"}
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
                        "serve.report", "serve.count"}:
        assert spans[name]["n"] == n_epochs, name
    assert spans["serve.count"]["n"] == n_epochs - 1
    for name in ("serve.bucket", "serve.arrays", "serve.build",
                 "serve.report"):
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
    pol, params, scn, stream, cfg = _case(telemetry=False)
    for _ in range(2):
        rep = serve_stream(pol, params, scn, stream, cfg)
        assert rep["counters"]["epoch_traces"] == 1


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
