"""Sharded serving: shard_map over the ``cells`` axis must be invisible.

Acceptance contract of the cell-sharded engine:
  * a one-device cells mesh reproduces the mesh-free engine to 1e-5 on
    per-request records, report figures, and telemetry window series —
    for the greedy baseline AND an untrained DQN, with both cross-cell
    couplings (shared cloud, shared edge groups) switched on — and the
    telemetry invariant audit passes on the sharded report
  * an 8-way forced-host-device mesh does the same (subprocess: the
    XLA_FLAGS device-count override must precede jax init)
  * misuse fails loudly: meshes without a ``cells`` axis, live streaming
    under a mesh, fleets that do not divide over the mesh
  * ``MeshInfo`` carries the new axis without disturbing the seed LM
    dp/tp detection, and ``serve_stream`` picks a registered cells mesh
    up from the sharding runtime registry
  * under a mesh a shard's bucket width is its largest burst rounded up
    to an eighth of its power of two, so streams of one rate share one
    compiled program
  * ``merge_shard_buffers`` reduces per-shard MetricBuffer copies with
    per-name gauge semantics (sum vs mean) and NaN-safe windows
  * the benchmark's four-chip deployment, cut to 64 cells on four forced
    host devices, passes the plain reference under its cell's limits;
    its epoch program is traced once a build (the initial state is
    placed on the mesh as the epochs return it), tags every cells-axis
    psum ``stage="exchange"`` (the single-device program carries no such
    tag), compiles to all-reduces of the bytes the benchmark's reader
    counts, and its report records the shard merge as ``serve.merge``
    inside ``serve.report``
"""
import contextlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import FleetConfig, random_fleet
from repro.policy import dqn_policy, heuristic_greedy_policy
from repro.serve import ServeConfig, poisson_request_stream, serve_stream
from repro.serve.engine import _tick_buckets, make_serve_engine
from repro.serve.stream import RequestStream
from repro.sharding.runtime import (CELLS_AXIS, cells_mesh, get_mesh_info,
                                    set_mesh_info)
from repro.telemetry import (MetricBuffer, audit_serve_report, build_trace,
                             merge_shard_buffers)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_MAX = 4
CELLS = 16


def _case(seed=11, rate=2.5, rounds=6):
    """A coupled serving case: non-singleton edge groups + both shared
    couplings on, telemetry threaded through the tick scan."""
    scfg = ServeConfig(n_max=N_MAX, shared_cloud=True, shared_edge=True,
                       telemetry=True)
    scn = random_fleet(jax.random.PRNGKey(seed), CELLS, n_max=N_MAX,
                       cells_per_edge=4)
    horizon = rounds * scfg.round_ms
    stream = poisson_request_stream(jax.random.PRNGKey(seed + 1), scn,
                                    horizon, rate=rate,
                                    round_ms=scfg.round_ms,
                                    epoch_ms=horizon / 3)
    return scn, stream, scfg


def _assert_reports_match(r1, r2, tol=1e-5):
    # figures: everything scalar except wall-clock timings and the mesh
    # stamp itself
    skip = {"mesh_cells", "compile_time_s", "run_time_s",
            "decisions_per_s", "active_decisions_per_s"}
    for k, v in r1.items():
        if k in skip or not isinstance(v, (int, float, type(None))):
            continue
        w = r2[k]
        if v is None or w is None:
            assert v == w, k
        else:
            assert abs(v - w) <= tol * max(1.0, abs(v)), (k, v, w)
    for k, v in r1["records"].items():
        np.testing.assert_allclose(
            np.asarray(v, np.float64), np.asarray(r2["records"][k],
                                                  np.float64),
            atol=tol, err_msg=f"records[{k}]")
    t1, t2 = r1["telemetry"], r2["telemetry"]
    np.testing.assert_array_equal(t1["latency_hist"], t2["latency_hist"])
    for name, s in t1["series"].items():
        a = np.asarray([np.nan if x is None else x for x in s], np.float64)
        b = np.asarray([np.nan if x is None else x
                        for x in t2["series"][name]], np.float64)
        np.testing.assert_allclose(a, b, atol=tol, err_msg=name)


@pytest.mark.parametrize("kind", ["greedy", "dqn"])
def test_one_device_mesh_parity(kind):
    cfg = FleetConfig(n_max=N_MAX)
    if kind == "greedy":
        pol = heuristic_greedy_policy(cfg.spec())
        params = pol.init(jax.random.PRNGKey(0))
    else:
        pol = dqn_policy(cfg.spec(), hidden=(16,))
        params = pol.init(jax.random.PRNGKey(5))
    scn, stream, scfg = _case()
    key = jax.random.PRNGKey(7)
    r1 = serve_stream(pol, params, scn, stream, scfg, key=key)
    rm = serve_stream(pol, params, scn, stream, scfg, key=key,
                      mesh=cells_mesh(1))
    assert r1["mesh_cells"] == 1 and rm["mesh_cells"] == 1
    assert rm["served_requests"] > 0
    _assert_reports_match(r1, rm)
    # the sharded report survives the conservation-law audit
    audit = audit_serve_report(
        rm, trace=build_trace(stream, rm["records"], scfg.tick_ms),
        n_cells=CELLS, n_max=N_MAX, queue_cap=scfg.queue_cap)
    audit.raise_on_failure()


# ------------------------------------------------- multi-device parity
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.fleet import FleetConfig, random_fleet
from repro.policy import dqn_policy, heuristic_greedy_policy
from repro.serve import ServeConfig, poisson_request_stream, serve_stream
from repro.sharding.runtime import cells_mesh, set_mesh_info
from repro.telemetry import audit_serve_report, build_trace

n_max, cells = 4, 32
cfg = FleetConfig(n_max=n_max)
scfg = ServeConfig(n_max=n_max, shared_cloud=True, shared_edge=True,
                   telemetry=True)
scn = random_fleet(jax.random.PRNGKey(11), cells, n_max=n_max,
                   cells_per_edge=4)
horizon = 6 * scfg.round_ms
stream = poisson_request_stream(jax.random.PRNGKey(12), scn, horizon,
                                rate=2.5, round_ms=scfg.round_ms,
                                epoch_ms=horizon / 3)
pols = {"greedy": heuristic_greedy_policy(cfg.spec()),
        "dqn": dqn_policy(cfg.spec(), hidden=(16,))}
mesh = cells_mesh()
assert mesh.shape["cells"] == 8
for name, pol in pols.items():
    params = pol.init(jax.random.PRNGKey(5))
    key = jax.random.PRNGKey(7)
    r1 = serve_stream(pol, params, scn, stream, scfg, key=key)
    r8 = serve_stream(pol, params, scn, stream, scfg, key=key, mesh=mesh)
    assert r8["mesh_cells"] == 8
    assert r8["served_requests"] == r1["served_requests"] > 0
    d = max(float(np.abs(np.asarray(r1["records"][f], np.float64)
                         - np.asarray(r8["records"][f],
                                      np.float64)).max())
            for f in r1["records"])
    assert d <= 1e-5, (name, d)
    for fig in ("p99_latency_ms", "slo_attainment", "violation_rate",
                "dropped_requests", "deferred_requests"):
        a, b = r1[fig], r8[fig]
        assert (a is None) == (b is None), fig
        if a is not None:
            assert abs(a - b) <= 1e-5 * max(1.0, abs(a)), (name, fig, a, b)
    np.testing.assert_array_equal(r1["telemetry"]["latency_hist"],
                                  r8["telemetry"]["latency_hist"])
    for sname, s in r1["telemetry"]["series"].items():
        a = np.asarray([np.nan if x is None else x for x in s])
        b = np.asarray([np.nan if x is None else x
                        for x in r8["telemetry"]["series"][sname]])
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=sname)
    audit_serve_report(
        r8, trace=build_trace(stream, r8["records"], scfg.tick_ms),
        n_cells=cells, n_max=n_max,
        queue_cap=scfg.queue_cap).raise_on_failure()
    print(name, "OK", d)

# a fleet that does not divide over the mesh fails loudly
bad = random_fleet(jax.random.PRNGKey(1), 28, n_max=n_max)
bs = poisson_request_stream(jax.random.PRNGKey(2), bad, 400.0, rate=1.0,
                            round_ms=scfg.round_ms, epoch_ms=400.0)
try:
    serve_stream(pols["greedy"], pols["greedy"].init(jax.random.PRNGKey(0)),
                 bad, bs, scfg, mesh=mesh)
    raise SystemExit("divisibility not enforced")
except ValueError as e:
    assert "divide" in str(e)

# registry pickup: a set_mesh_info-registered cells mesh is used without
# passing mesh= explicitly
set_mesh_info(mesh)
try:
    r = serve_stream(pols["greedy"],
                     pols["greedy"].init(jax.random.PRNGKey(0)),
                     scn, stream, scfg, key=jax.random.PRNGKey(7))
finally:
    set_mesh_info(None)
assert r["mesh_cells"] == 8
print("ALL_OK")
"""


def test_multi_device_parity_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL_OK" in proc.stdout


# ------------------------------------------------------- loud failures
def test_engine_rejects_mesh_without_cells_axis():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    pol = heuristic_greedy_policy(FleetConfig(n_max=N_MAX).spec())
    with pytest.raises(ValueError, match="cells"):
        make_serve_engine(pol, ServeConfig(n_max=N_MAX), mesh=mesh)


def test_engine_rejects_live_under_mesh():
    pol = heuristic_greedy_policy(FleetConfig(n_max=N_MAX).spec())
    with pytest.raises(ValueError, match="live"):
        make_serve_engine(pol, ServeConfig(n_max=N_MAX, telemetry=True),
                          live=object(), mesh=cells_mesh(1))


# -------------------------------------------------------- mesh registry
def test_mesh_info_cells_axis():
    set_mesh_info(None)
    try:
        set_mesh_info(cells_mesh(1))
        mi = get_mesh_info()
        assert mi.cells_axis == CELLS_AXIS
        assert mi.cells_size == 1
        assert mi.dp_axes == ()   # a cells mesh is not a dp/tp mesh
    finally:
        set_mesh_info(None)
    assert get_mesh_info() is None


def test_mesh_info_legacy_dp_tp_unchanged():
    from jax.sharding import Mesh
    set_mesh_info(None)
    try:
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        set_mesh_info(mesh)
        mi = get_mesh_info()
        assert mi.cells_axis is None and mi.cells_size == 1
        assert mi.dp_axes == ("data",)
        assert mi.tp_axis == "model"
    finally:
        set_mesh_info(None)


def test_cells_mesh_too_many_devices_errors():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        cells_mesh(jax.device_count() + 1)


def test_serve_stream_picks_up_registry_mesh():
    pol = heuristic_greedy_policy(FleetConfig(n_max=N_MAX).spec())
    scn, stream, scfg = _case(rounds=2)
    set_mesh_info(None)
    try:
        set_mesh_info(cells_mesh(1))
        rep = serve_stream(pol, pol.init(jax.random.PRNGKey(0)), scn,
                           stream, scfg, key=jax.random.PRNGKey(7))
    finally:
        set_mesh_info(None)
    assert rep["mesh_cells"] == 1


# ------------------------------------------------- merge_shard_buffers
def _burst_stream(burst: int) -> RequestStream:
    """``burst`` requests to cell 0 in the first tick, one to each other
    cell of an 8-cell fleet in the second."""
    t = np.r_[np.full(burst, 10.0), np.full(7, 60.0)].astype(np.float32)
    cell = np.r_[np.zeros(burst), np.arange(1, 8)].astype(np.int32)
    return RequestStream(t, cell, np.full(t.shape, 500.0, np.float32),
                         200.0, 100.0, 8)


@pytest.mark.parametrize("shards", [1, 2])
def test_shard_bucket_width_is_shared_by_nearby_bursts(shards):
    """Under a mesh a shard's bucket width is its largest burst rounded
    up to an eighth of its power of two (97 and 103 both give 104), so
    streams of one rate share one program; on one device it is the
    burst itself.  Every request lands in its tick and shard once."""
    widths = []
    for burst in (97, 103):
        stream = _burst_stream(burst)
        ids, now, live, n_epochs = _tick_buckets(stream, 50.0, 2, shards)
        assert ids.shape[:2] == (6, shards) and n_epochs == 3
        got = np.sort(ids[ids >= 0])
        np.testing.assert_array_equal(got, np.arange(stream.n_requests))
        assert (ids[1, 0] >= 0).sum() == burst
        widths.append(ids.shape[-1])
    assert widths == ([104, 104] if shards > 1 else [97, 103])


def test_merge_shard_buffers_semantics():
    edges = jnp.asarray([1.0, 10.0, 100.0])
    buf = MetricBuffer(
        edges=edges,
        hist=jnp.asarray([[1, 2], [3, 4]], jnp.int32),
        counters={"served": jnp.asarray([[1, 0, 2], [0, 5, 1]],
                                        jnp.int32)},
        gauges={"backlog": jnp.asarray([[1.0, np.nan, 2.0],
                                        [3.0, np.nan, np.nan]],
                                       jnp.float32),
                "queue_depth": jnp.asarray([[2.0, 4.0, np.nan],
                                            [4.0, np.nan, np.nan]],
                                           jnp.float32)})
    out = merge_shard_buffers(buf, gauge_reduce={"queue_depth": "mean"})
    np.testing.assert_array_equal(np.asarray(out.edges),
                                  np.asarray(edges))
    np.testing.assert_array_equal(np.asarray(out.hist), [4, 6])
    np.testing.assert_array_equal(np.asarray(out.counters["served"]),
                                  [1, 5, 3])
    # extensive gauge sums over the shards that wrote; all-NaN stays NaN
    got = np.asarray(out.gauges["backlog"])
    assert got[0] == 4.0 and np.isnan(got[1]) and got[2] == 2.0
    # intensive gauge averages over writing shards
    got = np.asarray(out.gauges["queue_depth"])
    assert got[0] == 3.0 and got[1] == 4.0 and np.isnan(got[2])


# ------------------------------------------------ the four-chip cell's path
# The benchmark's four-chip deployment cut to 64 cells, on four forced
# host devices: its own entry serves it (setting the process's cells
# mesh), the plain reference judges it under the cell's limits, and the
# epoch program's exchange is read from its HLO, before and after XLA's
# passes.
MESH4_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import json
from chipbench.lib import check
from chipbench.lib.registry import Bench
from repro.serve.engine import first_epoch_args, make_serve_engine
from repro.sharding.runtime import get_mesh_info

bench = Bench()
bytes_of = bench.reader("exchange_bytes_per_tick.mesh").all_reduce_bytes
wl = bench.workload("serve64k_mesh4")
config = bench.config(wl["config"])
config["fleet"]["n_cells"] = 64
mix = dict(bench.traffic(wl["traffic"]), horizon_ms=500.0, epoch_ms=250.0)
entry = bench.entry(config["entry"])
cell = entry.build(config, mix, 2**31 + 5)
rep = entry.one_pass(cell).report
verdict = check.judge(check.compare_serve(cell.setup, rep),
                      bench.limits("serve64k_mesh4"))

out = {"mesh_cells": rep["mesh_cells"], "served": rep["served_requests"],
       "correct": verdict["correct"], "checks": verdict["checks"],
       "counters": rep["counters"], "spans": sorted(rep["spans"])}
for name, mesh in (("sharded", get_mesh_info().mesh), ("single", None)):
    engine = make_serve_engine(cell.policy, cell.cfg, mesh=mesh)
    args = first_epoch_args(engine, cell.policy, cell.params, cell.scenario,
                            cell.stream, cell.key)
    lowered = engine.run_epoch.lower(*args)
    hlo = lowered.as_text(dialect="hlo")
    reduces = [ln for ln in hlo.splitlines() if " all-reduce(" in ln]
    compiled = [bytes_of(ln.strip())
                for ln in lowered.compile().as_text().splitlines()]
    out[name] = {"all_reduces": len(reduces),
                 "tagged": sum('stage="exchange"' in ln for ln in reduces),
                 "exchange_tags": hlo.count('stage="exchange"'),
                 "compiled_bytes": sorted(b for b in compiled
                                          if b is not None)}
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh4():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MESH4_SCRIPT, REPO],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(x for x in proc.stdout.splitlines()
                if x.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_mesh4_cell_matches_the_reference(mesh4):
    """The sharded ``serve_stream`` of the four-chip deployment passes the
    plain reference's comparison within the cell's limits."""
    assert mesh4["mesh_cells"] == 4 and mesh4["served"] > 0
    assert mesh4["correct"] is True, mesh4["checks"]


def test_mesh4_psums_carry_the_exchange_tag(mesh4):
    """Every cells-axis psum of the sharded epoch program is tagged
    ``stage="exchange"``; the single-device program has no psum and no
    such tag."""
    sharded, single = mesh4["sharded"], mesh4["single"]
    assert sharded["all_reduces"] > 0
    assert sharded["tagged"] == sharded["all_reduces"]
    assert single["all_reduces"] == 0 and single["exchange_tags"] == 0


def test_mesh4_exchange_bytes_count_the_tick_psums(mesh4):
    """The compiled sharded program exchanges, a tick, four int32 arrays
    over the fleet's 64 cells and three int32 scalars (the psums whose
    results the tick reads), and once an epoch the 4-byte decision
    count, as ``exchange_bytes_per_tick``'s parser reads the compiled
    all-reduces; the compiled single-device program has none."""
    per_tick = 4 * 64 * 4 + 3 * 4
    assert sum(mesh4["sharded"]["compiled_bytes"]) == per_tick + 4
    assert 4 in mesh4["sharded"]["compiled_bytes"]
    assert mesh4["single"]["compiled_bytes"] == []


def test_mesh4_epoch_program_traced_once(mesh4):
    """The initial state arrives on the mesh as every epoch returns it,
    so a build traces the sharded epoch program once."""
    assert mesh4["counters"]["epoch_traces"] == 1


def test_mesh4_report_records_the_merge(mesh4):
    assert "serve.merge" in mesh4["spans"]


def test_merge_span_is_a_child_of_the_report(monkeypatch):
    """``serve.merge`` (the shard copies to the host, merged) is recorded
    inside ``serve.report``, once a call."""
    from repro.serve import engine as engine_mod
    from repro.telemetry import profiling
    recs = []
    orig = profiling.recording

    @contextlib.contextmanager
    def keep():
        with orig() as rec:
            recs.append(rec)
            yield rec
    monkeypatch.setattr(engine_mod, "recording", keep)
    pol = heuristic_greedy_policy(FleetConfig(n_max=N_MAX).spec())
    scn, stream, scfg = _case(rounds=2)
    serve_stream(pol, pol.init(jax.random.PRNGKey(0)), scn, stream, scfg,
                 key=jax.random.PRNGKey(7))
    spans = recs[-1].spans
    merges = [s for s in spans if s[0] == "serve.merge"]
    assert len(merges) == 1
    parent = spans[merges[0][3]]
    assert parent[0] == "serve.report"
    assert parent[1] <= merges[0][1] <= merges[0][2] <= parent[2]
