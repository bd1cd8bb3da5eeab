"""The four-chip cell: a whole run of a 64-cell copy of ``serve64k_mesh4``
on four CPU devices, and the per-chip readers of the exchange on a
made-up four-chip trace.

The run goes to a subprocess: the device count must be forced before JAX
starts, and the cell's entry sets the process's cells mesh, which a
later run in the same process would pick up."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.lib import trace
from chipbench.lib.registry import Bench
from chipbench.tests.small import REPO, make_root

SEED = str(2**31 + 77)
CELL, REAL = "small_mesh4", "serve64k_mesh4"
MESH_METRICS = ("exchange_ms_per_tick.mesh", "exchange_bytes_per_tick.mesh",
                "merge_share.mesh", "device_ms_per_tick.mesh")
# what a CPU run can read: the program's span, not a device trace's
# operations
HOST_READ = {"merge_share.mesh"}

RUN = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root = sys.argv[1]
sys.path[:0] = [root, os.path.join(root, "src")]
from pathlib import Path
from chipbench import run
for t in ("0", "1"):
    rc = run.main(["--workload", "small_mesh4", "--seed", sys.argv[2],
                   "--seconds", "1", "--trace", t], require_tpu=False,
                  root=Path(root))
    assert rc == 0, rc
"""


def add_mesh_cell(root) -> None:
    """Add ``small_mesh4`` to a small copy (``small.make_root``): the real
    four-chip configuration at 64 cells under ``small_replay``'s mix and
    the real cell's limits, on the real cell's metrics."""
    real = Bench(REPO)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = real.workload(REAL)
    entry = next(c for c in real.spec["configs"] if c["name"] == wl["config"])
    cfg = real.config(wl["config"])
    cfg["fleet"]["n_cells"] = 64
    path = f"chipbench/configs/{CELL}.json"
    (root / path).write_text(json.dumps(cfg))
    here = root / "chipbench"
    (here / "traffic" / f"{CELL}.json").write_text(
        (here / "traffic" / "small_replay.json").read_text())
    (here / "limits" / f"{CELL}.json").write_text(
        (REPO / "chipbench" / "limits" / f"{REAL}.json").read_text())
    spec["configs"].append(dict(entry, name=CELL, file=path))
    spec["workloads"].append(dict(wl, name=CELL, config=CELL, traffic=CELL))
    for m in real.spec["end_to_end"] + real.spec["per_layer"]:
        if REAL in m.get("workloads", []):
            mine = next(x for x in spec["end_to_end"] + spec["per_layer"]
                        if x["name"] == m["name"])
            mine["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("mesh4"))
    add_mesh_cell(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", RUN, str(root), SEED],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    assert len(lines) == 2
    return lines


def test_mesh_cell_resolves_in_the_small_copy(tmp_path):
    root = make_root(tmp_path)
    add_mesh_cell(root)
    b = Bench(root)
    assert b.workload(CELL)["chips"] == 4
    assert b.config(CELL)["entry"] == "serve_mesh"
    assert {m["name"] for m in b.per_layer(CELL)} == set(MESH_METRICS)
    assert [m["name"] for m in b.end_to_end(CELL)] == [
        "requests_per_s", "peak_hbm_mb", "setup_s"]


def test_mesh_run_is_correct(mesh_runs):
    plain, traced = mesh_runs
    for out in (plain, traced):
        assert out["correct"] is True
        assert out["device"]["count"] == 4
        assert out["failed"] == 0
    assert set(plain["metrics"]) == {"requests_per_s", "peak_hbm_mb",
                                     "setup_s"}
    assert plain["metrics"]["requests_per_s"]["value"] > 0


def test_mesh_run_reads_its_metrics(mesh_runs):
    got = mesh_runs[1]["metrics"]
    for name in HOST_READ:
        assert got[name]["value"] > 0, name
    # a CPU run has no device plane to read
    assert set(got) == HOST_READ


def _op(name, shape, stage=None, kind="fusion"):
    attrs = f', frontend_attributes={{stage="{stage}"}}' if stage else ""
    return f"%{name} = {shape} {kind}(%p.1), channel_id=1{attrs}"


def test_exchange_reader_is_per_chip():
    """Four chips each run the same tick: the reader takes the tagged
    psum and the untagged (combined) all-reduce of every chip, and gives
    their time on one chip."""
    ops = {}
    for k in range(4):
        ops[f"/device:TPU:{k}"] = [
            (_op("psum.1", "s32[]", "exchange", "all-reduce"), 0, 10),
            (_op("all-reduce.2", "(s32[64]{0}, s32[])", None, "all-reduce"),
             100, 40 + k),
            (_op("fusion.3", "f32[8]{0}", "occupancy"), 200, 500),
            (_op("fusion.4", "s32[64]{0}", "exchange"), 800, 6),
            (_op("fusion.5", "f32[8]{0}"), 900, 70),
        ]
    red = trace.reduce_events(ops, [("epoch", 0, 2000)])
    assert red.n_devices == 4
    ctx = {"trace": red, "traced_ticks": 2}
    read = Bench(REPO).reader("exchange_ms_per_tick.mesh").read
    per_chip_ns = (4 * (10 + 6) + (40 + 41 + 42 + 43)) / 4
    assert read(ctx) == pytest.approx(per_chip_ns / 1e6 / 2)
    # device time on one chip, as the busy-time reader gives it
    busy_ms = Bench(REPO).reader("device_ms_per_tick.mesh").read(ctx)
    assert read(ctx) < busy_ms


def test_exchange_bytes_reader_is_per_chip():
    """Four chips each run two ticks of a tuple all-reduce and, once, a
    scalar one; the reader gives the bytes they carry a tick on one
    chip, and leaves out tagged operations that are no all-reduce and
    the ends of asynchronous ones."""
    tuple_ar = _op("all-reduce.2", "(s32[64]{0:T(256)}, s32[]{:T(128)}, "
                   "f32[2,3]{1,0})", None, "all-reduce")
    ops = {}
    for k in range(4):
        ops[f"/device:TPU:{k}"] = [
            (tuple_ar, 0, 40),
            (_op("fusion.4", "s32[64]{0}", "exchange"), 100, 6),
            (tuple_ar, 200, 40),
            (_op("psum.1", "s32[]", "exchange", "all-reduce-start"), 300, 5),
            (_op("psum.1.done", "s32[]", "exchange", "all-reduce-done"),
             400, 5),
        ]
    red = trace.reduce_events(ops, [("epoch", 0, 1000)])
    read = Bench(REPO).reader("exchange_bytes_per_tick.mesh").read
    per_chip = 2 * (64 * 4 + 4 + 6 * 4) + 4
    assert read({"trace": red, "traced_ticks": 2}) == per_chip / 2


def test_all_reduce_bytes_parses_hlo_shapes():
    parse = Bench(REPO).reader("exchange_bytes_per_tick.mesh").all_reduce_bytes
    assert parse("%psum.63 = s32[] all-reduce(%r.1), channel_id=1") == 4
    assert parse("%all-reduce.3 = (s32[65536]{0}, s32[]) all-reduce(%a, "
                 "%b), channel_id=1") == 65536 * 4 + 4
    assert parse("%ar.1 = (bf16[8,2]{1,0}, pred[3]{0}, u8[5]) all-reduce("
                 "%x)") == 8 * 2 * 2 + 3 + 5
    assert parse("%fusion.1 = s32[64]{0} fusion(%x), kind=kLoop") is None
    assert parse("%d.1 = s32[] all-reduce-done(%s)") is None


def test_exchange_reader_without_exchange_gives_none():
    ops = {f"/device:TPU:{k}": [(_op("fusion.1", "f32[8]{0}", "admit"),
                                 0, 50)] for k in range(4)}
    red = trace.reduce_events(ops, [("epoch", 0, 100)])
    for name in ("exchange_ms_per_tick.mesh", "exchange_bytes_per_tick.mesh"):
        read = Bench(REPO).reader(name).read
        assert read({"trace": red, "traced_ticks": 2}) is None
        assert read({"trace": None, "traced_ticks": 2}) is None
