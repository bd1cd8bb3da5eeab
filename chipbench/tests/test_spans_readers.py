"""The readers of the program's own spans, counters and stage tags: on
made-up inputs, on a program that has none of them (they give None), and
on a small serve pass traced on a TPU v5e with its stage tags."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench.lib import trace
from chipbench.lib.registry import Bench

B = Bench()
DATA = Path(__file__).resolve().parent / "data"
STAGE_READERS = ("telemetry_ms_per_tick.serve", "scatter_ms_per_tick.serve",
                 "occupancy_ms_per_tick.serve", "admit_ms_per_tick.serve")
HOST_READERS = ("bucket_share.serve", "dispatch_share.serve",
                "report_share.serve", "wait_ms_per_tick.serve",
                "wait_ms_per_tick.live", "refresh_ms_per_tick.live",
                "h2d_ms_per_tick.live", "dispatch_ms_per_tick.live",
                "count_ms_per_tick.live",
                "epoch_traces.serve", "epoch_traces.live",
                "backend_compiles.serve", "backend_compiles.live")


def _read(name, ctx):
    return B.reader(name).read(ctx)


def _span(n, total, self_s=None, first=None, p50=None):
    return {"n": n, "total_s": total,
            "self_s": total if self_s is None else self_s,
            "first_s": total / n if first is None else first,
            "p50_s": total / n if p50 is None else p50}


def _ctx(report, t0=10.0, t1=20.0, red=None, ticks=40):
    return {"pass": SimpleNamespace(t0=t0, t1=t1, stamps=[11.0],
                                    report=report),
            "trace": red, "traced_ticks": ticks}


def test_host_readers_on_a_made_up_pass():
    rep = {"n_ticks": 41, "n_epochs": 5,
           "spans": {"serve.bucket": _span(1, 2.5, self_s=2.0),
                     "serve.dispatch": _span(5, 1.5),
                     "serve.report": _span(1, 0.5),
                     "serve.wait": _span(5, 4.1)},
           "counters": {"epoch_traces": 1, "backend_compiles": 0}}
    ctx = _ctx(rep)                      # a pass of 10 s
    assert _read("bucket_share.serve", ctx) == pytest.approx(20.0)
    assert _read("dispatch_share.serve", ctx) == pytest.approx(15.0)
    assert _read("report_share.serve", ctx) == pytest.approx(5.0)
    assert _read("wait_ms_per_tick.serve", ctx) == pytest.approx(100.0)
    assert _read("epoch_traces.serve", ctx) == 1
    assert _read("backend_compiles.serve", ctx) == 0
    # medians are read only where an epoch is one tick
    assert _read("dispatch_ms_per_tick.live", ctx) is None


def test_median_readers_in_a_live_pass():
    rep = {"n_ticks": 1201, "n_epochs": 1201,
           "spans": {"serve.refresh": _span(1201, 3.0, p50=0.0004),
                     "serve.h2d": _span(1201, 0.4, p50=0.0003),
                     "serve.dispatch": _span(1201, 3.5, first=1.6,
                                             p50=0.0015),
                     "serve.wait": _span(1201, 0.2402),
                     "serve.count": _span(1200, 0.5, p50=0.0004)}}
    ctx = _ctx(rep)
    assert _read("refresh_ms_per_tick.live", ctx) == pytest.approx(0.4)
    assert _read("h2d_ms_per_tick.live", ctx) == pytest.approx(0.3)
    assert _read("dispatch_ms_per_tick.live", ctx) == pytest.approx(1.5)
    assert _read("wait_ms_per_tick.live", ctx) == pytest.approx(0.2)
    assert _read("count_ms_per_tick.live", ctx) == pytest.approx(0.4)


@pytest.mark.parametrize("name", HOST_READERS + STAGE_READERS)
def test_nothing_to_read_gives_none(name):
    """A program without spans, counters or stage tags: every reader
    gives None, so the metric is left out of the result line."""
    red = trace.reduce_events(
        {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion()", 0, 50)]},
        [("epoch", 0, 100)])
    assert _read(name, _ctx({"n_ticks": 41, "n_epochs": 5}, red=red)) \
        is None
    assert _read(name, _ctx({}, red=None)) is None


def test_stage_readers_sum_tagged_leaf_ops():
    tagged = lambda n, s: (f'%fusion.{n} = f32[8]{{0}} fusion(), '
                           f'frontend_attributes={{stage="{s}"}}')
    ops = {"/device:TPU:0": [
        (tagged(1, "telemetry"), 0, 300),
        (tagged(2, "telemetry"), 400, 100),
        ('%k.1 = f32[1,8]{1,0} custom-call(), custom_call_target='
         '"tpu_custom_call", frontend_attributes={kernel_metadata={},'
         'stage="occupancy"}', 600, 200),
        (tagged(3, "admit"), 900, 50),
        ("%fusion.4 = f32[8]{0} fusion()", 1000, 80),   # untagged
    ]}
    red = trace.reduce_events(ops, [("epoch", 0, 2000)])
    ctx = _ctx({}, red=red, ticks=2)
    assert _read("telemetry_ms_per_tick.serve", ctx) == pytest.approx(2e-4)
    assert _read("occupancy_ms_per_tick.serve", ctx) == pytest.approx(1e-4)
    assert _read("admit_ms_per_tick.serve", ctx) == pytest.approx(2.5e-5)
    assert _read("scatter_ms_per_tick.serve", ctx) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A 256-cell serve pass traced on a TPU v5e with its stage tags,
    and what the chip read of it (``record_trace.py``)."""
    tmp = tmp_path_factory.mktemp("tagged")
    raw = gzip.decompress((DATA / "serve_tagged.xplane.pb.gz").read_bytes())
    (tmp / "t.xplane.pb").write_bytes(raw)
    want = json.loads((DATA / "serve_tagged.expect.json").read_text())
    p = want["pass"]
    ctx = {"trace": trace.load(tmp),
           "pass": SimpleNamespace(t0=p["t0"], t1=p["t1"],
                                   stamps=p["stamps"], report=p["report"]),
           "traced_ticks": want["traced_ticks"]}
    return ctx, want


def test_recorded_trace_reads_as_on_the_chip(recorded):
    ctx, want = recorded
    red = ctx["trace"]
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    for name in STAGE_READERS:
        stage = name.split("_ms_per_tick")[0]
        assert _read(name, ctx) == pytest.approx(
            want["stage_ms_per_tick"][stage], rel=1e-9)
    for name in HOST_READERS:
        if name.endswith(".serve"):
            assert _read(name, ctx) is not None, name
    assert _read("epoch_traces.serve", ctx) == 1


def test_recorded_trace_kernels_still_found(recorded):
    """The kernels' custom calls gained a name and a stage attribute: the
    rooflines' interface patterns still find every call, and each
    kernel's time lies inside its stage's."""
    ctx, want = recorded
    red, ticks = ctx["trace"], ctx["traced_ticks"]
    stage = {"group_occupancy_roofline": "occupancy_ms_per_tick.serve",
             "queue_admit_roofline": "admit_ms_per_tick.serve"}
    for name, stage_reader in stage.items():
        calls, seconds, _ = trace.kernel_events(red, B.reader(name).PATTERN)
        assert calls == want[name]["calls"] > 0
        assert seconds == pytest.approx(want[name]["seconds"], rel=1e-9)
        assert seconds * 1e3 / ticks <= _read(stage_reader, ctx)


def test_recorded_trace_is_mostly_tagged(recorded):
    """Operations carrying a stage tag take at least 80% of the device's
    busy time per tick."""
    ctx, _ = recorded
    red = ctx["trace"]
    tagged = sum(d for text, (_, d) in red.leaf_ops.items()
                 if 'stage="' in text)
    busy_ms = _read("device_ms_per_tick.serve", ctx)
    assert tagged / 1e6 / ctx["traced_ticks"] >= 0.8 * busy_ms
