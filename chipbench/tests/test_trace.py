"""The trace reduction: busy time as a union, idle share, leaf kernel
events, and idle gaps named by the host's span."""
import re
from pathlib import Path

import pytest

from chipbench.lib import trace

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ('%k.1 = f32[1,128]{1,0} custom-call(f32[128,1]{1,0} %a, '
          's32[128,1]{1,0} %b, s32[1,128]{1,0} %c), '
          'custom_call_target="tpu_custom_call"')


def test_union_with_nesting_and_gaps():
    ops = {"/device:TPU:0": [
        ("%while.1 = ...", 100, 400),      # encloses the next two
        ("%fusion.1 = ...", 120, 100),
        (KERNEL, 300, 150),
        ("%fusion.2 = ...", 700, 100),     # after a gap of 200
    ]}
    spans = [("prep", 0, 100), ("epoch", 100, 650), ("epoch", 650, 1000)]
    red = trace.reduce_events(ops, spans)
    assert red.window == (0, 1000)
    assert red.busy_ns == 400 + 100
    assert red.n_devices == 1
    # the while loop is control flow, not a leaf
    assert set(red.leaf_ops) == {"%fusion.1 = ...", KERNEL, "%fusion.2 = ..."}
    # idle: 0-100 in prep, 500-700 and 800-1000 in the epochs
    assert red.gaps == [("epoch", 200), ("epoch", 200), ("prep", 100)]
    calls, seconds, _ = trace.kernel_events(
        red, re.compile(r"= f32\[1,\d+\]\S* custom-call\(f32\[\d+,1\]"))
    assert calls == 1 and seconds == pytest.approx(150e-9)


def test_busy_averages_over_devices():
    ops = {"/device:TPU:0": [("%a = ...", 0, 50)],
           "/device:TPU:1": [("%a = ...", 0, 100)]}
    red = trace.reduce_events(ops, [("epoch", 0, 100)])
    assert red.n_devices == 2 and red.busy_ns == 75


def test_breakdown_shape():
    ops = {"/device:TPU:0": [(f"%f.{i} = f32[8]{{0}} fusion()", 10 * i, 5)
                             for i in range(20)]}
    b = trace.breakdown(trace.reduce_events(ops, [("epoch", 0, 300)]))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0].startswith("f.")


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({}, [])


def test_recorded_chip_trace(tmp_path):
    """A 256-cell serve pass traced on a TPU v5e, kept under data/: the
    reduction reads the same numbers it read on the chip."""
    import gzip
    import json
    raw = gzip.decompress((DATA / "serve_small.xplane.pb.gz").read_bytes())
    (tmp_path / "t.xplane.pb").write_bytes(raw)
    red = trace.load(tmp_path)
    want = json.loads((DATA / "serve_small.expect.json").read_text())
    assert red.n_devices == 1
    assert 0 < red.busy_s < red.window_s
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    from chipbench.lib.registry import Bench
    for name in ("group_occupancy_roofline", "queue_admit_roofline"):
        pat = Bench().reader(name).PATTERN
        calls, seconds, _ = trace.kernel_events(red, pat)
        assert calls == want[name]["calls"]
        assert seconds == pytest.approx(want[name]["seconds"], rel=1e-9)
