"""Every name in BENCHMARK.json resolves to its file, and the file keeps
to the benchmark's format."""
import json
import re

import pytest

from chipbench.lib import check
from chipbench.lib.registry import ROOT, Bench

B = Bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    spec = B.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [e["name"] for e in spec[k]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])


@pytest.mark.parametrize("wl", [w["name"] for w in B.spec["workloads"]])
def test_cell_resolves(wl):
    w = B.workload(wl)
    cfg = B.config(w["config"])
    assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"]
    B.entry(cfg["entry"])
    assert B.traffic(w["traffic"])["horizon_ms"] > 0
    assert set(B.limits(wl)) == set(check.NUMBERS)
    e2e = {m["name"] for m in B.end_to_end(wl)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert B.per_layer(wl)
    for m in B.per_layer(wl):
        assert m["moves"] in e2e


@pytest.mark.parametrize("m", [m["name"] for m in B.spec["per_layer"]])
def test_reader_resolves(m):
    assert callable(B.reader(m).read)


def test_metric_fields():
    for m in B.spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in B.spec["end_to_end"] + B.spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            B.work(m["name"][:-len("_roofline")])
    layers = {m["layer"] for m in B.spec["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_configs_name_their_files():
    files = [c["file"] for c in B.spec["configs"]]
    assert len(files) == len(set(files))
    for c in B.spec["configs"]:
        assert c["file"].startswith("chipbench/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"]
        assert len(body["source"]) <= 200


def test_peaks_refuse_unknown_kind():
    assert B.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        B.peaks("TPU v9 imaginary")
