"""A small copy of the benchmark for the harness's own tests: the real
``chipbench`` files, the program's sources by a link, and a
``BENCHMARK.json`` whose cells run the real configurations cut to sizes
the CPU can serve in seconds, under the real cells' limits."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CUT = {  # small cell -> (real cell, cells, mix)
    "small_replay": ("serve64k_replay", 64,
                     {"rate_per_cell_per_s": 15.2, "horizon_ms": 500.0,
                      "epoch_ms": 250.0}),
    "small_live": ("serve64_live", 16,
                   {"rate_per_cell_per_s": 15.2, "horizon_ms": 1000.0,
                    "epoch_ms": 50.0}),
}


def make_root(tmp: Path) -> Path:
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    root = tmp / "bench"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    spec = copy.deepcopy(real)
    spec["configs"], spec["workloads"] = [], []
    for name, (cell, n_cells, mix) in CUT.items():
        wl = next(w for w in real["workloads"] if w["name"] == cell)
        cfg_entry = next(c for c in real["configs"] if c["name"] == wl["config"])
        cfg = json.loads((REPO / cfg_entry["file"]).read_text())
        cfg["fleet"]["n_cells"] = n_cells
        if cfg["trace"]["epochs"] is not None:
            cfg["trace"]["epochs"] = 5
        path = f"chipbench/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
        shutil.copy(REPO / "chipbench" / "limits" / f"{cell}.json",
                    root / "chipbench" / "limits" / f"{name}.json")
        spec["configs"].append(dict(cfg_entry, name=name, file=path))
        spec["workloads"].append(dict(wl, name=name, config=name,
                                      traffic=name))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n, (c, _, _) in CUT.items()
                              if c in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    # a traced run off the chip still looks its device kind up; the CPU's
    # entry exists only in this copy
    peaks = json.loads((root / "chipbench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    (root / "chipbench" / "peaks.json").write_text(json.dumps(peaks))
    return root
