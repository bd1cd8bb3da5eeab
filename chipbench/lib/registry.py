"""Lookup by name.  ``BENCHMARK.json`` at the checkout's root names every
configuration, traffic mix, cell and metric; each lives in a file of its
own under ``chipbench/``, found here by that name:

    configs/<config>.json     the deployment, with its source and cuts
    traffic/<mix>.json        a mix's parameters (lib/traffic.py reads them)
    entries/<entry>.py        the runner of one kind of entry point
    metrics/<metric>.py       the reader of one per-layer metric; a metric
                              named <quantity>.<part> (one quantity split
                              by the end-to-end metric it moves) is read
                              by metrics/<quantity>.py unless a file of
                              its full name exists
    work/<kernel>.py          a kernel call's operations and bytes
    limits/<cell>.json        the limits of a cell's output check
    peaks.json                the chips' published peaks, by device kind

Adding a configuration, mix, cell or metric adds files and entries; no
file that is already there changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]   # the checkout


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.here = self.root / "chipbench"
        self.spec = _json(self.root / "BENCHMARK.json")

    def _named(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry {name!r}")

    def workload(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        return _json(self.root / self._named("configs", name)["file"])

    def traffic(self, name: str) -> dict:
        return _json(self.here / "traffic" / f"{name}.json")

    def limits(self, workload: str) -> dict:
        return _json(self.here / "limits" / f"{workload}.json")

    def entry(self, kind: str):
        return _module(self.here / "entries" / f"{kind}.py", f"_entry_{kind}")

    def reader(self, metric: str):
        path = self.here / "metrics" / f"{metric}.py"
        if not path.is_file() and "." in metric:
            path = self.here / "metrics" / f"{metric.split('.')[0]}.py"
        return _module(path, "_metric_" + metric.replace(".", "_"))

    def work(self, kernel: str):
        return _module(self.here / "work" / f"{kernel}.py", f"_work_{kernel}")

    def peaks(self, device_kind: str) -> dict:
        table = _json(self.here / "peaks.json")["devices"]
        if device_kind not in table:
            raise KeyError(f"peaks.json has no device kind {device_kind!r}")
        return table[device_kind]

    def end_to_end(self, workload: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        mine = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])
                and m["moves"] in mine]
