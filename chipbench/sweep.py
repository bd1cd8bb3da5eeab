"""The knee of a serve cell's configuration, swept once on the chip to fix
the rate of its traffic mix:

    python chipbench/sweep.py --workload <cell> --rates 8 12 16 20 \
        [--horizon-ms 60000] [--cells 4096] [--passes 2] [--seed 1]

For every rate (requests per cell per second) it builds the cell with
its mix at that rate (and the horizon and fleet size, if given: a cell's
queues evolve alone, one request a tick at most, so a smaller fleet over
a longer horizon shows the same knee), runs ``--passes`` passes
of the timed path (the first compiles) and prints one JSON line: the
served, dropped and deferred shares, the served rate over the offered
one in the windows of the middle of the horizon (the fleet keeps up
while it is 1), SLO attainment, the p95 end-to-end latency, and the last
pass's wall time with its p95 tick.  The benchmark's own runs never run
this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--horizon-ms", type=float, default=None)
    ap.add_argument("--cells", type=int, default=None)
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from chipbench.lib.registry import Bench
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    bench = Bench(ROOT)
    w = bench.workload(args.workload)
    cfg, mix = bench.config(w["config"]), bench.traffic(w["traffic"])
    entry = bench.entry(cfg["entry"])
    if args.cells is not None:
        cfg["fleet"]["n_cells"] = args.cells
    for rate in args.rates:
        m = dict(mix, rate_per_cell_per_s=rate)
        if args.horizon_ms is not None:
            m["horizon_ms"] = args.horizon_ms
        t0 = time.perf_counter()
        cell = entry.build(cfg, m, args.seed)
        for _ in range(args.passes):
            p = entry.one_pass(cell)
        rep = p.report
        n = rep["n_requests"]
        ser = rep["telemetry"]["series"]
        adm = np.asarray(ser["admitted"], np.float64)
        srv = np.asarray(ser["served"], np.float64)
        lo, hi = len(adm) // 4, max(len(adm) // 4 + 1, 3 * len(adm) // 4)
        ticks = p.epoch_seconds() * 1e3
        print(json.dumps({
            "workload": args.workload, "cells": cfg["fleet"]["n_cells"],
            "rate_per_cell_per_s": rate,
            "n_requests": n, "max_burst": cell.max_burst,
            "served_share": rep["served_requests"] / n,
            "dropped_share": rep["dropped_requests"] / n,
            "deferred_share": rep["deferred_requests"] / n,
            "keeps_up": float(srv[lo:hi].sum() / max(1.0, adm[lo:hi].sum())),
            "slo_attainment": rep["slo_attainment"],
            "p95_latency_ms": rep.get("p95_latency_ms"),
            "pass_s": p.seconds, "horizon_s": cell.setup.horizon_ms / 1e3,
            "epoch_p95_ms": float(np.percentile(ticks, 95)),
            "build_and_passes_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
