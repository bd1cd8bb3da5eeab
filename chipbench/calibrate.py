"""The readings that the output check's limits are set from, on the chip
at the cell's own size, in one process:

    python chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 101 102 103

For every ``--seeds`` seed: one ``serve_stream`` pass of the timed path
(the first compiles), compared with the reference: the program's
readings, whose largest is the lower reading of each number.  For every
``--control-seeds`` seed: the control (the reference one precision below
the configuration's, in the program's place) compared the same way; its
smallest reading is the upper one.  One JSON line per seed; the
benchmark's own runs never run this.
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
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    from chipbench.lib import check
    from chipbench.lib.registry import Bench
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    bench = Bench(ROOT)
    w = bench.workload(args.workload)
    cfg, mix = bench.config(w["config"]), bench.traffic(w["traffic"])
    entry = bench.entry(cfg["entry"])
    for role, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seeds:
            t0 = time.perf_counter()
            cell = entry.build(cfg, mix, seed)
            if role == "program":
                rep = entry.one_pass(cell).report
            else:
                rep = check.control_report(cell.setup,
                                           *check.control_dtypes(cfg))
            t1 = time.perf_counter()
            nums = check.compare_serve(cell.setup, rep)
            print(json.dumps({"workload": args.workload, "role": role,
                              "seed": seed, **nums,
                              "served": int(rep["served_requests"]),
                              "run_s": t1 - t0,
                              "check_s": time.perf_counter() - t1}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
