"""One run of one benchmark cell on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It refuses to run (exit code 2, no
result) unless JAX's first device is a TPU and JAX sees as many chips as
the cell asks for.  Everything the run serves is drawn from ``--seed``.
It warms up every shape, then measures whole passes for ``--seconds``
(``--trace 0``: the cell's end-to-end metrics) or traces one pass
(``--trace 1``: its per-layer metrics, device busy time and a breakdown),
then compares the window's output with the plain reference.  The last
lines of standard error are the compared numbers beside their limits;
the last line of standard output is the result as one JSON object.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


class Clock:
    """The run's start time, its compile counter and the device's peak
    memory."""

    def __init__(self, counter):
        self.t_start = T_START
        self.counter = counter

    @staticmethod
    def peak_bytes() -> int:
        import jax
        # the CPU backend keeps no device statistics (0); a run off the
        # chip exists only in the harness's own tests
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, require_tpu: bool = True, root: Path = ROOT) -> int:
    """``require_tpu`` and ``root`` exist for the harness's own tests,
    which drive a run on the CPU from a small copy of the benchmark."""
    args = parse(argv)
    from chipbench.lib.registry import Bench
    bench = Bench(root)
    wl = bench.workload(args.workload)
    config = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    limits = bench.limits(args.workload)
    if not (root / "src" / "repro").is_dir():
        return _fail(f"no program under {root / 'src'}")
    sys.path.insert(0, str(root / "src"))

    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # every program a pass builds, however small, goes to the persistent
    # cache, so that a pass after the warm-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        return _fail(f"needs a TPU; JAX's first device is {dev.platform} "
                     f"({dev.device_kind})")
    if len(devices) < int(wl["chips"]):
        return _fail(f"the cell asks for {wl['chips']} chips, JAX sees "
                     f"{len(devices)}")

    from chipbench.lib.compiles import CompileCounter
    from chipbench.lib import trace as trace_lib
    clock = Clock(CompileCounter())
    entry = bench.entry(config["entry"])
    trace_dir = None
    if args.trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_"))
    try:
        info = entry.measure(config, mix, args, clock, limits,
                             trace_dir=trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": info["peak_bytes"]}
    result = {"correct": info["check"]["correct"],
              "attempted": info["attempted"], "failed": info["failed"]}
    if args.trace:
        red = info["traced"]
        ctx = entry.layer_context(info, bench, dev.device_kind)
        metrics = {}
        for m in bench.per_layer(args.workload):
            v = bench.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = trace_lib.breakdown(red)
    else:
        names = [m["name"] for m in bench.end_to_end(args.workload)]
        result["metrics"] = entry.end_to_end(info, names)
        result["device"] = device
    c = info["compiles"]
    print(f"window: {len(info['passes'])} passes in {info['window_s']:.3f} s;"
          f" backend compiles {c['backend_compiles']}, cache misses "
          f"{c['cache_misses']}, retrace {c['retrace_s'] / len(info['passes']):.3f}"
          f" s per pass; pass seconds "
          + " ".join(f"{p.seconds:.3f}" for p in info["passes"]),
          file=sys.stderr)
    checks = info["check"]["checks"]
    for name, v in checks.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
