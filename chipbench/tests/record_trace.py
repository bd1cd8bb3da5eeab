"""Record, on the chip, the small traced serve pass that the harness's
tests read:

    python chipbench/tests/record_trace.py --out <dir> [--cells 256]

The replay cell's configuration cut to ``--cells`` cells and 0.5 s of
its traffic, served by the harness's own pass under the replay cell's
trace plan (from the pass's entry to its return, harness spans ``prep``
and ``epoch``), after one warm-up pass under the same plan.  Writes
``<dir>/serve_tagged.xplane.pb.gz``, the trace, and
``<dir>/serve_tagged.expect.json``: what the reduction read from it on
the chip (busy and window seconds, each kernel's calls and seconds,
each stage's device ms per tick) and the pass's host data that the span
readers read (its clock stamps, ``spans``, ``counters``, tick counts).
"""
import argparse
import copy
import glob
import gzip
import json
import re
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

NAME = "serve_tagged"
CELL = "serve64k_replay"
MIX = {"rate_per_cell_per_s": 15.2, "horizon_ms": 500.0, "epoch_ms": 250.0}
STAGES = ("admit", "rounds", "observe", "act", "step", "scatter",
          "telemetry", "occupancy")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--cells", type=int, default=256)
    ap.add_argument("--seed", type=int, default=2147483001)
    args = ap.parse_args(argv)

    import jax
    from chipbench.lib import spans, trace
    from chipbench.lib.registry import Bench
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    bench = Bench(ROOT)
    config = copy.deepcopy(bench.config(bench.workload(CELL)["config"]))
    config["fleet"]["n_cells"] = args.cells
    entry = bench.entry(config["entry"])
    cell = entry.build(config, MIX, args.seed)
    work = Path(tempfile.mkdtemp(prefix="record_trace_"))
    plan = entry.TracePlan(work / "pass", 0, None)
    entry.one_pass(cell, plan)            # warm-up under the same plan
    trace.discard(plan.path)
    p = entry.one_pass(cell, plan)
    red = trace.load(plan.path)
    [xplane] = glob.glob(str(plan.path / "**" / "*.xplane.pb"),
                         recursive=True)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{NAME}.xplane.pb.gz").write_bytes(
        gzip.compress(Path(xplane).read_bytes()))
    trace.discard(work)

    ctx = {"trace": red, "traced_ticks": cell.live_ticks, "pass": p}
    want = {"busy_s": red.busy_s, "window_s": red.window_s,
            "traced_ticks": cell.live_ticks, "stage_ms_per_tick": {}}
    for name in ("group_occupancy_roofline", "queue_admit_roofline"):
        calls, seconds, _ = trace.kernel_events(
            red, bench.reader(name).PATTERN)
        want[name] = {"calls": calls, "seconds": seconds}
    for s in STAGES:
        want["stage_ms_per_tick"][s] = spans.stage_ms_per_tick(ctx, s)
    rep = p.report
    want["pass"] = {"t0": p.t0, "t1": p.t1, "stamps": p.stamps,
                    "report": {k: rep[k] for k in (
                        "spans", "counters", "n_ticks", "n_epochs")}}
    (out / f"{NAME}.expect.json").write_text(json.dumps(want, indent=1))
    print(json.dumps({k: v for k, v in want.items() if k != "pass"}))
    tags = sorted({m for t in red.leaf_ops
                   for m in re.findall(r'stage="(\w+)"', t)})
    print(json.dumps({"stages_in_trace": tags}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
