"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<k>``; their ``XLA Ops`` line holds
one event per HLO operation run, named by the instruction's HLO text,
with control flow (``while``, ``conditional``) as events that enclose
their body's operations.  The harness's own ``TraceAnnotation`` spans sit
on a host plane's ``python`` line, on the same clock.

* The window is from the start of the harness's first span to the end of
  its last (``SPANS``).
* Busy time is the union of the device's operation intervals inside the
  window, averaged over the devices that ran any; idle share is 1 minus
  busy over the window.
* Leaf operations (those that enclose no other) are totalled by their
  HLO text: the kernels' readers pick their events from these.
* An idle gap is a stretch of the window with no device operation; it is
  named by the harness span the host was in at its middle.
"""
from __future__ import annotations

import glob
import shutil
from pathlib import Path
from typing import NamedTuple

import jax

SPANS = ("prep", "epoch")
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


class Reduced(NamedTuple):
    window: tuple        # (start_ns, end_ns)
    busy_ns: float       # mean over the devices that ran operations
    n_devices: int
    leaf_ops: dict       # HLO text -> [count, total ns], all devices
    gaps: list           # [(name, ns)] idle gaps, longest first
    spans: list          # [(name, start_ns, end_ns)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9


def options() -> jax.profiler.ProfileOptions:
    """Device and host tracing, no Python tracer (it would slow every
    call the host makes and swell the trace)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def discard(path: Path) -> None:
    shutil.rmtree(path, ignore_errors=True)


def _union(intervals, lo, hi):
    """Total length of the union of [s, e) clipped to [lo, hi), and the
    uncovered stretches."""
    total, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s or e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
        total += e - max(s, cur)
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return total, gaps


def reduce_events(device_ops: dict, spans: list) -> Reduced:
    """``device_ops``: {plane: [(hlo_text, start_ns, duration_ns)]};
    ``spans``: [(name, start_ns, end_ns)] of the harness."""
    spans = sorted((s for s in spans if s[0] in SPANS), key=lambda s: s[1])
    if not spans:
        raise ValueError("the trace holds none of the harness's spans")
    lo, hi = spans[0][1], max(s[2] for s in spans)
    busy, leaf, all_gaps, n = 0.0, {}, [], 0
    for plane, evs in device_ops.items():
        evs = sorted(evs, key=lambda x: (x[1], -x[2]))
        ivs = [(s, s + d) for _, s, d in evs]
        if not ivs:
            continue
        n += 1
        b, gaps = _union(ivs, lo, hi)
        busy += b
        all_gaps += gaps
        for i, (text, s, d) in enumerate(evs):
            if s < lo or s >= hi:
                continue
            if i + 1 < len(evs) and evs[i + 1][1] < s + d:
                continue  # encloses the next operation: control flow
            agg = leaf.setdefault(text, [0, 0.0])
            agg[0] += 1
            agg[1] += d
    named = []
    for s, e in all_gaps:
        mid = 0.5 * (s + e)
        inner = [x for x in spans if x[1] <= mid < x[2]]
        name = min(inner, key=lambda x: x[2] - x[1])[0] if inner else "outside"
        named.append((name, e - s))
    named.sort(key=lambda x: -x[1])
    return Reduced((lo, hi), busy / max(n, 1), n, leaf, named, spans)


def load(path: Path) -> Reduced:
    files = glob.glob(str(Path(path) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {path}, found "
                                f"{len(files)}")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(files[0])
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns)
                          for e in line.events if e.name in SPANS]
    return reduce_events(device_ops, spans)


def short(text: str) -> str:
    """``%fusion.7 = f32[327680]... fusion(...)`` -> ``fusion.7
    f32[327680]``."""
    name, _, rest = text.partition(" = ")
    return f"{name.lstrip('%')} {rest.split('{')[0].split(' ')[0]}"[:80]


def breakdown(red: Reduced) -> dict:
    ops = sorted(red.leaf_ops.items(), key=lambda kv: -kv[1][1])[:TOP]
    return {"device_ops": [[short(t), v[1] / 1e9] for t, v in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in red.gaps[:TOP]]}


def kernel_events(red: Reduced, pattern) -> tuple:
    """(calls, total seconds, [match]) of leaf operations whose HLO text
    is a TPU custom call matching the compiled ``pattern``."""
    calls, ns, found = 0, 0.0, []
    for text, (n, d) in red.leaf_ops.items():
        if 'custom_call_target="tpu_custom_call"' not in text:
            continue
        m = pattern.search(text)
        if m:
            calls += n
            ns += d
            found.append((m, n))
    return calls, ns / 1e9, found
