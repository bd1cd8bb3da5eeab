"""Profiling hooks for benchmarks and ad-hoc runs.

``profiled()`` wraps a block of accelerator work and reports the
wall-clock split between the compile-bearing first call and steady-state
execution, plus peak memory:

    with profiled("serve") as prof:
        first_call()        # pays the XLA compile
        prof.split()        # compile/run boundary
        steady_state_calls()
    prof.report()           # {compile_time_s, run_time_s, ...}

Memory is the accelerator's ``peak_bytes_in_use`` on a device backend
(GPU/TPU), and the process peak RSS (``ru_maxrss``) on the CPU backend —
the field says which via ``memory_source``.

Set ``REPRO_PROFILE_DIR`` (or pass ``trace_dir``) to additionally record
a ``jax.profiler`` trace of the block for TensorBoard/Perfetto.

``span(name)`` names a stretch of host work.  It always enters a
``jax.profiler.TraceAnnotation``, so the span shows in any active
profiler trace on the same clock as the device's operations, and it
appends ``(name, start, end, parent)`` to the innermost active
:class:`SpanRecord` (``recording()``), whose ``summary()`` is JSON-safe:

    with recording() as rec:
        with span("serve.epoch"):
            with span("serve.wait"):
                ...
    rec.summary()   # {name: {n, total_s, self_s, first_s, p50_s}}

``compile_counts()`` / ``compiles_since()`` read a process-wide counter
of JAX's compile events (``jax.monitoring``): backend compiles, cache
hits, and the seconds spent tracing, lowering and compiling.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import resource
import statistics
import threading
import time

import jax

from repro.analysis import envflags

PROFILE_DIR_ENV = envflags.PROFILE_DIR


def device_peak_memory_bytes() -> int | None:
    """Peak allocation of the first local device.  The CPU backend keeps
    no device statistics and gives None; any other backend must report
    ``peak_bytes_in_use``, and raises if it does not — a host figure
    never stands in for the device's."""
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return None
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        raise RuntimeError(f"{dev.platform} device {dev.device_kind!r} "
                           f"reports no peak_bytes_in_use")
    return int(stats["peak_bytes_in_use"])


def host_peak_rss_bytes() -> int:
    # ru_maxrss is KiB on Linux, bytes on macOS
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(rss) * (1 if rss > 1 << 32 else 1024)


@dataclasses.dataclass
class Profile:  # repro-lint: allow=unfrozen-config-dataclass — host-side stopwatch, never a jit-static argument
    label: str
    compile_time_s: float | None = None
    run_time_s: float | None = None
    total_time_s: float | None = None
    peak_memory_mb: float | None = None
    memory_source: str | None = None
    _t0: float = 0.0
    _t_split: float | None = None

    def split(self) -> None:
        """Mark the compile/run boundary: everything before this call is
        compile (+ first execution), everything after is steady state."""
        self._t_split = time.perf_counter()

    def _finalize(self) -> None:
        t1 = time.perf_counter()
        self.total_time_s = t1 - self._t0
        if self._t_split is not None:
            self.compile_time_s = self._t_split - self._t0
            self.run_time_s = t1 - self._t_split
        else:  # no split marked: report the whole block as run time
            self.compile_time_s = 0.0
            self.run_time_s = self.total_time_s
        dev = device_peak_memory_bytes()
        mem = dev if dev is not None else host_peak_rss_bytes()
        self.memory_source = "device" if dev is not None else "host_rss"
        self.peak_memory_mb = mem / 2 ** 20

    def report(self) -> dict:
        return {"label": self.label,
                "compile_time_s": round(self.compile_time_s, 3),
                "run_time_s": round(self.run_time_s, 3),
                "total_time_s": round(self.total_time_s, 3),
                "peak_memory_mb": round(self.peak_memory_mb, 1),
                "memory_source": self.memory_source}


@contextlib.contextmanager
def profiled(label: str = "run", trace_dir: str | None = None):
    """Context wrapper: yields a :class:`Profile` whose ``split()`` the
    caller invokes after the compile-bearing first call; on exit the
    timing/memory fields are final.  A jax profiler trace of the block is
    written when ``trace_dir`` or ``$REPRO_PROFILE_DIR`` is set."""
    trace_dir = trace_dir or envflags.path_flag(PROFILE_DIR_ENV)
    prof = Profile(label)
    ctx = (jax.profiler.trace(os.path.join(trace_dir, label))
           if trace_dir else contextlib.nullcontext())
    with ctx:
        prof._t0 = time.perf_counter()
        try:
            yield prof
        finally:
            prof._finalize()


# ------------------------------------------------------------------ spans
class SpanRecord:
    """The spans entered while this record was active, in entry order:
    ``[name, start, end, parent]`` on ``time.perf_counter()``, ``parent``
    the index of the enclosing span here (-1 at the top), ``end`` None
    while the span is open."""

    def __init__(self):
        self.spans: list = []
        self._open: list = []   # indices of the spans entered, not exited

    def summary(self) -> dict:
        """``{name: {"n", "total_s", "self_s", "first_s", "p50_s"}}`` over
        the closed spans.  Self time is a span's duration minus its
        children's; ``first_s`` is its first occurrence, which carries
        one-time costs (tracing, compiling); ``p50_s`` its median, which
        neither those nor a rare stall move."""
        child = [0.0] * len(self.spans)
        for _, s, e, parent in self.spans:
            if e is not None and parent >= 0:
                child[parent] += e - s
        out, durations = {}, {}
        for (name, s, e, _), c in zip(self.spans, child):
            if e is None:
                continue
            d = e - s
            agg = out.get(name)
            if agg is None:
                out[name] = {"n": 1, "total_s": d, "self_s": d - c,
                             "first_s": d}
                durations[name] = [d]
            else:
                agg["n"] += 1
                agg["total_s"] += d
                agg["self_s"] += d - c
                durations[name].append(d)
        for name, agg in out.items():
            agg["p50_s"] = statistics.median(durations[name])
        return out


_local = threading.local()


def _records() -> list:
    stack = getattr(_local, "records", None)
    if stack is None:
        stack = _local.records = []
    return stack


@contextlib.contextmanager
def recording():
    """Make a new :class:`SpanRecord` the innermost active one in this
    thread for the block, and yield it."""
    rec = SpanRecord()
    stack = _records()
    stack.append(rec)
    try:
        yield rec
    finally:
        stack.remove(rec)


class Span:
    """A span while it runs; ``start`` and ``end`` (``perf_counter``) are
    set on entry and exit."""
    __slots__ = ("name", "start", "end", "_ann", "_rec", "_i")

    def __init__(self, name: str):
        self.name = name
        self.start = self.end = None

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        stack = _records()
        self._rec = rec = stack[-1] if stack else None
        self.start = time.perf_counter()
        if rec is not None:
            self._i = len(rec.spans)
            rec.spans.append([self.name, self.start, None,
                              rec._open[-1] if rec._open else -1])
            rec._open.append(self._i)
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        rec = self._rec
        if rec is not None:
            rec.spans[self._i][2] = self.end
            rec._open.pop()
        self._ann.__exit__(*exc)


def span(name: str) -> Span:
    """``with span(name):`` — see the module docstring."""
    return Span(name)


# --------------------------------------------------------------- compiles
# JAX reports every executable it builds as a backend-compile duration,
# whether XLA compiled it or the persistent cache gave it back; a cache
# read is also reported as a cache hit.  Backend compiles are the first
# count minus the second.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_SECONDS = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s",
            COMPILE_EVENT: "compile_s"}

_compiles = {"compile_events": 0, "cache_hits": 0,
             "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}
_compiles_lock = threading.Lock()
_listening = False


def _on_event(name, **_):
    if name == CACHE_HIT_EVENT:
        with _compiles_lock:
            _compiles["cache_hits"] += 1


def _on_duration(name, secs, **_):
    key = _SECONDS.get(name)
    if key is not None:
        with _compiles_lock:
            _compiles[key] += secs
            if name == COMPILE_EVENT:
                _compiles["compile_events"] += 1


def compile_counts() -> dict:
    """The process's compile totals since the first call (which starts
    listening to ``jax.monitoring``): take one before a block and hand
    it to :func:`compiles_since` after."""
    global _listening
    with _compiles_lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
        return dict(_compiles)


def compiles_since(before: dict) -> dict:
    """JSON-safe counts since ``before`` (a :func:`compile_counts`):
    ``backend_compiles`` (cache hits excluded), ``cache_hits``, and the
    seconds spent tracing (``trace_s``), lowering (``lower_s``) and
    compiling or loading from the cache (``compile_s``)."""
    now = compile_counts()
    d = {k: now[k] - before[k] for k in now}
    return {"backend_compiles": d["compile_events"] - d["cache_hits"],
            "cache_hits": d["cache_hits"], "trace_s": d["trace_s"],
            "lower_s": d["lower_s"], "compile_s": d["compile_s"]}
