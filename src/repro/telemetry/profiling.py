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
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import resource
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
