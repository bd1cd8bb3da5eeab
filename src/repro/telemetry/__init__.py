"""Unified observability for serving and training.

    metrics    jit-native MetricBuffer pytree: per-window counters and
               gauges plus a log-spaced latency histogram, threaded
               through the serve engine's tick scan and the hltrain
               session scan as device accumulators — no host syncs
               inside jit
    trace      sampled per-request lifecycle traces (arrival → admit /
               drop → round start → completion) as JSONL, with a
               round-trip validator CI runs on every smoke trace
    report     CLI that renders a served run from a trace file:
               windowed time-series table + tail-latency breakdown by
               cell and by action (``python -m repro.telemetry.report``)
    profiling  ``profiled()`` context wrapper: compile-vs-run wall-clock
               split, peak memory, optional ``jax.profiler`` trace dir
               (``REPRO_PROFILE_DIR``) — the benchmarks report through it;
               ``span()`` host spans into the active ``recording()`` and
               any profiler trace; a process-wide compile counter
    live       in-flight NDJSON export: ``LiveEmitter`` receives closed
               windows from inside the jitted scan via ``io_callback``
               and streams them with multi-window SLO burn-rate alerts
               (``serve_fleet --live``); ``TrainLiveEmitter`` does the
               same for hltrain sessions
    audit      invariant auditor: conservation laws over MetricBuffer
               windows and lifecycle traces (admits == serves + drops +
               still-queued, occupancy ≤ capacity, window sums == run
               totals) — library, CLI, and benchmark post-run hook
    canary     paired per-window diff of two policies served against the
               bit-identical arrival stream (``serve_fleet --canary``)
"""
from repro.telemetry.metrics import (MetricBuffer, metrics_init,
                                     count_event, set_gauge,
                                     observe_values, buffer_series,
                                     histogram_percentile,
                                     histogram_percentiles,
                                     merge_shard_buffers)
from repro.telemetry.trace import (build_trace, write_trace, read_trace,
                                   validate_trace)
from repro.telemetry.profiling import (Profile, SpanRecord, compile_counts,
                                       compiles_since, profiled, recording,
                                       span)
from repro.telemetry.live import (NdjsonSink, open_sink, BurnRateConfig,
                                  BurnRateAlerter, LiveEmitter,
                                  TrainLiveEmitter)
from repro.telemetry.audit import (AuditResult, audit_serve_report,
                                   audit_trace, audit_train_report)
from repro.telemetry.canary import canary_diff, render_canary

__all__ = [
    "MetricBuffer", "metrics_init", "count_event", "set_gauge",
    "observe_values", "buffer_series", "histogram_percentile",
    "histogram_percentiles", "merge_shard_buffers",
    "build_trace", "write_trace", "read_trace", "validate_trace",
    "Profile", "profiled", "SpanRecord", "recording", "span",
    "compile_counts", "compiles_since",
    "NdjsonSink", "open_sink", "BurnRateConfig", "BurnRateAlerter",
    "LiveEmitter", "TrainLiveEmitter",
    "AuditResult", "audit_serve_report", "audit_trace",
    "audit_train_report",
    "canary_diff", "render_canary",
]
