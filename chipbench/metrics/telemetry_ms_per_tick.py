"""Device time (ms) per traced tick of the operations tagged
``stage="telemetry"``: the telemetry stage: per-window counters and
gauges and the latency histogram."""
from chipbench.lib.spans import stage_ms_per_tick


def read(ctx):
    return stage_ms_per_tick(ctx, "telemetry")
