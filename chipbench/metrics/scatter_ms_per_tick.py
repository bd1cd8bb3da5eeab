"""Device time (ms) per traced tick of the operations tagged
``stage="scatter"``: the scatter stage: the per-request records of the
rounds that completed."""
from chipbench.lib.spans import stage_ms_per_tick


def read(ctx):
    return stage_ms_per_tick(ctx, "scatter")
