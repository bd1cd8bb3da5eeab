"""Device time (ms) per traced tick of the operations tagged
``stage="occupancy"``: edge-group occupancy, whatever implements it (the
kernel or a segment sum), over the observe and step stages around it."""
from chipbench.lib.spans import stage_ms_per_tick


def read(ctx):
    return stage_ms_per_tick(ctx, "occupancy")
