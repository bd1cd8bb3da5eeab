"""Device time (ms) per traced tick of the operations tagged
``stage="admit"``: the admit stage: the queue-admission kernel, the
ring-slot scatter and the dropped flags."""
from chipbench.lib.spans import stage_ms_per_tick


def read(ctx):
    return stage_ms_per_tick(ctx, "admit")
