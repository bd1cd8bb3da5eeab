"""Median host time (ms) of the per-epoch params refresh
(``serve.refresh``: the ``on_epoch`` hook) in a cell of one tick per
epoch."""
from chipbench.lib.spans import median_ms_per_tick


def read(ctx):
    return median_ms_per_tick(ctx, "serve.refresh")
