"""Median host time (ms) of pulling the epoch's decision count to the
host (``serve.count``, from the second epoch on) in a cell of one tick
per epoch."""
from chipbench.lib.spans import median_ms_per_tick


def read(ctx):
    return median_ms_per_tick(ctx, "serve.count")
