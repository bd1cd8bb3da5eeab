"""Median host time (ms) of the per-epoch transfer of the tick slices to
the device (``serve.h2d``) in a cell of one tick per epoch."""
from chipbench.lib.spans import median_ms_per_tick


def read(ctx):
    return median_ms_per_tick(ctx, "serve.h2d")
