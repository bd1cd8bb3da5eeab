"""Median host time (ms) inside ``engine.run_epoch`` up to its return
(``serve.dispatch``: the call's argument handling, cache lookup and
enqueue) in a cell of one tick per epoch."""
from chipbench.lib.spans import median_ms_per_tick


def read(ctx):
    return median_ms_per_tick(ctx, "serve.dispatch")
