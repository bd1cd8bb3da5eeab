"""Host time (ms) spent waiting for the epoch program's outputs (all
``serve.wait`` spans of the traced pass) per live tick of the pass. Read
as ``.serve`` (replay) and ``.live``."""
from chipbench.lib.spans import ms_per_tick


def read(ctx):
    return ms_per_tick(ctx, "serve.wait")
