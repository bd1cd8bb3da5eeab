"""Times the traced ``serve_stream`` call traced its epoch program (the
program's ``epoch_traces`` counter). Read as ``.serve`` and ``.live``."""
from chipbench.lib.spans import counter


def read(ctx):
    return counter(ctx, "epoch_traces")
