"""Backend compiles during the traced ``serve_stream`` call, persistent
cache hits excluded (the program's ``backend_compiles`` counter). 0 in a
warm pass. Read as ``.serve`` and ``.live``."""
from chipbench.lib.spans import counter


def read(ctx):
    return counter(ctx, "backend_compiles")
