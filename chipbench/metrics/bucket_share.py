"""Share (%) of the traced pass that ``serve_stream`` spends bucketing the
stream into ticks (``_tick_buckets``, the self time of its
``serve.bucket`` span) over the pass's length on the harness clock."""
from chipbench.lib.spans import pass_share


def read(ctx):
    return pass_share(ctx, "serve.bucket", "self_s")
