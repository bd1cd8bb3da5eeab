"""Share (%) of the traced pass that ``serve_stream`` spends bringing the
shard copies of the records and telemetry to the host and merging them
(the ``serve.merge`` span, inside ``serve.report``) over the pass's
length on the harness clock."""
from chipbench.lib.spans import pass_share


def read(ctx):
    return pass_share(ctx, "serve.merge")
