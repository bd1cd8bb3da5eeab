"""Share (%) of the traced pass that ``serve_stream`` spends building its
report (the ``serve.report`` span: shard merge, the records to the host,
``request_report``, the telemetry report) over the pass's length."""
from chipbench.lib.spans import pass_share


def read(ctx):
    return pass_share(ctx, "serve.report")
