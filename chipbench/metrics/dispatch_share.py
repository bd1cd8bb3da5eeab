"""Share (%) of the traced pass inside ``engine.run_epoch`` calls up to
their return (all ``serve.dispatch`` spans): tracing, lowering, the
compile or cache lookup, and the enqueue of every epoch, over the pass's
length on the harness clock. A pass that compiles reads most of its
length."""
from chipbench.lib.spans import pass_share


def read(ctx):
    return pass_share(ctx, "serve.dispatch")
