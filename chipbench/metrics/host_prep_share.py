"""Share (%) of the traced pass that the host spends before the first
epoch: ``serve_stream``'s bucketing of the stream into ticks, the stream
arrays, the engine's build and its state init.  Host clock: the pass's
entry to its first ``on_epoch`` stamp, over the pass's length."""


def read(ctx):
    p = ctx["pass"]
    if not p.stamps or p.t1 <= p.t0:
        return None
    return 100.0 * (p.stamps[0] - p.t0) / (p.t1 - p.t0)
