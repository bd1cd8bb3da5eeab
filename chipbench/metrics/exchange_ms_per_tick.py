"""Device time (ms) per traced tick, per chip, of the cross-chip
exchange: the leaf operations tagged ``stage="exchange"`` (the program's
``psum``s over the cells mesh), and the untagged ``all-reduce``s.  XLA's
all-reduce combiner merges a tick's tagged ``psum``s into tuple
``all-reduce``s and drops their tag; the few other untagged ones are the
small reductions over the sharded state that the report's merge makes.
The trace's leaf operations hold every chip's time, so the total is
divided by the chips that ran operations."""
import re

ALL_REDUCE = re.compile(r"^\S+ = .*? all-reduce(-start|-done)?\(")


def read(ctx):
    red, ticks = ctx["trace"], ctx["traced_ticks"]
    if red is None or red.n_devices == 0 or ticks <= 0:
        return None
    ns = [d for text, (_, d) in red.leaf_ops.items()
          if 'stage="exchange"' in text
          or (ALL_REDUCE.match(text) and 'stage="' not in text)]
    if not ns:
        return None
    return sum(ns) / 1e6 / ticks / red.n_devices
