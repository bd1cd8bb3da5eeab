"""Bytes per traced tick, per chip, that the cross-chip exchange reduces,
as the compiled program runs it: every leaf operation of the trace whose
HLO instruction is an ``all-reduce`` (or the start of an asynchronous
one), weighed by the bytes of its result's arrays.  XLA drops the psums
whose result nothing reads and combines the rest into tuple
all-reduces, so this counts what crosses the chips, not what was traced;
the report's merge adds a few small ones a pass.
The trace's leaf operations hold every chip's runs, so the total is
divided by the chips that ran operations."""
import math
import re

ALL_REDUCE = re.compile(r"^\S+ = (?P<shape>.*?) all-reduce(-start)?\(")
ARRAY = re.compile(r"\b(pred|bf16|[suf](?:8|16|32|64))\[([0-9,]*)\]")


def all_reduce_bytes(text: str):
    """Bytes of the arrays an HLO ``all-reduce`` instruction returns, or
    None if ``text`` is no such instruction."""
    m = ALL_REDUCE.match(text)
    if m is None:
        return None
    total = 0
    for dtype, dims in ARRAY.findall(m["shape"]):
        size = 1 if dtype == "pred" else int(dtype.lstrip("bsuf")) // 8
        total += size * math.prod(int(d) for d in dims.split(",") if d)
    return total


def read(ctx):
    red, ticks = ctx["trace"], ctx["traced_ticks"]
    if red is None or red.n_devices == 0 or ticks <= 0:
        return None
    runs = [(n, all_reduce_bytes(text))
            for text, (n, _) in red.leaf_ops.items()]
    runs = [(n, b) for n, b in runs if b is not None]
    if not runs:
        return None
    return sum(n * b for n, b in runs) / ticks / red.n_devices
