"""Share (%) of its roofline that the edge-group occupancy kernel reaches:
the least time its calls could take on this chip (the larger of
operations over peak FLOP/s and bytes over peak HBM bandwidth, from
``work/group_occupancy.py`` and ``peaks.json``) over the summed device
time of its events.

The kernel is found in the trace by its interface: a TPU custom call
taking the cells' values as an (n, 1) f32 column, their group ids as an
(n, 1) s32 column and as a (1, m) s32 row, and giving a (1, m) f32 row.
Once edge-group occupancy is computed otherwise, nothing matches and the
metric is left out."""
import re

from chipbench.lib.trace import kernel_events

PATTERN = re.compile(
    r"= f32\[1,\d+\]\S* custom-call\(f32\[\d+,1\]\S* %\S+, "
    r"s32\[\d+,1\]\S* %\S+, s32\[1,\d+\]")


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    calls, seconds, _ = kernel_events(red, PATTERN)
    if calls == 0 or seconds <= 0:
        return None
    w = ctx["work"]("group_occupancy").cost(ctx["shapes"])
    pk = ctx["peaks"]
    least = max(w["flops"] / pk["flops_per_s"], w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
