"""Share (%) of its roofline that the queue-admission kernel reaches: the
least time its calls could take on this chip (bytes over peak HBM
bandwidth, from ``work/queue_admit.py`` and ``peaks.json``) over the
summed device time of its events.

The kernel is found in the trace by its interface: a TPU custom call
taking the lanes' cells as an s32 vector and the queue lengths as
(rows, 128) s32 rows, and giving the new lengths and one s32 per lane.
Once admission is computed otherwise, nothing matches and the metric is
left out."""
import re

from chipbench.lib.trace import kernel_events

PATTERN = re.compile(
    r"= \(s32\[\d+,128\]\S*, s32\[\d+\]\S*\) custom-call\("
    r"s32\[\d+\]\S* %\S+, s32\[\d+,128\]")


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    calls, seconds, _ = kernel_events(red, PATTERN)
    if calls == 0 or seconds <= 0:
        return None
    w = ctx["work"]("queue_admit").cost(ctx["shapes"])
    pk = ctx["peaks"]
    least = max(w["flops"] / pk["flops_per_s"], w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
