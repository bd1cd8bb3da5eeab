"""The program's own measurements, as the per-layer readers see them.

``serve_stream`` returns ``report["spans"]`` (per span name: ``n``,
``total_s``, ``self_s``, ``first_s``, ``p50_s``, on the host clock) and
``report["counters"]`` (``epoch_traces``, ``backend_compiles``, ...); its
epoch program tags each device operation with the tick stage that issued
it, ``stage="<name>"`` in the operation's HLO text, which the trace's
``leaf_ops`` keep.  A program without them gives None here, and the
metric is left out of the result.
"""
from __future__ import annotations

from typing import Optional


def _report(ctx) -> dict:
    return ctx["pass"].report


def span(ctx, name: str) -> Optional[dict]:
    """The traced pass's summary of span ``name``, or None."""
    return (_report(ctx).get("spans") or {}).get(name)


def counter(ctx, name: str) -> Optional[float]:
    """The traced pass's counter ``name``, or None."""
    return (_report(ctx).get("counters") or {}).get(name)


def pass_share(ctx, name: str, key: str = "total_s") -> Optional[float]:
    """``key`` (``total_s`` or ``self_s``) of span ``name`` as a share
    (%) of the traced pass's length on the harness clock."""
    s, p = span(ctx, name), ctx["pass"]
    if s is None or p.t1 <= p.t0:
        return None
    return 100.0 * s[key] / (p.t1 - p.t0)


def ms_per_tick(ctx, name: str) -> Optional[float]:
    """All of span ``name`` over the pass's live ticks (ms)."""
    s, ticks = span(ctx, name), _report(ctx).get("n_ticks", 0)
    if s is None or ticks <= 0:
        return None
    return 1e3 * s["total_s"] / ticks


def median_ms_per_tick(ctx, name: str) -> Optional[float]:
    """Span ``name``'s median occurrence (ms), which neither the pass's
    one-time costs (the first epoch's trace and compile) nor the
    profiler's start and stop inside the harness's ``on_epoch`` hook move.
    Defined where an epoch is one tick, as in a live cell."""
    s, rep = span(ctx, name), _report(ctx)
    if s is None or rep.get("n_ticks") != rep.get("n_epochs"):
        return None
    return 1e3 * s["p50_s"]


def stage_ms_per_tick(ctx, stage: str) -> Optional[float]:
    """Device time (ms) of the leaf operations tagged ``stage`` over the
    ticks the trace holds; None where no operation carries the tag."""
    red, ticks = ctx["trace"], ctx["traced_ticks"]
    if red is None or ticks <= 0:
        return None
    tag = f'stage="{stage}"'
    ns = [d for text, (_, d) in red.leaf_ops.items() if tag in text]
    if not ns:
        return None
    return sum(ns) / 1e6 / ticks
