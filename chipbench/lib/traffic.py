"""The one traffic generator.  A mix is a JSON file of parameters under
``chipbench/traffic/``; nothing about a mix lives in code.

Every cell offers Poisson arrivals at the same rate.  The stream is that
process conditioned on two numbers, the request count N and the largest
number of arrivals in one tick A, because the engine's epoch program is
specialised on both: with them pinned, one compiled program serves every
seed.  Given N, a Poisson process's arrival times are independent and
uniform over the horizon and each arrival's cell is independent and
uniform over the cells (the standard conditional construction), so the
per-tick totals are multinomial, N over the K ticks.  The totals are
drawn again until their largest is A; the draws kept are the multinomial
conditioned on its maximum.  Each tick's arrivals then pick their cells
and their times uniformly inside the tick.  A is the median of that
maximum over ``BURST_DRAWS`` draws from a fixed seed, a burst that half
of all seeds reach.  Apart from the two pinned numbers the tick-to-tick
variation is Poisson's.

Tick k (k = 1..K, K = horizon / tick) holds the arrivals in
((k-1)·tick, k·tick), and the engine admits them at tick k.

Parameters of a mix:

``rate_per_cell_per_s``  mean arrivals per cell per second
``horizon_ms``           length of the stream (a whole number of ticks)
``epoch_ms``             the engine's epoch: host dispatch and parameter
                         refresh points (a whole number of ticks)

Every request's SLO budget is its cell's latency target.
"""
from __future__ import annotations

import functools

import numpy as np

EDGE_MARGIN = 1e-3   # share of a tick kept clear at each end of it
BURST_DRAWS = 2001   # draws that fix the pinned largest burst A
BURST_SEED = 20220223
BATCH = 256          # multinomial draws per rejection round


def shape(mix: dict, n_cells: int, tick_ms: float) -> tuple:
    """(K ticks, N requests, A largest burst): what the mix pins; it
    depends on the mix and the fleet's size alone."""
    horizon = float(mix["horizon_ms"])
    n_ticks = int(round(horizon / tick_ms))
    if abs(n_ticks * tick_ms - horizon) > 1e-6 or n_ticks < 1:
        raise ValueError(f"horizon_ms {horizon} is not a whole number of "
                         f"{tick_ms} ms ticks")
    n = int(round(n_cells * float(mix["rate_per_cell_per_s"])
                  * horizon / 1000.0))
    if n < 1:
        raise ValueError("the mix offers no request")
    return n_ticks, n, _burst(n, n_ticks)


@functools.lru_cache(maxsize=None)
def _burst(n: int, k: int) -> int:
    rng = np.random.default_rng(BURST_SEED)
    peak = rng.multinomial(n, np.full(k, 1.0 / k), size=BURST_DRAWS).max(1)
    return int(np.median(peak))


def tick_totals(mix: dict, n_cells: int, tick_ms: float,
                rng: np.random.Generator) -> np.ndarray:
    """(K,) arrivals per tick: N over K ticks, largest exactly A."""
    k, n, a = shape(mix, n_cells, tick_ms)
    p = np.full(k, 1.0 / k)
    while True:
        draws = rng.multinomial(n, p, size=BATCH)
        hit = np.flatnonzero(draws.max(1) == a)
        if hit.size:
            return draws[hit[0]].astype(np.int64)


def make_stream(mix: dict, n_cells: int, tick_ms: float, latency_target,
                rng: np.random.Generator) -> dict:
    """Arrival-time-sorted stream: ``t_ms`` (N,) float32, ``cell`` (N,)
    int32, ``slo_ms`` (N,) float32, plus ``horizon_ms``, ``epoch_ms``,
    ``tick_totals`` and ``max_burst`` (A)."""
    epoch = float(mix["epoch_ms"])
    if abs(epoch / tick_ms - round(epoch / tick_ms)) > 1e-9:
        raise ValueError(f"epoch_ms {epoch} is not a whole number of ticks")
    totals = tick_totals(mix, n_cells, tick_ms, rng)
    n = int(totals.sum())
    cell = rng.integers(0, n_cells, n).astype(np.int32)
    tick = np.repeat(np.arange(totals.size), totals)
    u = rng.random(n)
    # sort inside each tick: ticks are already in order
    u = u[np.lexsort((u, tick))]
    t = tick_ms * (tick + EDGE_MARGIN + (1 - 2 * EDGE_MARGIN) * u)
    return {"t_ms": t.astype(np.float32), "cell": cell,
            "slo_ms": np.asarray(latency_target, np.float32)[cell],
            "horizon_ms": float(mix["horizon_ms"]), "epoch_ms": epoch,
            "tick_totals": totals, "max_burst": int(totals.max())}
