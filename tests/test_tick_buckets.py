"""Host bucketing of a request stream into ticks and shards.

``_tick_buckets`` fills its (T, S, A) id rows with one stable sort on
``tick·S + shard``.  Held against the per-request loop it replaced, kept
here as the reference: the same id rows (values and int32 dtype), tick
times, live-tick mask and epoch count, for 1, 2 and 4 shards, on
unsorted streams, arrivals on tick boundaries, arrivals at or past the
horizon, a stream with no arrival inside the window, and a seeded
Poisson stream over 64 cells.
"""
import numpy as np
import pytest

from repro.serve.engine import _tick_buckets
from repro.serve.stream import RequestStream

TICK_MS = 50.0
TICKS_PER_EPOCH = 4
N_CELLS = 64


def _loop_buckets(stream: RequestStream, tick_ms: float,
                  ticks_per_epoch: int, n_shards: int = 1):
    """The per-request fill: each admitted request in stream order goes
    to the next free slot of its (tick, shard) row."""
    n_ticks = max(1, int(np.ceil(stream.horizon_ms / tick_ms))) + 1
    n_epochs = -(-n_ticks // ticks_per_epoch)
    T = n_epochs * ticks_per_epoch
    tick_of = np.ceil(np.asarray(stream.t_ms, np.float64)
                      / tick_ms).astype(np.int64)
    ok = tick_of < n_ticks
    shard_of = (np.asarray(stream.cell, np.int64)
                // (stream.n_cells // n_shards))
    counts = np.bincount((tick_of * n_shards + shard_of)[ok],
                         minlength=T * n_shards)
    A = max(1, int(counts.max()) if counts.size else 1)
    if n_shards > 1:
        q = 1 << max(0, A.bit_length() - 4)
        A = -(-A // q) * q
    ids = np.full((T, n_shards, A), -1, np.int32)
    cursor = np.zeros((T, n_shards), np.int64)
    for i in np.nonzero(ok)[0]:
        t, s = tick_of[i], shard_of[i]
        ids[t, s, cursor[t, s]] = i
        cursor[t, s] += 1
    now = (np.arange(T, dtype=np.float64) * tick_ms).astype(np.float32)
    live = np.arange(T) < n_ticks
    return ids, now, live, n_epochs


def _stream(t, cell, horizon_ms=500.0) -> RequestStream:
    t = np.asarray(t, np.float32)
    return RequestStream(t, np.asarray(cell, np.int32),
                         np.full(t.shape, 400.0, np.float32),
                         horizon_ms, TICK_MS * TICKS_PER_EPOCH, N_CELLS)


def _poisson(seed, n=3000, horizon_ms=500.0, shuffle=False):
    rng = np.random.default_rng(seed)
    t = np.sort(rng.uniform(0.0, horizon_ms, n))
    cell = rng.integers(0, N_CELLS, n)
    if shuffle:
        p = rng.permutation(n)
        t, cell = t[p], cell[p]
    return _stream(t, cell, horizon_ms)


def _boundaries():
    # every arrival exactly on a tick's wall clock, several to a cell
    t = np.repeat(np.arange(11) * TICK_MS, 7)
    return _stream(t, np.arange(t.size) % N_CELLS)


def _past_horizon():
    # 500 ms horizon → 11 live ticks; arrivals at 500 ms (tick 10) are
    # live, those past 500 ms (tick 11 and on) fall outside the window
    t = np.r_[np.linspace(0.0, 499.0, 200), np.full(9, 500.0),
              np.full(5, 500.5), np.full(6, 550.0), np.full(4, 9000.0)]
    cell = np.random.default_rng(3).integers(0, N_CELLS, t.size)
    return _stream(t, cell)


def _outside_window():
    t = np.r_[np.full(5, 700.0), np.full(3, 12345.0)]
    return _stream(t, np.arange(t.size) * 7 % N_CELLS)


STREAMS = {
    "poisson": lambda: _poisson(20220223),
    "poisson_unsorted": lambda: _poisson(17, shuffle=True),
    "tick_boundaries": _boundaries,
    "past_horizon": _past_horizon,
    "outside_window": _outside_window,
}


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("name", sorted(STREAMS))
def test_tick_buckets_match_the_loop(name, shards):
    stream = STREAMS[name]()
    got = _tick_buckets(stream, TICK_MS, TICKS_PER_EPOCH, shards)
    want = _loop_buckets(stream, TICK_MS, TICKS_PER_EPOCH, shards)
    assert got[0].dtype == np.int32
    np.testing.assert_array_equal(got[0], want[0])
    assert got[1].dtype == want[1].dtype
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    assert got[3] == want[3]


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_no_arrival_in_window_gives_one_empty_slot(shards):
    ids, now, live, n_epochs = _tick_buckets(
        _outside_window(), TICK_MS, TICKS_PER_EPOCH, shards)
    assert ids.shape == (n_epochs * TICKS_PER_EPOCH, shards, 1)
    assert (ids == -1).all()
    assert live.sum() == 11
