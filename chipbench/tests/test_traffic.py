"""The conditioned Poisson generator: exactly N requests and a largest
burst of exactly A on every seed, Poisson variation between ticks,
seed-determined output, per-cell means within sampling error."""
import numpy as np
import pytest

from chipbench.lib import traffic

MIX = {"rate_per_cell_per_s": 16.0, "horizon_ms": 2000.0, "epoch_ms": 500.0}


def _stream(mix, seed, cells=256, tick=50.0):
    return traffic.make_stream(mix, cells, tick, np.full(cells, 400.0),
                               np.random.default_rng(seed))


@pytest.mark.parametrize("seed", [1, 2**31 + 3, 2**40 + 7])
def test_n_and_burst_pinned(seed):
    k, n, a = traffic.shape(MIX, 256, 50.0)
    assert (k, n) == (40, 256 * 16 * 2)
    s = _stream(MIX, seed)
    assert s["t_ms"].size == s["tick_totals"].sum() == n
    assert s["max_burst"] == s["tick_totals"].max() == a
    # the engine's bucketing: tick k takes the arrivals in ((k-1)t, kt)
    tick_of = np.ceil(s["t_ms"].astype(np.float64) / 50.0).astype(int)
    np.testing.assert_array_equal(np.bincount(tick_of, minlength=k + 1)[1:],
                                  s["tick_totals"])
    assert tick_of.min() >= 1 and np.all(np.diff(s["t_ms"]) >= 0)


def test_burst_is_a_typical_maximum():
    # A sits inside the spread of the unconditioned maximum, above the mean
    k, n, a = traffic.shape(MIX, 256, 50.0)
    peaks = np.random.default_rng(0).multinomial(
        n, np.full(k, 1 / k), size=4000).max(1)
    assert np.quantile(peaks, 0.25) <= a <= np.quantile(peaks, 0.75)
    assert a > n / k


def test_tick_variation_is_poisson():
    # 64 cells at 16/s over 60 s: 51.2 a tick; multinomial variance ~ mean
    mix = {"rate_per_cell_per_s": 16.0, "horizon_ms": 60000.0,
           "epoch_ms": 50.0}
    tot = _stream(mix, 11, cells=64)["tick_totals"]
    assert 0.85 < tot.var() / tot.mean() < 1.15


def test_seed_determines_output_and_not_shape():
    a, b, c = _stream(MIX, 7), _stream(MIX, 7), _stream(MIX, 8)
    for k in ("t_ms", "cell", "slo_ms", "tick_totals"):
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["cell"], c["cell"])
    assert not np.array_equal(a["tick_totals"], c["tick_totals"])
    assert a["t_ms"].size == c["t_ms"].size
    assert a["max_burst"] == c["max_burst"]


def test_per_cell_means_within_sampling_error():
    mix = dict(MIX, horizon_ms=20000.0)
    s = _stream(mix, 3, cells=64)
    counts = np.bincount(s["cell"], minlength=64)
    mean = 16.0 * 20.0
    # multinomial per cell: sd ~ sqrt(mean); 5 sd covers 64 cells
    assert np.abs(counts - mean).max() < 5 * np.sqrt(mean)
    assert abs(counts.mean() - mean) < 1e-9


def test_bad_mix_refused():
    with pytest.raises(ValueError):
        traffic.shape(dict(MIX, horizon_ms=2010.0), 8, 50.0)
    with pytest.raises(ValueError):
        _stream(dict(MIX, epoch_ms=70.0), 1)
