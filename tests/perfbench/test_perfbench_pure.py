"""The benchmark's arithmetic and generator: pure Python, no jax."""

import statistics

import numpy as np
import pytest

from perfbench.kernels import auction, peaks
from perfbench.lib import stats, traffic

POISSON = {"kind": "poisson", "rate_pods_per_s": 200, "resident_bound": 8}
BURST = {"kind": "burst", "rate_pods_per_s": 50, "resident_bound": 8,
         "burst_every_s": 2.0, "burst_size": 30}


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 0) == 1
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_window_counts_half_open_interval():
    stamps = [0.999, 1.0, 1.5, 2.999, 3.0, 3.5]
    assert stats.in_window(stamps, 1.0, 2.0) == 3


def test_iqr_spread_matches_statistics_quantiles():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


def test_union_and_merge_of_intervals():
    iv = [(0, 1), (0.5, 2), (3, 4), (3.5, 3.6), (5, 5)]
    assert stats.union_seconds(iv) == pytest.approx(3.0)
    assert stats.merged(iv) == [(0, 2), (3, 4)]


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 77])
def test_poisson_arrivals_are_deterministic_in_the_seed(seed):
    a = traffic.arrivals(POISSON, 5.0, seed)
    b = traffic.arrivals(POISSON, 5.0, seed)
    assert np.array_equal(a, b)
    assert len(a) == 1000        # rate x seconds, whatever the seed
    assert a[-1] == pytest.approx(5.0)
    assert np.all(np.diff(a) > 0)


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = traffic.arrivals(POISSON, 5.0, 1)
    b = traffic.arrivals(POISSON, 5.0, 2)
    assert not np.array_equal(a, b)
    ga = np.sort(np.diff(np.concatenate([[0.0], a])))
    gb = np.sort(np.diff(np.concatenate([[0.0], b])))
    assert np.allclose(ga, gb, rtol=0, atol=1e-9)
    # exponential: the mean gap is 1/rate, the median ln2/rate
    assert ga.mean() == pytest.approx(1 / 200)
    assert np.median(ga) == pytest.approx(np.log(2) / 200, rel=0.02)
    # another stream of one seed is another order too
    c = traffic.arrivals(POISSON, 5.0, 1, stream=1)
    assert not np.array_equal(a, c)


def test_burst_adds_pods_due_at_one_instant():
    a = traffic.arrivals(BURST, 5.0, 3)
    assert len(a) == 250 + 2 * 30
    assert np.sum(a == 2.0) == 30 and np.sum(a == 4.0) == 30
    assert np.all(np.diff(a) >= 0)


@pytest.mark.parametrize("bad", [
    {"kind": "nope", "resident_bound": 1},
    {"kind": "closed", "resident_bound": 1},
    {"kind": "closed", "depth": 4, "resident_bound": 0},
    {"kind": "poisson", "resident_bound": 1},
    {"kind": "burst", "rate_pods_per_s": 5, "resident_bound": 1},
])
def test_traffic_validation_refuses(bad):
    with pytest.raises(ValueError):
        traffic.validate(bad)


def test_closed_traffic_has_no_schedule():
    with pytest.raises(ValueError):
        traffic.arrivals({"kind": "closed", "depth": 4,
                          "resident_bound": 2}, 1.0, 0)


def test_unknown_device_has_no_peak():
    assert peaks.peak("TPU v5 lite").flops_per_s == 197e12
    assert peaks.peak("TPU v5 lite").bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_auction_model_counts_from_shapes_and_rounds():
    assert auction.OPS_PER_PAIR == 27
    assert auction.ops(1024, 5000, 1) == 1024 * 5000 * 27
    assert auction.ops(1024, 5000, 3) == 3 * auction.ops(1024, 5000, 1)
    with_terms = auction.ops(1024, 5000, 2, resident_pods=2024, terms=True)
    assert with_terms == (auction.ops(1024, 5000, 2)
                          + 1024 * 2024 * 3 + 1024 * 1024 * 3 * 2)
    assert auction.bytes_moved(1024, 5000, 1) == 4 * (
        2 * 5000 * 4 + 1024 * 4 + 3 * 1024)
    least = auction.least_seconds(1024, 5000, 4, 197e12, 819e9)
    assert least["bound"] == "operations"
    assert least["seconds"] == pytest.approx(
        4 * 1024 * 5000 * 27 / 197e12)
