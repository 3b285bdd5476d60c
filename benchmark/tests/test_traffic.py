"""The traffic generator: every seed offers the same work, in another
order; the burst mix puts its share inside the burst window."""

import numpy as np
import pytest

from benchmark import prom, traffic


@pytest.mark.parametrize("mix_name", ["serve-steady-wide",
                                      "serve-burst-wide"])
def test_every_seed_offers_the_same_amount_of_work(mix_name):
    mix = traffic.load_mix(mix_name)
    counts = []
    for seed in (1, 2**31 + 3, 99):
        due, users = traffic.schedule(mix, 1234.5, 32.0, 5000, seed)
        assert len(due) == len(users) == round(1234.5 * 32)
        assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 32
        assert users.min() >= 0 and users.max() < 5000
        counts.append(np.histogram(due, bins=32, range=(0, 32))[0])
    assert all(np.array_equal(counts[0], c) for c in counts[1:])
    a = traffic.schedule(mix, 500, 4.0, 5000, 7)
    b = traffic.schedule(mix, 500, 4.0, 5000, 7)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    c = traffic.schedule(mix, 500, 4.0, 5000, 8)
    assert not np.array_equal(a[0], c[0])


def test_a_third_of_each_second_falls_inside_one_100ms_burst():
    mix = traffic.load_mix("serve-burst-wide")
    due, _ = traffic.schedule(mix, 900, 10.0, 5000, 42)
    for sec in range(10):
        t = due[(due >= sec) & (due < sec + 1)] - sec
        assert len(t) == 900
        # the densest 100 ms holds the burst's third plus its share of
        # the rest
        best = max(((t >= a) & (t < a + 0.1)).sum()
                   for a in np.arange(0, 0.9, 0.005))
        assert 280 <= best <= 300 + 120


def test_users_are_zipf_over_a_seeded_permutation():
    mix = traffic.load_mix("serve-steady-wide")
    _, users = traffic.schedule(mix, 5000, 10.0, 1000, 3)
    counts = np.sort(np.bincount(users, minlength=1000))[::-1]
    # Zipf(1) over 1000: the hottest user draws ~13%, the top ten ~39%
    assert 0.10 < counts[0] / len(users) < 0.17
    assert 0.33 < counts[:10].sum() / len(users) < 0.45


def test_histogram_delta_quantile():
    text0 = ('h_bucket{le="0.001"} 5\nh_bucket{le="0.002"} 10\n'
             'h_bucket{le="+Inf"} 10\nh_count 10\nh_sum 0.012\n')
    text1 = ('h_bucket{le="0.001"} 5\nh_bucket{le="0.002"} 110\n'
             'h_bucket{le="+Inf"} 110\nh_count 110\nh_sum 0.2\n# x\n')
    s0, s1 = prom.parse(text0), prom.parse(text1)
    buckets = prom.histogram_delta(s0, s1, "h")
    assert buckets == [(0.001, 0.0), (0.002, 100.0), (float("inf"), 0.0)]
    assert prom.histogram_quantile(buckets, 0.95) == pytest.approx(0.00195)
    assert prom.delta(s0, s1, "h_count") == 100
    assert prom.histogram_quantile([(0.1, 0.0)], 0.5) is None
