"""Window and percentile arithmetic of the benchmark."""
import math
import statistics

import types

import pytest

from bench import common, stats
from bench.recorder import Recorder


def test_percentile_interpolates_between_ranks():
    vals = list(range(1, 11))  # 1..10
    assert stats.percentile(vals, 0) == 1
    assert stats.percentile(vals, 100) == 10
    assert stats.percentile(vals, 50) == pytest.approx(5.5)
    assert stats.percentile(vals, 90) == pytest.approx(9.1)
    assert stats.percentile([], 90) is None


def test_percentile_counts_failures_as_infinite():
    vals = [1.0] * 9 + [math.inf]
    assert stats.percentile(vals, 80) == 1.0
    assert math.isinf(stats.percentile(vals, 95))
    assert math.isinf(stats.percentile([1.0, math.inf, math.inf], 90))


def test_spread_is_interquartile_over_median():
    vals = [10, 11, 12, 13, 14, 15]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)


def test_in_window_is_inclusive():
    assert stats.in_window([0.0, 1.0, 2.0, 3.0], 1.0, 2.0) == 2


def test_union_and_gaps_partition_the_window():
    iv = [(0, 2), (1, 3), (5, 6), (8, 12)]
    assert stats.union_length(iv) == 3 + 1 + 4
    assert stats.union_length(iv, 1, 9) == 2 + 1 + 1
    g = stats.gaps(iv, 0, 10)
    assert g == [(3, 5), (6, 8)]
    assert stats.union_length(iv, 0, 10) + sum(e - s for s, e in g) == 10
    assert stats.gaps([], 0, 4) == [(0, 4)]



def test_tpot_mean_weighs_each_request_due_in_the_window_alike():
    rec = Recorder()
    for uid, times, measured in [(0, [0.0, 1.0, 2.0], True),  # 1 s/token
                                 (1, [0.0] + [0.1] * 5, True),  # 0.02
                                 (2, [0.0, 9.0], False),  # not due
                                 (3, [5.0], True)]:  # one token: none
        rec.expect(uid, 0.0, 8, measured)
        rec.trails[uid].token_times = times
        rec.trails[uid].outcome = "finished"
    reader = common.load_reader(common.ROOT, "tpot_mean_ms")
    run = types.SimpleNamespace(rec=rec)
    assert reader.read(run) == pytest.approx(1000.0 * (1.0 + 0.02) / 2)
    assert reader.read(types.SimpleNamespace(rec=Recorder())) is None
