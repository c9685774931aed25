"""The traffic generator: the same seed gives the same traffic, and every
seed gets the same work in the same order (only the prompt tokens
differ)."""
import collections

import numpy as np
import pytest

from bench.traffic import Traffic, quantile, stratified

OPEN = {"loop": "open", "rate_per_s": 3.0,
        "prompt": {"dist": "uniform", "min": 64, "max": 384},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                   "min": 16, "max": 256}}
CLOSED = {"loop": "closed", "clients_per_slot": 2, "block": 8,
          "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                     "min": 128, "max": 2048},
          "output": {"dist": "lognormal", "median": 128, "sigma": 1.0,
                     "min": 16, "max": 512}}
BIG = 2**31 + 12345


def _open(seed):
    t = Traffic(OPEN, seed, 20, 1000)
    return t, t.window_requests()


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**40 + 3])
def test_same_seed_same_traffic(seed):
    (a, ra), (b, rb) = _open(seed), _open(seed)
    assert [(r.arrival, r.prompt_len, r.out_len) for r in ra] == \
        [(r.arrival, r.prompt_len, r.out_len) for r in rb]
    np.testing.assert_array_equal(a.tokens(3, 50), b.tokens(3, 50))
    fa, fb = a.filler_requests(len(ra)), b.filler_requests(len(rb))
    for _ in range(70):
        x, y = next(fa), next(fb)
        assert (x.uid, x.arrival, x.prompt_len) == \
            (y.uid, y.arrival, y.prompt_len)


def test_seeds_share_the_work_in_another_order():
    # (the order is now the same for every seed: in a closed loop it
    # decides which requests a window holds)
    (_, ra), (_, rb) = _open(1), _open(BIG)
    assert len(ra) == len(rb) == 60
    assert [(r.arrival, r.prompt_len, r.out_len) for r in ra] == \
        [(r.arrival, r.prompt_len, r.out_len) for r in rb]
    # a shuffled stratified sample, not sorted lengths
    assert [r.prompt_len for r in ra] != sorted(r.prompt_len for r in ra)
    assert all(0 <= r.arrival < 20 for r in ra)
    assert ra[0].arrival == 0.0
    da = np.diff([r.arrival for r in ra])
    assert not np.allclose(da, sorted(da))
    assert abs(da.mean() - 1 / 3.0) < 0.02


def test_tokens_differ_by_seed_and_uid_and_stay_in_vocab():
    a = Traffic(OPEN, 1, 20, 1000)
    b = Traffic(OPEN, 2, 20, 1000)
    t = a.tokens(5, 4000)
    assert t.dtype == np.int32 and t.min() >= 0 and t.max() < 1000
    assert not np.array_equal(t, b.tokens(5, 4000))
    assert not np.array_equal(t, a.tokens(6, 4000))


def test_closed_loop_blocks_hold_one_multiset():
    a, b = Traffic(CLOSED, 3, 50, 1000), Traffic(CLOSED, BIG, 50, 1000)
    whole = collections.Counter(stratified(CLOSED["prompt"], 8))
    for k in (1, 4):
        n = 8 * k
        sa = [a.closed_request(i, 0, True).prompt_len for i in range(n)]
        sb = [b.closed_request(i, 0, True).prompt_len for i in range(n)]
        assert sa == sb
        for j in range(k):  # every block of 8 is the stratified multiset
            assert collections.Counter(sa[8 * j:8 * j + 8]) == whole
    assert a.clients(8) == 16


def test_quantiles_respect_the_clip_and_the_median():
    d = CLOSED["prompt"]
    assert quantile(d, 0.5) == 1024
    assert quantile(d, 1e-9) == 128 and quantile(d, 1 - 1e-9) == 2048
    u = {"dist": "uniform", "min": 16, "max": 64}
    s = stratified(u, 49)
    assert min(s) == 16 and max(s) == 64 and s == sorted(s)
    with pytest.raises(ValueError):
        quantile({"dist": "zipf", "min": 1, "max": 2}, 0.5)
