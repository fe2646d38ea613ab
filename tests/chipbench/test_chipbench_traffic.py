"""The traffic generator: found by name, determined by the seed, the same
work for every seed."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from chipbench_testing import config, mix
from chipbench import traffic

SEEDS = (3, 2**31 + 11)


def _make(seed, name="rank50-saturated", **params):
    return traffic.make_traffic(mix(name, **params), config("taobao_ssa-fp32"), seed, 2.0)


def _poisson(seed):
    return _make(seed, "rank50-saturated", arrivals="poisson", rate_per_s=400.0)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(seed):
    a, b = _poisson(seed), _poisson(seed)
    for f in dataclasses.fields(traffic.Traffic):
        np.testing.assert_array_equal(getattr(a, f.name), getattr(b, f.name))


LOGNORMAL = {"lognormal_median": 50, "sigma": 0.8, "min": 8, "max": 512}


def test_other_seed_other_requests_same_amount_of_work(candidates=50):
    a, b = (_make(s, arrivals="poisson", rate_per_s=400.0, candidates=candidates)
            for s in SEEDS)
    assert not np.array_equal(a.cand_item[:1000], b.cand_item[:1000])
    assert not np.array_equal(a.due, b.due)
    np.testing.assert_array_equal(np.sort(a.hist_len), np.sort(b.hist_len))
    np.testing.assert_array_equal(np.sort(a.count), np.sort(b.count))
    np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0)),
                               np.sort(np.diff(b.due, prepend=0)))


def test_poisson_due_times_have_the_rate():
    due = traffic.poisson_due(1000.0, 5.0, np.random.default_rng(0))
    assert np.all(np.diff(due) > 0) and 0 <= due[0] and due[-1] < 5.0
    assert len(due) == pytest.approx(5000, rel=0.01)
    gaps = np.diff(due)
    assert np.mean(gaps) == pytest.approx(1e-3, rel=0.02)
    assert np.std(gaps) == pytest.approx(1e-3, rel=0.1)  # exponential: std = mean


def test_requests_follow_the_mix():
    t = _make(SEEDS[1])
    assert t.due is None and np.all(t.count == 50)
    n_users = len(t.user)  # lengths 25, 26, ... in turn, shuffled
    np.testing.assert_array_equal(np.sort(t.hist_len), np.sort(25 + np.arange(n_users) % 76))
    L = t.hist_item.shape[1]
    assert np.all(t.hist_item[np.arange(L)[None] >= t.hist_len[:, None]] == 0)
    # half of each request's candidates come from its user's history
    for n in range(len(t.req_pool)):
        p = t.req_pool[n]
        first = t.cand_item[t.cand_start[n]:t.cand_start[n] + 25]
        assert set(first) <= set(t.hist_item[p, :t.hist_len[p]])
    assert np.all(t.content(250, 260) == np.arange(250, 260) % 256)
    pool, cand = t.row_index(255, 257)  # wraps to content 0
    np.testing.assert_array_equal(pool, np.repeat(t.req_pool[[255, 0]], 50))
    np.testing.assert_array_equal(cand, np.r_[255 * 50:256 * 50, 0:50])
    assert t.rows(255, 257) == 100 and t.fill(0, 10**6, 4096) == 81 and t.fill(3, 5, 4096) == 5


def test_other_seed_same_candidate_counts():
    test_other_seed_other_requests_same_amount_of_work(candidates=LOGNORMAL)


def test_candidate_counts_follow_a_lognormal():
    t = _make(SEEDS[0], candidates=LOGNORMAL)
    assert t.count.min() >= 8 and t.count.max() <= 512
    assert np.median(t.count) == pytest.approx(50, abs=1)
    assert t.cand_start[-1] == t.count.sum() and len(t.cand_item) == t.count.sum()
    for n in range(len(t.req_pool)):  # a half, rounded, from the history
        p, h = t.req_pool[n], int(np.rint(t.count[n] / 2))
        first = t.cand_item[t.cand_start[n]:t.cand_start[n] + h]
        assert set(first) <= set(t.hist_item[p, :t.hist_len[p]])
    assert t.fill(0, 10**6, 4096) == int(np.searchsorted(np.cumsum(t.count), 4096, "right"))


def test_bursts_keep_the_mean_rate():
    burst = {"factor": 3.0, "seconds": 1.0, "every_s": 5.0}
    t = traffic.make_traffic(mix("rank50-saturated", arrivals="poisson", rate_per_s=1000.0,
                                 burst=burst), config("taobao_ssa-fp32"), SEEDS[0], 10.0)
    per_s = np.histogram(t.due, bins=10, range=(0, 10))[0]
    assert len(t.due) == pytest.approx(10 * 1000, rel=0.01)
    assert per_s[[0, 5]] == pytest.approx([3000, 3000], rel=0.05)
    assert per_s[[1, 2, 3, 4, 6, 7, 8, 9]] == pytest.approx([500] * 8, rel=0.15)


def test_the_generator_is_found_by_name():
    with pytest.raises(ValueError, match="no traffic generator"):
        _make(1, generator="no_such_generator")


def test_a_bad_mix_is_refused():
    with pytest.raises(ValueError):
        _make(1, arrivals="bursty")
    with pytest.raises(ValueError):
        _make(1, hist_len=[10, 500])
    with pytest.raises(ValueError):
        _make(1, unknown_key=1)
    with pytest.raises(ValueError):
        _make(1, burst={"factor": 3.0, "seconds": 1.0, "every_s": 5.0})  # saturated
