"""The harness's dispatcher: whole requests, as many as fit 4,096 rows (81
of 50 candidates), padded to the smallest bucket, first come first served."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testing import config, mix
from chipbench import harness, traffic


def test_buckets():
    assert harness.BUCKETS == (64, 128, 256, 512, 1024, 2048, 4096)
    assert harness.bucket_for(50) == 64 and harness.bucket_for(4050) == 4096
    assert harness.bucket_for(4096) == 4096
    with pytest.raises(ValueError):
        harness.bucket_for(4097)
    assert harness.buckets_used(_traffic()) == [4096]
    open_loop = dataclasses.replace(_traffic(), due=np.zeros(1))
    assert harness.buckets_used(open_loop) == list(harness.BUCKETS)
    assert harness.buckets_used(_traffic(candidates=100)) == [4096]
    assert harness.buckets_used(dataclasses.replace(_traffic(candidates=100), due=np.zeros(1))) \
        == [128, 256, 512, 1024, 2048, 4096]
    assert harness.buckets_used(_traffic(candidates=LOGNORMAL)) == [4096]
    with pytest.raises(ValueError):
        harness.buckets_used(_traffic(candidates=5000))


class Echo:
    """Scores each row with its candidate id, and records every batch."""

    def __init__(self):
        self.calls = []
        self.step = jax.jit(lambda p, b: b["item"].astype(jnp.float32))

    def batch(self, tr, first, stop, rows):
        self.calls.append((first, stop, rows))
        items = tr.cand_item[tr.row_index(first, stop)[1]]
        return {"item": np.pad(items, (0, rows - len(items)))}


LOGNORMAL = {"lognormal_median": 50, "sigma": 0.8, "min": 8, "max": 512}


def _traffic(**params):
    return traffic.make_traffic(mix("rank50-saturated", **params),
                                config("taobao_ssa-fp32"), 5, 1.0)


def _check_answers(echo, batches, tr):
    for b in batches:
        np.testing.assert_array_equal(b.scores, tr.cand_item[tr.row_index(b.first, b.stop)[1]])
    assert [b.first for b in batches[1:]] == [b.stop for b in batches[:-1]]
    for first, stop, rows in echo.calls:
        assert rows == harness.bucket_for(tr.rows(first, stop))


def test_saturated_batches_are_full():
    echo, tr = Echo(), _traffic()
    batches, t_open, t_close = harness.serve(echo, None, tr, 0.3)
    assert batches and all(b.stop - b.first == 81 and b.rows == 4050 for b in batches)
    assert all(rows == 4096 for _, _, rows in echo.calls)
    _check_answers(echo, batches, tr)


def test_open_loop_serves_every_due_request_once_in_order():
    echo = Echo()
    tr = dataclasses.replace(_traffic(), due=np.sort(np.random.default_rng(0).uniform(0, 0.5, 3000)))
    batches, t_open, _ = harness.serve(echo, None, tr, 0.5)
    assert batches[0].first == 0 and batches[-1].stop == 3000
    assert all(1 <= b.stop - b.first <= 81 for b in batches)
    _check_answers(echo, batches, tr)
    lat = harness.latencies_ms(batches, tr, t_open)
    assert np.all(lat > 0)
    assert harness.e2e_value("p50_ms", 0, batches, tr, t_open, 0) > 0


def test_saturated_batches_of_varied_requests_are_as_full_as_whole_requests_allow():
    echo, tr = Echo(), _traffic(candidates=LOGNORMAL)
    batches, _, _ = harness.serve(echo, None, tr, 0.3)
    for b in batches:
        assert b.rows == tr.rows(b.first, b.stop) <= 4096
        assert b.rows + tr.count[tr.content(b.stop, b.stop + 1)][0] > 4096
    _check_answers(echo, batches, tr)
