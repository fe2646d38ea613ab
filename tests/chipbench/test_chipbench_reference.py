"""The taobao_ssa reference gathers each row's ids once for all its row
blocks; its answers are the same, bit for bit, in blocks as in one."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from chipbench_testing import config, mix, small
from chipbench import harness, traffic
from chipbench.models import taobao_ssa

HIST_BLOCK, ROW_BLOCK = 48, 2048  # several blocks of each, the last one padded


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
@pytest.mark.parametrize("name", ["taobao_ssa-fp32", "taobao_ssa-int8"])
def test_gathering_once_answers_bit_for_bit_as_before(name, seed, monkeypatch):
    monkeypatch.setattr(taobao_ssa, "HIST_BLOCK", HIST_BLOCK)
    cfg = small(config(name))
    tr = traffic.make_traffic(mix("rank50-saturated", hist_len=[5, cfg["seq_len"]]), cfg,
                              seed, 1.0)
    pool, cand = tr.row_index(3, 3 + 170)  # 8,500 rows: 5 blocks, the last one padded
    model = harness.model_for(cfg)
    key = jax.random.key(seed)
    monkeypatch.setattr(taobao_ssa, "ROW_BLOCK", len(pool))
    whole = model.reference(key, tr, pool, cand)
    monkeypatch.setattr(taobao_ssa, "ROW_BLOCK", ROW_BLOCK)
    got = model.reference(key, tr, pool, cand)
    assert got.shape == (len(pool),) and np.all(np.isfinite(got))
    np.testing.assert_array_equal(got, whole)
