"""A second model joins the benchmark by new files alone.

A toy pointwise ranker is registered here as the driver
`chipbench.models.toy_pointwise`, as a new file would be, with its own
`CPU_CUT`, `CPU_WIDTHS`, plain reference and bfloat16 control. Nothing
under `chipbench/` names it: the harness finds it by the configuration's
`"model"` key, and the CPU checks cut it by its own constants."""
from __future__ import annotations

import copy
import math
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_testing as ct
from chipbench import harness

NAME = "toy_pointwise"
CFG = {"name": "toy_pointwise-fp32", "model": NAME, "users": 1_000_000, "items": 500_000,
       "categories": 5_000, "seq_len": 100, "dim": 32, "hidden": 64, "weights": "float32",
       "peak": "bf16_flops", "limits": {"share_off_2e-3": 3e-4}}
CELL = {"name": "toy-bulk", "config": CFG["name"], "traffic": "rank50-saturated", "chips": 1,
        "why": "a second model under the saturated mix"}


def _weights(cfg: dict, key) -> dict:
    d, h = cfg["dim"], cfg["hidden"]
    shapes = {"user": (cfg["users"], d), "item": (cfg["items"], d),
              "category": (cfg["categories"], d), "w1": (4 * d, h), "b1": (h,),
              "w2": (h, 1), "b2": (1,)}
    keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
    scale = {"w1": 1 / math.sqrt(4 * d), "w2": 1 / math.sqrt(h), "b1": 0.1, "b2": 0.1}
    return {n: scale.get(n, 0.5) * jax.random.normal(keys[n], s) for n, s in shapes.items()}


def _program(w: dict, b: dict):
    """One row per candidate, each row with its user's whole history."""
    cand = w["item"][b["item"]] + w["category"][b["category"]]
    hist = w["item"][b["hist_item"]] + w["category"][b["hist_category"]]
    valid = (jnp.arange(hist.shape[1])[None] < b["hist_len"][:, None])[..., None]
    pooled = jnp.sum(hist * valid, 1) / jnp.maximum(jnp.sum(valid, 1), 1)
    x = jnp.concatenate([w["user"][b["user"]], cand, pooled, pooled * cand], -1)
    h = jax.nn.relu(x @ w["w1"] + w["b1"])
    return jax.nn.sigmoid((h @ w["w2"] + w["b2"])[:, 0])


def _reference(w: dict, hist_item, hist_category, hist_len, inverse, user, item,
               category):
    """Each distinct history pooled once, then every row scored, at `highest`."""
    valid = jnp.arange(hist_item.shape[1])[None] < hist_len[:, None]
    units = w["item"][hist_item] + w["category"][hist_category]
    total = jnp.einsum("ul,uld->ud", valid.astype(units.dtype), units, precision="highest")
    pooled = total / jnp.maximum(hist_len, 1)[:, None].astype(units.dtype)
    p, c = pooled[inverse], w["item"][item] + w["category"][category]
    x = jnp.concatenate([w["user"][user], c, p, p * c], -1)
    h = jax.nn.relu(jnp.dot(x, w["w1"], precision="highest") + w["b1"])
    return jax.nn.sigmoid((jnp.dot(h, w["w2"], precision="highest") + w["b2"])[:, 0])


class _Model:
    def __init__(self, cfg: dict):
        self.cfg, self.peak = cfg, cfg["peak"]
        self.step = jax.jit(_program)

    def params(self, key):
        return jax.jit(lambda k: _weights(self.cfg, k))(key)

    def batch(self, tr, first, stop, rows):
        pool, cand = tr.row_index(first, stop)
        out = {"user": tr.user[pool], "item": tr.cand_item[cand],
               "category": tr.cand_category[cand], "hist_item": tr.hist_item[pool],
               "hist_category": tr.hist_category[pool], "hist_len": tr.hist_len[pool]}
        return {k: np.pad(v, [(0, rows - len(pool))] + [(0, 0)] * (v.ndim - 1))
                for k, v in out.items()}

    def request_flops(self, n_cand):
        d, h = self.cfg["dim"], self.cfg["hidden"]
        return n_cand * 2 * (4 * d * h + h)

    def reference(self, key, tr, pool, cand, control=False):
        w = jax.jit(lambda k: _weights(self.cfg, k))(key)
        if control:
            w = jax.tree.map(lambda x: x.astype(jnp.bfloat16), w)
        users, inverse = np.unique(pool, return_inverse=True)
        out = jax.jit(_reference)(w, tr.hist_item[users], tr.hist_category[users],
                                  tr.hist_len[users], inverse, tr.user[pool],
                                  tr.cand_item[cand], tr.cand_category[cand])
        return np.asarray(out, np.float32)


@pytest.fixture(autouse=True)
def toy_driver(monkeypatch):
    """The driver as `chipbench/models/toy_pointwise.py` would hold it."""
    mod = types.ModuleType(f"chipbench.models.{NAME}")
    mod.Model = _Model
    mod.CPU_CUT = dict(users=1000, items=2000, categories=100)
    mod.CPU_WIDTHS = dict(seq_len=12, dim=8, hidden=16)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def _spec() -> dict:
    """BENCHMARK.json with the toy's configuration and cell as new entries,
    the cell joining the `workloads` lists of the metrics that the other
    saturated cells report."""
    s = copy.deepcopy(ct.spec())
    s["configs"].append({"name": CFG["name"], "file": f"chipbench/configs/{CFG['name']}.json"})
    s["workloads"].append(CELL)
    for m in s["end_to_end"] + s["per_layer"]:
        if "taobao_fp32-bulk" in m.get("workloads", []):
            m["workloads"].append(CELL["name"])
    return s


def test_the_checks_cut_it_by_its_own_constants(toy_driver):
    small = ct.small(CFG)
    assert {k: small[k] for k in ("users", "items", "categories", "seq_len", "dim", "hidden")} \
        == {**toy_driver.CPU_CUT, **toy_driver.CPU_WIDTHS}
    assert isinstance(harness.model_for(small), _Model)


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_a_whole_run_of_it_is_correct(trace):
    cfg = ct.small(CFG)
    metrics = harness.cell_metrics(_spec(), CELL["name"], "per_layer" if trace else "end_to_end")
    res = ct.run(CELL, cfg, ct.small_mix(CELL["traffic"], cfg), metrics, 2**31 + 5, trace)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0, res
    if trace:  # the CPU has no TPU plane: only the host's dispatch spans are read
        assert set(res["metrics"]) == {"host_ms_per_batch.bulk"}
    else:
        assert set(res["metrics"]) == {"rows_per_s", "setup_s"}
        assert res["metrics"]["rows_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", list(ct.FAULTS.values()), ids=list(ct.FAULTS))
def test_a_planted_fault_is_not_correct(fault):
    cfg = ct.small(CFG)
    metrics = harness.cell_metrics(_spec(), CELL["name"], "end_to_end")
    with ct.planted(fault):
        res = ct.run(CELL, cfg, ct.small_mix(CELL["traffic"], cfg), metrics, 2**31 + 5)
    assert not res["correct"], res["checks"]


def test_its_control_fails_a_limit(toy_driver):
    cfg = {**CFG, **toy_driver.CPU_CUT}
    control = ct.control_readings(harness.model_for(cfg), cfg)
    assert [n for n, lim in cfg["limits"].items() if control[n] > lim], control
