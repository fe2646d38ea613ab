"""DIN's configuration, `chipbench/models/din.py` and its plain reference.

The program's serve step answers as `chipbench/reference/din.py` on seeded
weights at the published widths (vocabularies cut by `CPU_CUT` of
`chipbench/models/din.py`), row by row and through `ServeStep`'s compaction,
within a tolerance that the bfloat16 control fails."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from chipbench_testing import ROOT, config, mix, spec
from chipbench import harness, traffic
from chipbench.models import din
from chipbench.reference import din as ref

# On the CPU the program and the reference both compute in float32; they
# differ only by the order of the sums, ~1e-7 in a probability. One
# bfloat16 rounding of a logit near 0 already moves it by ~1e-3.
TOL = 1e-5


def _published() -> dict:
    return json.loads((ROOT / "chipbench" / "configs" / "din-fp32.json").read_text())


def test_the_configuration_holds_the_programs_widths_and_its_cut():
    from repro.configs.din import config as program

    cfg, p = _published(), program()
    assert (cfg["embed_dim"], cfg["seq_len"], tuple(cfg["attn"]), tuple(cfg["tower"])) == \
        (p.embed_dim, p.seq_len, p.attn_mlp_dims, p.mlp_dims) == (18, 100, (80, 40), (200, 80))
    assert {k: (v["published"], v["here"]) for k, v in cfg["reduced"].items()} == \
        {"items": (600_000_000, 75_000_000), "users": (60_000_000, 7_500_000)}
    assert all(cfg[k] == v["here"] for k, v in cfg["reduced"].items())
    (entry,) = [c for c in spec()["configs"] if c["name"] == cfg["name"]]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    rows = ref.table_rows(cfg)
    assert sum(v * cfg["embed_dim"] * 4 for v in (cfg["users"], cfg["items"],
                                                  cfg["categories"])) == 5_947_200_000
    assert rows["item"] == (75_000_320, 18) and rows["user"] == (7_500_288, 18)


def test_request_flops_match_the_shapes():
    cfg = _published()
    per_step = 144 * 80 + 80 * 40 + 40 * 1 + 36  # the attention MLP and the weighted sum
    tower = 162 * 200 + 200 * 80 + 80 * 1
    assert ref.request_flops(cfg, 1) == 2 * (100 * per_step + tower) == 3_056_160
    assert ref.request_flops(cfg, 50) == 152_808_000
    assert din.Model(config("din-fp32")).request_flops(50) == 152_808_000


@pytest.fixture(scope="module")
def served():
    """`din.Model` at the published widths, vocabularies cut, and a saturated
    window's first 6 requests as one 512-row bucket."""
    cfg = config("din-fp32")
    model = harness.model_for(cfg)
    tr = traffic.make_traffic(mix("rank50-saturated"), cfg, 2**31 + 11, 1.0)
    key = jax.random.key(2**31 + 11)
    pool, cand = tr.row_index(0, 6)
    return model, model.params(key), key, tr, pool, cand, model.batch(tr, 0, 6, 512)


@pytest.mark.parametrize("path", ["pointwise", "compact"])
def test_the_serve_step_answers_as_the_reference(served, path):
    model, params, key, tr, pool, cand, batch = served
    step = model.step
    got = np.asarray(step(params, batch) if path == "compact" else step.jitted(params, batch))
    assert step.encoded == (512 // 8 if path == "compact" else 0)
    got = got[:len(pool)].astype(np.float64)
    want = model.reference(key, tr, pool, cand).astype(np.float64)
    control = model.reference(key, tr, pool, cand, control=True).astype(np.float64)
    assert len(pool) == 300 and np.all(np.isfinite(got))
    assert np.max(np.abs(got - want)) <= TOL
    assert np.max(np.abs(control - want)) > TOL
    # the answers spread as a ranker's do: neither all alike nor saturated
    assert 0.02 < np.std(want) and 0.05 < want.min() and want.max() < 0.95


def test_the_attention_weighs_the_steps_unevenly(served):
    """At the seeded spreads the attention's scores differ across the real
    steps, so that a fault in its MLP shows in the answers."""
    model, _, key, tr, pool, cand, _ = served
    w = model.params(key)
    u, total, valid = ref.history(w, tr.hist_item[pool[:50]], tr.hist_category[pool[:50]],
                                  tr.hist_len[pool[:50]])
    q = np.concatenate([w["tables"]["item"][tr.cand_item[cand[:50]]],
                        w["tables"]["category"][tr.cand_category[cand[:50]]]], axis=-1)
    h = np.concatenate([np.broadcast_to(q[:, None], u.shape), u, q[:, None] - u, q[:, None] * u],
                       axis=-1)
    for i in range(2):
        h = jax.nn.sigmoid(h @ w[f"attn_w{i}"] + w[f"attn_b{i}"])
    s = np.asarray((h @ w["attn_wout"])[..., 0])
    spread = [np.std(r[v]) for r, v in zip(s, np.asarray(valid))]
    assert np.mean(spread) > 1.0


def test_reference_blocks_answer_bit_for_bit(served, monkeypatch):
    model, _, key, tr, pool, cand, _ = served
    whole = model.reference(key, tr, pool, cand)
    monkeypatch.setattr(din, "ROW_BLOCK", 128)  # 3 blocks, the last one padded
    np.testing.assert_array_equal(model.reference(key, tr, pool, cand), whole)


def test_the_layer_split_runs_the_cell_with_no_edit():
    """`chipbench/layers.py` runs `din_fp32-bulk` as `run.py` does; the CPU
    has no device plane, so what it reads of the device is empty."""
    from chipbench import layers
    from chipbench_testing import small_cell

    cell, cfg, m = small_cell("din_fp32-bulk")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        traced = layers.split(cell, cfg, m, 2**31 + 13, 0.5, True)
    assert traced["rows"] > 0 and traced["layer_us_per_row"] == {}
    assert traced["top_ops"] == {k: [] for k in layers.LAYERS + (layers.UNSCOPED,)}
