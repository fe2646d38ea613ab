"""BENCHMARK.json, the files it names, the peaks and the analytic work."""
from __future__ import annotations

import json
import re

import pytest

from chipbench_testing import ROOT, spec
from chipbench import harness, peaks
from chipbench.reference import taobao_ssa as ref

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _published():
    return json.loads((ROOT / "chipbench" / "configs" / "taobao_ssa-fp32.json").read_text())


def test_request_flops_match_the_shapes():
    cfg = _published()
    assert ref.request_flops(cfg, 0) == 2 * (4 * 2 * 100 * 64 * 64 + 2 * 2 * 100 * 100 * 64
                                            + 2 * 2 * 100 * 64 * 256) == 24_780_800
    assert ref.request_flops(cfg, 1) - ref.request_flops(cfg, 0) == \
        2 * (208 * 200 + 200 * 80 + 80 * 1) == 115_360
    assert ref.request_flops(cfg, 50) == 30_548_800


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert peaks.peak("TPU v5 lite", "int8_ops") == 393e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary", "bf16_flops")


def test_every_name_in_the_spec_has_its_file():
    s = spec()
    configs = {c["name"]: c for c in s["configs"]}
    for c in s["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["peak"] in peaks.PEAKS["TPU v5 lite"]
        assert (ROOT / "chipbench" / "models" / f"{cfg['model']}.py").is_file()
    cells = {w["name"] for w in s["workloads"]}
    for w in s["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        mix = json.loads((ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "chipbench" / "generators" / f"{mix['generator']}.py").is_file()
        assert harness.cell_metrics(s, w["name"], "end_to_end")
        assert harness.cell_metrics(s, w["name"], "per_layer")
    for m in s["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m["workloads"]) <= cells
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]] + list(configs) + list(cells)
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in s["end_to_end"] + s["per_layer"])


def test_each_cell_reports_setup_and_another_end_to_end_metric():
    s = spec()
    for w in s["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(s, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        moved = {m["moves"] for m in harness.cell_metrics(s, w["name"], "per_layer")}
        assert moved <= e2e
