"""What decides `correct`: a sound run passes, and the control fails.

The control is the reference one precision step below the configuration
(bfloat16 for float32, 4-bit for int8), at the published widths with the
vocabularies cut by each configuration's driver."""
from __future__ import annotations

import pytest

from chipbench_testing import config, control_readings, run_small, spec
from chipbench import harness

CELLS = [w["name"] for w in spec()["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_sound_run_is_correct(cell_name):
    res = run_small(cell_name)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0, res
    assert list(res)[-1] == "checks"


def test_a_traced_run_is_correct_and_reads_its_window():
    res = run_small(CELLS[0], trace=True)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["device"]["window_s"] > 0 and list(res)[-2:] == ["breakdown", "checks"]
    # the CPU has no TPU plane: only the host's dispatch spans are read
    assert set(res["metrics"]) == {"host_ms_per_batch.rank"}
    assert res["metrics"]["host_ms_per_batch.rank"]["value"] > 0


@pytest.mark.parametrize("name", sorted({c["name"] for c in spec()["configs"]}))
def test_the_control_fails_a_limit(name):
    cfg = config(name)
    control = control_readings(harness.model_for(cfg), cfg)
    failed = [n for n, lim in cfg["limits"].items() if control[n] > lim]
    assert failed, (control, cfg["limits"])
