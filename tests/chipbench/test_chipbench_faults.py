"""Each fault the cells can have, planted in the program's serve step
underneath a whole run of the harness (at small widths, skipping only the
look for a chip), makes `correct` come out false."""
from __future__ import annotations

import pytest

from chipbench_testing import FAULTS, planted, run_small, spec

CELLS = [w["name"] for w in spec()["workloads"]]


@pytest.mark.parametrize("fault", list(FAULTS.values()), ids=list(FAULTS))
@pytest.mark.parametrize("cell_name", CELLS)
def test_a_broken_serve_step_is_not_correct(cell_name, fault):
    with planted(fault):
        res = run_small(cell_name)
    assert not res["correct"], res["checks"]
