"""Each fault the cells can have, planted in the program's serve step
underneath a whole run of the harness (at small widths, skipping only the
look for a chip), makes `correct` come out false."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench_testing import run_small, spec

CELLS = [w["name"] for w in spec()["workloads"]]


def _altered(step):
    """The first request of each batch gets wrong answers."""
    return jax.jit(lambda p, b: step(p, b).at[:50].add(0.1))


def _half(step):
    """Only the first half of the batch is computed; the rest is a copy."""
    def broken(p, b):
        half = b["item"].shape[0] // 2
        out = step(p, {k: v[:half] for k, v in b.items()})
        return jnp.concatenate([out, out])
    return jax.jit(broken)


@pytest.mark.parametrize("fault", [_altered, _half], ids=["answer_altered", "half_batch"])
@pytest.mark.parametrize("cell_name", CELLS)
def test_a_broken_serve_step_is_not_correct(cell_name, fault, monkeypatch):
    import repro.launch.serve as serve

    make = serve.make_serve_step
    monkeypatch.setattr(serve, "make_serve_step", lambda *a: fault(make(*a)))
    res = run_small(cell_name)
    assert not res["correct"], res["checks"]
