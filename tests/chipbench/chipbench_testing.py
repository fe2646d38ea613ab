"""Small sizes of the benchmark's configurations and mixes, for the CPU.

Each configuration is cut by its own driver, `chipbench/models/<model>.py`:
`CPU_CUT` cuts its vocabularies, `CPU_WIDTHS` sets the small widths at
which a whole cell runs here."""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def config(name: str, **sizes) -> dict:
    """The configuration `name` with its driver's `CPU_CUT`: vocabularies
    cut to a few thousand rows; every width stays as published unless
    `sizes` says."""
    from chipbench import harness

    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    return {**cfg, **harness.driver(cfg).CPU_CUT, **sizes}


def small(cfg: dict) -> dict:
    """`cfg` cut to its driver's `CPU_CUT` and `CPU_WIDTHS`."""
    from chipbench import harness

    d = harness.driver(cfg)
    return {**cfg, **d.CPU_CUT, **d.CPU_WIDTHS}


def mix(name: str, **params) -> dict:
    m = json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json").read_text())
    m.update(users=64, distinct_requests=256)
    m.update(params)
    return m


def small_mix(name: str, cfg: dict) -> dict:
    """The mix `name` for a small configuration: 64 users, histories of 5
    up to its `seq_len`, and an open loop at 300 requests/s."""
    m = mix(name, hist_len=[5, cfg["seq_len"]])
    if m["arrivals"] == "poisson":
        m["rate_per_s"] = 300.0
    return m


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def small_cell(cell_name: str) -> tuple:
    """(cell, configuration, mix) of the cell `cell_name` at small sizes."""
    from chipbench import harness

    cell, cfg, _ = harness.load_cell(spec(), cell_name)
    cfg = small(cfg)
    return cell, cfg, small_mix(cell["traffic"], cfg)


def run(cell: dict, cfg: dict, m: dict, metrics: list, seed: int, trace: bool = False) -> dict:
    """A whole run of the harness of a 0.5 s window, on the CPU: the look
    for a chip passes the CPU's devices, and the CPU gets a peak of 1e12."""
    import jax
    import pytest

    from chipbench import harness, peaks

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        mp.setattr(peaks, "peak", lambda kind, which: 1e12)
        return harness.run_cell(cell, cfg, m, metrics, seed, 0.5, trace, time.perf_counter())


def run_small(cell_name, seed=2**31 + 3, trace=False):
    """A whole run of the cell `cell_name` at small widths, on the CPU."""
    from chipbench import harness

    cell, cfg, m = small_cell(cell_name)
    metrics = harness.cell_metrics(spec(), cell_name, "per_layer" if trace else "end_to_end")
    return run(cell, cfg, m, metrics, seed, trace)


def control_readings(model, cfg: dict, seed: int = 17) -> dict:
    """What `correct` compares, of the control against the reference, on a
    second of the saturated mix's requests."""
    import jax
    import numpy as np

    from chipbench import harness, traffic

    tr = traffic.make_traffic(mix("rank50-saturated"), cfg, seed, 1.0)
    key = jax.random.key(seed)
    rows = tr.row_index(0, tr.contents)
    return harness.readings(model.reference(key, tr, *rows, control=True).astype(np.float64),
                            model.reference(key, tr, *rows).astype(np.float64))


@contextlib.contextmanager
def planted(fault):
    """While open, every model the harness builds serves through
    `fault(step)` in place of its step."""
    import pytest

    from chipbench import harness

    make = harness.model_for

    def broken(cfg):
        model = make(cfg)
        model.step = fault(model.step)
        return model

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "model_for", broken)
        yield


def altered(step):
    """The first request of each batch gets wrong answers."""
    import jax

    return jax.jit(lambda p, b: step(p, b).at[:50].add(0.1))


def half(step):
    """Only the first half of the batch is computed; the rest is a copy."""
    import jax
    import jax.numpy as jnp

    def broken(p, b):
        n = len(next(iter(b.values()))) // 2
        out = step(p, {k: v[:n] for k, v in b.items()})
        return jnp.concatenate([out, out])
    return jax.jit(broken)


FAULTS = {"answer_altered": altered, "half_batch": half}
