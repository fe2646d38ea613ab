"""Small sizes of the benchmark's configurations and mixes, for the CPU."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def config(name: str, **sizes) -> dict:
    """The configuration `name` with the vocabularies cut to a few
    thousand rows; every width stays as published unless `sizes` says."""
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    cfg.update(users=2000, items=3000, categories=300)
    cfg.update(sizes)
    return cfg


def mix(name: str, **params) -> dict:
    m = json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json").read_text())
    m.update(users=64, distinct_requests=256)
    m.update(params)
    return m


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


SMALL = dict(seq_len=20, d_model=16, embed_dim=16, d_ff=64, n_heads=2, tower=[24, 8],
             user_dim=8)


def run_small(cell_name, seed=2**31 + 3, trace=False):
    """A whole run of the harness at small widths, on the CPU: the look for
    a chip passes the CPU's devices, and the CPU gets a peak of 1e12."""
    import jax
    import pytest

    from chipbench import harness, peaks

    cell, cfg, m = harness.load_cell(spec(), cell_name)
    cfg = config(cfg["name"], **SMALL)
    m = mix(cell["traffic"], hist_len=[5, 20],
            **({"rate_per_s": 300.0} if m["arrivals"] == "poisson" else {}))
    metrics = harness.cell_metrics(spec(), cell_name, "per_layer" if trace else "end_to_end")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        mp.setattr(peaks, "peak", lambda kind, which: 1e12)
        return harness.run_cell(cell, cfg, m, metrics, seed, 0.5, trace, time.perf_counter())
