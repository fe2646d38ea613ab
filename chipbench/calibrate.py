"""Measurements that set the benchmark's fixed numbers; the benchmark's own
runs never call this. One process each, so everything compiles once.

    python3 chipbench/calibrate.py knee --workload <open-loop cell> --seed <n> \
        --seconds <s> --fractions 0.5,0.7,...
        Capacity from a saturated window, then the open loop at each
        fraction of it: p50, p99 and the p99 of the requests due in the
        last fifth of the window (a backlog that grows shows there).

    python3 chipbench/calibrate.py limits --workload <cell> --seeds <a,b,...> \
        --seconds <s>
        For each seed, the gaps between the served answers of a window of
        the cell's own traffic and the reference's: mean, quantiles, widest,
        and the share off by more than each of `OFF_BY_CANDIDATES`. For
        the first `--control-seeds` seeds, the same for the control: the
        reference one precision step lower, in the program's place, on the
        same served requests.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def knee(cell_name: str, seed: int, seconds: float, fractions) -> None:
    import numpy as np

    from chipbench import harness, traffic

    spec = harness.load_spec()
    _, cfg, mix = harness.load_cell(spec, cell_name)
    model, _, params, tr = harness.prepare(cfg, mix, seed, seconds)
    batches, t0, t1 = harness.serve(model, params, dataclasses.replace(tr, due=None), seconds)
    cap = sum(b.stop - b.first for b in batches if b.t_done <= t1) / (t1 - t0)
    print(json.dumps({"saturated_requests_per_s": cap}), flush=True)
    rng = np.random.default_rng(seed)
    for f in fractions:
        due = traffic.poisson_due(f * cap, seconds, rng)
        run = dataclasses.replace(tr, due=due)
        batches, t0, _ = harness.serve(model, params, run, seconds)
        lat = harness.latencies_ms(batches, run, t0)
        print(json.dumps({"fraction": f, "rate_per_s": f * cap, "requests": len(due),
                          "p50_ms": float(np.percentile(lat, 50)),
                          "p99_ms": float(np.percentile(lat, 99)),
                          "late_p99_ms": float(np.percentile(lat[due >= 0.8 * seconds], 99)),
                          "mean_requests_per_batch": float(np.mean(
                              [b.stop - b.first for b in batches]))}), flush=True)


OFF_BY_CANDIDATES = (5e-4, 1e-3, 1.5e-3, 2e-3, 3e-3)


def _gaps(served, reference) -> dict:
    import numpy as np

    gap = np.where(np.isfinite(served), np.abs(served - reference), np.inf).ravel()
    return {"mean": float(np.mean(gap)), "max": float(np.max(gap)),
            "p99": float(np.quantile(gap, 0.99)), "p999": float(np.quantile(gap, 0.999)),
            **{f"share_off_{t:g}": float(np.mean(~(gap <= t))) for t in OFF_BY_CANDIDATES}}


def limits(cell_name: str, seeds, seconds: float, control_seeds: int) -> None:
    import gc
    import time

    from chipbench import harness

    spec = harness.load_spec()
    _, cfg, mix = harness.load_cell(spec, cell_name)
    for i, seed in enumerate(seeds):
        model, key, params, tr = harness.prepare(cfg, mix, seed, seconds)
        batches, _, _ = harness.serve(model, params, tr, seconds)
        del params
        gc.collect()
        t0 = time.perf_counter()
        served, reference = harness.answers(model, key, tr, batches)
        out = {"cell": cell_name, "seed": seed,
               "answers": int(served.size), "reference_s": time.perf_counter() - t0,
               "program": _gaps(served, reference)}
        if i < control_seeds:
            _, lower = harness.answers(model, key, tr, batches, control=True)
            out["control"] = _gaps(lower, reference)
        print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("knee", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.85,0.9,0.95,1.0")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control on the first this many seeds")
    args = ap.parse_args(argv)
    from chipbench import harness, run

    harness.require_chips(1)
    run.use_cache()
    if args.what == "knee":
        knee(args.workload, args.seed, args.seconds,
             [float(f) for f in args.fractions.split(",")])
    else:
        limits(args.workload, [int(s) for s in args.seeds.split(",")], args.seconds,
               args.control_seeds)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
