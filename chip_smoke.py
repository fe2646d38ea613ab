"""Bring-up smoke of the serving path on one TPU chip.

    python chip_smoke.py             # one TPU chip, taobao_ssa at published widths
    python chip_smoke.py --rehearse  # the same phases on the CPU at reduced widths

One process, seven phases, one stdout line each, through the entry points
of `repro.launch.serve`: the device, the model, a few train steps, the
Table-I ladder, every variant's jitted serve step at batch 1, 32 and 512,
the baseline against the same step at "highest" matmul precision, and
calibration plus one short ElasticEngine run per variant; then one line on
the compile cache. The calibrated times are smoke readings of a cold
process, not benchmark figures.

A failed check raises, and the script exits non-zero. Without --rehearse
it needs a TPU and never falls back to the CPU. The last stdout line is
exactly {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}};
a rehearsal's says "cpu" and is never a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "taobao_ssa"
VARIANTS = ("baseline", "quantized", "pruned", "pruned_quantized", "distilled")
TRAIN_STEPS = 5
TRAIN_BATCH = 512
LADDER_STEPS = {"finetune_steps": 2, "qat_steps": 2, "distill_steps": 3}
SERVE_SIZES = (1, 32, 512)
# The baseline's probabilities at default matmul precision against the same
# step at "highest", max |dp| over a 512-row batch. On the TPU the default
# f32 matmul rounds its inputs to bf16 (relative error 2^-9); through two
# encoder blocks and the tower that came to 1.0e-3 on a v5e, so the bound
# leaves a tenfold margin.
PRECISION_BOUND = 1e-2
ENGINE_PROFILE = "spike"
ENGINE_HORIZON_S = 5.0
CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields)}", flush=True)


def _cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, reduced widths; for tests, never a chip run")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro.core.compression_loop import LadderConfig, run_ladder, variant_stats
    from repro.launch.serve import (build_and_pretrain, calibrate_variant,
                                    make_serve_step, recsys_rules, request_batches,
                                    run_engine)
    from repro.launch.train import sized_config
    from repro.models.common import param_bytes, param_count
    from repro.models.recsys import api as rec_api

    counts = {v: 0 for v in CACHE_EVENTS.values()}

    def on_event(event, **_):
        if event in CACHE_EVENTS:
            counts[CACHE_EVENTS[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    try:
        # 1 · device
        t0 = time.perf_counter()
        devices = jax.devices()
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices)}
        emit("device", **device, backend=jax.default_backend(), rehearse=args.rehearse)
        want = "cpu" if args.rehearse else "tpu"
        check(device["platform"] == want and jax.default_backend() == want,
              f"JAX found platform {device['platform']!r}; this run needs {want!r}")

        # 2 · model
        size = "reduced" if args.rehearse else "published"
        cfg = sized_config(ARCH, size)
        defs = rec_api.param_defs(cfg)
        emit("model", arch=ARCH, size=size, params=param_count(defs),
             table_params=param_count(defs["tables"]), bytes=param_bytes(defs),
             tables={f.name: [f.vocab, cfg.field_dim(f)] for f in cfg.owned_fields()},
             seq_len=cfg.seq_len)

        # 3 · train
        t = time.perf_counter()
        rules = recsys_rules()
        params, data, losses = build_and_pretrain(
            cfg, rules, steps=TRAIN_STEPS, batch=TRAIN_BATCH)
        check(len(losses) == TRAIN_STEPS, f"took {len(losses)} of {TRAIN_STEPS} steps")
        check(all(np.isfinite(losses)), f"non-finite train loss: {losses}")
        emit("train", batch=TRAIN_BATCH, steps_taken=len(losses), losses=losses,
             wall_s=time.perf_counter() - t)

        # 4 · ladder
        t = time.perf_counter()
        ladder = run_ladder(params, cfg, rules, lambda: data(1),
                            LadderConfig(**LADDER_STEPS))
        check(tuple(ladder) == VARIANTS, f"ladder built {tuple(ladder)}")
        emit("ladder", steps=LADDER_STEPS, variants=variant_stats(ladder),
             wall_s=time.perf_counter() - t)

        # 5 · serve
        t = time.perf_counter()
        batches = request_batches(cfg)
        steps = {name: make_serve_step(ladder[name]["cfg"], rules) for name in VARIANTS}
        served, p512 = {}, {}
        for name in VARIANTS:
            row = served[name] = {}
            for n in SERVE_SIZES:
                p = np.asarray(jax.block_until_ready(
                    steps[name](ladder[name]["params"], batches[n])))
                check(p.shape == (n,), f"{name} B={n}: output shape {p.shape}")
                check(bool(np.all(np.isfinite(p)) and np.all((p >= 0) & (p <= 1))),
                      f"{name} B={n}: probabilities not finite in [0, 1]")
                row[f"B={n}"] = [float(p.min()), float(p.max())]
                if n == 512:
                    p512[name] = p
            row["mean_abs_diff_vs_baseline_B=512"] = float(
                np.mean(np.abs(p512[name] - p512["baseline"])))
        emit("serve", sizes=SERVE_SIZES, prob_min_max=served,
             wall_s=time.perf_counter() - t)

        # 6 · precision
        base = ladder["baseline"]
        p_default = np.asarray(steps["baseline"](base["params"], batches[512]))
        with jax.default_matmul_precision("highest"):
            p_highest = np.asarray(steps["baseline"](base["params"], batches[512]))
        diff = np.abs(p_default - p_highest)
        emit("precision", batch=512, max_abs_diff=float(diff.max()),
             mean_abs_diff=float(diff.mean()), bound=PRECISION_BOUND)
        check(float(diff.max()) <= PRECISION_BOUND,
              f"baseline differs from its 'highest'-precision run by {float(diff.max())}")

        # 7 · calibration and engine (smoke readings, not a benchmark)
        t = time.perf_counter()
        smoke = {}
        for name in VARIANTS:
            lat = calibrate_variant(steps[name], ladder[name]["params"], batches)
            res = run_engine(name, lat, ENGINE_PROFILE, ENGINE_HORIZON_S)
            check(all(np.isfinite(lat.times)) and res["throughput"] > 0,
                  f"{name}: calibration {lat.times} or engine {res} unusable")
            smoke[name] = {"service_ms": {int(n): float(s) * 1e3
                                          for n, s in zip(lat.sizes, lat.times)},
                           "engine": res}
        emit("calibrate_smoke", note="cold-process smoke timings, not a benchmark",
             device=device["kind"], profile=ENGINE_PROFILE,
             horizon_s=ENGINE_HORIZON_S, variants=smoke,
             wall_s=time.perf_counter() - t)

        cache_dir = jax.config.jax_compilation_cache_dir
        emit("compile_cache", dir=cache_dir, entries=_cache_entries(cache_dir),
             **counts, total_wall_s=time.perf_counter() - t0)
    finally:
        jax.monitoring.unregister_event_listener(on_event)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    if "--rehearse" in sys.argv[1:]:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before JAX is imported
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke failed: {e}", file=sys.stderr)
        sys.exit(1)
