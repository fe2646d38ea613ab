"""Serving launcher: builds the Table-I variant ladder for a recsys arch,
calibrates per-variant latency on REAL jitted executables, and runs the
elastic engine against a traffic profile.

`python -m repro.launch.serve --arch taobao_ssa --profile spike [--size published]`

The steps are functions so that `chip_smoke.py` drives the same path:
`build_and_pretrain` -> `run_ladder` -> `make_serve_step` +
`calibrate_variant` -> `run_engine`.
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.core.compression_loop import LadderConfig, run_ladder, variant_stats
from repro.core.serving.engine import ElasticEngine, EngineConfig, poisson_arrivals
from repro.core.serving.rate_limiter import TierPolicy
from repro.core.serving.replica import LatencyModel, ReplicaSpec
from repro.distributed.sharding import FAMILY_RULES, adapt_rules
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.launch.train import SIZES, make_data, sized_config
from repro.models.common import init_params
from repro.models.recsys import api as rec_api
from repro.training.optimizer import get_optimizer
from repro.training.train_loop import make_train_step

PROFILES = {
    "steady": lambda t: 300.0,
    "spike": lambda t: 150.0 if t < 15 else (1200.0 if t < 40 else 200.0),
    "ramp": lambda t: 50.0 + 20.0 * t,
}

CALIBRATION_SIZES = (1, 8, 32, 128, 512)


def recsys_rules():
    return adapt_rules(FAMILY_RULES["recsys"], make_test_mesh())


def build_and_pretrain(cfg, rules, *, steps: int, batch: int):
    """Initialise params from seed 0 and take `steps` AdamW steps, so the
    ladder compresses a trained model. Returns (params, data, losses):
    `data(i)` yields the i-th seeded batch stream, and `losses` holds one
    float per step actually taken."""
    data = make_data(cfg, batch)
    params = init_params(rec_api.param_defs(cfg), jax.random.key(0))
    opt = get_optimizer("adamw", 1e-3)
    step = jax.jit(make_train_step(lambda p, b: rec_api.loss(p, b, cfg, rules), opt))
    state = opt.init(params)
    losses = []
    for _, b in zip(range(steps), data(0)):
        params, state, metrics = step(params, state, b)
        losses.append(float(metrics["loss"]))
    return params, data, losses


def make_serve_step(cfg, rules):
    """The jitted serve step of one variant: probabilities for a batch."""
    return jax.jit(lambda p, b: rec_api.serve(p, b, cfg, rules))


def request_batches(cfg):
    """One fixed request batch (labels dropped) per calibration size, cut
    from the first batch of the held-out stream 2."""
    b = next(make_data(cfg, max(CALIBRATION_SIZES))(2))
    return {n: {k: v[:n] for k, v in b.items() if k != "label"}
            for n in CALIBRATION_SIZES}


def calibrate_variant(step, params, batches) -> LatencyModel:
    """Time the real executable at every size in `batches`."""

    def run(n):
        jax.block_until_ready(step(params, batches[n]))

    return LatencyModel.calibrate(run, sizes=tuple(sorted(batches)), reps=3)


def run_engine(name: str, lat: LatencyModel, profile: str, horizon: float) -> dict:
    """One ElasticEngine run of a variant's calibrated curve."""
    spec = ReplicaSpec(name, lat, cold_start_s=5.0, warm_start_s=0.2)
    eng = ElasticEngine(
        spec,
        EngineConfig(n_replicas=2, autoscale=True, slo_p99_s=0.1),
        tiers={"tier0": TierPolicy(2000, 200), "tier1": TierPolicy(2000, 200)},
    )
    arrivals = poisson_arrivals(PROFILES[profile], horizon, seed=0)
    res = eng.run(arrivals, until=horizon)
    return {
        "p50_ms": res["p50"] * 1e3,
        "p99_ms": res["p99"] * 1e3,
        "throughput": res["throughput"],
        "rejected": res["rejected"],
        "latency_1": lat(1) * 1e3,
        "latency_512": lat(512) * 1e3,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="taobao_ssa")
    ap.add_argument("--size", default="reduced", choices=SIZES)
    ap.add_argument("--profile", default="spike", choices=sorted(PROFILES))
    ap.add_argument("--horizon", type=float, default=60.0)
    ap.add_argument("--train-steps", type=int, default=40)
    ap.add_argument("--variants", default="baseline,quantized,pruned,pruned_quantized,distilled")
    args = ap.parse_args()

    use_compile_cache()
    cfg = sized_config(args.arch, args.size)
    rules = recsys_rules()
    params, data, _ = build_and_pretrain(cfg, rules, steps=args.train_steps, batch=256)
    ladder = run_ladder(
        params, cfg, rules, lambda: data(1),
        LadderConfig(finetune_steps=10, qat_steps=10, distill_steps=15),
    )
    batches = request_batches(cfg)

    results = {}
    for name in args.variants.split(","):
        v = ladder[name]
        lat = calibrate_variant(make_serve_step(v["cfg"], rules), v["params"], batches)
        res = results[name] = run_engine(name, lat, args.profile, args.horizon)
        print(f"{name:18s} p50={res['p50_ms']:7.1f}ms p99={res['p99_ms']:7.1f}ms "
              f"thpt={res['throughput']:7.0f}/s svc(512)={res['latency_512']:6.1f}ms")

    stats = variant_stats(ladder)
    print(json.dumps({"serving": results, "stats": stats}, indent=2, default=str))


if __name__ == "__main__":
    main()
