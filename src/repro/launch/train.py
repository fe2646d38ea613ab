"""Training launcher: `python -m repro.launch.train --arch taobao_ssa ...`

Runs a REAL training loop on the default backend, at the arch's published
widths (`--size published`) or cut to CPU scale (`--size reduced`, the
default). Wires: config -> model -> optimizer -> fault-tolerant loop
(checkpoint/resume) -> metrics log.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import get_config
from repro.data import synthetic
from repro.distributed.sharding import FAMILY_RULES, adapt_rules
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.common import init_params
from repro.training.fault_tolerance import FTConfig, ResilientTrainer
from repro.training.optimizer import get_optimizer
from repro.training.train_loop import make_train_step


def reduced_config(cfg):
    """Shrink any arch config to CPU-trainable scale (smoke/driver runs)."""
    if cfg.family == "lm":
        return dataclasses.replace(
            cfg, n_layers=2, d_model=128, n_heads=4, n_kv_heads=min(cfg.n_kv_heads, 4),
            d_ff=256, vocab_size=512, head_dim=32,
            n_experts=min(cfg.n_experts, 8) if cfg.n_experts else 0,
            top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        )
    if cfg.family == "recsys":
        fields = tuple(
            dataclasses.replace(f, vocab=min(f.vocab, 5000)) for f in cfg.fields
        )
        return dataclasses.replace(cfg, fields=fields)
    return cfg  # nequip is already small


SIZES = ("published", "reduced")
CKPT_ROOT = Path(__file__).resolve().parents[3] / ".ckpt"


def sized_config(arch: str, size: str):
    """The arch's config at its published widths, or cut by reduced_config."""
    if size not in SIZES:
        raise ValueError(f"size must be one of {SIZES}, not {size!r}")
    cfg = get_config(arch)
    return cfg if size == "published" else reduced_config(cfg)


def make_loss(cfg, rules):
    if cfg.family == "lm":
        from repro.models import transformer as tf

        return lambda p, b: tf.loss(p, b, cfg, rules)
    if cfg.family == "recsys":
        from repro.models.recsys import api

        return lambda p, b: api.loss(p, b, cfg, rules)
    from repro.models.gnn import nequip

    return lambda p, b: nequip.node_class_loss(p, b, cfg, rules)


def make_data(cfg, batch: int, seed_base: int = 0):
    if cfg.family == "lm":
        return lambda step: (
            {k: jax.numpy.asarray(v) for k, v in b.items()}
            for b in synthetic.lm_token_batches(
                cfg.vocab_size, batch, 128, 10**9, seed=seed_base + step
            )
        )
    if cfg.family == "recsys":
        if cfg.interaction in ("fm", "self_attn"):
            gen = lambda step: synthetic.criteo_batches(cfg, batch, 10**9, seed=seed_base + step)
        else:
            gen = lambda step: synthetic.taobao_batches(cfg, batch, 10**9, seed=seed_base + step)
        return lambda step: (
            {k: jax.numpy.asarray(v) for k, v in b.items()} for b in gen(step)
        )
    def graphs(step):
        i = step
        while True:
            g = synthetic.random_graph(512, 8, n_classes=7, seed=seed_base + i)
            yield {k: jax.numpy.asarray(v) for k, v in g.items()}
            i += 1
    return graphs


def param_defs_for(cfg):
    if cfg.family == "lm":
        from repro.models import transformer as tf

        return tf.param_defs(cfg)
    if cfg.family == "recsys":
        from repro.models.recsys import api

        return api.param_defs(cfg)
    from repro.models.gnn import nequip

    return nequip.param_defs(cfg, n_classes=7)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="taobao_ssa")
    ap.add_argument("--size", default="reduced", choices=SIZES)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="",
                    help="default <repo>/.ckpt/<arch>_<size>; a run resumes from "
                         "the latest checkpoint there")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    use_compile_cache()
    cfg = sized_config(args.arch, args.size)
    mesh = make_test_mesh()
    rules = adapt_rules(FAMILY_RULES[cfg.family], mesh)

    params = init_params(param_defs_for(cfg), jax.random.key(0))
    opt = get_optimizer(args.optimizer, args.lr)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(make_loss(cfg, rules), opt))

    ckpt_dir = args.ckpt_dir or str(CKPT_ROOT / f"{args.arch}_{args.size}")
    trainer = ResilientTrainer(
        step_fn,
        FTConfig(ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every),
        make_batches=make_data(cfg, args.batch),
    )
    t0 = time.time()
    params, opt_state, restarts, last = trainer.run(params, opt_state, args.steps)
    dt = time.time() - t0
    taken = last - trainer.resumed_from
    print(
        json.dumps(
            {"arch": args.arch, "size": args.size, "resumed_from": trainer.resumed_from,
             "steps": last, "steps_taken": taken, "restarts": restarts,
             "wall_s": round(dt, 2), "steps_per_s": round(taken / dt, 2)}
        )
    )


if __name__ == "__main__":
    main()
