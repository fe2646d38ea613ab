"""The paper's closed loop (§III.B Fig 3 + §V ladder): prune → fine-tune →
quantize → QAT, plus the distilled student — producing the five Table-I
variants of any recsys model:

  baseline / quantized / pruned / pruned_quantized / distilled

Each variant is a parameter tree in the representations of
core/lightweight.py; the SAME model code serves all five.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import RecSysConfig
from repro.core import distillation, pruning, quantization
from repro.models.recsys import api as rec_api
from repro.training.optimizer import get_optimizer
from repro.training.train_loop import make_train_step


@dataclasses.dataclass
class LadderConfig:
    prune_target: float = 0.4  # paper: ≈40% params removed
    prune_rounds: int = 3  # paper: K = 3
    structured: bool = False  # block-structured variant (TPU-fast path)
    block: int = 128
    finetune_steps: int = 30
    qat_steps: int = 30
    distill_steps: int = 60
    lr: float = 1e-3


def _is_masked(x) -> bool:
    return isinstance(x, dict) and "mask" in x and "w" in x


def _restore_masks(params, ref):
    """Masks are constants of the pruning stage — never optimizer state."""
    return jax.tree.map(
        lambda p, r: {"w": p["w"], "mask": r["mask"]} if _is_masked(r) else p,
        params, ref, is_leaf=_is_masked,
    )


def _finetune(params, cfg, rules, batches, steps, lr, *, qat=False):
    opt = get_optimizer("adamw", lr)

    def loss_fn(p, b):
        p_eff = quantization.qat_params(p) if qat else p
        return rec_api.loss(p_eff, b, cfg, rules)

    step = make_train_step(loss_fn, opt)
    state = opt.init(params)
    jstep = jax.jit(step)
    ref = params
    for i, b in zip(range(steps), batches):
        params, state, _ = jstep(params, state, b)
        params = _restore_masks(params, ref)
    return params


def run_ladder(
    teacher_params: Dict,
    cfg: RecSysConfig,
    rules,
    batch_fn: Callable[[], Iterator],
    ladder: Optional[LadderConfig] = None,
    *,
    rng=None,
) -> Dict[str, Dict]:
    """Returns {variant: (params, cfg)} for the five Table-I rows."""
    lc = ladder or LadderConfig()
    rng = rng if rng is not None else jax.random.key(0)
    out: Dict[str, Dict] = {}
    out["baseline"] = {"params": teacher_params, "cfg": cfg}

    # ---- Quantized (C5 alone: PTQ of weights + tables) ----
    out["quantized"] = {
        "params": quantization.quantize_tree(teacher_params), "cfg": cfg
    }

    # ---- Pruned (C4: K rounds of dynamic-threshold + fine-tune) ----
    pruned = teacher_params
    for ratio in pruning.prune_schedule(lc.prune_target, lc.prune_rounds):
        pruned = pruning.prune_tree(
            pruned, ratio, structured=lc.structured, block=lc.block
        )
        pruned = _finetune(pruned, cfg, rules, batch_fn(), lc.finetune_steps, lc.lr)
    out["pruned"] = {"params": pruned, "cfg": cfg}

    # ---- Pruned + Quantized (C4 → QAT → int8 storage) ----
    pq = _finetune(pruned, cfg, rules, batch_fn(), lc.qat_steps, lc.lr, qat=True)
    out["pruned_quantized"] = {"params": quantization.quantize_tree(pq), "cfg": cfg}

    # ---- Distilled (C3 + C1 student) ----
    s_cfg = distillation.make_student_cfg(cfg)
    student = distillation.init_student_from_teacher(teacher_params, s_cfg, rng)
    opt = get_optimizer("adamw", lc.lr)

    # the teacher is an argument, not a closure: jit would embed closed-over
    # arrays in the program as constants (all of its tables, at full size)
    @jax.jit
    def step(p, state, b, teacher):
        def d_loss(p, b):
            return distillation.distill_loss(p, teacher, b, s_cfg, cfg, rules)

        return make_train_step(d_loss, opt)(p, state, b)

    state = opt.init(student)
    for i, b in zip(range(lc.distill_steps), batch_fn()):
        student, state, m = step(student, state, b, teacher_params)
    out["distilled"] = {"params": student, "cfg": s_cfg}
    return out


def variant_stats(variants: Dict[str, Dict]) -> Dict[str, Dict]:
    """Params / storage / sparsity per variant (Fig 7 accounting)."""
    stats = {}
    for name, v in variants.items():
        p = v["params"]
        n_params = 0
        for leaf in jax.tree.leaves(
            p, is_leaf=lambda x: isinstance(x, dict) and ("q" in x or "w" in x or "a" in x or "gw" in x)
        ):
            if isinstance(leaf, dict):
                if "q" in leaf:
                    n_params += leaf["q"].size
                elif "w" in leaf:
                    n_params += int(jnp.sum(leaf["mask"]))
                elif "a" in leaf:
                    n_params += leaf["a"].size + leaf["b"].size
                elif "gw" in leaf:
                    n_params += leaf["gw"].size
            else:
                n_params += leaf.size
        stats[name] = {
            "params": int(n_params),
            "bytes": quantization.model_bytes(p),
            "sparsity": pruning.sparsity(p),
        }
    return stats
