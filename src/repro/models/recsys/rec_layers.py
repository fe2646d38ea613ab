"""Small shared layers for the recsys towers."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamDef


def mlp_defs(name: str, in_dim: int, dims: Tuple[int, ...], out_dim: int = 1,
             prelu: bool = True) -> Dict:
    """MLP tower ParamDefs: dims hidden layers + linear head to out_dim;
    with `prelu`, a PReLU slope per hidden unit."""
    defs = {}
    prev = in_dim
    for i, d in enumerate(dims):
        defs[f"{name}_w{i}"] = ParamDef((prev, d), (None, None), jnp.float32, "fan_in")
        defs[f"{name}_b{i}"] = ParamDef((d,), (None,), jnp.float32, "zeros")
        if prelu:
            defs[f"{name}_a{i}"] = ParamDef((d,), (None,), jnp.float32, "zeros")
        prev = d
    defs[f"{name}_wout"] = ParamDef((prev, out_dim), (None, None), jnp.float32, "fan_in")
    defs[f"{name}_bout"] = ParamDef((out_dim,), (None,), jnp.float32, "zeros")
    return defs


def prelu(x, a):
    return jnp.where(x >= 0, x, a * x)


def mlp_apply(params: Dict, name: str, x, n_layers: int, act=None):
    """All matmuls go through the compressible-linear dispatch so the C4/C5
    ladder (masked / int8 / low-rank reps) applies to every tower. `act`
    replaces PReLU after each hidden layer (`mlp_defs(prelu=False)`)."""
    from repro.core.lightweight import linear

    for i in range(n_layers):
        x = linear(params[f"{name}_w{i}"], x) + params[f"{name}_b{i}"]
        x = act(x) if act else prelu(x, params[f"{name}_a{i}"])
    return linear(params[f"{name}_wout"], x) + params[f"{name}_bout"]


def bce_with_logits(logits, labels):
    """Numerically stable binary cross entropy."""
    z = logits.astype(jnp.float32)
    y = labels.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))
