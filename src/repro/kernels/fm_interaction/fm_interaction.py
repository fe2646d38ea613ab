"""Pallas TPU kernel: fused FM 2-way interaction (Rendle's sum-square trick).

½‖Σ_f e_bf‖² − ½Σ_f‖e_bf‖² per example, fused in one VMEM pass over the
[bb, F, k] block — the unfused jnp path materializes both the field sum and
the squared tensor in HBM; here they never leave VMEM. Batch is the only
grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(e_ref, out_ref):
    e = e_ref[...]  # [bb, F, k]
    s = jnp.sum(e, axis=1)  # [bb, k]
    sq = jnp.sum(jnp.square(e), axis=1)  # [bb, k]
    out_ref[...] = 0.5 * jnp.sum(jnp.square(s) - sq, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def fm_interaction_kernel(e, *, bb: int = 256, interpret: bool = False):
    """e: f32 [B, F, k], B % bb == 0 -> [B]."""
    B, F, k = e.shape
    return pl.pallas_call(
        _kernel,
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, F, k), lambda b: (b, 0, 0))],
        # a [bb, 1] column block: a 1-D (bb,) block does not match the
        # chip's tiled layout of a rank-1 operand
        out_specs=pl.BlockSpec((bb, 1), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        interpret=interpret,
    )(e)[:, 0]
