"""Pallas TPU kernel: W8A8 int8 x int8 -> int32 matmul + dequant epilogue.

The MXU executes int8 pairs at 2x bf16 rate on v5e (394 vs 197 TOPS) — this
kernel is the paper's C5 'zero-copy integer inference' adapted to TPU:
int8 tiles stream HBM->VMEM (4x less traffic than f32), accumulate in an
int32 VMEM scratch across the K grid dimension, and the per-row/per-column
scales are applied once in the epilogue at the last K step.

Grid: (M/bm, N/bn, K/bk), K innermost so the scratch accumulator lives
across the K sweep of one (m, n) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, as_ref, bs_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        # [bm, 1] x [1, bn] broadcast: the scales stay 2-D so their blocks
        # tile like any other operand on the chip
        out_ref[...] = acc_ref[...].astype(jnp.float32) * as_ref[...] * bs_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(
    a_q, b_q, a_scale, b_scale, *, bm: int = 128, bn: int = 128, bk: int = 128,
    interpret: bool = False,
):
    """a_q int8 [M,K]; b_q int8 [K,N]; a_scale f32 [M]; b_scale f32 [N].
    Each of M, N, K is a multiple of its block, or equal to it."""
    M, K = a_q.shape
    _, N = b_q.shape
    if M % bm or N % bn or K % bk:
        raise ValueError(f"[{M},{K}] x [{K},{N}] does not tile into ({bm}, {bn}, {bk}) blocks")
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bm, 1), lambda m, n, k: (m, 0)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a_q, b_q, a_scale.reshape(M, 1), b_scale.reshape(1, N))
