"""Jitted public wrapper: quantized linear y = x_q @ w_q with dequant.

On CPU hosts the Pallas body runs under interpret=True (bit-exact
semantics); on TPU it compiles to the MXU int8 path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.int8_matmul.int8_matmul import int8_matmul
from repro.kernels.int8_matmul.ref import quantize_activations


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _tile(dim: int, block: int):
    """(block, padded dim): a dim no larger than the block is one whole
    block; a larger one is padded up to a multiple of the block."""
    if dim <= block:
        return dim, dim
    return block, -(-dim // block) * block


def _pad(a, shape):
    return jnp.pad(a, [(0, t - s) for s, t in zip(a.shape, shape)])


def quantized_linear(x, w_rep, *, bm: int = 128, bn: int = 128, bk: int = 128):
    """x: f32 [M, K]; w_rep: {"q": int8 [K,N], "s": f32 [N]} (C5 storage rep).
    Dynamically quantizes activations per-row and runs the int8 kernel on
    operands zero-padded to whole blocks; zero rows and columns add nothing
    to the int32 sums, and the padded outputs are sliced away."""
    M, K = x.shape
    N = w_rep["q"].shape[1]
    x_q, x_s = quantize_activations(x)
    bm, Mp = _tile(M, bm)
    bn, Np = _tile(N, bn)
    bk, Kp = _tile(K, bk)
    out = int8_matmul(
        _pad(x_q, (Mp, Kp)), _pad(w_rep["q"], (Kp, Np)),
        _pad(x_s, (Mp,)), _pad(w_rep["s"], (Np,)),
        bm=bm, bn=bn, bk=bk, interpret=_interpret(),
    )
    return out[:M, :N]
