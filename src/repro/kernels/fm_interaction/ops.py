"""Jitted wrapper for the fused FM interaction."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fm_interaction.fm_interaction import fm_interaction_kernel


def fm_interaction_op(e, *, bb: int = 256):
    """e: [B, F, k] -> [B]. A batch that does not fill whole blocks is
    zero-padded (a zero row interacts to zero) and sliced back."""
    B = e.shape[0]
    if B <= bb:
        bb = B
    Bp = -(-B // bb) * bb
    e = jnp.pad(e, ((0, Bp - B), (0, 0), (0, 0)))
    return fm_interaction_kernel(e, bb=bb, interpret=jax.default_backend() == "cpu")[:B]
