"""Mesh construction. Functions only — importing this module never touches
jax device state."""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16x16 (256 chips) single-pod, 2x16x16 multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — "
            "run under launch/dryrun.py which sets "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes), devices=devices[:n])


def make_test_mesh():
    """1-device mesh for smoke tests and CPU benchmarks."""
    return jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
