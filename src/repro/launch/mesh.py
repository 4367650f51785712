"""Production mesh definition.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  The single-pod mesh is 16x16 = 256 chips (data x model);
the multi-pod mesh is 2x16x16 = 512 chips with a leading "pod" axis that the
sharding rules fold into data parallelism (gradient all-reduce crosses pods).
The mesh is parametric: ``make_mesh_shape`` scales to larger deployments
(e.g. (8, 16, 32) = 4096 chips) with the same sharding rules.
"""

from __future__ import annotations

import math

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={n} before any jax import")
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_mesh(shape: tuple, axes: tuple):
    """Parametric mesh for scale studies (same rules, any chip count)."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"any jax import")
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape), axes)


# TPU v5e hardware constants used by the roofline analysis (§Roofline).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
