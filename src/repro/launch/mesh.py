"""Mesh construction.

A function, not a module-level constant — importing this module must never
touch jax device state (the dry-run pins the device count before any jax
initialization).
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int],
              axes: Sequence[str] = ("data", "model")):
    """Mesh of ``shape`` over the first ``prod(shape)`` devices present.

    Every axis is ``Auto``: the ring's ``shard_map`` steps and the GSPMD
    paths' ``with_sharding_constraint`` both need sharding propagated by
    the compiler, which ``jax.make_mesh``'s default ``Explicit`` axes
    refuse. Raises when fewer devices exist than the mesh asks for.
    """
    devices = jax.devices()
    n = math.prod(shape)
    if len(devices) < n:
        raise ValueError(f"mesh {tuple(shape)} needs {n} devices, "
                         f"{len(devices)} present")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices[:n])


# -- hardware constants (TPU v5e target) ------------------------------------
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
CHIP_HBM_BYTES = 16 * (1 << 30)
