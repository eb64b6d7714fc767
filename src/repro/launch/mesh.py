"""Production mesh construction.

Never touches jax device state at import time — everything is a function.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the "pod" axis
is the slow inter-pod fabric (the paper's PCIe analogue) and carries only
data-parallel gradient reduction (+ optional int8 compression, optim/).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the models place work with
    sharding constraints and leave the rest to the partitioner, so no axis
    may be ``Explicit`` (the type-level sharding ``jax.make_mesh`` defaults
    to, under which gathers and contractions over sharded dims must name
    their output sharding)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (axis names kept compatible)."""
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (assignment brief)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
DCN_BW = 6.25e9  # bytes/s per chip, inter-pod (modeled)
HBM_PER_CHIP = 16 * 1024**3  # v5e: 16 GiB
