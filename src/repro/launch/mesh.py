"""Production meshes (TPU v5e). Single pod: 256 chips as (data=16, model=16);
multi-pod: 2 pods = 512 chips as (pod=2, data=16, model=16).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (smoke tests see 1 CPU device; only dryrun.py forces 512
host devices via XLA_FLAGS before any jax import)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: placement comes from the
    shardings the programs are given (GSPMD), as the sharding rules and the
    serving topology expect — ``make_mesh`` defaults to ``Explicit`` axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Mesh over the first ``data * model`` devices — the host devices of
    xla_force_host_platform_device_count in tests, the chips on a TPU host."""
    return auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12         # FLOP/s
HBM_BW = 819e9                   # bytes/s
ICI_BW = 50e9                    # bytes/s per link
