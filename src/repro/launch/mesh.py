"""Production meshes (assignment spec).

Defined as functions so importing this module never touches JAX device
state — ``dryrun.py`` must set XLA_FLAGS before any mesh is built.
"""
from __future__ import annotations

import jax

SINGLE_POD = (16, 16)            # 256 chips / pod (TPU v5e)
MULTI_POD = (2, 16, 16)          # 2 pods = 512 chips

# v5e hardware constants for the roofline (assignment spec)
PEAK_FLOPS_BF16 = 197e12         # per chip
HBM_BW = 819e9                   # bytes/s per chip
ICI_BW = 50e9                    # bytes/s per link


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the GSPMD rules in
    ``repro.distributed`` place arrays by ``NamedSharding`` and constraints,
    which explicit-sharding axes (``make_mesh``'s default) reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int = 1):
    """Small mesh over however many devices exist (tests / CPU smoke)."""
    return auto_mesh((data, model), ("data", "model"))
