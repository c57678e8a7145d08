"""Production meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — only ``dryrun.py`` forces the
512-device host platform.

Every axis is ``AxisType.Auto``: the sharding rules in
``models/sharding.py`` leave it to GSPMD to insert the collectives a
contraction over a sharded dimension needs.  (``jax.make_mesh`` defaults
to ``Explicit`` axes, under which such a matmul is a type error.)
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 1):
    """Whatever devices exist, as a (data, model) mesh — for CPU tests."""
    n = min(n_devices, len(jax.devices()))
    return make_mesh((n, 1), ("data", "model"))


# TPU v5e hardware constants (roofline denominators; see EXPERIMENTS.md).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
HBM_PER_CHIP = 16 * 1024**3   # bytes
