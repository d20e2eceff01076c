"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the pod axis is
pure data parallelism over DCN; gradients cross pods once per step.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count *before* first jax use.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "HW"]


class HW:
    """TPU v5e-class hardware constants used by the roofline analysis."""

    PEAK_FLOPS_BF16 = 197e12        # per chip
    HBM_BW = 819e9                  # bytes/s per chip
    ICI_BW = 50e9                   # bytes/s per link (intra-pod)
    DCN_BW = 6.25e9                 # bytes/s per host (inter-pod, 50 Gb/s)
    HBM_BYTES = 16 * (1 << 30)      # 16 GiB per chip
    VMEM_BYTES = 128 * (1 << 20)    # ~128 MiB vector memory


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (smoke tests use small shapes on 1 device).  Axes are
    ``Auto``: the models place data with ``with_sharding_constraint`` and
    let GSPMD propagate, which JAX's default Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
