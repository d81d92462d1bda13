"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state: callers decide when devices are materialized.

Target hardware: TPU v5e pods — 256 chips/pod arranged (16, 16) as
(data, model); the multi-pod mesh prepends a ``pod`` axis (2 pods = 512
chips).  Axis meanings:

  pod    cross-pod data parallelism (slow DCN/optical links; gradient
         all-reduce only, optionally int8-compressed)
  data   in-pod data parallelism + FSDP parameter sharding
  model  tensor/expert parallelism (fast ICI)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    In jax 0.9 mesh axes default to ``Explicit``, which rejects the
    ``P.UNCONSTRAINED`` entries of the activation pins in
    ``repro.distributed.sharding``; the models are written for GSPMD's
    automatic propagation."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(devices: int | None = None):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = devices or len(jax.devices())
    if n == 1:
        return make_mesh((1, 1), ("data", "model"))
    d = max(1, n // 2)
    return make_mesh((d, n // d), ("data", "model"))


# v5e hardware constants (roofline denominators).
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_LINK_BW = 50e9             # bytes/s per link
CHIPS_PER_POD = 256
HBM_PER_CHIP = 16 * 1024 ** 3
