"""Production mesh definitions.

A function (not a module-level constant) so importing this module never
touches jax device state.  The single-pod mesh is a TPU v5e-256 pod
(16 x 16); the multi-pod mesh stacks 2 pods (2 x 16 x 16 = 512 chips)
with the ``pod`` axis crossing the DCI/RDMA domain — exactly the link
layer RoCE BALBOA serves.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over real local devices (examples / tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def n_chips(mesh) -> int:
    return int(mesh.devices.size)
