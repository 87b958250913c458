"""Device meshes (TPU v5e).

The mesh factories are FUNCTIONS (never module-level constants) so
importing this module never touches jax device state.

``make_device_mesh`` lays ``(data, model)`` over the devices present: the
launcher's mesh on one chip, four chips, or forced host devices.

Single-pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries
cross-pod D-SGD gossip (dsgd_pod mode) or plain cross-pod data parallelism.
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_device_mesh", "V5E"]


# TPU v5e hardware constants used by the roofline analysis.
V5E = {
    "peak_flops_bf16": 197e12,  # FLOP/s per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link (per direction, approx.)
    "hbm_bytes": 16 * 2**30,
    "chips_per_pod": 256,
}


def make_production_mesh(*, multi_pod: bool = False):
    """The deployment mesh: 16x16 single pod or 2x16x16 across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_device_mesh(data: int, model: int):
    """A ``(data, model)`` mesh over the ``data * model`` devices present."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
