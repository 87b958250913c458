"""Pallas TPU kernels for the system's compute hot spots.

Each kernel ships three files:
  <name>.py -- pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    -- jitted public wrapper (padding, dispatch, fallbacks)
  ref.py    -- pure-jnp oracle used by the allclose test suites

Kernels are validated on CPU in interpret mode (``default_interpret``: the
interpreter runs on the ``cpu`` backend only); block shapes are chosen for
TPU v5e (BQ/BKV multiples of 128 for the MXU, working sets << 16 MiB VMEM).
"""

from . import flash_attention, gossip_mix, rglru_scan
from .interpret import default_interpret

__all__ = ["default_interpret", "flash_attention", "gossip_mix", "rglru_scan"]
