"""Jitted public wrapper for the RG-LRU scan kernel (padding + dispatch)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ref import rglru_scan_ref
from .rglru_scan import DEFAULT_BLOCK_D, DEFAULT_BLOCK_S, rglru_scan_pallas

__all__ = ["rglru_scan"]


@functools.partial(jax.jit, static_argnames=("block_s", "block_d", "interpret", "use_ref"))
def rglru_scan(
    a: jax.Array,
    b: jax.Array,
    *,
    block_s: int = DEFAULT_BLOCK_S,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """Linear recurrence h_t = a_t h_{t-1} + b_t over axis 1 of (B, S, D).

    Pads S and D to the kernel tiles and strips the padding. Padded time
    steps use a = 1, b = 0 (identity recurrence -> no effect on real steps:
    the pad sits at the END of the sequence); padded feature lanes are junk
    and sliced off.
    """
    if use_ref:
        return rglru_scan_ref(a, b)
    B, S, D = a.shape
    if S < block_s:  # tiny sequences: the tiled kernel is pure overhead
        return rglru_scan_ref(a, b)
    pad_s = (-S) % block_s
    pad_d = (-D) % block_d
    if pad_s or pad_d:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_d)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_d)))
    out = rglru_scan_pallas(a, b, block_s=block_s, block_d=block_d, interpret=interpret)
    return out[:, :S, :D]
