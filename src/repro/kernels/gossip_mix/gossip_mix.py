"""Pallas TPU kernel for the D-SGD gossip mixing step ``out = W @ theta``.

The mixing matrix ``W`` (n x n, n = node count, small) lives entirely in
VMEM; the parameter matrix ``theta`` (n, P) is tiled along the parameter
axis so each grid step streams one (n, BLOCK_P) tile HBM -> VMEM, performs a
tiny MXU matmul against W, and writes the mixed tile back.

VMEM budget per grid step (BLOCK_P = 2048, n <= 64, f32):
  theta tile  n * BLOCK_P * 4  <= 512 KiB
  out tile    n * BLOCK_P * 4  <= 512 KiB
  W           n * n * 4        <=  16 KiB          -- well under ~16 MiB VMEM.

The parameter axis is padded to a multiple of BLOCK_P by the ops.py wrapper
(lane dimension stays a multiple of 128 for the MXU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret

DEFAULT_BLOCK_P = 2048


def _gossip_kernel(w_ref, theta_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)
    x = theta_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.dot(
        w, x, preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def gossip_mix_pallas(
    theta: jax.Array,
    W: jax.Array,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool | None = None,
) -> jax.Array:
    """``out = W @ theta`` with theta (n, P), P a multiple of ``block_p``."""
    n, P = theta.shape
    if P % block_p != 0:
        raise ValueError(f"P={P} must be a multiple of block_p={block_p}")
    grid = (P // block_p,)
    return pl.pallas_call(
        _gossip_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda p: (0, 0)),  # W: whole matrix, reused
            pl.BlockSpec((n, block_p), lambda p: (0, p)),
        ],
        out_specs=pl.BlockSpec((n, block_p), lambda p: (0, p)),
        out_shape=jax.ShapeDtypeStruct((n, P), theta.dtype),
        interpret=resolve_interpret(interpret),
    )(W, theta)
