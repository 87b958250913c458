"""Pallas TPU kernel for the RG-LRU linear scan  h_t = a_t * h_{t-1} + b_t.

Tiling: grid = (B, D / BLOCK_D, S / BLOCK_S) with the time axis innermost
("arbitrary" semantics) so a per-(batch, feature-block) carry persists in
VMEM scratch across time blocks. Within a block the recurrence runs as a
vectorized associative scan over the (BLOCK_S, BLOCK_D) tile -- O(log S)
depth on the VPU -- and the carried state folds in as

    h_block = A_cum * h_carry + B_cum

where (A_cum, B_cum) is the blockwise prefix composition.

VMEM per grid step (BLOCK_S = 256, BLOCK_D = 512, f32):
  a tile + b tile + out tile = 3 * 256*512*4 = 1.5 MiB, carry 2 KiB --
  comfortably double-bufferable in v5e's ~16 MiB VMEM. BLOCK_D is a
  multiple of 128 (lane width).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret


DEFAULT_BLOCK_S = 256
DEFAULT_BLOCK_D = 512


def _rglru_kernel(a_ref, b_ref, out_ref, h_scratch):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    a = a_ref[0].astype(jnp.float32)  # (BS, BD)
    b = b_ref[0].astype(jnp.float32)

    def combine(prev, cur):
        a1, b1 = prev
        a2, b2 = cur
        return a1 * a2, a2 * b1 + b2

    A_cum, B_cum = jax.lax.associative_scan(combine, (a, b), axis=0)
    h = A_cum * h_scratch[...] + B_cum  # fold the carried state
    out_ref[0] = h.astype(out_ref.dtype)
    h_scratch[...] = h[-1:]


@functools.partial(jax.jit, static_argnames=("block_s", "block_d", "interpret"))
def rglru_scan_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_s: int = DEFAULT_BLOCK_S,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool | None = None,
) -> jax.Array:
    """a, b: (B, S, D); S % block_s == 0, D % block_d == 0."""
    B, S, D = a.shape
    if S % block_s or D % block_d:
        raise ValueError(f"S={S}, D={D} must tile by ({block_s}, {block_d})")
    grid = (B, D // block_d, S // block_s)

    def idx(bi, di, si):
        return (bi, si, di)

    return pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_d), idx),
            pl.BlockSpec((1, block_s, block_d), idx),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_d), idx),
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(a, b)
