"""Pallas TPU kernel for Birkhoff-schedule gossip mixing.

Computes ``out = sum_l coeffs[l] * theta[perms[l]]`` -- the D-SGD averaging
step executed in its sparse Birkhoff decomposition (L gather-AXPYs,
``O(L n P)``) instead of the dense ``W @ theta`` matmul (``O(n^2 P)``).
After ``l`` Frank-Wolfe iterations of STL-FW the learned ``W`` has at most
``l + 1`` atoms (Theorem 2), so for a budget-constrained topology this is
the natural *compute* format, not just the ppermute transport format.

Layout: the parameter axis is tiled in (n, BLOCK_P) blocks streamed
HBM -> VMEM; the (L, n) permutation table and (L,) coefficients ride the
scalar-prefetch path (SMEM) so the gather indices are available before the
tile body runs. Accumulation is f32 in a VMEM scratch tile regardless of
``theta.dtype``. A theta narrower than f32 is first widened into a second
f32 scratch tile: the row gather reads one row at a dynamic index, and
Mosaic slices packed (bf16) rows only at multiples of their sublane tiling.

VMEM per grid step, with the theta and out tiles double-buffered by the
pipeline (BLOCK_P = 2048, s = bytes per theta element):
  theta tiles  2 * n * BLOCK_P * s
  out tiles    2 * n * BLOCK_P * s
  acc tile     n * BLOCK_P * 4
  wide tile    n * BLOCK_P * 4   (only when s < 4)
That is 40 KiB per node in f32 and 32 KiB per node in bf16: 3.9 MiB and
3.1 MiB at n = 100, and about 16 MiB (a TPU v5e's default scoped VMEM
limit) only near n = 400 in f32 or n = 500 in bf16.

The wrapper in ops.py pads P to a multiple of BLOCK_P (or receives a
pre-padded single-buffer from ``repro.core.mixing.ravel_stack``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

DEFAULT_BLOCK_P = 2048


def _gossip_schedule_kernel(perm_ref, coeff_ref, theta_ref, out_ref, acc_ref, *wide):
    """One (n, BLOCK_P) tile: acc[i] = sum_l coeff[l] * theta[perm[l, i]]."""
    L, n = perm_ref.shape
    acc_ref[...] = jnp.zeros_like(acc_ref)
    if wide:
        (src_ref,) = wide
        src_ref[...] = theta_ref[...].astype(jnp.float32)
    else:
        src_ref = theta_ref

    def atom_body(l, _):
        gamma = coeff_ref[l].astype(jnp.float32)

        def row_body(i, _):
            src = perm_ref[l, i]
            row = src_ref[pl.ds(src, 1), :].astype(jnp.float32)
            acc_ref[pl.ds(i, 1), :] += gamma * row
            return 0

        return jax.lax.fori_loop(0, n, row_body, 0)

    jax.lax.fori_loop(0, L, atom_body, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def gossip_schedule_pallas(
    theta: jax.Array,
    coeffs: jax.Array,
    perms: jax.Array,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool | None = None,
) -> jax.Array:
    """``out = sum_l coeffs[l] theta[perms[l]]``, theta (n, P), P % block_p == 0.

    Args:
      theta: (n, P) stacked flat parameters.
      coeffs: (L,) float32 convex-combination coefficients.
      perms: (L, n) int32; ``perms[l, i] = j`` means node i receives node j's
        parameters in atom l.
    """
    n, P = theta.shape
    L = perms.shape[0]
    if perms.shape != (L, n):
        raise ValueError(f"perms must be (L, n), got {perms.shape} for n={n}")
    if coeffs.shape != (L,):
        raise ValueError(f"coeffs must be ({L},), got {coeffs.shape}")
    if P % block_p != 0:
        raise ValueError(f"P={P} must be a multiple of block_p={block_p}")
    grid = (P // block_p,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # perms + coeffs live in SMEM, prefetched
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_p), lambda p, *prefetch: (0, p)),
        ],
        out_specs=pl.BlockSpec((n, block_p), lambda p, *prefetch: (0, p)),
        # acc tile, plus the widened theta tile when theta is not f32
        scratch_shapes=[pltpu.VMEM((n, block_p), jnp.float32)]
        * (1 if theta.dtype == jnp.float32 else 2),
    )
    return pl.pallas_call(
        _gossip_schedule_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, P), theta.dtype),
        interpret=resolve_interpret(interpret),
    )(perms.astype(jnp.int32), coeffs.astype(jnp.float32), theta)
