"""Jitted public wrappers for the gossip mixing kernels.

Handles padding of the parameter axis to the kernel tile width, and the
dense-vs-schedule
dispatch: the dense matmul kernel is the right tool at ``L ~ n`` (an
unstructured W has up to n atoms), the schedule kernel at ``L << n``
(learned sparse topologies). ``gossip_apply`` picks automatically via the
``repro.core.mixing.preferred_transport`` cost model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gossip_mix import DEFAULT_BLOCK_P, gossip_mix_pallas
from .gossip_schedule import gossip_schedule_pallas
from .ref import gossip_mix_ref, gossip_schedule_ref

__all__ = ["gossip_mix", "gossip_schedule", "gossip_apply"]


@functools.partial(
    jax.jit, static_argnames=("block_p", "interpret", "use_ref")
)
def _gossip_mix_impl(theta, W, block_p, interpret, use_ref):
    if use_ref:
        return gossip_mix_ref(theta, W)
    n, P = theta.shape
    # Small parameter axes are cheaper as one einsum than one padded tile.
    if P < block_p:
        return gossip_mix_ref(theta, W)
    pad = (-P) % block_p
    if pad:
        theta_p = jnp.pad(theta, ((0, 0), (0, pad)))
    else:
        theta_p = theta
    out = gossip_mix_pallas(theta_p, W.astype(theta.dtype), block_p=block_p, interpret=interpret)
    return out[:, :P]


def gossip_mix(
    theta: jax.Array,
    W: jax.Array,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """Dense mixing ``out[i] = sum_j W[i, j] theta[j]`` for (n, P) theta.

    Pads the parameter axis to a multiple of ``block_p`` (the kernel's VMEM
    tile width), dispatches to the Pallas kernel, and strips the padding.
    ``interpret=None`` resolves by ``repro.kernels.default_interpret``.
    ``use_ref=True`` routes to the pure-jnp oracle (for A/B testing).
    """
    return _gossip_mix_impl(theta, W, block_p, interpret, use_ref)


@functools.partial(
    jax.jit, static_argnames=("block_p", "interpret", "use_ref", "pre_padded")
)
def _gossip_schedule_impl(theta, coeffs, perms, block_p, interpret, use_ref, pre_padded):
    if use_ref:
        return gossip_schedule_ref(theta, coeffs, perms)
    n, P = theta.shape
    if pre_padded:
        if P % block_p != 0:
            raise ValueError(
                f"pre_padded theta has P={P}, not a multiple of block_p={block_p}"
            )
        return gossip_schedule_pallas(
            theta, coeffs, perms, block_p=block_p, interpret=interpret
        )
    if P < block_p:
        return gossip_schedule_ref(theta, coeffs, perms)
    pad = (-P) % block_p
    theta_p = jnp.pad(theta, ((0, 0), (0, pad))) if pad else theta
    out = gossip_schedule_pallas(
        theta_p, coeffs, perms, block_p=block_p, interpret=interpret
    )
    return out[:, :P]


def gossip_schedule(
    theta: jax.Array,
    coeffs,
    perms,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool | None = None,
    use_ref: bool = False,
    pre_padded: bool = False,
) -> jax.Array:
    """Birkhoff mixing ``out = sum_l coeffs[l] theta[perms[l]]`` for (n, P) theta.

    ``pre_padded=True`` asserts the caller already padded P to a multiple of
    ``block_p`` (the single-buffer path pads once at flatten time via
    ``ravel_stack``) and skips the per-call pad/strip entirely.
    ``interpret=None`` resolves by ``repro.kernels.default_interpret``.
    """
    coeffs = jnp.asarray(coeffs, jnp.float32)
    perms = jnp.asarray(perms, jnp.int32)
    return _gossip_schedule_impl(
        theta, coeffs, perms, block_p, interpret, use_ref, pre_padded
    )


def gossip_apply(
    theta: jax.Array,
    W: jax.Array | None = None,
    schedule=None,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool | None = None,
) -> jax.Array:
    """Cost-model dispatch between the dense and schedule kernels.

    ``schedule`` is a ``repro.core.mixing.BirkhoffSchedule``. With both W and
    schedule available the ``preferred_transport`` model picks; with only one
    available that one runs.
    """
    from repro.core.mixing import preferred_transport

    if schedule is None and W is None:
        raise ValueError("gossip_apply needs W or schedule")
    if schedule is not None:
        n = theta.shape[0]
        # Unlike the XLA _mix_schedule_flat path, the Pallas kernel gathers
        # EVERY atom including identities, so all atoms count as cost here.
        choice = (
            "schedule"
            if W is None
            else preferred_transport(n, schedule.n_atoms)
        )
        if choice == "schedule":
            return gossip_schedule(
                theta,
                schedule.coeff_array(),
                schedule.perm_array(),
                block_p=block_p,
                interpret=interpret,
            )
    return gossip_mix(theta, W, block_p=block_p, interpret=interpret)
