"""Gossip-mixing executions of a doubly-stochastic matrix W, in JAX.

Four interchangeable transports for the D-SGD averaging step
``Theta <- Theta W^T`` (i.e. ``theta_i <- sum_j W_ij theta_j``):

1. ``mix_dense``            -- stacked einsum over a leading node axis,
                               optionally through the Pallas ``gossip_mix``
                               matmul kernel. Cost ``O(n^2 P)`` MACs.
2. ``mix_schedule_stacked`` -- Birkhoff-decomposed *compute* format: after
                               ``l`` Frank-Wolfe steps the learned ``W`` is a
                               convex combination of at most ``l+1``
                               permutation atoms (Theorem 2), so the product
                               ``Theta W^T`` collapses to ``L`` row-gathers +
                               AXPYs: ``out = sum_l gamma_l theta[perm_l]``.
                               Cost ``O(L n P)`` with ``L << n``. For eager
                               callers and steady-state flat buffers, the
                               single-buffer path (``ravel_stack``) flattens
                               the whole pytree into one contiguous (n, P)
                               array so mixing is ONE dispatch per step
                               instead of one per leaf, optionally through
                               the Pallas ``gossip_schedule`` kernel; inside
                               jit the per-leaf default fuses copy-free.
3. ``mix_ppermute``         -- the same Birkhoff schedule as
                               ``jax.lax.ppermute`` collectives, for use
                               *inside* ``shard_map`` where each mesh index
                               along ``axis_name`` holds one node's
                               parameters. The TPU-native transport: d_max
                               atoms cost exactly d_max collective-permutes.
4. ``mix_allreduce``        -- ``W = 11^T/n`` (C-PSGD baseline) via
                               ``lax.pmean``.

Which transport when
--------------------

=====================  =====================  ===============================
Situation              Transport              Why
=====================  =====================  ===============================
single-host simulator, ``mix_schedule_        L gathers + AXPYs beat the
learned/sparse W       stacked``              n x n matmul when L <~ n/4;
(L atoms, L << n)                             single-buffer = 1 dispatch/step
single-host simulator, ``mix_dense``          matmul is optimal at L ~ n
dense or unstructured                         (Sinkhorn W, complete graph);
W                                             MXU-friendly
device mesh, one node  ``mix_ppermute``       moves only d_max permutes of
per mesh index                                bytes; no (n, P) materialize
device mesh, complete  ``mix_allreduce``      all-reduce hardware path
graph (C-PSGD)
=====================  =====================  ===============================

``mix_stacked`` picks between (1) and (2) automatically: a measured
autotune table first (``autotune_transport`` -- per-(n, L, P)-bucket
timings memoized to experiments/bench/transport_autotune.json, written
explicitly via ``transport="autotune"``), falling back to the closed
form ``preferred_transport`` -- the cost model ``L <= n / dense_speedup``
(gather AXPYs are memory-bound at ~L reads/element; the dense matmul
amortizes to ~n MACs/element but runs at matmul throughput, worth
``dense_speedup ~ 4x`` on CPU BLAS -- a calibrated, overridable
parameter, see ``preferred_transport`` and docs/architecture.md). All
transports act on arbitrary parameter pytrees.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BirkhoffSchedule",
    "ScheduleArrays",
    "schedule_to_arrays",
    "arrays_to_matrix",
    "truncate_schedule",
    "degrade_schedule",
    "StaleBuffer",
    "stale_buffer_init",
    "stale_push",
    "stale_view",
    "mix_schedule_arrays_stale",
    "StragglerPolicy",
    "straggler_stream",
    "straggler_pool_stream",
    "degrade_pool_gammas",
    "WireCorruption",
    "corrupt_wire",
    "ScreenStats",
    "mix_schedule_arrays_screened",
    "ShardStaleState",
    "shard_stale_init",
    "shard_stale_push",
    "mix_arrays_sharded_stale",
    "mix_ppermute_pool_stale",
    "mix_schedule_arrays",
    "mix_dense_sharded",
    "PermPool",
    "PoolSwap",
    "mix_ppermute_pool",
    "mix_arrays_sharded",
    "preferred_sharded_transport",
    "autotune_sharded_transport",
    "measure_sharded_transport",
    "StackRavelSpec",
    "ravel_stack",
    "unravel_stack",
    "preferred_transport",
    "autotune_transport",
    "measure_transport",
    "transport_autotune_path",
    "mix_dense",
    "mix_schedule_stacked",
    "mix_stacked",
    "mix_ppermute",
    "mix_allreduce",
    "schedule_from_result",
    "schedule_from_matrix",
]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class BirkhoffSchedule:
    """A mixing matrix as a convex combination of permutations.

    ``coeffs[l]`` weights atom ``l``; ``perms[l][i] = j`` means node ``i``
    receives node ``j``'s parameters in atom ``l`` (i.e. ``P_l[i, j] = 1``,
    so ``W = sum_l coeffs[l] P_l``). Atom arrays are static python tuples so
    the schedule is hashable and can close over a jitted step function.
    """

    coeffs: tuple[float, ...]
    perms: tuple[tuple[int, ...], ...]

    @property
    def n_nodes(self) -> int:
        return len(self.perms[0])

    @property
    def n_atoms(self) -> int:
        return len(self.coeffs)

    @property
    def n_communication_atoms(self) -> int:
        """Atoms that move data (non-identity permutations)."""
        return sum(1 for p in self.perms if tuple(p) != tuple(range(len(p))))

    def identity_weight(self) -> float:
        """Total coefficient mass on identity atoms (a local scale, no I/O)."""
        ident = tuple(range(self.n_nodes))
        return sum(c for c, p in zip(self.coeffs, self.perms) if tuple(p) == ident)

    def communication_atoms(self) -> list[tuple[float, tuple[int, ...]]]:
        """(gamma, perm) pairs for the non-identity atoms."""
        ident = tuple(range(self.n_nodes))
        return [
            (float(c), tuple(p))
            for c, p in zip(self.coeffs, self.perms)
            if tuple(p) != ident
        ]

    def perm_array(self) -> np.ndarray:
        """All atoms as an (L, n) int32 index array (kernel input format)."""
        return np.asarray(self.perms, dtype=np.int32).reshape(self.n_atoms, self.n_nodes)

    def coeff_array(self) -> np.ndarray:
        return np.asarray(self.coeffs, dtype=np.float32)

    def to_matrix(self) -> np.ndarray:
        n = self.n_nodes
        W = np.zeros((n, n))
        for c, perm in zip(self.coeffs, self.perms):
            W[np.arange(n), list(perm)] += c
        return W


def schedule_from_result(result) -> BirkhoffSchedule:
    """Build a schedule from an ``STLFWResult`` (drops zero-weight atoms)."""
    coeffs, perms = [], []
    for c, perm in result.active_atoms():
        coeffs.append(float(c))
        perms.append(tuple(int(x) for x in perm))
    return BirkhoffSchedule(coeffs=tuple(coeffs), perms=tuple(perms))


def schedule_from_matrix(W: np.ndarray, max_atoms: int | None = None, tol: float = 1e-9) -> BirkhoffSchedule:
    """Greedy Birkhoff-von-Neumann decomposition of an arbitrary doubly-
    stochastic matrix (used for baseline topologies like rings/regular
    graphs so they can ride the same ppermute transport).

    Repeatedly extracts the permutation supported on the largest entries via
    a max-weight assignment, removing ``min`` of its entries each time.
    """
    from .assignment import linear_assignment

    W = np.asarray(W, dtype=np.float64).copy()
    n = W.shape[0]
    coeffs: list[float] = []
    perms: list[tuple[int, ...]] = []
    remaining = W.copy()
    limit = max_atoms if max_atoms is not None else n * n
    for _ in range(limit):
        total = remaining.sum()
        if total <= tol * n:
            break
        # max-weight perfect matching on the remaining mass: forbid zeros.
        cost = np.where(remaining > tol, -remaining, 1e6)
        perm = linear_assignment(cost)
        vals = remaining[np.arange(n), perm]
        if np.any(vals <= tol):
            break
        gamma = float(vals.min())
        coeffs.append(gamma)
        perms.append(tuple(int(x) for x in perm))
        remaining[np.arange(n), perm] -= gamma
    if not coeffs:
        coeffs, perms = [1.0], [tuple(range(n))]
    # Renormalize tiny residual mass into the coefficients.
    s = sum(coeffs)
    coeffs = [c / s for c in coeffs]
    return BirkhoffSchedule(coeffs=tuple(coeffs), perms=tuple(perms))


# ---------------------------------------------------------------------------
# Data-plane schedule format (online topology adaptation)
# ---------------------------------------------------------------------------
#
# ``BirkhoffSchedule`` is deliberately *static*: its atoms are python
# tuples a jitted step function closes over, which is what lets XLA fold
# identity atoms into a free scale and constant-fold the gather indices.
# The flip side is that swapping W mid-run changes the closure and
# RETRACES every compiled rollout -- unacceptable for online topology
# adaptation, where a refresh controller replaces W while a scanned
# trainer is running. ``ScheduleArrays`` is the data-plane twin: the
# same Birkhoff decomposition as two fixed-shape arrays (coefficients
# and a permutation table, padded to a fixed atom capacity ``l_max``
# with zero-weight identity atoms) that travel through jit/scan carries
# as ordinary operands. Two schedules with the same ``(l_max, n)`` are
# interchangeable values of ONE compiled computation: a hot swap is a
# buffer update, never a retrace (asserted in tests/test_online.py and
# the CI smoke tier via benchmarks/bench_online.py).


class ScheduleArrays(NamedTuple):
    """A Birkhoff schedule as data: ``W = sum_l gammas[l] P_{perms[l]}``.

    Attributes:
      gammas: (l_max,) float32 convex coefficients (sum to 1; padding
        atoms carry exactly 0).
      perms: (l_max, n) int32 permutation table, ``perms[l, i] = j``
        meaning node ``i`` receives node ``j``'s parameters in atom
        ``l``; padding rows are the identity permutation.

    A NamedTuple of two arrays is natively a pytree, so a
    ``ScheduleArrays`` can sit in a ``lax.scan`` carry, be donated, or
    be passed straight through ``jax.jit`` -- the compiled trace is
    keyed on shapes only, which is the whole point.
    """

    gammas: jax.Array
    perms: jax.Array

    @property
    def l_max(self) -> int:
        return self.perms.shape[0]

    @property
    def n_nodes(self) -> int:
        return self.perms.shape[1]


def schedule_to_arrays(
    schedule: BirkhoffSchedule, l_max: int | None = None
) -> ScheduleArrays:
    """Pad a static schedule into the fixed-shape data-plane format.

    ``l_max`` fixes the atom capacity; every refresh must pad to the
    SAME ``l_max`` or the hot swap stops being shape-stable (and
    retraces). Padding atoms are identity permutations with coefficient
    0 -- they gather and add exact zeros, so the mixed result is
    bitwise what the unpadded schedule produces.
    """
    L = schedule.n_atoms
    n = schedule.n_nodes
    if l_max is None:
        l_max = L
    if L > l_max:
        raise ValueError(
            f"schedule has {L} atoms > l_max={l_max}; truncate first "
            "(see truncate_schedule)"
        )
    gammas = np.zeros((l_max,), np.float32)
    perms = np.tile(np.arange(n, dtype=np.int32), (l_max, 1))
    gammas[:L] = schedule.coeff_array()
    if L:
        perms[:L] = schedule.perm_array()
    return ScheduleArrays(gammas=jnp.asarray(gammas), perms=jnp.asarray(perms))


def arrays_to_matrix(arrays: ScheduleArrays) -> np.ndarray:
    """Densify a data-plane schedule (host-side, for validation/analysis)."""
    gammas = np.asarray(arrays.gammas, np.float64)
    perms = np.asarray(arrays.perms)
    n = perms.shape[1]
    W = np.zeros((n, n))
    rows = np.arange(n)
    for g, perm in zip(gammas, perms):
        W[rows, perm] += g
    return W


def truncate_schedule(schedule: BirkhoffSchedule, l_max: int) -> BirkhoffSchedule:
    """Keep the ``l_max`` largest-coefficient atoms and renormalize.

    A renormalized sub-combination of permutation atoms is still doubly
    stochastic, so the truncated W stays a valid mixing matrix; what is
    lost is a small amount of mixing mass (bounded by the dropped
    coefficients' sum). Online refreshes use this to keep the schedule's
    atom count -- and hence the data-plane capacity and per-step
    communication degree -- fixed across refreshes.
    """
    if l_max < 1:
        raise ValueError("l_max must be >= 1")
    if schedule.n_atoms <= l_max:
        return schedule
    order = np.argsort(np.asarray(schedule.coeffs))[::-1][:l_max]
    order = np.sort(order)  # keep original atom order (identity first)
    coeffs = [schedule.coeffs[i] for i in order]
    total = sum(coeffs)
    if total <= 0.0:
        raise ValueError("truncate_schedule: kept atoms carry no mass")
    return BirkhoffSchedule(
        coeffs=tuple(c / total for c in coeffs),
        perms=tuple(schedule.perms[i] for i in order),
    )


def _mix_arrays_flat(flat: jax.Array, arrays: ScheduleArrays) -> jax.Array:
    """``out = sum_l gammas[l] flat[perms[l]]`` with traced gammas/perms.

    A ``lax.scan`` over the atom axis keeps the HLO size O(1) in
    ``l_max`` (the static schedule path unrolls instead, which is fine
    because identity atoms constant-fold there; here every atom is a
    runtime value, including the zero-weight padding, whose gathers
    contribute exact zeros).
    """
    if flat.shape[0] != arrays.n_nodes:
        raise ValueError(
            f"schedule arrays are for {arrays.n_nodes} nodes but the stacked "
            f"parameters have leading axis {flat.shape[0]}"
        )

    def body(acc, gp):
        g, perm = gp
        return acc + g.astype(flat.dtype) * jnp.take(flat, perm, axis=0), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros_like(flat), (arrays.gammas, arrays.perms)
    )
    return acc


def mix_schedule_arrays(
    params_stack: PyTree,
    arrays: ScheduleArrays,
    *,
    single_buffer: bool = False,
    use_kernel: bool = False,
    block_p: int | None = None,
    corrupt: "WireCorruption | None" = None,
) -> PyTree:
    """Data-plane Birkhoff mixing: ``l_max`` gathers + AXPYs, schedule as
    runtime arrays (the online hot-swap transport).

    Semantics match :func:`mix_schedule_stacked` on the equivalent
    static schedule; cost is ``O(l_max n P)`` (padding atoms are not
    free here -- choose ``l_max`` as the actual communication budget).
    ``use_kernel`` routes through the Pallas ``gossip_schedule`` kernel
    (implies single_buffer) -- its coefficient/permutation operands are
    ordinary arrays, so the kernel path hot-swaps as freely as the XLA
    one.

    ``corrupt`` (a :class:`WireCorruption`) poisons each sender's
    outgoing payload at the wire; ``None`` routes to the untouched
    transport at trace time, so corruption-off arms are trivially
    bitwise. Self-loops move no bytes and stay clean.
    """
    if corrupt is not None:
        if use_kernel:
            raise ValueError(
                "corrupt is not supported on the kernel path: corrupt the "
                "flat wire buffer before the kernel call instead"
            )
        if single_buffer:
            flat, spec = ravel_stack(params_stack, pad_to=block_p)
            flat = jax.lax.optimization_barrier(flat)
            return unravel_stack(
                _mix_arrays_flat_corrupt(flat, arrays, corrupt), spec
            )
        return jax.tree_util.tree_map(
            lambda x: _mix_arrays_flat_corrupt(
                x.reshape(x.shape[0], -1), arrays, corrupt
            ).reshape(x.shape),
            params_stack,
        )
    if use_kernel:
        from repro.kernels.gossip_mix import ops as gossip_ops
        from repro.kernels.gossip_mix.gossip_schedule import DEFAULT_BLOCK_P

        pad_to = block_p or DEFAULT_BLOCK_P
        flat, spec = ravel_stack(params_stack, pad_to=pad_to)
        mixed = gossip_ops.gossip_schedule(
            flat,
            arrays.gammas,
            arrays.perms,
            block_p=pad_to,
            pre_padded=True,
        )
        return unravel_stack(mixed, spec)
    if single_buffer:
        flat, spec = ravel_stack(params_stack, pad_to=block_p)
        flat = jax.lax.optimization_barrier(flat)
        return unravel_stack(_mix_arrays_flat(flat, arrays), spec)
    return jax.tree_util.tree_map(
        lambda x: _mix_arrays_flat(x.reshape(x.shape[0], -1), arrays).reshape(x.shape),
        params_stack,
    )


# ---------------------------------------------------------------------------
# Degraded mixing: fault repair on the data-plane schedule
# ---------------------------------------------------------------------------
#
# A crash or a dropped gossip edge invalidates some of the transfers a
# Birkhoff atom prescribes. Zeroing the broken entries of W would break
# double stochasticity (the lost mass has to go somewhere, and a naive
# per-entry self-loop redirect fixes the row sum while corrupting the
# column sum). The repair below works at the PERMUTATION level instead:
# every cycle of an atom that touches a broken transfer is collapsed to
# fixed points (each node in the cycle keeps its own parameters). A
# permutation with some cycles replaced by fixed points is still an
# exact permutation, so each repaired atom is exactly doubly stochastic
# and the convex combination W' = sum_l gammas[l] P'_l is too -- to
# machine precision, with the coefficients UNCHANGED (the same
# convex-combination argument as ``PermPool.project``, without even
# needing the renormalization). A dead node ends up a fixed point of
# every atom, so its row and column of W' are exactly ``e_i``: it
# neither receives nor contributes until it rejoins.
#
# Because the repair only rewrites the ``perms`` table values (same
# shapes), a degraded schedule is an ordinary ``ScheduleArrays`` value:
# hot-swapping it into a compiled rollout is a pure value change --
# zero retraces, the PR 4/5 idiom (asserted in tests/test_faults.py).


def _repair_perm(perm: np.ndarray, broken: np.ndarray) -> np.ndarray:
    """Collapse every cycle of ``perm`` containing a broken position.

    ``broken[i]`` marks the transfer into position ``i`` (i.e. the edge
    ``perm[i] -> i``) as undeliverable. Cycle-granular repair keeps the
    result an exact permutation: partial cycles cannot be patched
    entry-wise without double-assigning some source.
    """
    n = perm.shape[0]
    out = perm.copy()
    visited = np.zeros(n, bool)
    for start in range(n):
        if visited[start]:
            continue
        cycle = []
        i = start
        bad = False
        while not visited[i]:
            visited[i] = True
            cycle.append(i)
            bad = bad or bool(broken[i])
            i = perm[i]
        if bad:
            idx = np.asarray(cycle)
            out[idx] = idx
    return out


def degrade_schedule(
    arrays: ScheduleArrays,
    alive_mask: np.ndarray,
    dropped_edges=(),
) -> ScheduleArrays:
    """Repair a data-plane schedule on the surviving nodes/edges.

    Args:
      arrays: the fault-free schedule (``W = sum_l gammas[l] P_l``).
      alive_mask: (n,) bool; ``False`` marks a crashed node.
      dropped_edges: iterable of ``(src, dst)`` pairs (or an (m, 2)
        array) -- node ``dst`` fails to receive node ``src``'s
        parameters this step. Self-loops never appear here (they move
        no bytes and cannot drop).

    Returns a ``ScheduleArrays`` with the SAME gammas and shape whose
    atoms are repaired permutations (see :func:`_repair_perm`): exactly
    doubly stochastic, dead nodes isolated to ``e_i``, lost atom mass
    redirected to self-loops. Swapping it into a compiled rollout is a
    pure value change (zero retraces). Host-side numpy -- faults are
    exogenous control-plane events, like the topology refreshes.
    """
    perms = np.asarray(arrays.perms)
    l_max, n = perms.shape
    alive = np.asarray(alive_mask, dtype=bool).reshape(n)
    drop = np.zeros((n, n), dtype=bool)
    edges = np.asarray(list(dropped_edges) if not isinstance(dropped_edges, np.ndarray) else dropped_edges)
    if edges.size:
        edges = edges.reshape(-1, 2).astype(np.int64)
        if edges.min() < 0 or edges.max() >= n:
            raise ValueError(f"dropped edge index out of range for n={n}")
        drop[edges[:, 0], edges[:, 1]] = True
    rows = np.arange(n)
    out = perms.copy()
    for l in range(l_max):
        p = perms[l]
        nonself = p != rows
        broken = nonself & (~alive | ~alive[p] | drop[p, rows])
        if broken.any():
            out[l] = _repair_perm(p, broken)
    return ScheduleArrays(
        gammas=jnp.asarray(np.asarray(arrays.gammas)),
        perms=jnp.asarray(out, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Stale-theta mixing: bounded-delay stragglers via a ring buffer
# ---------------------------------------------------------------------------
#
# The bounded-delay straggler model: node j's parameters reach the
# mixing step with staleness tau_j^t <= tau_max, i.e.
# ``theta_i <- sum_j W_ij theta_j^{t + 1/2 - tau_j^t}`` (source-indexed
# delay: a straggler is late everywhere at once). The ring buffer keeps
# the last ``depth = tau_max + 1`` half-step states in the scan carry
# -- fixed shape (depth, n, P) -- and the per-step delay vector rides
# as scan data, so a delay change (a straggler appearing or catching
# up) is a pure value change into the compiled rollout. With all
# delays 0 the buffer read returns the value just pushed, and
# ``mix_schedule_arrays_stale`` reduces BITWISE to
# :func:`_mix_arrays_flat` on the current state (asserted in
# tests/test_faults.py) -- the fault-free trajectory is the zero-delay
# special case, not a separate code path.


class StaleBuffer(NamedTuple):
    """Ring buffer of the last ``depth`` (n, P) half-step states.

    ``head`` indexes the most recent push; slot ``(head - d) % depth``
    holds the state from ``d`` pushes ago. A NamedTuple of two arrays,
    so it rides a ``lax.scan`` carry like ``ScheduleArrays`` does.
    """

    buf: jax.Array  # (depth, n, P)
    head: jax.Array  # () int32

    @property
    def depth(self) -> int:
        return self.buf.shape[0]


def stale_buffer_init(flat: jax.Array, depth: int) -> StaleBuffer:
    """Fill all ``depth`` slots with ``flat`` (so a delay larger than the
    number of pushes so far reads the initial state, never garbage)."""
    if depth < 1:
        raise ValueError(f"depth must be >= 1 (tau_max + 1), got {depth}")
    if flat.ndim != 2:
        raise ValueError(f"flat must be (n, P), got shape {flat.shape}")
    buf = jnp.tile(flat[None], (depth, 1, 1))
    return StaleBuffer(buf=buf, head=jnp.zeros((), jnp.int32))


def stale_push(buffer: StaleBuffer, flat: jax.Array) -> StaleBuffer:
    """Advance the ring: write ``flat`` into the next slot."""
    depth = buffer.buf.shape[0]
    head = jax.lax.rem(buffer.head + 1, jnp.asarray(depth, buffer.head.dtype))
    buf = jax.lax.dynamic_update_index_in_dim(buffer.buf, flat, head, axis=0)
    return StaleBuffer(buf=buf, head=head)


def stale_view(buffer: StaleBuffer, delays: jax.Array) -> jax.Array:
    """Per-source delayed read: row ``j`` of the result is node ``j``'s
    state from ``delays[j]`` pushes ago (``delays`` (n,) int, values in
    [0, depth); larger values alias modulo the ring depth -- size the
    buffer with ``depth = tau_max + 1``)."""
    depth = buffer.buf.shape[0]
    n = buffer.buf.shape[1]
    slot = jnp.mod(buffer.head - delays, depth)
    return buffer.buf[slot, jnp.arange(n)]


def mix_schedule_arrays_stale(
    buffer: StaleBuffer,
    arrays: ScheduleArrays,
    delays: jax.Array,
    corrupt: "WireCorruption | None" = None,
) -> jax.Array:
    """Bounded-delay data-plane mixing on the flat (n, P) convention.

    ``out = sum_l gammas[l] theta_stale[perms[l]]`` where
    ``theta_stale`` is the delayed view of the ring buffer. Accumulation
    order matches :func:`_mix_arrays_flat` op-for-op, so zero delays
    reproduce the fault-free mixing bitwise. ``corrupt`` poisons each
    sender's delivered payload at the wire (a node corrupt at step t
    poisons everything it delivers at t, buffered re-sends included;
    self-loops stay clean); ``None`` is the untouched transport.
    """
    view = stale_view(buffer, delays)
    if corrupt is not None:
        return _mix_arrays_flat_corrupt(view, arrays, corrupt)
    return _mix_arrays_flat(view, arrays)


# ---------------------------------------------------------------------------
# Straggler policy: wait vs deadline-based graceful degradation
# ---------------------------------------------------------------------------
#
# The ring buffer above implements the MECHANISM of bounded-delay
# mixing; the policy below decides, per node per step, what a delay
# MEANS. Under ``wait`` every late payload is consumed at its (clamped)
# staleness -- the unified bounded-delay model of Koloskova et al.,
# where convergence survives any tau <= tau_max. Under ``degrade`` a
# delay past the deadline is treated as an outage for that one step:
# the schedule is repaired on the on-time support (same cycle-collapse
# as :func:`degrade_schedule`, so W stays EXACTLY doubly stochastic)
# and the late node keeps its own parameters -- graceful degradation
# instead of a barrier stall. Both arms are host-side control-plane
# decisions: what reaches the compiled rollout is a repaired
# ``ScheduleArrays`` value plus an effective int32 delay vector, both
# ordinary scan data, so switching policies (or a straggler appearing)
# never retraces.


@dataclasses.dataclass(frozen=True)
class StragglerPolicy:
    """Deadline policy for bounded-delay gossip (frozen/hashable).

    Attributes:
      mode: ``"wait"`` consumes every payload at its staleness, clamped
        to ``tau_max`` (the ring depth bounds how far back a view can
        reach); ``"degrade"`` treats any delay PAST ``tau_max`` as an
        offline node for that step and repairs the schedule on the
        on-time support.
      tau_max: the staleness deadline. The ring buffer consuming this
        policy must have ``depth == ring_depth == tau_max + 1``.
    """

    mode: str = "wait"
    tau_max: int = 1

    def __post_init__(self):
        if self.mode not in ("wait", "degrade"):
            raise ValueError(
                f"StragglerPolicy mode must be 'wait' or 'degrade', "
                f"got {self.mode!r}"
            )
        if self.tau_max < 0:
            raise ValueError(f"tau_max must be >= 0, got {self.tau_max}")

    @property
    def ring_depth(self) -> int:
        return self.tau_max + 1

    def apply(
        self,
        arrays: ScheduleArrays,
        delays,
        alive_mask=None,
        dropped_edges=(),
    ) -> tuple[ScheduleArrays, np.ndarray]:
        """Resolve one step's raw delay vector against the deadline.

        Returns ``(arrays', eff_delays)``: the (possibly repaired)
        schedule to mix with and the effective (n,) int32 delay vector
        to read the ring at. Host-side numpy -- faults and deadlines
        are exogenous control-plane events, like topology refreshes.
        Composes with crash faults: ``alive_mask``/``dropped_edges``
        are folded into the SAME single repair, and offline nodes
        always get effective delay 0 (the alive mask governs them, not
        staleness).
        """
        delays = np.asarray(delays, np.int64).reshape(-1)
        n = delays.shape[0]
        if arrays.n_nodes != n:
            raise ValueError(
                f"delays are for {n} nodes, schedule for {arrays.n_nodes}"
            )
        if delays.min() < 0:
            raise ValueError("delays must be non-negative")
        alive = (
            np.ones(n, bool)
            if alive_mask is None
            else np.asarray(alive_mask, bool).reshape(n)
        )
        if self.mode == "wait":
            eff = np.minimum(delays, self.tau_max)
            mask = alive
        else:
            late = delays > self.tau_max
            eff = np.where(late, 0, delays)
            mask = alive & ~late
        eff = np.where(alive, eff, 0).astype(np.int32)
        edges = np.asarray(
            dropped_edges
            if isinstance(dropped_edges, np.ndarray)
            else list(dropped_edges)
        )
        if not mask.all() or edges.size:
            arrays = degrade_schedule(arrays, mask, edges)
        return arrays, eff


def straggler_stream(
    policy: StragglerPolicy,
    arrays: ScheduleArrays,
    delays,
    alive=None,
    edges_at=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Resolve a (T, n) raw delay trace into stacked per-step scan xs.

    Returns ``(gammas (T, l_max), perms (T, l_max, n), eff (T, n))`` --
    the exact xs a scanned stale rollout consumes (one schedule value
    and one delay vector per step, all data). ``alive`` is an optional
    (T, n) bool mask and ``edges_at(t)`` an optional per-step dropped-
    edge callback, both folded into each step's single repair.
    """
    delays = np.asarray(delays, np.int64)
    if delays.ndim != 2:
        raise ValueError(f"delays must be (T, n), got shape {delays.shape}")
    T = delays.shape[0]
    g_rows, p_rows, d_rows = [], [], []
    for t in range(T):
        a_t = None if alive is None else np.asarray(alive)[t]
        e_t = () if edges_at is None else edges_at(t)
        sa, eff = policy.apply(
            arrays, delays[t], alive_mask=a_t, dropped_edges=e_t
        )
        g_rows.append(np.asarray(sa.gammas, np.float32))
        p_rows.append(np.asarray(sa.perms, np.int32))
        d_rows.append(eff)
    return (
        jnp.asarray(np.stack(g_rows)),
        jnp.asarray(np.stack(p_rows)),
        jnp.asarray(np.stack(d_rows)),
    )


def degrade_pool_gammas(pool: "PermPool", gammas, offline_mask) -> np.ndarray:
    """Repair pool-coordinate mixing when some nodes are offline/late.

    The pool transport cannot rewrite its (compiled-in) permutation
    slots, so the repair is coarser than :func:`degrade_schedule`'s
    cycle collapse: every non-identity slot that moves data to or from
    an offline node is zeroed and its coefficient mass moved to an
    identity slot. The result is still an exact convex combination of
    permutations (doubly stochastic to machine precision) in which
    every offline node is a fixed point of every surviving atom -- the
    same isolation guarantee, paid for with more lost mixing mass.
    Host-side numpy; the returned (capacity,) float32 vector is a pure
    gamma value change (zero retraces).
    """
    g = np.asarray(gammas, np.float64).copy()
    if g.shape != (pool.capacity,):
        raise ValueError(
            f"gammas must be ({pool.capacity},), got {g.shape}"
        )
    off = np.asarray(offline_mask, bool).reshape(pool.n_nodes)
    if not off.any():
        return g.astype(np.float32)
    ident = pool.identity
    moved = 0.0
    for l, p in enumerate(pool.perms):
        if p == ident:
            continue
        touches = any(
            p[i] != i and (off[i] or off[p[i]]) for i in range(pool.n_nodes)
        )
        if touches:
            moved += g[l]
            g[l] = 0.0
    # the identity slot is only needed when there is mass to absorb: a
    # pool whose staged atoms all survive (e.g. every offline node was
    # already a fixed point of every slot) repairs to itself. The moved
    # mass is ADDED to the identity coefficient, never renormalized --
    # the total stays exactly the input's, so a node whose every
    # neighbor slot was zeroed ends up with its full row mass on the
    # identity atom: row exactly e_i, no empty-mass division anywhere.
    if moved != 0.0:
        try:
            id_slot = pool.perms.index(ident)
        except ValueError:
            raise ValueError(
                "degrade_pool_gammas needs an identity slot to absorb the "
                "dropped mass; stage the pool with headroom "
                "(PermPool.from_schedule pads with identities)"
            ) from None
        g[id_slot] += moved
    return g.astype(np.float32)


def straggler_pool_stream(
    policy: StragglerPolicy,
    gammas,
    pool: "PermPool",
    delays,
) -> tuple[jax.Array, jax.Array]:
    """Pool-transport twin of :func:`straggler_stream`: resolve a
    (T, n) raw delay trace into per-step pool coordinates.

    Returns ``(gammas (T, capacity), eff (T, n))``. Under ``"wait"``
    every step keeps the base gamma vector and clamps delays to the
    deadline; under ``"degrade"`` past-deadline nodes are repaired out
    via :func:`degrade_pool_gammas` (their effective delay drops to 0 --
    the repaired atoms self-loop them, so they keep their own fresh
    half-step). Host-side numpy, stacked to scan xs: a straggler burst
    is a pure value change on the compiled pool transport.
    """
    d = np.asarray(delays, np.int64)
    if d.ndim != 2:
        raise ValueError(f"delays must be (T, n), got shape {d.shape}")
    if d.shape[1] != pool.n_nodes:
        raise ValueError(
            f"delays are for {d.shape[1]} nodes, pool for {pool.n_nodes}"
        )
    if d.size and d.min() < 0:
        raise ValueError("delays must be non-negative")
    base = np.asarray(gammas, np.float32).reshape(pool.capacity)
    T = d.shape[0]
    g_out = np.empty((T, pool.capacity), np.float32)
    e_out = np.empty(d.shape, np.int32)
    for t in range(T):
        if policy.mode == "wait":
            g_out[t] = base
            e_out[t] = np.minimum(d[t], policy.tau_max)
        else:
            late = d[t] > policy.tau_max
            e_out[t] = np.where(late, 0, d[t])
            g_out[t] = (
                degrade_pool_gammas(pool, base, late) if late.any() else base
            )
    return jnp.asarray(g_out), jnp.asarray(e_out)


# ---------------------------------------------------------------------------
# Wire corruption and receiver-side screening (Byzantine-ish senders)
# ---------------------------------------------------------------------------
#
# The fault layer above models nodes that DISAPPEAR; the ops below model
# nodes that LIE. Corruption applies to the SENT payload at the wire --
# a per-sender multiplicative factor (nan / -1 / scale k) plus a
# per-sender XOR mask on the f32 bit pattern (bitflip) -- and never to
# the sender's own local state: self-loops move no bytes, so every
# transport keeps the self-contribution clean. Both planes are pure
# value ops on (n,)-vectors that ride a ``lax.scan`` as data, so a node
# turning corrupt (or recovering) never retraces, exactly like a crash.
#
# Screening is receiver-side and split across the trace boundary: the
# only IN-GRAPH defense is the hard non-finite guard (a NaN payload is
# substituted by the receiver's own payload -- a row-convex repair, the
# single survival path before the host confirms a quarantine), while the
# norm/cosine screens are computed as per-edge STATISTICS (``sq_own``,
# ``sq_recv``, ``dot``, ``finite``) that come back as scan outputs for
# the host-side ``repro.faults.quarantine`` controller to threshold
# against the live heterogeneity probes. Thresholding in-graph would
# bake a policy constant into the trace; thresholding on the host keeps
# the screen a control-plane decision, like the topology refreshes.


class WireCorruption(NamedTuple):
    """Per-sender wire corruption for one mixing step (scan data).

    ``mult`` (n,) f32 multiplies the sender's outgoing payload (1.0 =
    honest, ``nan`` poisons, ``-1`` sign-flips, ``k`` rescales);
    ``xor`` (n,) int32 is XOR-ed into the f32 bit pattern afterwards
    (0 = honest; a single exponent-bit flip models memory corruption).
    Senders with ``mult == 1 and xor == 0`` are delivered BITWISE
    verbatim -- the corrupted path selects the untouched payload rather
    than trusting ``x * 1.0`` round-trips.
    """

    mult: jax.Array  # (n,) float32
    xor: jax.Array  # (n,) int32


def corrupt_wire(wire: jax.Array, corrupt: WireCorruption) -> jax.Array:
    """Apply per-sender corruption to an (n, P) f32 wire buffer.

    Pure value op: honest rows are selected bitwise-untouched, corrupt
    rows are ``bitcast(bitcast(x * mult) ^ xor)``. The payload must be
    f32 (the wire dtype of every transport here; the bitcast plane is
    only defined against a fixed bit layout).
    """
    if wire.dtype != jnp.float32:
        raise ValueError(
            f"corrupt_wire needs an f32 wire payload, got {wire.dtype}"
        )
    bcast = (wire.shape[0],) + (1,) * (wire.ndim - 1)
    mult = corrupt.mult.astype(jnp.float32).reshape(bcast)
    xor = corrupt.xor.astype(jnp.int32).reshape(bcast)
    bent = jax.lax.bitcast_convert_type(wire * mult, jnp.int32)
    bent = jax.lax.bitcast_convert_type(bent ^ xor, jnp.float32)
    # nan != 1.0 is True, so the nan mode lands in the corrupt branch
    dirty = (mult != jnp.float32(1.0)) | (xor != 0)
    return jnp.where(dirty, bent, wire)


def _corrupt_own(x32: jax.Array, corrupt: "WireCorruption", i: jax.Array) -> jax.Array:
    """Shard-side twin of :func:`corrupt_wire`: node ``i`` corrupts its
    OWN outgoing leaf payload (scalar mult/xor picked by axis index)."""
    m = jax.lax.dynamic_index_in_dim(
        corrupt.mult.astype(jnp.float32), i, axis=0, keepdims=False
    )
    b = jax.lax.dynamic_index_in_dim(
        corrupt.xor.astype(jnp.int32), i, axis=0, keepdims=False
    )
    bent = jax.lax.bitcast_convert_type(x32 * m, jnp.int32)
    bent = jax.lax.bitcast_convert_type(bent ^ b, jnp.float32)
    return jnp.where((m != jnp.float32(1.0)) | (b != 0), bent, x32)


def _mix_arrays_flat_corrupt(
    flat: jax.Array, arrays: ScheduleArrays, corrupt: WireCorruption
) -> jax.Array:
    """:func:`_mix_arrays_flat` with the non-self contributions routed
    through the corrupted wire (self-loops move no bytes: a corrupt
    node's own contribution to itself stays clean)."""
    if flat.shape[0] != arrays.n_nodes:
        raise ValueError(
            f"schedule arrays are for {arrays.n_nodes} nodes but the stacked "
            f"parameters have leading axis {flat.shape[0]}"
        )
    wire = corrupt_wire(flat, corrupt)
    rows = jnp.arange(flat.shape[0])
    bcast = (flat.shape[0],) + (1,) * (flat.ndim - 1)

    def body(acc, gp):
        g, perm = gp
        recv = jnp.where(
            (perm == rows).reshape(bcast), flat, jnp.take(wire, perm, axis=0)
        )
        return acc + g.astype(flat.dtype) * recv, None

    acc, _ = jax.lax.scan(
        body, jnp.zeros_like(flat), (arrays.gammas, arrays.perms)
    )
    return acc


class ScreenStats(NamedTuple):
    """Per-edge screening statistics from one screened mixing step.

    For atom ``l`` and receiver ``i`` the sender is ``perms[l, i]``;
    entries where ``perms[l, i] == i`` are self-loops (no wire payload
    -- the host-side screen skips them). All four planes are cheap
    reductions of values the mix already touches, so screening rides
    the scan as outputs instead of a second pass.
    """

    sq_own: jax.Array  # (n,)        ||own payload||^2 per receiver
    sq_recv: jax.Array  # (l_max, n)  ||received payload||^2 per edge
    dot: jax.Array  # (l_max, n)  <received, own> per edge
    finite: jax.Array  # (l_max, n)  all-finite flag per edge


def mix_schedule_arrays_screened(
    buffer: StaleBuffer,
    arrays: ScheduleArrays,
    delays: jax.Array,
    own: jax.Array,
    corrupt: WireCorruption | None = None,
    *,
    guard: bool = True,
) -> tuple[jax.Array, ScreenStats]:
    """Screened bounded-delay mixing: corrupted wire in, stats out.

    The screened twin of :func:`mix_schedule_arrays_stale`: non-self
    contributions come off the (optionally corrupted) wire, and every
    edge emits its norm/inner-product/finiteness statistics for the
    host-side screen. ``own`` is the receiver's reference payload --
    its fresh half-step, the exact value it pushed this step.

    ``guard=True`` substitutes the receiver's OWN payload for any
    non-finite contribution (each repaired row stays a convex
    combination -- the receiver's weight absorbs the poisoned edge's
    mass -- though W is no longer column-stochastic on that edge until
    the host quarantine lands, which is why the guard is a detection-
    window bridge, not the repair). With ``guard=False`` the poison
    propagates -- the honest screen-off baseline arm. With no
    corruption and all-finite payloads the mixed output is bitwise
    :func:`mix_schedule_arrays_stale` (asserted in tests).
    """
    view = stale_view(buffer, delays)
    wire = view if corrupt is None else corrupt_wire(view, corrupt)
    rows = jnp.arange(view.shape[0])
    sq_own = jnp.sum(own * own, axis=1)

    def body(acc, gp):
        g, perm = gp
        recv = jnp.where(
            (perm == rows)[:, None], view, jnp.take(wire, perm, axis=0)
        )
        ok = jnp.all(jnp.isfinite(recv), axis=1)
        sq = jnp.sum(recv * recv, axis=1)
        dt = jnp.sum(recv * own, axis=1)
        safe = jnp.where(ok[:, None], recv, own) if guard else recv
        return acc + g.astype(view.dtype) * safe, (sq, dt, ok)

    acc, (sqs, dots, oks) = jax.lax.scan(
        body, jnp.zeros_like(view), (arrays.gammas, arrays.perms)
    )
    return acc, ScreenStats(sq_own=sq_own, sq_recv=sqs, dot=dots, finite=oks)


# ---------------------------------------------------------------------------
# Sharded bounded-delay transports (stale ring inside shard_map)
# ---------------------------------------------------------------------------
#
# The mesh twins of the ring buffer above. Inside ``shard_map`` every
# node holds only its own parameter shard, so the ring is per-node and
# SENDER-side: each node keeps its own last ``depth`` wire payloads
# (f32, the exact value the fresh transports put on the wire) and
# contributes the slot ``delays[i]`` pushes back -- source-indexed
# delay, matching :func:`stale_view` row-for-row. The ring pytree and
# the delay vector ride the training carry as data: a straggler
# appearing, a deadline decision, or a hot-swapped schedule are all
# pure value changes into the compiled step. With ``delays == 0`` the
# slot just pushed is read back verbatim, so both transports reduce
# BITWISE to their fresh counterparts (asserted in
# tests/test_staleness.py on a forced-8-device mesh).


class ShardStaleState(NamedTuple):
    """Per-node sender-side ring of the last ``depth`` wire payloads.

    ``rings`` mirrors the parameter pytree with per-leaf shape
    ``(depth, *leaf.shape)`` in f32 (the wire dtype of the sharded
    transports); ``head`` indexes the most recent push. A NamedTuple of
    arrays, so it rides a scan carry / opt-state slot like
    :class:`StaleBuffer` does.
    """

    rings: PyTree
    head: jax.Array  # () int32

    @property
    def depth(self) -> int:
        return jax.tree_util.tree_leaves(self.rings)[0].shape[0]


def shard_stale_init(params: PyTree, depth: int) -> ShardStaleState:
    """Fill all ``depth`` slots of every leaf ring with the current
    payload (a delay larger than the pushes so far reads the initial
    state, never garbage)."""
    if depth < 1:
        raise ValueError(f"depth must be >= 1 (tau_max + 1), got {depth}")
    rings = jax.tree_util.tree_map(
        lambda x: jnp.tile(
            x.astype(jnp.float32)[None], (depth,) + (1,) * x.ndim
        ),
        params,
    )
    return ShardStaleState(rings=rings, head=jnp.zeros((), jnp.int32))


def shard_stale_push(state: ShardStaleState, params: PyTree) -> ShardStaleState:
    """Advance the shared head and write this step's payloads."""
    depth = state.depth
    head = jax.lax.rem(state.head + 1, jnp.asarray(depth, state.head.dtype))
    rings = jax.tree_util.tree_map(
        lambda r, x: jax.lax.dynamic_update_index_in_dim(
            r, x.astype(jnp.float32), head, axis=0
        ),
        state.rings,
        params,
    )
    return ShardStaleState(rings=rings, head=head)


def _stale_slot(state: ShardStaleState, delays: jax.Array, axis_name: str):
    """This node's ring slot under source-indexed delay ``delays[i]``."""
    i = jax.lax.axis_index(axis_name)
    d = jax.lax.dynamic_index_in_dim(delays, i, axis=0, keepdims=False)
    return jnp.mod(state.head - d, state.depth)


def _zip_leaf_map(params: PyTree, rings: PyTree, mix_leaf, serialize: bool) -> PyTree:
    """Two-tree :func:`_serialized_leaf_map`: walk (param, ring) leaf
    pairs with the same one-gather-live-at-a-time barrier chaining."""
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    r_leaves = treedef.flatten_up_to(rings)
    outs: list[jax.Array] = []
    token = None
    for x, r in zip(p_leaves, r_leaves):
        if serialize and token is not None:
            r, _ = jax.lax.optimization_barrier((r, token))
        out = mix_leaf(x, r)
        token = out
        outs.append(out)
    return jax.tree_util.tree_unflatten(treedef, outs)


def mix_arrays_sharded_stale(
    params: PyTree,
    state: ShardStaleState,
    arrays: ScheduleArrays,
    delays: jax.Array,
    axis_name: str,
    *,
    serialize: bool = True,
    corrupt: "WireCorruption | None" = None,
) -> tuple[PyTree, ShardStaleState]:
    """Bounded-delay :func:`mix_arrays_sharded`: all-gather of DELAYED
    payloads, schedule and delays as data.

    Pushes this step's params into the ring, reads back this node's
    payload from ``delays[i]`` pushes ago, gathers, and accumulates
    ``sum_l gammas[l] * gathered[perms[l, i]]`` exactly as the fresh
    transport does -- with ``delays == 0`` the slot read returns the
    value just pushed, so the result is bitwise the fresh mix. Returns
    ``(mixed, new_state)``; the caller threads the ring through its
    carry (fixed shape: hot swaps stay value changes). ``corrupt``
    poisons this node's outgoing gathered payload (the receiver's own
    row is restored clean after the gather: self-loops move no bytes).
    """
    state = shard_stale_push(state, params)
    slot = _stale_slot(state, delays, axis_name)
    i = jax.lax.axis_index(axis_name)
    srcs = arrays.perms[:, i]

    def mix_leaf(x, ring):
        d32 = jax.lax.dynamic_index_in_dim(ring, slot, axis=0, keepdims=False)
        wire = d32 if corrupt is None else _corrupt_own(d32, corrupt, i)
        g = jax.lax.all_gather(wire, axis_name)
        if corrupt is not None:
            g = jax.lax.dynamic_update_index_in_dim(g, d32, i, axis=0)

        def body(acc, gs):
            gamma, src = gs
            contrib = jax.lax.dynamic_index_in_dim(g, src, axis=0, keepdims=False)
            return acc + gamma.astype(jnp.float32) * contrib, None

        acc, _ = jax.lax.scan(
            body, jnp.zeros_like(d32), (arrays.gammas, srcs)
        )
        return acc.astype(x.dtype)

    mixed = _zip_leaf_map(params, state.rings, mix_leaf, serialize)
    return mixed, state


def mix_ppermute_pool_stale(
    params: PyTree,
    state: ShardStaleState,
    gammas: jax.Array,
    pool: "PermPool",
    delays: jax.Array,
    axis_name: str,
    corrupt: "WireCorruption | None" = None,
) -> tuple[PyTree, ShardStaleState]:
    """Bounded-delay :func:`mix_ppermute_pool`: each staged ppermute
    moves the DELAYED payload; gammas and delays are data.

    Identity slots contribute the node's own delayed payload (the
    sender-side ring applies to self-delivery too, matching
    :func:`stale_view` semantics), non-identity slots ppermute it.
    Accumulation (f32, slot order, zeros init) mirrors the fresh pool
    transport op-for-op, so ``delays == 0`` reproduces it bitwise.
    Returns ``(mixed, new_state)``. ``corrupt`` poisons the payload
    each non-identity ppermute moves; identity slots and the fixed
    points of staged atoms are self-deliveries (no bytes) and stay
    clean.
    """
    n = pool.n_nodes
    ident = pool.identity
    if gammas.shape != (pool.capacity,):
        raise ValueError(
            f"gammas must be ({pool.capacity},) to match the pool, "
            f"got {gammas.shape}"
        )
    state = shard_stale_push(state, params)
    slot = _stale_slot(state, delays, axis_name)
    i = jax.lax.axis_index(axis_name)

    def mix_leaf(x, ring):
        d32 = jax.lax.dynamic_index_in_dim(ring, slot, axis=0, keepdims=False)
        wire = d32 if corrupt is None else _corrupt_own(d32, corrupt, i)
        acc = jnp.zeros_like(d32)
        for l, perm in enumerate(pool.perms):
            if perm == ident:
                contrib = d32
            else:
                pairs = [(int(perm[q]), q) for q in range(n)]
                contrib = jax.lax.ppermute(wire, axis_name, pairs)
                if corrupt is not None:
                    fixed = np.array([perm[q] == q for q in range(n)])
                    if fixed.any():
                        sel = jax.lax.dynamic_index_in_dim(
                            jnp.asarray(fixed), i, axis=0, keepdims=False
                        )
                        contrib = jnp.where(sel, d32, contrib)
            acc = acc + gammas[l].astype(jnp.float32) * contrib
        return acc.astype(x.dtype)

    mixed = _zip_leaf_map(params, state.rings, mix_leaf, serialize=False)
    return mixed, state


def _serialized_leaf_map(params: PyTree, mix_leaf, serialize: bool) -> PyTree:
    """tree_map with an explicit leaf-to-leaf data dependency.

    Gather-based sharded transports materialize an ``(n, P_leaf)``
    all-gather output per leaf; without ordering constraints XLA's
    scheduler is free to issue every leaf's gather before any leaf's
    contraction, so the peak live footprint is the FULL gathered stack
    ``n x sum_leaf P_leaf`` (the PR-4 regression). Chaining each leaf's
    input through an ``optimization_barrier`` on the previous leaf's
    output forces gather_k to wait for contraction_{k-1}, so at most
    ONE leaf's gather is live at a time: peak ``n x max_leaf`` instead
    of ``n x P_total`` (verified by a compiled-memory check in
    tests/test_distributed.py). The barrier is the identity on values
    -- results are bitwise unchanged.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    outs: list[jax.Array] = []
    token = None
    for x in leaves:
        if serialize and token is not None:
            x, _ = jax.lax.optimization_barrier((x, token))
        out = mix_leaf(x)
        token = out
        outs.append(out)
    return jax.tree_util.tree_unflatten(treedef, outs)


def mix_dense_sharded(
    params: PyTree,
    W: jax.Array,
    axis_name: str,
    *,
    serialize: bool = True,
    corrupt: "WireCorruption | None" = None,
) -> PyTree:
    """Dense mixing *inside* ``shard_map`` with W as data (traced).

    Each index along ``axis_name`` holds one node's parameter pytree;
    the mixed result is ``theta_i <- sum_j W[i, j] theta_j`` via an
    ``all_gather`` over the node axis followed by a row contraction.
    This is the mesh-trainer twin of :func:`mix_schedule_arrays`: W is
    an ordinary operand, so an online refresh swaps it with zero
    retraces -- ``lax.ppermute`` cannot do that (its permutation pairs
    are baked into the trace). The price is communication: an
    all-gather moves ``O(n P)`` bytes where the static ppermute
    schedule (and the pre-staged :func:`mix_ppermute_pool`) move
    ``d_max`` permutes; use this transport while a topology is being
    adapted online on out-of-pool atoms, and prefer the staged pool
    when the refresh stays inside it.

    ``serialize=True`` (default) chains the per-leaf gathers so only
    one leaf's ``(n, P_leaf)`` all-gather output is ever live -- see
    :func:`_serialized_leaf_map`; ``serialize=False`` keeps the PR-4
    unordered behavior (A/B + the memory regression test).

    The contraction runs in f32 (same rationale as ``mix_allreduce``).
    ``corrupt`` poisons this node's outgoing gathered payload (own row
    restored clean after the gather -- self-loops move no bytes).
    """
    i = jax.lax.axis_index(axis_name)
    row = W[i].astype(jnp.float32)

    def mix_leaf(x):
        x32 = x.astype(jnp.float32)
        wire = x32 if corrupt is None else _corrupt_own(x32, corrupt, i)
        g = jax.lax.all_gather(wire, axis_name)
        if corrupt is not None:
            g = jax.lax.dynamic_update_index_in_dim(g, x32, i, axis=0)
        return jnp.tensordot(row, g, axes=([0], [0])).astype(x.dtype)

    return _serialized_leaf_map(params, mix_leaf, serialize)


def mix_arrays_sharded(
    params: PyTree,
    arrays: ScheduleArrays,
    axis_name: str,
    *,
    serialize: bool = True,
    corrupt: "WireCorruption | None" = None,
) -> PyTree:
    """``ScheduleArrays`` mixing *inside* ``shard_map`` via all-gather.

    The sharded twin of :func:`mix_schedule_arrays`: gathers the node
    axis once per leaf, then accumulates ``sum_l gammas[l] *
    gathered[perms[l, i]]`` with the coefficients AND the permutation
    table as traced data -- a hot swap of either is a pure value
    change. Communication is still the all-gather's ``O(n P)`` bytes;
    what the arrays buy over :func:`mix_dense_sharded` is (a) ``l_max``
    AXPYs instead of an n-term row contraction and (b) an accumulation
    order identical slot-for-slot to :func:`mix_ppermute_pool`, so the
    two transports agree BITWISE on the same schedule (asserted on a
    CPU mesh in tests/test_distributed.py) -- the property that lets a
    trainer fall back from the staged pool to all-gather mid-run
    without perturbing the trajectory.

    ``corrupt`` poisons this node's outgoing gathered payload; the
    receiver's own row is restored clean after the gather (self-loops
    move no bytes).
    """
    i = jax.lax.axis_index(axis_name)
    srcs = arrays.perms[:, i]  # (l_max,) rows this node receives, per atom

    def mix_leaf(x):
        x32 = x.astype(jnp.float32)
        wire = x32 if corrupt is None else _corrupt_own(x32, corrupt, i)
        g = jax.lax.all_gather(wire, axis_name)
        if corrupt is not None:
            g = jax.lax.dynamic_update_index_in_dim(g, x32, i, axis=0)

        def body(acc, gs):
            gamma, src = gs
            contrib = jax.lax.dynamic_index_in_dim(g, src, axis=0, keepdims=False)
            return acc + gamma.astype(jnp.float32) * contrib, None

        acc, _ = jax.lax.scan(
            body, jnp.zeros_like(x32), (arrays.gammas, srcs)
        )
        return acc.astype(x.dtype)

    return _serialized_leaf_map(params, mix_leaf, serialize)


# ---------------------------------------------------------------------------
# Pre-staged ppermute atom pool (sparse retrace-free sharded transport)
# ---------------------------------------------------------------------------
#
# ``mix_ppermute`` is sparse (d_max permutes of bytes) but static: its
# permutation pairs are baked into the trace, so an online W swap
# retraces. ``mix_dense_sharded``/``mix_arrays_sharded`` are hot-
# swappable but move the all-gather's O(nP) bytes. The pool is the
# missing point in that square: compile the UNION of K permutation
# atoms once (the initial solve's Birkhoff atoms plus identity headroom
# slots), with the per-atom convex coefficients as a (K,) data vector.
# A refresh whose atoms stay inside the pool is a pure gamma-value
# change -- zero retraces, and the bytes stay O(K P) with K ~ d_max --
# while an out-of-pool refresh restages the pool once (a single counted
# recompile, logged by the trainers and asserted rare in the benches).


@dataclasses.dataclass(frozen=True)
class PermPool:
    """A fixed, compiled-in set of permutation atoms ("slots").

    ``perms`` holds ``capacity`` static permutations, identity-padded:
    identity slots cost nothing (a local scale, no communication) and
    serve as headroom -- but REPLACING a slot's permutation changes the
    compiled trace, which is exactly the pool-miss recompile the
    schedule projection exists to avoid. Frozen + tuple-of-tuples, so a
    jitted step function can close over a pool hashably.

    The runtime coefficients live OUTSIDE the pool, as a ``(capacity,)``
    gamma vector threaded through the step as data (see
    :func:`mix_ppermute_pool`); ``project`` maps any
    :class:`BirkhoffSchedule` onto that vector.
    """

    perms: tuple[tuple[int, ...], ...]

    def __post_init__(self):
        if not self.perms:
            raise ValueError("PermPool needs at least one slot")
        n = len(self.perms[0])
        for p in self.perms:
            if len(p) != n or sorted(p) != list(range(n)):
                raise ValueError(f"pool slot {p!r} is not a permutation of {n}")

    @property
    def capacity(self) -> int:
        return len(self.perms)

    @property
    def n_nodes(self) -> int:
        return len(self.perms[0])

    @property
    def identity(self) -> tuple[int, ...]:
        return tuple(range(self.n_nodes))

    @property
    def n_comm_slots(self) -> int:
        """Non-identity slots: each moves P bytes per node per mix step
        (gamma 0 or not -- a staged ppermute executes unconditionally)."""
        ident = self.identity
        return sum(1 for p in self.perms if p != ident)

    @classmethod
    def from_schedule(
        cls, schedule: BirkhoffSchedule, capacity: int | None = None
    ) -> "PermPool":
        """Stage a schedule's atoms (deduplicated, order kept), identity-
        padding up to ``capacity`` headroom slots.

        A schedule with more atoms than ``capacity`` is truncated first
        (largest coefficients kept -- :func:`truncate_schedule`), so a
        restage always fits.
        """
        if capacity is not None and capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if capacity is not None and schedule.n_atoms > capacity:
            schedule = truncate_schedule(schedule, capacity)
        seen: dict[tuple[int, ...], None] = {}
        for p in schedule.perms:
            seen.setdefault(tuple(int(x) for x in p))
        slots = list(seen)
        n = schedule.n_nodes
        cap = capacity if capacity is not None else len(slots)
        ident = tuple(range(n))
        while len(slots) < cap:
            slots.append(ident)
        return cls(perms=tuple(slots))

    def _slot_index(self) -> dict[tuple[int, ...], int]:
        idx: dict[tuple[int, ...], int] = {}
        for l, p in enumerate(self.perms):
            idx.setdefault(p, l)
        return idx

    def project(self, schedule: BirkhoffSchedule) -> tuple[np.ndarray, float]:
        """Schedule -> pool-aligned gammas; returns ``(gammas, dropped)``.

        Atoms staged in the pool land in their slot; atoms NOT in the
        pool are dropped and their total coefficient mass returned as
        ``dropped`` (pre-renormalization). The kept coefficients are
        renormalized, so the executed W stays doubly stochastic -- the
        same pool-aware truncation argument as
        :func:`truncate_schedule`, with the pool membership (not the
        coefficient rank) deciding who is kept. The caller compares
        ``dropped`` against its miss tolerance to decide between an
        in-pool swap and a restage.
        """
        if schedule.n_nodes != self.n_nodes:
            raise ValueError(
                f"schedule is for {schedule.n_nodes} nodes, pool for {self.n_nodes}"
            )
        idx = self._slot_index()
        gammas = np.zeros((self.capacity,), np.float64)
        dropped = 0.0
        for c, p in zip(schedule.coeffs, schedule.perms):
            slot = idx.get(tuple(int(x) for x in p))
            if slot is None:
                dropped += float(c)
            else:
                gammas[slot] += float(c)
        kept = gammas.sum()
        if kept > 0.0:
            gammas /= kept
        return gammas.astype(np.float32), float(dropped)

    def contains(self, schedule: BirkhoffSchedule) -> bool:
        """True iff every atom of ``schedule`` is staged in this pool."""
        _, dropped = self.project(schedule)
        return dropped == 0.0

    def arrays_for(self, gammas: np.ndarray) -> ScheduleArrays:
        """Pool-aligned gammas as a :class:`ScheduleArrays` (slot order
        preserved) -- the exact operand :func:`mix_arrays_sharded` needs
        to reproduce the pool transport bitwise."""
        gammas = np.asarray(gammas, np.float32)
        if gammas.shape != (self.capacity,):
            raise ValueError(
                f"gammas must be ({self.capacity},), got {gammas.shape}"
            )
        perms = np.asarray(self.perms, np.int32).reshape(self.capacity, self.n_nodes)
        return ScheduleArrays(gammas=jnp.asarray(gammas), perms=jnp.asarray(perms))

    def to_matrix(self, gammas: np.ndarray) -> np.ndarray:
        """Densify pool slots + gammas (host-side validation)."""
        return arrays_to_matrix(self.arrays_for(gammas))


@dataclasses.dataclass(frozen=True)
class PoolSwap:
    """A topology update in pool coordinates (what an online refresh
    hands a pool-transport trainer at a segment boundary).

    ``pool is None`` means the update stayed inside the trainer's
    staged pool: applying it is a pure ``(capacity,)`` gamma value
    change (zero retraces). A non-None ``pool`` is a RESTAGE -- the
    refresh emitted out-of-pool atoms beyond the miss tolerance, the
    new pool must be compiled in (one counted recompile on the pool
    transport; pure data on the all-gather transport, which executes
    pool gammas as their ScheduleArrays twin), and ``gammas`` is
    aligned to the NEW pool's slots. ``dropped_mass`` records the
    coefficient mass the projection discarded: the out-of-pool mass
    for an in-pool swap, the capacity-truncation residue for a restage
    (0 iff every refreshed atom fit the pool).
    """

    gammas: np.ndarray
    pool: "PermPool | None" = None
    dropped_mass: float = 0.0

    @property
    def restaged(self) -> bool:
        return self.pool is not None


def mix_ppermute_pool(
    params: PyTree,
    gammas: jax.Array,
    pool: PermPool,
    axis_name: str,
    corrupt: "WireCorruption | None" = None,
) -> PyTree:
    """Staged-pool sharded mixing: K compiled ppermutes, gammas as data.

    For use inside ``shard_map`` where each index along ``axis_name``
    holds one node's parameters. Every non-identity pool slot executes
    its (statically staged) ``ppermute`` unconditionally -- gamma 0
    zeroes the contribution but not the transfer, which is what keeps
    the trace independent of the gamma VALUES: an in-pool topology swap
    is a buffer update. Identity slots are a local scale (no
    communication), so headroom costs nothing until staged.

    Per node per step this moves ``pool.n_comm_slots x P`` bytes (f32)
    versus the all-gather transports' ``(n-1) x P`` -- the O(d_max P)
    sparse-communication payoff of the learned topology, now surviving
    a W swap without recompiling.

    The accumulation (f32, slot order, zeros init) mirrors
    :func:`mix_arrays_sharded` op-for-op so the two transports agree
    bitwise on the same schedule.

    ``corrupt`` poisons the payload each non-identity ppermute moves;
    identity slots and the fixed points of staged atoms are
    self-deliveries (no bytes) and stay clean.
    """
    n = pool.n_nodes
    ident = pool.identity
    if gammas.shape != (pool.capacity,):
        raise ValueError(
            f"gammas must be ({pool.capacity},) to match the pool, "
            f"got {gammas.shape}"
        )
    i = jax.lax.axis_index(axis_name) if corrupt is not None else None

    def mix_leaf(x):
        x32 = x.astype(jnp.float32)
        wire = x32 if corrupt is None else _corrupt_own(x32, corrupt, i)
        acc = jnp.zeros_like(x32)
        for l, perm in enumerate(pool.perms):
            if perm == ident:
                contrib = x32
            else:
                pairs = [(int(perm[q]), q) for q in range(n)]
                contrib = jax.lax.ppermute(wire, axis_name, pairs)
                if corrupt is not None:
                    fixed = np.array([perm[q] == q for q in range(n)])
                    if fixed.any():
                        sel = jax.lax.dynamic_index_in_dim(
                            jnp.asarray(fixed), i, axis=0, keepdims=False
                        )
                        contrib = jnp.where(sel, x32, contrib)
            acc = acc + gammas[l].astype(jnp.float32) * contrib
        return acc.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf, params)


# ---------------------------------------------------------------------------
# Single-buffer flatten/unflatten (ravel the stack ONCE, mix in one dispatch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StackRavelSpec:
    """Static recipe for packing an (n, ...)-leaved pytree into one (n, P)
    buffer and back. Hashable, so jitted functions can close over it."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]  # per-leaf shapes *without* node axis
    dtypes: tuple[Any, ...]
    n_nodes: int
    total: int  # sum of leaf sizes (pre-padding)
    padded: int  # buffer width P (>= total; padded to pad_to)

    @property
    def pad(self) -> int:
        return self.padded - self.total


def ravel_stack(params_stack: PyTree, pad_to: int | None = None) -> tuple[jax.Array, StackRavelSpec]:
    """Flatten an (n, ...)-leaved pytree into one contiguous (n, P) buffer.

    ``pad_to`` pads the parameter axis once, at flatten time, to a multiple
    of the given block width -- so downstream Pallas kernels (which tile P in
    ``block_p``-wide lanes) never re-pad per call. The buffer dtype is the
    common ``result_type`` of the leaves; ``unravel_stack`` casts back.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params_stack)
    if not leaves:
        raise ValueError("ravel_stack: empty pytree")
    n = leaves[0].shape[0]
    for leaf in leaves:
        if leaf.ndim < 1 or leaf.shape[0] != n:
            raise ValueError(
                f"ravel_stack: every leaf needs leading node axis {n}, "
                f"got shape {leaf.shape}"
            )
    dtypes = tuple(leaf.dtype for leaf in leaves)
    buf_dtype = jnp.result_type(*dtypes)
    shapes = tuple(tuple(leaf.shape[1:]) for leaf in leaves)
    sizes = [int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes]
    total = int(sum(sizes))
    padded = total
    if pad_to is not None and pad_to > 0:
        padded = ((total + pad_to - 1) // pad_to) * pad_to
    flat = jnp.concatenate(
        [leaf.reshape(n, -1).astype(buf_dtype) for leaf in leaves], axis=1
    )
    if padded > total:
        flat = jnp.pad(flat, ((0, 0), (0, padded - total)))
    spec = StackRavelSpec(
        treedef=treedef,
        shapes=shapes,
        dtypes=dtypes,
        n_nodes=n,
        total=total,
        padded=padded,
    )
    return flat, spec


def unravel_stack(flat: jax.Array, spec: StackRavelSpec) -> PyTree:
    """Inverse of ``ravel_stack`` (drops padding, restores shapes/dtypes)."""
    n = spec.n_nodes
    leaves = []
    offset = 0
    for shape, dtype in zip(spec.shapes, spec.dtypes):
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        piece = jax.lax.slice_in_dim(flat, offset, offset + size, axis=1)
        leaves.append(piece.reshape((n,) + shape).astype(dtype))
        offset += size
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

# Measured per-element throughput advantage of the dense matmul transport
# over gather AXPYs, calibrated on CPU BLAS (see docs/architecture.md,
# "Mixing cost model"). On TPU the MXU widens this gap, pushing the
# crossover toward dense -- recalibrate there (ROADMAP open item).
DENSE_THROUGHPUT_ADVANTAGE = 4.0


def preferred_transport(
    n_nodes: int,
    n_atoms: int,
    dense_speedup: float = DENSE_THROUGHPUT_ADVANTAGE,
) -> str:
    """Pick ``"schedule"`` vs ``"dense"`` for the stacked simulator.

    The schedule transport does ``n_atoms`` memory-bound row-gather AXPYs
    per element; the dense transport does ``n_nodes`` MACs per element at
    matmul throughput. ``dense_speedup`` is the measured per-element
    throughput ratio between the two regimes: the crossover is
    ``schedule`` iff ``n_atoms <= n_nodes / dense_speedup``.

    The default 4.0 is CPU-calibrated (BLAS matmul vs strided gathers;
    the ``L <= n/4`` rule quoted in the docs). It is a *hardware*
    constant, not a law: on TPU the MXU runs matmuls proportionally
    faster, so a larger ``dense_speedup`` (crossover toward dense) is
    expected -- pass a measured value here, or override the module-level
    ``DENSE_THROUGHPUT_ADVANTAGE`` once, after benchmarking on the target
    accelerator (``python -m benchmarks.run --only mixing``).
    """
    if dense_speedup <= 0:
        raise ValueError(f"dense_speedup must be positive, got {dense_speedup}")
    return "schedule" if n_atoms <= max(1, int(n_nodes / dense_speedup)) else "dense"


# ---------------------------------------------------------------------------
# Measured transport autotune table
# ---------------------------------------------------------------------------
#
# The closed form above is a CPU-calibrated model with a documented TPU
# caveat. The autotune table replaces the model with measurements where
# they exist: each (hardware, n_nodes, n_atoms, P) bucket -- sizes
# rounded up to powers of two so nearby shapes share an entry, keyed by
# a hardware fingerprint (cpu core count / accelerator device kind, see
# _hw_tag) so one machine's timings never apply to different hardware --
# is timed ONCE locally (both transports, jitted, steady state) and
# memoized to experiments/bench/transport_autotune.json. Lookups never measure;
# measuring is explicit (``autotune_transport(measure=True)`` or
# ``mix_stacked(transport="autotune")``), so ``transport="auto"`` stays
# side-effect free and falls back to the closed form on unmeasured
# buckets -- which keeps the TPU caveat honest: an unmeasured accelerator
# uses the conservative model until someone runs the autotuner there.

_AUTOTUNE_ENV = "REPRO_TRANSPORT_AUTOTUNE"
_autotune_cache: dict[str, dict] | None = None
_autotune_cache_path: str | None = None


def transport_autotune_path() -> str:
    """Location of the autotune table (override via $REPRO_TRANSPORT_AUTOTUNE)."""
    import os

    env = os.environ.get(_AUTOTUNE_ENV)
    if env:
        return env
    return os.path.normpath(os.path.join(
        os.path.dirname(__file__), "..", "..", "..",
        "experiments", "bench", "transport_autotune.json",
    ))


def _pow2_up(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


# Measuring caps the timed buffer at this many total elements (n * P):
# both transports stream linearly in P, so the per-element winner at the
# capped width transfers to wider buffers -- while an uncapped pow2 P at
# LM scale (P ~ 1e9) would allocate hundreds of GiB for the synthetic
# theta and time minutes of dense matmuls.
_MEASURE_MAX_ELEMENTS = 1 << 24  # 64 MiB of f32


def _hw_tag() -> str:
    """Hardware fingerprint for autotune keys.

    A measured winner is only trusted on hardware like the machine that
    measured it: the jax backend alone is too coarse (a 2-vCPU CI
    container and a 64-core BLAS server are both "cpu" but disagree on
    crossovers), so CPU keys carry the core count plus the machine
    architecture, and accelerator keys the device kind. Foreign entries
    simply miss, falling back to the conservative closed form. The tag
    is a heuristic, not a guarantee: two same-arch hosts with the same
    core count but different cache/BLAS behavior still share entries --
    re-run ``transport="autotune"`` locally when in doubt (the local
    measurement overwrites the shipped one).
    """
    import os
    import platform
    import re

    backend = jax.default_backend()
    if backend == "cpu":
        arch = platform.machine() or "unknown"
        return f"cpu{os.cpu_count()}-{arch.lower()}"
    kind = getattr(jax.devices()[0], "device_kind", backend)
    return re.sub(r"[^A-Za-z0-9]+", "-", str(kind)).strip("-").lower()


def _bucket_key(n_nodes: int, n_atoms: int, p: int) -> str:
    return (
        f"{_hw_tag()}_n{_pow2_up(n_nodes)}"
        f"_L{_pow2_up(n_atoms)}_P{_pow2_up(p)}"
    )


def _load_autotune(path: str) -> dict[str, dict]:
    global _autotune_cache, _autotune_cache_path
    if _autotune_cache is not None and _autotune_cache_path == path:
        return _autotune_cache
    import json
    import os

    table: dict[str, dict] = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                table = json.load(f)
        except (OSError, ValueError):  # unreadable table == no table
            table = {}
    _autotune_cache, _autotune_cache_path = table, path
    return table


def _best_of_timed(f, arg, iters: int, repeats: int) -> float:
    """Steady-state us/call: min over ``repeats`` of an ``iters``-call
    average (jitted f, one warmup). The min is the standard noise-robust
    estimator of achievable throughput -- on throttled shared machines
    single timings vary 2-4x and would flip near-crossover buckets."""
    import time

    out = f(arg)
    jax.block_until_ready(out)
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(arg)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)
    return best


def _persist_autotune(path: str, table: dict) -> None:
    """Atomically write the autotune table -- but only into a directory
    that already exists (the checkout's experiments/bench/, or wherever
    $REPRO_TRANSPORT_AUTOTUNE points after the caller created it): an
    installed package must not grow a junk `experiments/` tree inside
    the interpreter prefix just because its default relative path
    resolved somewhere writable. Read-only installs keep the
    measurement in memory."""
    global _autotune_cache
    import json
    import os

    try:
        if os.path.isdir(os.path.dirname(path)):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(table, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
    except OSError:
        pass
    _autotune_cache = table


def measure_transport(
    n_nodes: int, n_atoms: int, p: int, *, iters: int = 5, repeats: int = 3,
    seed: int = 0
) -> dict:
    """Time both stacked transports once at this bucket size (jitted,
    steady state, synthetic data) and return the measurement record.

    The timed width is capped so the synthetic buffer stays at most
    ``_MEASURE_MAX_ELEMENTS`` (both transports are linear in P; at LM
    scale an uncapped pow2 P would allocate hundreds of GiB). The
    record keeps the requested ``p`` plus the ``p_measured`` actually
    timed; timing protocol in :func:`_best_of_timed`.
    """
    p_measured = min(int(p), max(4096, _MEASURE_MAX_ELEMENTS // max(1, n_nodes)))
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.normal(size=(n_nodes, p_measured)), jnp.float32)
    perms = [rng.permutation(n_nodes) for _ in range(n_atoms)]
    coeffs = np.full(n_atoms, 1.0 / n_atoms)
    sched = BirkhoffSchedule(
        coeffs=tuple(float(c) for c in coeffs),
        perms=tuple(tuple(int(x) for x in p_) for p_ in perms),
    )
    W = jnp.asarray(sched.to_matrix(), jnp.float32)

    f_sched = jax.jit(lambda x: _mix_schedule_flat(x, sched))
    f_dense = jax.jit(lambda x: jnp.tensordot(W, x, axes=([1], [0])))

    schedule_us = _best_of_timed(f_sched, theta, iters, repeats)
    dense_us = _best_of_timed(f_dense, theta, iters, repeats)
    return {
        "n_nodes": n_nodes,
        "n_atoms": n_atoms,
        "p": p,
        "p_measured": p_measured,
        "schedule_us": schedule_us,
        "dense_us": dense_us,
        "winner": "schedule" if schedule_us <= dense_us else "dense",
        "backend": jax.default_backend(),
        "hw": _hw_tag(),
    }


def autotune_transport(
    n_nodes: int,
    n_atoms: int,
    p: int,
    *,
    measure: bool = False,
    path: str | None = None,
    dense_speedup: float = DENSE_THROUGHPUT_ADVANTAGE,
) -> str:
    """``"schedule"`` or ``"dense"`` from the measured autotune table.

    Looks up the power-of-two bucket of ``(n_nodes, n_atoms, p)`` in
    ``transport_autotune_path()``. On a hit, returns the measured
    winner. On a miss: with ``measure=True`` times both transports at
    the bucket-rounded sizes, memoizes the record, and returns its
    winner; otherwise falls back to the closed-form
    :func:`preferred_transport` (the conservative model -- unmeasured
    hardware keeps the documented crossover).
    """
    path = path or transport_autotune_path()
    key = _bucket_key(n_nodes, n_atoms, p)
    table = _load_autotune(path)
    entry = table.get(key)
    if entry is not None and entry.get("winner") in ("schedule", "dense"):
        return entry["winner"]
    if not measure:
        return preferred_transport(n_nodes, n_atoms, dense_speedup)

    entry = measure_transport(_pow2_up(n_nodes), _pow2_up(n_atoms), _pow2_up(p))
    table = dict(table)
    table[key] = entry
    _persist_autotune(path, table)
    return entry["winner"]


# ---------------------------------------------------------------------------
# Sharded (hot-swappable) transport cost model + autotune
# ---------------------------------------------------------------------------

# Measured per-byte throughput advantage of one fused all-gather over a
# chain of K separate ppermute collectives (the all-gather amortizes
# launch latency and runs the backend's fused ring path; each staged
# ppermute pays its own dispatch). CPU-mesh calibrated; like
# DENSE_THROUGHPUT_ADVANTAGE it is a hardware constant, not a law --
# the autotune table overrides it wherever a measurement exists.
ALLGATHER_THROUGHPUT_ADVANTAGE = 2.0


def preferred_sharded_transport(
    n_nodes: int,
    n_comm_slots: int,
    allgather_speedup: float = ALLGATHER_THROUGHPUT_ADVANTAGE,
) -> str:
    """Pick ``"pool"`` vs ``"allgather"`` for the hot-swappable mesh mix.

    Closed form on bytes: the staged pool receives ``n_comm_slots x P``
    bytes per node per step (one permute per staged non-identity slot,
    gamma 0 or not), the all-gather ``(n_nodes - 1) x P``.
    ``allgather_speedup`` discounts the all-gather's per-byte cost (one
    fused collective vs K dispatches): the crossover is ``pool`` iff
    ``n_comm_slots <= (n_nodes - 1) / allgather_speedup``. Like
    :func:`preferred_transport` this is the conservative fallback --
    measured buckets in the autotune table win (see
    :func:`autotune_sharded_transport`).
    """
    if allgather_speedup <= 0:
        raise ValueError(f"allgather_speedup must be positive, got {allgather_speedup}")
    return (
        "pool"
        if n_comm_slots <= max(1, int((n_nodes - 1) / allgather_speedup))
        else "allgather"
    )


def _sharded_bucket_key(n_nodes: int, n_comm_slots: int, p: int) -> str:
    # "sh_" prefix keeps the sharded-transport entries disjoint from the
    # stacked-transport keys in the same autotune JSON (schema extension,
    # not a second table -- docs/architecture.md "Mixing cost model").
    return (
        f"sh_{_hw_tag()}_n{_pow2_up(n_nodes)}"
        f"_K{_pow2_up(n_comm_slots)}_P{_pow2_up(p)}"
    )


def measure_sharded_transport(
    n_nodes: int, n_comm_slots: int, p: int, *, mesh, axis_name: str = "data",
    iters: int = 5, repeats: int = 3, seed: int = 0,
) -> dict:
    """Time staged-pool vs all-gather mixing inside ``shard_map`` once.

    Needs a live mesh whose ``axis_name`` axis has ``n_nodes`` indices
    (so it can only run where such a mesh exists -- the benches force
    host devices in a subprocess; a plain 1-device process cannot
    measure and keeps the closed form). Same protocol as
    :func:`measure_transport` (:func:`_best_of_timed`), synthetic (n, p)
    f32 data, width capped at ``_MEASURE_MAX_ELEMENTS`` total elements.
    """
    from jax.sharding import PartitionSpec

    if mesh.shape[axis_name] != n_nodes:
        raise ValueError(
            f"mesh axis {axis_name!r} has {mesh.shape[axis_name]} indices, "
            f"need n_nodes={n_nodes}"
        )
    p_measured = min(int(p), max(4096, _MEASURE_MAX_ELEMENTS // max(1, n_nodes)))
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.normal(size=(n_nodes, p_measured)), jnp.float32)
    slots = [
        tuple(int(x) for x in rng.permutation(n_nodes))
        for _ in range(n_comm_slots)
    ]
    pool = PermPool(perms=tuple(slots))
    gammas_np, _ = pool.project(
        BirkhoffSchedule(
            coeffs=tuple(1.0 / len(slots) for _ in slots), perms=tuple(slots)
        )
    )
    gammas = jnp.asarray(gammas_np)
    arrays = pool.arrays_for(gammas_np)
    spec = PartitionSpec(axis_name)

    def sharded(fn):
        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                axis_names={axis_name}, check_vma=False,
            )
        )

    f_pool = sharded(lambda x: mix_ppermute_pool(x, gammas, pool, axis_name))
    f_ag = sharded(lambda x: mix_arrays_sharded(x, arrays, axis_name))

    pool_us = _best_of_timed(f_pool, theta, iters, repeats)
    allgather_us = _best_of_timed(f_ag, theta, iters, repeats)
    return {
        "n_nodes": n_nodes,
        "n_comm_slots": n_comm_slots,
        "p": p,
        "p_measured": p_measured,
        "pool_us": pool_us,
        "allgather_us": allgather_us,
        "winner": "pool" if pool_us <= allgather_us else "allgather",
        "backend": jax.default_backend(),
        "hw": _hw_tag(),
    }


def autotune_sharded_transport(
    n_nodes: int,
    n_comm_slots: int,
    p: int,
    *,
    measure: bool = False,
    mesh=None,
    axis_name: str = "data",
    path: str | None = None,
    allgather_speedup: float = ALLGATHER_THROUGHPUT_ADVANTAGE,
) -> str:
    """``"pool"`` or ``"allgather"`` from the measured autotune table.

    Same two-layer contract as :func:`autotune_transport`, same JSON
    table (keys prefixed ``sh_``): a measured bucket returns its
    winner; a miss falls back to :func:`preferred_sharded_transport`
    unless ``measure=True`` AND a suitable ``mesh`` is supplied, in
    which case both transports are timed once and the record memoized.
    Lookup (``measure=False``) never times anything, so unmeasured
    hardware keeps the conservative closed form.
    """
    path = path or transport_autotune_path()
    key = _sharded_bucket_key(n_nodes, n_comm_slots, p)
    table = _load_autotune(path)
    entry = table.get(key)
    if entry is not None and entry.get("winner") in ("pool", "allgather"):
        return entry["winner"]
    if not measure or mesh is None:
        return preferred_sharded_transport(n_nodes, n_comm_slots, allgather_speedup)

    entry = measure_sharded_transport(
        n_nodes, _pow2_up(n_comm_slots), _pow2_up(p), mesh=mesh, axis_name=axis_name
    )
    table = dict(table)
    table[key] = entry
    _persist_autotune(path, table)
    return entry["winner"]


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------

def mix_dense(params_stack: PyTree, W: jax.Array, use_kernel: bool = False) -> PyTree:
    """Dense mixing over a leading node axis: ``out[i] = sum_j W[i,j] x[j]``.

    Args:
      params_stack: pytree whose leaves have shape (n, ...).
      W: (n, n) mixing matrix.
      use_kernel: route 2D-flattened leaves through the Pallas gossip_mix
        kernel (interpreted on the ``cpu`` backend only, by
        ``repro.kernels.default_interpret``) instead of einsum.
    """
    if use_kernel:
        from repro.kernels.gossip_mix import ops as gossip_ops

        def mix_leaf(x):
            n = x.shape[0]
            flat = x.reshape(n, -1)
            out = gossip_ops.gossip_mix(flat, W.astype(flat.dtype))
            return out.reshape(x.shape)

        return jax.tree_util.tree_map(mix_leaf, params_stack)

    def mix_leaf(x):
        return jnp.tensordot(W.astype(x.dtype), x, axes=([1], [0]))

    return jax.tree_util.tree_map(mix_leaf, params_stack)


def _mix_schedule_flat(flat: jax.Array, schedule: BirkhoffSchedule) -> jax.Array:
    """``out = sum_l gamma_l flat[perm_l]`` on one (n, P) buffer.

    Identity atoms are folded into a single scale (no gather); each
    communication atom is one row-gather + AXPY. XLA fuses the chain into a
    single pass over the buffer.
    """
    if flat.shape[0] != schedule.n_nodes:
        raise ValueError(
            f"schedule is for {schedule.n_nodes} nodes but the stacked "
            f"parameters have leading axis {flat.shape[0]}"
        )
    ident_w = schedule.identity_weight()
    comm = schedule.communication_atoms()
    acc = None
    if ident_w != 0.0:
        acc = jnp.asarray(ident_w, flat.dtype) * flat
    for gamma, perm in comm:
        contrib = jnp.asarray(gamma, flat.dtype) * flat[jnp.asarray(perm, jnp.int32)]
        acc = contrib if acc is None else acc + contrib
    return flat if acc is None else acc


def mix_schedule_stacked(
    params_stack: PyTree,
    schedule: BirkhoffSchedule,
    *,
    single_buffer: bool = False,
    use_kernel: bool = False,
    block_p: int | None = None,
) -> PyTree:
    """Sparse Birkhoff mixing on stacked parameters: L gathers + AXPYs.

    ``out = sum_l gamma_l theta[perm_l]`` -- cost ``O(L n P)`` versus the
    dense transport's ``O(n^2 P)``; after ``l`` Frank-Wolfe iterations
    ``L <= l + 1`` (Theorem 2), so a learned topology with budget ``l`` mixes
    in ``O(l n P)`` regardless of ``n``.

    Args:
      params_stack: pytree whose leaves have shape (n, ...).
      schedule: the Birkhoff decomposition of W (static; hashable).
      single_buffer: flatten the whole pytree into one (n, P) buffer so the
        mixing is ONE dispatch per step instead of one per leaf. This is the
        right call in eager code (dispatch-bound: one fused op beats ~2
        dispatches per leaf) and for buffers that stay flat across steps
        (see ``ravel_stack``). Inside jit leave it False: XLA already fuses
        the per-leaf gathers with zero copies, whereas flattening pays the
        concat/split passes every step.
      use_kernel: route the flat buffer through the Pallas
        ``gossip_schedule`` kernel (implies single_buffer; interpreted on
        the ``cpu`` backend only, by ``repro.kernels.default_interpret``).
      block_p: pad the flat buffer to a multiple of this at flatten time
        (defaults to the kernel's tile width when ``use_kernel``).
    """
    if use_kernel:
        from repro.kernels.gossip_mix import ops as gossip_ops
        from repro.kernels.gossip_mix.gossip_schedule import DEFAULT_BLOCK_P

        pad_to = block_p or DEFAULT_BLOCK_P
        flat, spec = ravel_stack(params_stack, pad_to=pad_to)
        mixed = gossip_ops.gossip_schedule(
            flat,
            schedule.coeff_array(),
            schedule.perm_array(),
            block_p=pad_to,
            pre_padded=True,
        )
        return unravel_stack(mixed, spec)
    if single_buffer:
        flat, spec = ravel_stack(params_stack, pad_to=block_p)
        # barrier: without it XLA refuses the concat into each of the L
        # gather consumers, recomputing the packed buffer per atom (~6x
        # regression measured); materialize it once instead.
        flat = jax.lax.optimization_barrier(flat)
        return unravel_stack(_mix_schedule_flat(flat, schedule), spec)
    return jax.tree_util.tree_map(
        lambda x: _mix_schedule_flat(x.reshape(x.shape[0], -1), schedule).reshape(x.shape),
        params_stack,
    )


def mix_stacked(
    params_stack: PyTree,
    W: jax.Array | None = None,
    schedule: BirkhoffSchedule | ScheduleArrays | None = None,
    *,
    transport: str = "auto",
    use_kernel: bool = False,
    single_buffer: bool = False,
    dense_speedup: float = DENSE_THROUGHPUT_ADVANTAGE,
) -> PyTree:
    """Unified stacked-mixing entry point with automatic transport choice.

    ``schedule`` may be a static :class:`BirkhoffSchedule` (closure
    format -- constant-folds, retraces on change) or a
    :class:`ScheduleArrays` (data format -- hot-swappable with zero
    retraces). The data format always executes on the arrays transport:
    any static W passed alongside it is, by construction, stale the
    moment a hot swap lands, so the dense path is refused rather than
    silently mixing with yesterday's topology.

    ``transport``:
      * ``"auto"``     -- measured autotune-table winner for this
                          (n, L, P) bucket when a measurement exists
                          (``autotune_transport``; lookup only, never
                          times anything), else the ``preferred_transport``
                          closed form, when both a schedule and a W are
                          usable -- else whichever is available.
                          ``dense_speedup`` tunes the closed-form
                          fallback's crossover.
      * ``"autotune"`` -- like ``"auto"``, but on a table miss time both
                          transports once at this bucket and memoize the
                          result to ``transport_autotune_path()``.
      * ``"dense"``    -- force the einsum/matmul path (W required, or
                          densified from the schedule per call -- pass a
                          precomputed W on hot paths).
      * ``"schedule"`` -- force the Birkhoff gather path (schedule required).
    """
    if transport not in ("auto", "autotune", "dense", "schedule"):
        raise ValueError(f"unknown transport {transport!r}")
    if isinstance(schedule, ScheduleArrays):
        # A hot-swappable schedule is by definition never in sync with a
        # precomputed static W: auto-selecting the dense transport here
        # would mix with the STALE W forever and turn every online
        # refresh into a silent no-op (the swap still lands in the carry
        # and n_traces stays 1, so nothing would look wrong). The data
        # format therefore always takes the arrays path; an explicit
        # transport="dense" is rejected rather than half-honored.
        if transport == "dense":
            raise ValueError(
                "transport='dense' cannot execute a ScheduleArrays (it would "
                "mix with a static W that a hot swap never updates); convert "
                "with arrays_to_matrix host-side if you really want dense"
            )
        return mix_schedule_arrays(
            params_stack, schedule,
            single_buffer=single_buffer, use_kernel=use_kernel,
        )
    if transport in ("auto", "autotune"):
        measure = transport == "autotune"
        if schedule is None:
            transport = "dense"
        elif W is None:
            # no usable W: the dense path would densify the schedule per
            # call (O(L n^2) + transfer) -- a cost the measurement does
            # not include -- so never let a memoized "dense" win here
            transport = "schedule"
        else:
            # identity atoms fold into a free scale in the schedule path
            # (no gather), so only communication atoms count as cost.
            leaves = jax.tree_util.tree_leaves(params_stack)
            n_nodes = schedule.n_nodes
            p_total = sum(
                int(np.prod(leaf.shape[1:], dtype=np.int64)) if leaf.ndim > 1 else 1
                for leaf in leaves
            )
            transport = autotune_transport(
                n_nodes,
                schedule.n_communication_atoms,
                p_total,
                measure=measure,
                dense_speedup=dense_speedup,
            )
    if transport == "schedule":
        if schedule is None:
            raise ValueError("transport='schedule' requires a BirkhoffSchedule")
        return mix_schedule_stacked(
            params_stack, schedule, single_buffer=single_buffer, use_kernel=use_kernel
        )
    if W is None:
        if schedule is None:
            raise ValueError("mix_stacked needs W or schedule")
        W = jnp.asarray(schedule.to_matrix(), jnp.float32)
    return mix_dense(params_stack, W, use_kernel=use_kernel)


def mix_ppermute(params: PyTree, schedule: BirkhoffSchedule, axis_name: str) -> PyTree:
    """Birkhoff ppermute mixing, for use inside ``shard_map``.

    Each index along ``axis_name`` holds one node's parameter pytree. The
    mixed parameters are ``sum_l gamma_l * ppermute(params, P_l)`` where the
    identity atom short-circuits to a local scale (no communication).

    ``ppermute`` pairs are (source, destination): node ``i`` receives from
    ``perm[i]``, so we emit pairs ``(perm[i], i)``.
    """
    n = schedule.n_nodes
    identity = tuple(range(n))

    def mix_leaf(x):
        acc = None
        for gamma, perm in zip(schedule.coeffs, schedule.perms):
            if perm == identity:
                contrib = x * gamma
            else:
                pairs = [(int(perm[i]), i) for i in range(n)]
                contrib = jax.lax.ppermute(x, axis_name, pairs) * gamma
            acc = contrib if acc is None else acc + contrib
        return acc

    return jax.tree_util.tree_map(mix_leaf, params)


def mix_allreduce(params: PyTree, axis_name: str) -> PyTree:
    """Complete-graph mixing (C-PSGD): ``theta_i <- mean_j theta_j``.

    The reduction runs in f32: numerically safer for bf16 parameters, and it
    sidesteps an XLA-CPU AllReducePromotion crash on bf16 all-reduces.
    """
    return jax.tree_util.tree_map(
        lambda x: jax.lax.pmean(x.astype(jnp.float32), axis_name).astype(x.dtype),
        params,
    )
