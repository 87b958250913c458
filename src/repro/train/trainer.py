"""n-node D-SGD simulator (the paper's experimental rig).

Simulates Algorithm 1 exactly on a single device: per-node parameters are
stacked on a leading node axis, local gradients are computed with
``vmap(grad)``, and the mixing step runs through any stacked transport
(dense ``Theta W^T``, the sparse Birkhoff gather schedule, or the Pallas
gossip kernels). This reproduces the paper's n=100 experiments bit-for-bit
up to RNG.

Rollout compilation: by default each driver compiles the whole multi-step
rollout between eval points with ``jax.lax.scan`` (``rollout="scan"``), so
there is no per-step dispatch and no ``float(loss)`` host round-trip inside
the hot loop -- error/loss traces are accumulated on device and fetched once
per segment. ``rollout="loop"`` keeps the step-by-step Python loop (same
jitted step function, bit-identical trajectories) for debugging and A/B
benchmarking.

Two ready-made drivers:
* ``run_mean_estimation`` -- Section 6.1 / Example 1 quadratic task, with
  closed-form error tracking against theta*.
* ``run_classification``  -- Section 6.2-style label-skew classification
  (linear model or MLP) on a partitioned synthetic dataset.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import ef_init, ef_stale_mix_flat, make_compressor
from repro.core.dsgd import DSGDState, dsgd_init, dsgd_step_stacked
from repro.core.mixing import (
    BirkhoffSchedule,
    ScheduleArrays,
    StragglerPolicy,
    mix_schedule_arrays_stale,
    ravel_stack,
    stale_buffer_init,
    stale_push,
    straggler_stream,
    unravel_stack,
)
from repro.data.synthetic import MeanEstimationTask
from repro.obs.probes import HealthProbes, compute_probes
from repro.obs.trace import Tracer
from .metrics import (
    CommMeter,
    MetricLogger,
    consensus_distance,
    mix_bytes_per_step,
    sq_error_series,
    staleness_transfer_fracs,
)

# instrumented code paths take an always-on tracer (span() bodies still
# run); callers opt in by passing a real one
_NULL_TRACER = Tracer(enabled=False)


def _online_comm_meter(
    n_nodes: int, params_per_node: int, compression=None
) -> CommMeter:
    """Modeled comm meter for a data-plane (hot-swappable) schedule.

    The simulator runs on one host, so these are the bytes the SAME
    run would move on a device mesh: the ``ScheduleArrays`` transport
    there is the all-gather (``mix_arrays_sharded``) -- ``(n-1) P``
    received per node per step -- until a ``PermPool`` trainer brings
    it down to the staged slot count (``lm_trainer.run_segments``
    meters that case from its own transport). ``compression`` swaps in
    the compressed wire layout (``(n-1) x wire_bytes(P)``).
    """
    return CommMeter(per_step_bytes=mix_bytes_per_step(
        "allgather", n_nodes=n_nodes, p_total=params_per_node,
        compression=compression,
    ))

PyTree = Any

__all__ = [
    "run_mean_estimation",
    "init_linear_classifier",
    "init_mlp_classifier",
    "classifier_loss",
    "classifier_accuracy",
    "run_classification",
]


def _check_staleness_args(staleness, delays, steps, n, online, rollout):
    """Validate + normalize the (staleness, delays) pair shared by both
    simulator drivers. Returns the (steps, n) int32 raw-delay trace, or
    None when no policy is given."""
    if staleness is None:
        if delays is not None:
            raise ValueError(
                "delays without staleness: pass a StragglerPolicy to say "
                "how the delay trace should be consumed (wait vs degrade)"
            )
        return None
    if not isinstance(staleness, StragglerPolicy):
        raise TypeError(
            f"staleness must be a StragglerPolicy, got {type(staleness).__name__}"
        )
    if not online:
        raise ValueError(
            "staleness rides the retrace-free data plane: pass the "
            "schedule as ScheduleArrays (a static schedule cannot carry "
            "the ring buffer / per-step delay data)"
        )
    if rollout != "scan":
        raise ValueError(
            "staleness needs rollout='scan': the per-step schedule and "
            "delay vectors travel as scan xs"
        )
    if delays is None:
        delays = np.zeros((steps, n), np.int32)
    delays = np.asarray(delays)
    if delays.shape != (steps, n):
        raise ValueError(
            f"delays must be (steps={steps}, n={n}), got {delays.shape}"
        )
    if delays.size and delays.min() < 0:
        raise ValueError("delays must be non-negative")
    return delays.astype(np.int32)


def _check_probe_args(probes, pi_hat, n, online, rollout, staleness):
    """Validate the (probes, pi_hat) pair shared by both simulator
    drivers; returns pi_hat as a device f32 array (or None)."""
    if probes is None:
        if pi_hat is not None:
            raise ValueError(
                "pi_hat without probes: pass HealthProbes(tau_bar=True) to "
                "say what the estimate is for"
            )
        return None
    if not isinstance(probes, HealthProbes):
        raise TypeError(
            f"probes must be a HealthProbes, got {type(probes).__name__}"
        )
    if not online:
        raise ValueError(
            "health probes ride the retrace-free data plane: pass the "
            "schedule as ScheduleArrays (probe values are per-step scan "
            "outputs of the compiled rollout)"
        )
    if rollout != "scan":
        raise ValueError(
            "health probes need rollout='scan': per-step probe values come "
            "back as scan outputs, not per-dispatch host reads"
        )
    if staleness is not None:
        raise ValueError(
            "health probes under bounded-delay gossip are not supported "
            "yet: run probes on the fresh online path, or sample at eval "
            "boundaries under staleness"
        )
    if probes.tau_bar:
        if pi_hat is None:
            raise ValueError(
                "HealthProbes(tau_bar=True) needs pi_hat: the live (n, K) "
                "label-histogram estimate the Prop. 2 proxy is evaluated at"
            )
        pi_hat = jnp.asarray(pi_hat, jnp.float32)
        if pi_hat.ndim != 2 or pi_hat.shape[0] != n:
            raise ValueError(
                f"pi_hat must be (n={n}, K), got {tuple(pi_hat.shape)}"
            )
        return pi_hat
    if pi_hat is not None:
        raise ValueError("pi_hat given but probes.tau_bar is off")
    return None


def _live_pi_hat(on_segment, current):
    """Snapshot the hook's live Pi estimate (an OnlineTopologyController
    exposes ``.estimator.Pi_hat``), so the tau_bar probe tracks the
    estimate as a per-segment VALUE change; hooks without an estimator
    keep the caller-provided pi_hat."""
    est = getattr(on_segment, "estimator", None)
    live = getattr(est, "Pi_hat", None) if est is not None else None
    return current if live is None else jnp.asarray(live, jnp.float32)


def _staleness_meter_fracs(delays, staleness) -> tuple[float, float]:
    """Mean (delivered_frac, deferred_frac) over a (k, n) delay window --
    the :meth:`CommMeter.tick` pair, from the closed-form model."""
    fates = [
        staleness_transfer_fracs(row, staleness.tau_max, staleness.mode)
        for row in np.asarray(delays)
    ]
    on_time = float(np.mean([f[0] for f in fates])) if fates else 1.0
    deferred = float(np.mean([f[1] for f in fates])) if fates else 0.0
    return on_time + deferred, deferred


# ---------------------------------------------------------------------------
# Section 6.1: decentralized mean estimation
# ---------------------------------------------------------------------------

def run_mean_estimation(
    task: MeanEstimationTask,
    W: np.ndarray | None,
    steps: int = 50,
    lr: float = 0.1,
    batch: int = 1,
    seed: int = 0,
    use_kernel: bool = False,
    schedule: BirkhoffSchedule | ScheduleArrays | None = None,
    transport: str = "auto",
    rollout: str = "scan",
    zs: np.ndarray | None = None,
    on_segment=None,
    segment_len: int | None = None,
    compression=None,
    staleness: StragglerPolicy | None = None,
    delays: np.ndarray | None = None,
    probes: HealthProbes | None = None,
    pi_hat: np.ndarray | None = None,
    tracer: Tracer | None = None,
    retrace_guard=None,
) -> dict:
    """D-SGD on ``F_i(theta, z) = (theta - z)^2``; returns error traces.

    Returns dict with 'mean_sq_error' (n^-1 ||theta - theta*||^2 per step),
    'max_sq_error', 'min_sq_error' (the paper's dashed lines), and the final
    per-node parameters.

    ``rollout="scan"`` compiles all ``steps`` iterations into one
    ``lax.scan`` (noise is presampled host-side with the same RNG call
    sequence as the loop, so both rollouts traverse identical data);
    ``rollout="loop"`` dispatches the same jitted step per iteration.

    Online topology adaptation: pass ``schedule`` as a fixed-shape
    ``ScheduleArrays`` and the mixing matrix becomes *data* -- the
    rollout is compiled once and a mid-run schedule swap never
    retraces it (the returned dict carries ``"n_traces"`` to prove it).
    ``on_segment(t) -> ScheduleArrays | None`` is called after each
    ``segment_len``-step segment (e.g. an
    ``repro.online.OnlineTopologyController``); a non-None return hot-
    swaps the schedule for the following segments. ``zs`` overrides the
    presampled observations with an explicit (steps, n, batch) stream
    (how the drift scenarios of ``repro.data.drift`` are injected --
    the observation noise is exogenous to training, so a drifting task
    is just a different precomputed stream).

    ``compression`` (a ``repro.core.compression.Compressor`` or a spec
    string like ``"bf16"`` / ``"topk:0.25"``) mixes through the
    EF-compressed data-plane transport instead: the error-feedback
    memory rides the rollout carry (fixed shape -- hot swaps still
    retrace nothing) and the returned ``comm`` meters the compressed
    wire. Requires the online ``ScheduleArrays`` schedule; the identity
    wire routes to the uncompressed transport bitwise.

    ``staleness`` (a ``repro.core.mixing.StragglerPolicy``) turns on
    bounded-delay gossip: ``delays`` is the raw (steps, n) per-source
    delay trace (e.g. ``FaultPlan.delays``; defaults to all-zero), the
    policy resolves it per step into a repaired schedule + effective
    delays, and the half-steps mix through the staleness ring buffer
    riding the scan carry. Composes with ``compression`` (EF memory and
    stale ring share one carry) and with ``on_segment`` hot swaps (the
    refreshed base is re-resolved from the next segment on). All-zero
    delays reproduce the fresh run BITWISE. Requires the online
    ``ScheduleArrays`` schedule and ``rollout="scan"``.

    ``probes`` (a ``repro.obs.HealthProbes``) threads the paper's health
    quantities -- consensus distance, gradient deviation, and (with
    ``pi_hat``, the (n, K) live label-histogram estimate) Prop. 2's
    ``tau_bar`` at the in-carry schedule -- into the compiled rollout's
    per-step outputs as pure value computations: the returned dict gains
    ``"health"`` (one (steps,) series per probe) and ``n_traces`` stays
    1 across hot swaps. When ``on_segment`` is an
    ``OnlineTopologyController``, ``pi_hat`` re-snapshots its live
    estimator at every boundary. ``tracer`` (a ``repro.obs.Tracer``)
    records a ``sim.segment`` span per rollout segment;
    ``retrace_guard`` (a ``repro.obs.RetraceGuard``) counts rollout
    compiles under ``"mean_estimation.roll"``.
    """
    if rollout not in ("scan", "loop"):
        raise ValueError(f"unknown rollout {rollout!r}")
    compressor = make_compressor(compression)
    n = task.n_nodes
    rng = np.random.default_rng(seed)
    theta = jnp.zeros((n, 1))
    state = dsgd_init(theta)
    Wj = jnp.asarray(W, jnp.float32) if W is not None else None
    theta_star = jnp.asarray(task.theta_star, jnp.float32)
    if zs is None:
        # Presample the noise exactly as the per-step loop would draw it.
        zs_host = [task.sample(batch, rng) for _ in range(steps)]
        zs = jnp.asarray(
            np.stack(zs_host) if zs_host else np.zeros((0, n, batch)), jnp.float32
        )  # (steps, n, batch)
    else:
        zs = jnp.asarray(zs, jnp.float32)
        if zs.ndim != 3 or zs.shape[0] != steps or zs.shape[1] != n:
            raise ValueError(
                f"zs must be (steps={steps}, n={n}, batch), got {zs.shape}"
            )

    online = isinstance(schedule, ScheduleArrays)
    if on_segment is not None and not online:
        raise ValueError(
            "on_segment hot-swapping needs the schedule as ScheduleArrays "
            "(a static BirkhoffSchedule is baked into the trace)"
        )
    if compressor is not None and not online:
        raise ValueError(
            "compression rides the retrace-free data plane: pass the "
            "schedule as ScheduleArrays (static schedules have no EF carry)"
        )
    delays_arr = _check_staleness_args(
        staleness, delays, steps, n, online, rollout
    )
    pi_hat = _check_probe_args(probes, pi_hat, n, online, rollout, staleness)
    if staleness is not None:
        return _run_mean_estimation_stale(
            theta, zs, schedule,
            steps=steps, segment_len=segment_len, on_segment=on_segment,
            lr=lr, theta_star=theta_star, staleness=staleness,
            delays=delays_arr, compressor=compressor,
        )

    def make_step(sched, ph=None):
        def step(carry, z):
            if compressor is not None:
                theta, st, e = carry
            else:
                theta, st = carry
            grads = 2.0 * (theta - z.mean(axis=1, keepdims=True))
            if compressor is not None:
                theta, st, e = dsgd_step_stacked(
                    theta, grads, st, Wj, lr,
                    use_kernel=use_kernel, schedule=sched, transport=transport,
                    ef=e, compression=compressor,
                )
                new_carry = (theta, st, e)
            else:
                theta, st = dsgd_step_stacked(
                    theta, grads, st, Wj, lr,
                    use_kernel=use_kernel, schedule=sched, transport=transport,
                )
                new_carry = (theta, st)
            outs = (jnp.square(theta[:, 0] - theta_star),)
            if probes is not None:
                # pure value computations on the post-mix params / this
                # step's grads -- extra scan outputs, zero retraces
                pv = compute_probes(
                    probes, params_stack=theta, grads_stack=grads,
                    arrays=sched, pi_hat=ph,
                )
                outs = outs + tuple(pv.values())
            return new_carry, outs
        return step

    if online:
        return _run_mean_estimation_online(
            theta, state, zs, make_step, schedule,
            steps=steps, segment_len=segment_len, on_segment=on_segment,
            rollout=rollout, compressor=compressor,
            probes=probes, pi_hat=pi_hat, tracer=tracer,
            retrace_guard=retrace_guard,
        )

    step = make_step(schedule)

    if rollout == "scan":
        @jax.jit
        def roll(theta, st, zs):
            return jax.lax.scan(step, (theta, st), zs)

        (theta, state), (err_steps,) = roll(theta, state, zs)
        errs = [np.asarray(err_steps)]
    else:
        step_j = jax.jit(step)
        carry = (theta, state)
        errs = []
        for t in range(steps):
            carry, (err,) = step_j(carry, zs[t])
            errs.append(np.asarray(err))
        theta, state = carry
    return {**sq_error_series(errs, n), "theta": np.asarray(theta)}


def _run_mean_estimation_online(
    theta,
    state,
    zs,
    make_step,
    sched0: ScheduleArrays,
    *,
    steps: int,
    segment_len: int | None,
    on_segment,
    rollout: str,
    compressor=None,
    probes=None,
    pi_hat=None,
    tracer=None,
    retrace_guard=None,
) -> dict:
    """Mean-estimation driver with the schedule threaded as data.

    The ``ScheduleArrays`` rides in the rollout carry, so every segment
    -- before or after a hot swap -- executes the SAME compiled
    computation. ``n_traces`` in the returned dict counts actual traces
    of the rollout: 1 per distinct segment length (exactly 1 when
    ``segment_len`` divides ``steps``), regardless of how many times
    the schedule was swapped. Under ``compressor`` the EF memory joins
    the carry (fixed shape, like the schedule itself), so the count
    stays 1 in compressed runs too. ``pi_hat`` (tau_bar probe only)
    enters the jitted rollout as an ordinary operand -- per-segment
    estimator updates are value changes.
    """
    tracer = _NULL_TRACER if tracer is None else tracer
    n_traces = 0
    if rollout == "scan":
        def roll_impl(carry, zs_seg, ph):
            nonlocal n_traces
            n_traces += 1
            if retrace_guard is not None:
                retrace_guard.record("mean_estimation.roll")
            inner, sa = carry[:-1], carry[-1]
            inner, traces = jax.lax.scan(make_step(sa, ph), inner, zs_seg)
            return inner + (sa,), traces
        roll = jax.jit(roll_impl)
    else:
        def step_impl(carry, z, ph):
            nonlocal n_traces
            n_traces += 1
            if retrace_guard is not None:
                retrace_guard.record("mean_estimation.roll")
            inner, sa = carry[:-1], carry[-1]
            inner, out = make_step(sa, ph)(inner, z)
            return inner + (sa,), out
        step_j = jax.jit(step_impl)

        def roll(carry, zs_seg, ph):
            outs = []
            for t in range(zs_seg.shape[0]):
                carry, out = step_j(carry, zs_seg[t], ph)
                outs.append(out)
            stacked = [
                jnp.stack([o[i] for o in outs]) for i in range(len(outs[0]))
            ]
            return carry, tuple(stacked)

    # NB: `is None`, not truthiness -- segment_len=0 must hit the
    # validation below, not silently become one full-run segment
    seg = int(segment_len) if segment_len is not None else max(steps, 1)
    if seg < 1:
        raise ValueError(f"segment_len must be >= 1, got {segment_len}")
    if compressor is not None:
        carry = (theta, state, ef_init(theta), sched0)
    else:
        carry = (theta, state, sched0)
    errs_l = []
    probe_names = probes.names() if probes is not None else ()
    health_l: dict[str, list] = {nm: [] for nm in probe_names}
    swaps: list[int] = []
    meter = _online_comm_meter(
        theta.shape[0], int(np.prod(theta.shape[1:])), compression=compressor
    )
    ph = pi_hat  # None is a valid (empty-pytree) jit operand when tau_bar off
    t0 = 0
    while t0 < steps:
        length = min(seg, steps - t0)
        with tracer.span("sim.segment", t0=t0, k=length):
            carry, traces = roll(carry, zs[t0 : t0 + length], ph)
            traces = jax.block_until_ready(traces)
        errs_l.append(np.asarray(traces[0]))
        for nm, series in zip(probe_names, traces[1:]):
            health_l[nm].append(np.asarray(series))
        meter.tick(length)
        t0 += length
        if on_segment is not None and t0 < steps:
            # no hook after the final segment: a refresh triggered there
            # would burn a warm solve whose schedule nothing executes
            new_sa = on_segment(t0 - 1)
            if new_sa is not None:
                carry = carry[:-1] + (new_sa,)
                swaps.append(t0 - 1)
            if ph is not None:
                # tau_bar tracks the hook's live estimator as a VALUE
                ph = _live_pi_hat(on_segment, ph)
    theta = carry[0]
    empty = np.zeros((0,))
    out = {
        **sq_error_series(errs_l, theta.shape[0]),
        "theta": np.asarray(theta),
        "n_traces": n_traces,
        "swaps": swaps,
        "comm": meter.summary(),
        "compression": compressor.label if compressor is not None else None,
    }
    if probes is not None:
        out["health"] = {
            nm: (np.concatenate(v) if v else empty) for nm, v in health_l.items()
        }
    return out


def _run_mean_estimation_stale(
    theta,
    zs,
    sched0: ScheduleArrays,
    *,
    steps: int,
    segment_len: int | None,
    on_segment,
    lr: float,
    theta_star,
    staleness: StragglerPolicy,
    delays: np.ndarray,
    compressor=None,
) -> dict:
    """Mean-estimation driver under bounded-delay gossip.

    Same step math as the fresh online driver op-for-op (grads, local
    half-step) with the mixing routed through the staleness ring: the
    per-step policy-resolved ``(gammas, perms, eff_delays)`` ride the
    scan as xs (fixed shapes whatever the delays -- zero retraces), the
    ring buffer (and the EF memory, under ``compressor``) rides the
    carry. A hot swap rebases the HOST-side schedule the policy
    resolves from; the compiled rollout never notices. All-zero delays
    read back the value just pushed, so the trajectory is bitwise the
    fresh driver's.
    """
    n = theta.shape[0]
    lr = float(lr)
    buffer = stale_buffer_init(theta, staleness.ring_depth)
    n_traces = 0

    def roll_impl(carry, xs):
        nonlocal n_traces
        n_traces += 1

        def step(c, x):
            z, g_t, p_t, d_t = x
            sa = ScheduleArrays(gammas=g_t, perms=p_t)
            grads_of = lambda th: 2.0 * (th - z.mean(axis=1, keepdims=True))
            if compressor is not None:
                th, e, buf = c
                half = th - lr * grads_of(th)
                th, e, buf = ef_stale_mix_flat(half, e, buf, sa, d_t, compressor)
                new_c = (th, e, buf)
            else:
                th, buf = c
                half = th - lr * grads_of(th)
                buf = stale_push(buf, half)
                th = mix_schedule_arrays_stale(buf, sa, d_t)
                new_c = (th, buf)
            return new_c, jnp.square(th[:, 0] - theta_star)

        return jax.lax.scan(step, carry, xs)

    roll = jax.jit(roll_impl)
    seg = int(segment_len) if segment_len is not None else max(steps, 1)
    if seg < 1:
        raise ValueError(f"segment_len must be >= 1, got {segment_len}")
    if compressor is not None:
        carry = (theta, ef_init(theta), buffer)
    else:
        carry = (theta, buffer)
    base = sched0
    meter = _online_comm_meter(n, 1, compression=compressor)
    errs_l = []
    swaps: list[int] = []
    t0 = 0
    while t0 < steps:
        k = min(seg, steps - t0)
        g_k, p_k, d_k = straggler_stream(staleness, base, delays[t0 : t0 + k])
        carry, errs = roll(carry, (zs[t0 : t0 + k], g_k, p_k, d_k))
        errs_l.append(np.asarray(errs))
        delivered, deferred = _staleness_meter_fracs(
            delays[t0 : t0 + k], staleness
        )
        meter.tick(k, delivered_frac=delivered, deferred_frac=deferred)
        t0 += k
        if on_segment is not None and t0 < steps:
            new_sa = on_segment(t0 - 1)
            if new_sa is not None:
                base = new_sa
                swaps.append(t0 - 1)
    return {
        **sq_error_series(errs_l, n),
        "theta": np.asarray(carry[0]),
        "n_traces": n_traces,
        "swaps": swaps,
        "comm": meter.summary(),
        "compression": compressor.label if compressor is not None else None,
        "staleness": {"mode": staleness.mode, "tau_max": staleness.tau_max},
    }


# ---------------------------------------------------------------------------
# Section 6.2: label-skew classification
# ---------------------------------------------------------------------------

def init_linear_classifier(rng: jax.Array, dim: int, num_classes: int) -> PyTree:
    k1, _ = jax.random.split(rng)
    return {
        "w": jax.random.normal(k1, (dim, num_classes)) * 0.01,
        "b": jnp.zeros((num_classes,)),
    }


def init_mlp_classifier(
    rng: jax.Array, dim: int, num_classes: int, hidden: int = 64
) -> PyTree:
    k1, k2 = jax.random.split(rng)
    return {
        "w1": jax.random.normal(k1, (dim, hidden)) * (2.0 / dim) ** 0.5,
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden, num_classes)) * (2.0 / hidden) ** 0.5,
        "b2": jnp.zeros((num_classes,)),
    }


def _classifier_logits(params: PyTree, x: jax.Array) -> jax.Array:
    if "w1" in params:
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]
    return x @ params["w"] + params["b"]


def classifier_loss(params: PyTree, x: jax.Array, y: jax.Array) -> jax.Array:
    logits = _classifier_logits(params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def classifier_accuracy(params: PyTree, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean(jnp.argmax(_classifier_logits(params, x), -1) == y)


@dataclasses.dataclass
class _NodeData:
    """Per-node dataset views, padded to a common length for stacking."""

    x: jax.Array  # (n, max_len, dim)
    y: jax.Array  # (n, max_len)
    lengths: jax.Array  # (n,)


def _stack_node_data(X, y, indices_per_node) -> _NodeData:
    n = len(indices_per_node)
    max_len = max(len(idx) for idx in indices_per_node)
    dim = X.shape[1]
    xs = np.zeros((n, max_len, dim), np.float32)
    ys = np.zeros((n, max_len), np.int32)
    lens = np.zeros((n,), np.int32)
    for i, idx in enumerate(indices_per_node):
        L = len(idx)
        xs[i, :L] = X[idx]
        ys[i, :L] = y[idx]
        lens[i] = L
        if L > 0 and L < max_len:  # cyclic pad so sampling stays uniform
            reps = idx[np.arange(max_len - L) % L]
            xs[i, L:] = X[reps]
            ys[i, L:] = y[reps]
            lens[i] = max_len
    return _NodeData(jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(lens))


def _eval_segments(steps: int, eval_every: int, do_eval: bool) -> list[tuple[int, bool]]:
    """Split [0, steps) into scan segments ending at eval points.

    Returns (segment_length, evaluate_after) pairs covering all steps in
    order, where ``evaluate_after`` marks the loop's eval condition
    ``t % eval_every == 0 or t == steps - 1`` on the segment's last step.
    """
    if steps <= 0:
        return []
    if not do_eval:
        # no eval points: one full-length scan, no per-segment host sync
        return [(steps, False)]
    segments: list[tuple[int, bool]] = []
    start = 0
    while start < steps:
        end = start
        while end < steps - 1 and not (end % eval_every == 0 or end == steps - 1):
            end += 1
        segments.append((end - start + 1, True))
        start = end + 1
    return segments


def run_classification(
    X: np.ndarray,
    y: np.ndarray,
    indices_per_node: list[np.ndarray],
    W: np.ndarray | None,
    *,
    model: str = "linear",
    hidden: int = 64,
    steps: int = 300,
    batch_size: int = 32,
    lr: float = 0.1,
    eval_every: int = 20,
    X_test: np.ndarray | None = None,
    y_test: np.ndarray | None = None,
    seed: int = 0,
    use_kernel: bool = False,
    schedule: BirkhoffSchedule | ScheduleArrays | None = None,
    transport: str = "auto",
    rollout: str = "scan",
    on_segment=None,
    compression=None,
    staleness: StragglerPolicy | None = None,
    delays: np.ndarray | None = None,
    probes: HealthProbes | None = None,
    pi_hat: np.ndarray | None = None,
    tracer: Tracer | None = None,
    retrace_guard=None,
) -> MetricLogger:
    """D-SGD classification with per-node local data (Algorithm 1).

    Logs train loss (node mean) every step and test accuracy min/mean/max
    across nodes at eval points. ``rollout="scan"`` compiles the steps
    between consecutive eval points into single ``lax.scan`` rollouts (the
    per-step losses come back as one array per segment -- no host sync in
    the hot loop); ``rollout="loop"`` runs the same jitted step per
    iteration and produces a bit-identical trace.

    Online topology adaptation: with ``schedule`` as a fixed-shape
    ``ScheduleArrays`` the mixing schedule travels in the rollout carry
    as data, and ``on_segment(t) -> ScheduleArrays | None`` (called
    after each scan segment / at eval boundaries) can hot-swap it with
    zero retraces. The returned logger's ``aux`` dict records
    ``n_traces`` (compiled-rollout traces: one per distinct segment
    length -- swaps add none) and ``swaps`` (steps where a swap
    landed). ``compression`` composes with the online path exactly as
    in :func:`run_mean_estimation`: EF memory in the carry, compressed
    wire in ``aux["comm"]``, zero extra traces.

    ``staleness`` / ``delays`` turn on bounded-delay gossip exactly as
    in :func:`run_mean_estimation`: the half-step pytree is raveled
    into one (n, P) buffer, pushed into the staleness ring riding the
    scan carry, and mixed under the policy-resolved per-step schedule
    + effective delays (scan xs). Composes with ``compression`` (EF
    memory and stale ring in ONE carry) and ``on_segment`` hot swaps;
    all-zero delays are bitwise the fresh run. Scan rollout + online
    ``ScheduleArrays`` required.

    ``probes`` / ``pi_hat`` / ``tracer`` / ``retrace_guard`` work as in
    :func:`run_mean_estimation`: per-step health series land in
    ``logger.aux["health"]``, segments get ``sim.segment`` spans, and
    rollout compiles are counted under ``"classification.roll"``.
    Requires the online scan rollout; probe outputs are extra scan ys,
    so the loss trajectory is BITWISE the probes-off run's.
    """
    if rollout not in ("scan", "loop"):
        raise ValueError(f"unknown rollout {rollout!r}")
    online = isinstance(schedule, ScheduleArrays)
    if on_segment is not None and not online:
        raise ValueError(
            "on_segment hot-swapping needs the schedule as ScheduleArrays "
            "(a static BirkhoffSchedule is baked into the trace)"
        )
    compressor = make_compressor(compression)
    if compressor is not None and not online:
        raise ValueError(
            "compression rides the retrace-free data plane: pass the "
            "schedule as ScheduleArrays (static schedules have no EF carry)"
        )
    n = len(indices_per_node)
    delays_arr = _check_staleness_args(
        staleness, delays, steps, n, online, rollout
    )
    pi_hat = _check_probe_args(probes, pi_hat, n, online, rollout, staleness)
    tracer = _NULL_TRACER if tracer is None else tracer
    num_classes = int(y.max()) + 1
    dim = X.shape[1]
    data = _stack_node_data(X, y, indices_per_node)
    rng = jax.random.PRNGKey(seed)
    init_fn = (
        (lambda r: init_linear_classifier(r, dim, num_classes))
        if model == "linear"
        else (lambda r: init_mlp_classifier(r, dim, num_classes, hidden))
    )
    params0 = init_fn(rng)
    # same init on every node (theta_i^0 = theta^0, as in Algorithm 1)
    params = jax.tree_util.tree_map(lambda p: jnp.stack([p] * n), params0)
    state = dsgd_init(params)
    Wj = jnp.asarray(W, jnp.float32) if W is not None else None

    grad_fn = jax.grad(classifier_loss)

    def node_grads(p, x_node, y_node, length, k):
        idx = jax.random.randint(k, (batch_size,), 0, jnp.maximum(length, 1))
        xb = x_node[idx]
        yb = y_node[idx]
        loss = classifier_loss(p, xb, yb)
        return grad_fn(p, xb, yb), loss

    def step(carry, _, ph=None):
        if online and compressor is not None:
            params, state, key, e, sa = carry
            sched_t = sa
        elif online:
            params, state, key, sa = carry
            sched_t = sa
        else:
            params, state, key = carry
            sched_t = schedule
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, n)
        grads, losses = jax.vmap(node_grads)(params, data.x, data.y, data.lengths, keys)
        if compressor is not None:
            new_params, new_state, new_e = dsgd_step_stacked(
                params, grads, state, Wj, lr,
                use_kernel=use_kernel, schedule=sched_t, transport=transport,
                ef=e, compression=compressor,
            )
            out_carry = (new_params, new_state, key, new_e, sa)
        else:
            new_params, new_state = dsgd_step_stacked(
                params, grads, state, Wj, lr,
                use_kernel=use_kernel, schedule=sched_t, transport=transport,
            )
            out_carry = (
                (new_params, new_state, key, sa)
                if online
                else (new_params, new_state, key)
            )
        if probes is None:
            return out_carry, losses.mean()
        # extra scan ys only -- the loss trajectory is bitwise unchanged
        pv = compute_probes(
            probes, params_stack=new_params, grads_stack=grads,
            arrays=sched_t, pi_hat=ph,
        )
        return out_carry, (losses.mean(),) + tuple(pv.values())

    @jax.jit
    def eval_fn(params, X_t, y_t):
        return jax.vmap(lambda p: classifier_accuracy(p, X_t, y_t))(params)

    logger = MetricLogger()
    key = jax.random.PRNGKey(seed + 1)
    do_eval = X_test is not None
    X_t = jnp.asarray(X_test) if do_eval else None
    y_t = jnp.asarray(y_test) if do_eval else None

    def log_segment(t0: int, losses: np.ndarray, params, evaluate: bool) -> None:
        for j, loss in enumerate(losses):
            t = t0 + j
            last = j == len(losses) - 1
            if last and evaluate and (t % eval_every == 0 or t == steps - 1):
                accs = np.asarray(eval_fn(params, X_t, y_t))
                logger.log(
                    t,
                    loss=float(loss),
                    acc_mean=float(accs.mean()),
                    acc_min=float(accs.min()),
                    acc_max=float(accs.max()),
                    consensus=float(consensus_distance(params)),
                )
            else:
                logger.log(t, loss=float(loss))

    n_traces = 0
    swaps: list[int] = []
    probe_names = probes.names() if probes is not None else ()
    health_l: dict[str, list] = {nm: [] for nm in probe_names}
    ph = pi_hat  # None is a valid (empty-pytree) jit operand when tau_bar off

    def maybe_swap(t: int, carry):
        """Hot-swap the carried schedule if the hook hands back a new one."""
        if on_segment is None:
            return carry
        new_sa = on_segment(t)
        if new_sa is None:
            return carry
        swaps.append(t)
        return (*carry[:-1], new_sa)

    # on_segment needs segment boundaries even when there is no eval
    # data: segmenting is decoupled from evaluation (the eval calls
    # themselves stay gated on do_eval), so a hook-driven run without
    # X_test still swaps at eval_every boundaries -- identically in
    # both rollouts -- instead of silently degrading to one
    # end-of-run call.
    segmented = do_eval or on_segment is not None

    if staleness is not None:
        # bounded-delay branch: the half-step pytree ravels into one
        # (n, P) buffer so the ring holds ONE array; schedule + delays
        # arrive as scan xs (policy-resolved host-side per segment)
        flat0, ravel_spec = ravel_stack(params)
        buffer = stale_buffer_init(flat0, staleness.ring_depth)

        def stale_step(carry, x):
            if compressor is not None:
                params, state, key, e, buf = carry
            else:
                params, state, key, buf = carry
            g_t, p_t, d_t = x
            sa_t = ScheduleArrays(gammas=g_t, perms=p_t)
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, n)
            grads, losses = jax.vmap(node_grads)(
                params, data.x, data.y, data.lengths, keys
            )
            half = jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params, grads
            )
            flat, _ = ravel_stack(half)
            if compressor is not None:
                mixed, e, buf = ef_stale_mix_flat(
                    flat, e, buf, sa_t, d_t, compressor
                )
                rest = (e, buf)
            else:
                buf = stale_push(buf, flat)
                mixed = mix_schedule_arrays_stale(buf, sa_t, d_t)
                rest = (buf,)
            new_params = unravel_stack(mixed, ravel_spec)
            new_state = DSGDState(step=state.step + 1, momentum=None)
            return (new_params, new_state, key) + rest, losses.mean()

        def roll_stale_impl(carry, xs):
            nonlocal n_traces
            n_traces += 1
            if retrace_guard is not None:
                retrace_guard.record("classification.roll")
            return jax.lax.scan(stale_step, carry, xs)

        roll_stale = jax.jit(roll_stale_impl)
        if compressor is not None:
            carry = (params, state, key, jnp.zeros_like(flat0), buffer)
        else:
            carry = (params, state, key, buffer)
        base_sa = schedule
        t0 = 0
        for seg_len, evaluate in _eval_segments(steps, eval_every, segmented):
            xs = straggler_stream(
                staleness, base_sa, delays_arr[t0 : t0 + seg_len]
            )
            with tracer.span("sim.segment", t0=t0, k=seg_len):
                carry, losses = roll_stale(carry, xs)
                losses = jax.block_until_ready(losses)
            log_segment(t0, np.asarray(losses), carry[0], evaluate and do_eval)
            t0 += seg_len
            if t0 < steps and on_segment is not None:
                new_sa = on_segment(t0 - 1)
                if new_sa is not None:
                    base_sa = new_sa  # re-resolved from the next segment on
                    swaps.append(t0 - 1)
    elif rollout == "scan":
        @functools.partial(jax.jit, static_argnames=("length",))
        def roll(carry, length: int, ph=None):
            nonlocal n_traces
            n_traces += 1
            if retrace_guard is not None:
                retrace_guard.record("classification.roll")
            return jax.lax.scan(
                lambda c, x: step(c, x, ph), carry, None, length=length
            )

        if online and compressor is not None:
            carry = (params, state, key, ef_init(params), schedule)
        elif online:
            carry = (params, state, key, schedule)
        else:
            carry = (params, state, key)
        t0 = 0
        for seg_len, evaluate in _eval_segments(steps, eval_every, segmented):
            with tracer.span("sim.segment", t0=t0, k=seg_len):
                carry, traces = roll(carry, seg_len, ph)
                traces = jax.block_until_ready(traces)
            if probes is not None:
                losses = traces[0]
                for nm, series in zip(probe_names, traces[1:]):
                    health_l[nm].append(np.asarray(series))
            else:
                losses = traces
            log_segment(t0, np.asarray(losses), carry[0], evaluate and do_eval)
            t0 += seg_len
            if t0 < steps:  # no hook after the final segment (see above)
                carry = maybe_swap(t0 - 1, carry)
                if ph is not None:
                    # tau_bar tracks the hook's live estimator as a VALUE
                    ph = _live_pi_hat(on_segment, ph)
    else:
        def step_impl(carry, x):
            nonlocal n_traces
            n_traces += 1
            if retrace_guard is not None:
                retrace_guard.record("classification.roll")
            return step(carry, x)

        step_j = jax.jit(step_impl)
        if online and compressor is not None:
            carry = (params, state, key, ef_init(params), schedule)
        elif online:
            carry = (params, state, key, schedule)
        else:
            carry = (params, state, key)
        for t in range(steps):
            carry, loss = step_j(carry, None)
            log_segment(t, np.asarray(loss)[None], carry[0], do_eval)
            # same boundaries the scan segments end on, minus the final
            # step; the hook guard also keeps eval_every=0 runs (legal
            # when neither eval nor a hook needs boundaries) modulo-free
            if on_segment is not None and t % eval_every == 0 and t < steps - 1:
                carry = maybe_swap(t, carry)
    logger.aux["n_traces"] = n_traces
    logger.aux["swaps"] = swaps
    if probes is not None:
        empty = np.zeros((0,))
        logger.aux["health"] = {
            nm: (np.concatenate(v) if v else empty)
            for nm, v in health_l.items()
        }
    if online:
        meter = _online_comm_meter(
            n,
            sum(int(np.prod(np.asarray(p.shape))) for p in
                jax.tree_util.tree_leaves(params0)),
            compression=compressor,
        )
        if staleness is not None:
            delivered, deferred = _staleness_meter_fracs(delays_arr, staleness)
            meter.tick(steps, delivered_frac=delivered, deferred_frac=deferred)
            logger.aux["staleness"] = {
                "mode": staleness.mode, "tau_max": staleness.tau_max,
            }
        else:
            meter.tick(steps)
        logger.aux["comm"] = meter.summary()
        logger.aux["compression"] = (
            compressor.label if compressor is not None else None
        )
    return logger
