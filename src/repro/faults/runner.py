"""Mean-estimation D-SGD under injected faults, with crash recovery.

The faulty twin of ``repro.train.trainer.run_mean_estimation``'s online
driver, same step math op-for-op:

    grads = 2 (theta - z_bar)                    # quadratic task
    half  = theta - lr * grads                   # local half-step
    push half into the staleness ring buffer
    theta = sum_l gammas_t[l] * stale[perms_t[l]]  # degraded + delayed mix

The per-step fault data -- degraded ``(gammas, perms)`` tables and the
delay vector -- ride the ``lax.scan`` as xs with fixed shapes, so every
fault event (a crash's degraded-W swap, a straggler's buffer delay, the
post-rejoin renormalization back to the full schedule) is a pure value
change into ONE compiled rollout (``n_traces == 1``, asserted in tests
and the CI smoke bench). A zero-fault plan reproduces the fault-free
driver's trajectory bitwise (delays 0 read back the value just pushed;
``degrade_schedule`` with everyone alive is the identity).

Crash recovery: at segment boundaries the carry (theta, ring buffer,
and the CURRENT base schedule -- so a pre-crash topology refresh
survives) checkpoints via ``repro.train.checkpoints``; ``resume=True``
restores the latest checkpoint and continues bitwise, because every
fault draw is random-access from the plan's seed (no replay needed).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mixing import (
    ScheduleArrays,
    StragglerPolicy,
    WireCorruption,
    mix_schedule_arrays_screened,
    mix_schedule_arrays_stale,
    stale_buffer_init,
    stale_push,
)
from repro.obs.trace import Tracer
from repro.train.checkpoints import latest_step, restore_checkpoint, save_checkpoint
from repro.train.metrics import CommMeter, mix_bytes_per_step, sq_error_series

_NULL_TRACER = Tracer(enabled=False)

from .plan import FaultInjector, FaultPlan

__all__ = ["run_faulty_mean_estimation"]


def run_faulty_mean_estimation(
    task,
    plan: FaultPlan,
    schedule: ScheduleArrays,
    *,
    lr: float = 0.1,
    batch: int = 1,
    seed: int = 0,
    segment_len: int | None = None,
    on_segment: Callable | None = None,
    zs: np.ndarray | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    stop_after_segments: int | None = None,
    staleness: StragglerPolicy | None = None,
    quarantine=None,
    tracer: "Tracer | None" = None,
    retrace_guard=None,
) -> dict:
    """D-SGD mean estimation under a seeded fault plan.

    Args:
      task: a ``MeanEstimationTask`` (supplies ``theta_star`` and the
        observation sampler; ``zs`` overrides the presampled stream).
      plan: the fault trace; ``plan.steps`` is the run length.
      schedule: fault-free base topology as fixed-shape
        ``ScheduleArrays`` (refreshes swap it via ``on_segment``).
      segment_len: boundary spacing for the hook/checkpoints (defaults
        to one full-run segment).
      on_segment: ``hook(t) -> ScheduleArrays | None`` called after
        every segment except the last; a non-None return rebases the
        injector on the new topology (same shape). Same contract as the
        fault-free drivers, so an ``OnlineTopologyController`` plugs in
        unchanged.
      checkpoint_dir / checkpoint_every: save the carry every
        ``checkpoint_every``-th segment boundary (plus at an early
        stop). ``resume=True`` restores the newest checkpoint and
        continues bitwise; returned traces then cover only the resumed
        tail (``resumed_from`` records the restart step).
      stop_after_segments: execute at most this many segments in this
        process then return (the scripted "crash" of recovery drills);
        ``stopped_at`` records where.
      staleness: a ``StragglerPolicy`` resolving the plan's raw delays
        against a deadline. ``"wait"`` consumes every late payload at
        its (clamped) staleness; ``"degrade"`` treats past-deadline
        stragglers as offline for the step (one combined schedule
        repair with the crash/drop faults). The ring depth becomes the
        POLICY's ``ring_depth`` and the meter splits delivered bytes
        into on-time vs deferred (``comm["deferred_bytes"]``). ``None``
        keeps the PR 6 behavior: raw delays, ring sized by the plan.
      quarantine: a :class:`repro.faults.quarantine.QuarantineController`
        -- enables the screened transport (non-finite guard in-graph,
        norm/deviation screens host-side), folds the controller's mask
        into the injector's schedule repair at every segment boundary,
        and meters ``quarantined_bytes``. Routing is decided at TRACE
        time: with ``quarantine=None`` and a corruption-free plan the
        original unscreened scan body runs, so corruption-off arms are
        bitwise-identical to prior releases. A corrupting plan with
        ``quarantine=None`` runs the screened transport with the guard
        OFF -- the honest screen-off divergence baseline.
      tracer: a ``repro.obs.Tracer`` -- records ``sim.segment`` spans
        per rollout segment and ``faults.stream`` spans for the
        host-side fault resolution (via the injector).
      retrace_guard: a ``repro.obs.RetraceGuard`` -- rollout compiles
        are counted under ``"faults.roll"``.

    Returns a dict with the fault-free driver's keys
    (``mean/max/min_sq_error``, ``theta``, ``n_traces``, ``swaps``,
    ``comm``) plus ``resumed_from``, ``stopped_at``, and
    ``alive_frac`` (the plan's mean alive fraction over the run).
    """
    steps = plan.steps
    n = task.n_nodes
    if plan.n_nodes != n:
        raise ValueError(f"plan is for {plan.n_nodes} nodes, task for {n}")
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    seg = int(segment_len) if segment_len is not None else max(steps, 1)
    if seg < 1:
        raise ValueError(f"segment_len must be >= 1, got {segment_len}")

    rng = np.random.default_rng(seed)
    theta = jnp.zeros((n, 1))
    theta_star = jnp.asarray(task.theta_star, jnp.float32)
    if zs is None:
        # identical call sequence to run_mean_estimation: a zero-fault
        # plan at the same seed traverses the same observations
        zs_host = [task.sample(batch, rng) for _ in range(steps)]
        zs = np.stack(zs_host) if zs_host else np.zeros((0, n, batch))
    zs = jnp.asarray(zs, jnp.float32)
    if zs.ndim != 3 or zs.shape[0] != steps or zs.shape[1] != n:
        raise ValueError(f"zs must be ({steps}, {n}, batch), got {zs.shape}")

    depth = staleness.ring_depth if staleness is not None else plan.ring_depth
    buffer = stale_buffer_init(theta, depth)
    tracer = _NULL_TRACER if tracer is None else tracer
    injector = FaultInjector(
        plan, schedule, policy=staleness,
        tracer=tracer if tracer.enabled else None,
    )
    lr = float(lr)

    n_traces = 0
    # trace-time routing: the screened body only exists when the plan
    # corrupts or a quarantine controller screens -- a corruption-off
    # run compiles the EXACT prior scan, so its trajectory is bitwise
    screened = plan.has_corruption or quarantine is not None
    guard = quarantine is not None

    def roll_impl(carry, xs):
        nonlocal n_traces
        n_traces += 1
        if retrace_guard is not None:
            retrace_guard.record("faults.roll")

        def step(c, x):
            th, buf = c
            z, g_t, p_t, d_t = x
            grads = 2.0 * (th - z.mean(axis=1, keepdims=True))
            half = th - lr * grads
            buf = stale_push(buf, half)
            th = mix_schedule_arrays_stale(
                buf, ScheduleArrays(gammas=g_t, perms=p_t), d_t
            )
            return (th, buf), (jnp.square(th[:, 0] - theta_star),)

        return jax.lax.scan(step, carry, xs)

    def roll_screened_impl(carry, xs):
        nonlocal n_traces
        n_traces += 1
        if retrace_guard is not None:
            retrace_guard.record("faults.roll")

        def step(c, x):
            th, buf = c
            z, g_t, p_t, d_t, m_t, x_t = x
            grads = 2.0 * (th - z.mean(axis=1, keepdims=True))
            half = th - lr * grads
            buf = stale_push(buf, half)
            th, stats = mix_schedule_arrays_screened(
                buf,
                ScheduleArrays(gammas=g_t, perms=p_t),
                d_t,
                half,
                corrupt=WireCorruption(mult=m_t, xor=x_t),
                guard=guard,
            )
            err = jnp.square(th[:, 0] - theta_star)
            # live probes the host-side screen derives its honest-
            # deviation allowance from (max over nodes, not mean: the
            # zero-false-positive bound is a triangle inequality
            # against the worst honest node)
            hbar = jnp.mean(half, axis=0, keepdims=True)
            cons = jnp.max(jnp.sum(jnp.square(half - hbar), axis=1))
            gbar = jnp.mean(grads, axis=0, keepdims=True)
            gdev = jnp.max(jnp.sum(jnp.square(grads - gbar), axis=1))
            gbar_sq = jnp.sum(jnp.square(gbar))
            return (th, buf), (
                err, stats, cons, gdev, gbar_sq,
            )

        return jax.lax.scan(step, carry, xs)

    roll = jax.jit(roll_screened_impl if screened else roll_impl)

    t0 = 0
    resumed_from = None
    if checkpoint_dir is not None and resume:
        last = latest_step(checkpoint_dir)
        if last is not None:
            like = {
                "theta": theta,
                "buf": buffer.buf,
                "head": buffer.head,
                "gammas": injector.base.gammas,
                "perms": injector.base.perms,
            }
            tree, _meta = restore_checkpoint(checkpoint_dir, last, like)
            theta = jnp.asarray(tree["theta"])
            buffer = type(buffer)(
                buf=jnp.asarray(tree["buf"]), head=jnp.asarray(tree["head"])
            )
            injector.rebind(ScheduleArrays(
                gammas=jnp.asarray(tree["gammas"]),
                perms=jnp.asarray(tree["perms"]),
            ))
            t0 = int(last)
            resumed_from = t0

    def save(t: int) -> None:
        save_checkpoint(
            checkpoint_dir,
            t,
            {
                "theta": theta,
                "buf": buffer.buf,
                "head": buffer.head,
                "gammas": injector.base.gammas,
                "perms": injector.base.perms,
            },
            metadata={"t": int(t), "seed": int(seed)},
        )

    meter = CommMeter(per_step_bytes=mix_bytes_per_step(
        "allgather", n_nodes=n, p_total=1,
    ))
    nodes_l: list[np.ndarray] = []
    swaps: list[int] = []
    stopped_at = None
    seg_idx = 0
    carry = (theta, buffer)
    while t0 < steps:
        k = min(seg, steps - t0)
        gammas_k, perms_k, delays_k = injector.stream(t0, k)
        # the mask ACTIVE during this segment (transitions from ingest
        # below only land on the next one) -- also the honest basis for
        # this segment's quarantined-byte fate
        qmask = injector.quarantined.copy()
        with tracer.span("sim.segment", t0=t0, k=k):
            if screened:
                mult_k, xor_k = injector.corrupt_stream(t0, k)
                carry, (e_nodes, stats, cons, gdev, gbars) = roll(
                    carry,
                    (zs[t0 : t0 + k], jnp.asarray(gammas_k),
                     jnp.asarray(perms_k), jnp.asarray(delays_k),
                     jnp.asarray(mult_k), jnp.asarray(xor_k)),
                )
            else:
                carry, (e_nodes,) = roll(
                    carry,
                    (zs[t0 : t0 + k], jnp.asarray(gammas_k),
                     jnp.asarray(perms_k), jnp.asarray(delays_k)),
                )
            nodes_l.append(np.asarray(e_nodes))
        if staleness is not None:
            fates = [
                plan.transfer_fracs(
                    t, deadline=staleness.tau_max, mode=staleness.mode
                )
                for t in range(t0, t0 + k)
            ]
            on_time = float(np.mean([f[0] for f in fates]))
            deferred = float(np.mean([f[1] for f in fates]))
            q_frac = float(np.mean([
                plan.quarantined_frac(
                    t, qmask, deadline=staleness.tau_max, mode=staleness.mode
                )
                for t in range(t0, t0 + k)
            ])) if qmask.any() else 0.0
            meter.tick(
                k, delivered_frac=on_time + deferred, deferred_frac=deferred,
                quarantined_frac=q_frac,
            )
        else:
            frac = float(
                np.mean([plan.delivered_frac(t) for t in range(t0, t0 + k)])
            )
            q_frac = float(np.mean([
                plan.quarantined_frac(t, qmask) for t in range(t0, t0 + k)
            ])) if qmask.any() else 0.0
            meter.tick(k, delivered_frac=frac, quarantined_frac=q_frac)
        if quarantine is not None:
            new_mask = quarantine.ingest(
                t0, stats, gammas_k, perms_k,
                {"consensus_sq": np.asarray(cons),
                 "gdev_sq": np.asarray(gdev),
                 "gbar_sq": np.asarray(gbars)},
            )
            injector.set_quarantine(new_mask)
        t0 += k
        seg_idx += 1
        theta, buffer = carry
        if on_segment is not None and t0 < steps:
            update = on_segment(t0 - 1)
            if update is not None:
                injector.rebind(update)
                swaps.append(t0 - 1)
        if checkpoint_dir is not None and (
            seg_idx % checkpoint_every == 0 or t0 >= steps
        ):
            save(t0)
        if stop_after_segments is not None and seg_idx >= stop_after_segments and t0 < steps:
            if checkpoint_dir is not None and seg_idx % checkpoint_every != 0:
                save(t0)  # the crash drill must leave a resumable state
            stopped_at = t0
            break

    return {
        **sq_error_series(nodes_l, n),
        "theta": np.asarray(theta),
        "n_traces": n_traces,
        "swaps": swaps,
        "comm": meter.summary(),
        "resumed_from": resumed_from,
        "stopped_at": stopped_at,
        "alive_frac": plan.alive_frac(),
        "quarantine": None if quarantine is None else quarantine.summary(),
        # per-node (steps, n) error trace, screened path only: the bench
        # separates honest-node tail loss from the quarantined nodes'
        # solo-SGD error (the Byzantine-robust convention -- a liar's
        # own loss is not the defense's responsibility)
        "sq_error_nodes": (
            np.concatenate(nodes_l) if screened and nodes_l else None
        ),
    }
