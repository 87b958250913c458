"""Mesh-sharded large-model trainer: D-SGD over the data axis + tensor
parallelism over the model axis.

Three distribution modes (DESIGN.md Section 3.2):

* ``dsgd``     -- each index of the ``data`` mesh axis is one D-SGD node
                  holding its own model replica (params get a leading node
                  axis sharded over ``data``; each replica is TP-sharded over
                  ``model``). The mixing step executes the learned topology's
                  Birkhoff decomposition as a ``ppermute`` schedule
                  (d_max collective-permutes instead of an all-reduce).
* ``fsdp``     -- C-PSGD baseline / fallback: one global model, params
                  sharded over (data x model), gradients all-reduced by
                  GSPMD. Equivalent to D-SGD with W = 11^T/n.
* ``dsgd_pod`` -- multi-pod: pods are the D-SGD nodes (params stacked over
                  ``pod``); within a pod, classic data parallelism; across
                  pods, the sparse gossip schedule rides the slow DCN links.

``make_train_setup`` returns everything the launcher / dry-run needs:
the jitted-able step function, in/out shardings, and abstract input specs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.compression import (
    Compressor,
    ef_init,
    make_compressor,
    mix_arrays_sharded_ef,
    mix_arrays_sharded_stale_ef,
    mix_dense_sharded_ef,
    mix_ppermute_pool_ef,
    mix_ppermute_pool_stale_ef,
)
from repro.core.mixing import (
    BirkhoffSchedule,
    PermPool,
    PoolSwap,
    ScheduleArrays,
    ShardStaleState,
    StragglerPolicy,
    autotune_sharded_transport,
    mix_arrays_sharded,
    mix_arrays_sharded_stale,
    mix_dense_sharded,
    mix_ppermute,
    mix_ppermute_pool,
    mix_ppermute_pool_stale,
    straggler_pool_stream,
    straggler_stream,
)
from repro.models import registry
from repro.models.common import ModelConfig
from repro.obs.probes import HealthProbes
from repro.obs.trace import Tracer
from .checkpoints import latest_step, restore_checkpoint, save_checkpoint
from .metrics import CommMeter, mix_bytes_per_step, staleness_transfer_fracs
from .sharding import make_param_specs

# instrumented paths take an always-on tracer; callers opt in with a real one
_NULL_TRACER = Tracer(enabled=False)

PyTree = Any

__all__ = ["TrainSetup", "make_train_setup", "gossip_fn"]


@dataclasses.dataclass
class TrainSetup:
    """Everything needed to jit / lower a distributed train step.

    With ``online_w=True`` the step function takes the mixing matrix as
    a trailing *data* argument -- ``train_step(params, opt_state, batch,
    mix_w)`` -- so an online topology refresh swaps W by passing a
    different (n, n) array, never by rebuilding/retracing the step.
    """

    train_step: Callable  # (params, opt_state, batch[, mix_w]) -> (params, opt_state, loss)
    init_params: Callable  # (rng) -> params (abstract-safe via jax.eval_shape)
    param_specs: PyTree
    batch_spec: PyTree
    mode: str
    n_nodes: int
    online_w: bool = False
    # hot-swappable sharded mixing (online_w dsgd mode only):
    #   "allgather" -- mix_dense_sharded / mix_arrays_sharded (O(nP) bytes,
    #                  any W swaps with zero retraces)
    #   "pool"      -- mix_ppermute_pool over `pool` (O(K P) bytes; in-pool
    #                  gamma swaps are value changes, restages recompile)
    sharded_transport: str | None = None
    pool: PermPool | None = None
    # modeled bytes RECEIVED per node per mixing step (see
    # train.metrics.mix_bytes_per_step); None when nothing communicates
    comm_bytes_per_step: int | None = None
    # resolved wire format (repro.core.compression.Compressor) when the
    # online transports run EF-compressed gossip; None = uncompressed
    compression: "Compressor | None" = None
    # bounded-delay gossip policy (repro.core.mixing.StragglerPolicy).
    # When set, the step takes per-step delays as a second trailing data
    # argument -- train_step(params, opt_state, batch, mix_w, delays) --
    # and the sender-side stale ring travels in the opt-state dict under
    # "stale" (build it with init_opt_state). None = fresh gossip.
    staleness: "StragglerPolicy | None" = None
    # in-rollout health probes (repro.obs.HealthProbes; consensus /
    # grad_dev only -- tau_bar is a simulator probe). When set, the
    # step's loss output becomes the dict {"loss": ..., <probe>: ...}
    # of replicated scalars, computed INSIDE the shard_map as pure
    # collectives -- probe values per step, zero extra traces, and the
    # loss trajectory bitwise the probes-off run's.
    probes: "HealthProbes | None" = None

    def abstract_params(self) -> PyTree:
        return jax.eval_shape(self.init_params, jax.random.PRNGKey(0))

    def init_opt_state(self, params: PyTree):
        """Initial opt/comm state for ``train_step``, matching this
        setup's carried-state convention: ``None`` when nothing is
        carried, a bare momentum tree for plain momentum, a dict with
        ``"step"`` (gossip_every), ``"m"`` (momentum), and/or ``"ef"``
        (the per-node error-feedback memory of compressed mixing --
        required whenever ``compression`` is set)."""
        if self._init_opt_state is None:
            raise ValueError(
                "init_opt_state needs a setup built by make_train_setup"
            )
        return self._init_opt_state(params)

    def multi_step_fn(self, rollout: str = "scan") -> Callable:
        """Multi-step train fn: ``(params, opt_state, batches) -> (params,
        opt_state, losses)`` where every ``batches`` leaf carries a leading
        time axis ``(k, ...)`` of per-step batches.

        ``rollout="scan"`` compiles all ``k`` inner steps into one
        ``jax.lax.scan`` whose carry holds the (mixed) parameters and the
        opt/step state -- so ``gossip_every`` off-steps, the grad-accum
        microbatch scan, and the Birkhoff ppermute mixing all execute
        with no per-step Python dispatch and no host sync inside the
        segment (the per-step losses come back as one ``(k,)`` array).
        ``rollout="loop"`` dispatches the same jitted ``train_step`` per
        iteration from Python -- same trace per step, bit-identical
        trajectories (verified in tests/test_distributed.py) -- kept for
        debugging and A/B benchmarking, exactly like the simulator
        drivers in ``train/trainer.py``.

        Jit the scan variant (``jax.jit(setup.multi_step_fn())``) and
        feed it segments of ``k`` steps between eval points.

        With ``online_w=True`` both variants take the mixing matrix as a
        trailing argument -- ``multi_step(params, opt_state, batches,
        mix_w)`` -- and thread it through the scan as an ordinary traced
        operand: calling the same jitted multi-step with a refreshed W
        is a value change, not a shape change, so the hot swap compiles
        nothing (asserted in tests/test_distributed.py).

        With ``staleness`` set the signature grows per-STEP operands --
        ``multi_step(params, opt_state, batches, mix_stack, delays)``
        where ``mix_stack`` stacks the per-step mixing operand over a
        leading ``(k, ...)`` time axis (a ``ScheduleArrays`` of stacked
        gammas/perms, or ``(k, capacity)`` pool gammas) and ``delays``
        is ``(k, n)`` int32 -- both scanned as xs, so a straggler burst
        or a per-step degrade repair is pure data into the one trace.
        ``TrainSetup.run_segments`` builds these stacks from the policy
        and a raw delay trace; see ``straggler_stream`` /
        ``straggler_pool_stream``.
        """
        if rollout == "scan":
            def multi_step(params, momentum_state, batches, *mix_w):
                self._check_online_args(mix_w)
                stale = self.online_w and self.staleness is not None
                # fresh mixing operands are loop-invariant (closed over);
                # stale operands are per-step and scan as xs
                xs = (batches,) + mix_w if stale else batches

                def body(carry, x):
                    p, m = carry
                    step_args = x if stale else (x,) + mix_w
                    p, m, loss = self.train_step(p, m, *step_args)
                    return (p, m), loss

                (params, momentum_state), losses = jax.lax.scan(
                    body, (params, momentum_state), xs
                )
                return params, momentum_state, losses

            return multi_step
        if rollout == "loop":
            def multi_step(params, momentum_state, batches, *mix_w):
                self._check_online_args(mix_w)
                if self._jitted_step is None:
                    self._jitted_step = jax.jit(self.train_step)
                k = jax.tree_util.tree_leaves(batches)[0].shape[0]
                stale = self.online_w and self.staleness is not None
                losses = []
                for t in range(k):
                    batch_t = jax.tree_util.tree_map(lambda x: x[t], batches)
                    # per-step slices of the stacked stale operands; the
                    # fresh path passes mix_w through whole
                    extra = (
                        tuple(
                            jax.tree_util.tree_map(lambda x: x[t], w)
                            for w in mix_w
                        )
                        if stale
                        else mix_w
                    )
                    params, momentum_state, loss = self._jitted_step(
                        params, momentum_state, batch_t, *extra
                    )
                    losses.append(loss)
                # tree-stack, not jnp.stack: with probes the per-step
                # output is the {"loss", <probe>...} dict
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *losses
                )
                return params, momentum_state, stacked

            return multi_step
        raise ValueError(f"unknown rollout {rollout!r}")

    def _check_online_args(self, mix_w: tuple) -> None:
        if self.online_w and self.staleness is not None:
            if len(mix_w) != 2:
                raise TypeError(
                    "staleness setup: call multi_step(params, opt_state, "
                    "batches, mix_stack, delays)"
                )
            return
        if self.online_w and len(mix_w) != 1:
            raise TypeError(
                "online_w setup: call multi_step(params, opt_state, batches, mix_w)"
            )
        if not self.online_w and mix_w:
            raise TypeError(
                "this setup was built without online_w; no mix_w argument expected"
            )

    def run_segments(
        self,
        params,
        opt_state,
        batches,
        mix,
        *,
        segment_len: int,
        on_segment: Callable | None = None,
        rollout: str = "scan",
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume: bool = False,
        stop_after_segments: int | None = None,
        delays=None,
        quarantine=None,
        tracer: "Tracer | None" = None,
        retrace_guard=None,
    ) -> dict:
        """Segmented online rollout with hot-swap handoff at boundaries.

        Runs the jitted multi-step over ``segment_len``-step slices of
        ``batches`` (leaves ``(steps, ...)``), calling ``on_segment(t)``
        after every segment except the last (same contract as the
        simulator drivers in ``repro.train.trainer``). The hook may
        return:

        * ``None``            -- keep mixing with the current operand;
        * a ``ScheduleArrays`` or an ``(n, n)`` array -- swapped in as
          the next segments' ``mix_w`` (pure value change on the
          allgather transport: zero retraces);
        * a :class:`~repro.core.mixing.PoolSwap` -- pool-coordinate
          update: an in-pool swap replaces the gamma vector (zero
          retraces); a restage on the pool transport rebuilds the setup
          around the new pool and recompiles ONCE (counted in
          ``recompiles`` -- the logged pool-miss fallback), while on
          the all-gather transport (which executes pool gammas as their
          ``ScheduleArrays`` twin) even a restage is a pure value
          change.

        An overlapped refresh controller fits this hook unchanged: it
        returns ``None`` while its background solve runs and hands the
        finished swap back at a later boundary, so the rollout never
        waits on the solve.

        Crash recovery: with ``checkpoint_dir`` set, the carry
        (``params``, ``opt_state``, and the CURRENT mixing operand --
        so a pre-crash hot swap survives) is saved via
        ``repro.train.checkpoints`` every ``checkpoint_every``-th
        segment boundary, AFTER the hook (plus at the end and at an
        early stop). ``resume=True`` restores the newest checkpoint
        and continues; because the same jitted multi-step replays the
        same batch slices from the same restored values, the resumed
        trajectory is bitwise the uninterrupted one (asserted in
        tests). ``stop_after_segments`` ends the run early after that
        many executed segments -- the scripted "crash" of recovery
        drills -- recording ``stopped_at``. The checkpointed operand
        covers the value-swap paths (W / ScheduleArrays / in-pool
        gammas); a mid-run pool RESTAGE rebuilds the setup, which a
        checkpoint cannot capture -- resume from the returned ``setup``
        in that case.

        Bounded-delay gossip: on a ``staleness`` setup, ``delays`` is
        the raw ``(steps, n)`` non-negative delay trace (default all
        zeros -- bitwise the fresh run). Each segment resolves its slice
        against the policy host-side (``straggler_stream`` /
        ``straggler_pool_stream``) into per-step stacked operands, so
        wait-clamping, per-step degrade repairs, AND a hook's hot swap
        all stay value changes into the one compiled multi-step. The
        hook still trades in BASE operands (ScheduleArrays / pool
        gammas; dense W has no per-sender ring semantics and is
        rejected), and the checkpoint stores the base operand -- a
        resumed run re-resolves the same delays from ``t0``, bitwise.
        The meter splits delivered bytes into on-time vs deferred per
        the closed form (``comm["deferred_bytes"]``).

        Quarantine accounting: ``quarantine`` (duck-typed -- any object
        with ``mask() -> (n,) bool`` and ``summary() -> dict``, e.g. a
        :class:`repro.faults.quarantine.QuarantineController` whose
        screens run elsewhere) makes the meter charge the
        ``quarantined_bytes`` fate per segment from the all-gather
        closed form ``1 - (n-h)(n-h-1) / (n(n-1))`` for ``h`` isolated
        nodes (scaled into the delivered volume under staleness -- the
        model treats delay fates as independent of quarantine status),
        and the controller's lifecycle summary lands in the result
        under ``"quarantine"``. Typically the same controller also
        chains the topology hook: pass ``on_segment=qc.on_segment``.

        Telemetry: ``tracer`` (a ``repro.obs.Tracer``) records
        ``segment.rollout`` / ``segment.restage`` / ``segment.checkpoint``
        spans; ``retrace_guard`` (a ``repro.obs.RetraceGuard``) counts
        multi-step compiles under ``"run_segments.multi_step"``. On a
        ``probes`` setup the per-step health series come back under
        ``"health"`` (one ``(steps,)`` array per probe) while
        ``"losses"`` stays the plain loss trajectory.

        Returns ``{"params", "opt_state", "losses", "n_traces",
        "swaps", "recompiles", "segment_s", "comm", "setup", "mix",
        "resumed_from", "stopped_at"}``
        -- ``n_traces`` counts multi-step traces (1 when
        ``segment_len`` divides ``steps`` and no restage happened; a
        pool-transport restage adds exactly one), ``segment_s``
        per-segment wall seconds (the overlap benches' jitter probe),
        ``comm`` the :class:`~repro.train.metrics.CommMeter` summary of
        modeled mixing bytes. ``setup`` and ``mix`` are the LIVE setup
        (rebuilt if a restage happened -- continue chunked training
        from these, not from ``self``, or post-restage gammas would
        execute on the stale pool's staged permutations) and the final
        mixing operand.
        """
        if not self.online_w:
            raise ValueError("run_segments needs an online_w=True setup")
        if segment_len < 1:
            raise ValueError(f"segment_len must be >= 1, got {segment_len}")
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}"
            )
        steps = jax.tree_util.tree_leaves(batches)[0].shape[0]
        setup = self
        tracer = _NULL_TRACER if tracer is None else tracer
        n_traces = 0
        if self.staleness is None:
            if delays is not None:
                raise ValueError(
                    "delays given but this setup has no staleness policy: "
                    "build with make_train_setup(staleness=StragglerPolicy(...))"
                )
        else:
            delays = (
                np.zeros((steps, setup.n_nodes), np.int64)
                if delays is None
                else np.asarray(delays, np.int64)
            )
            if delays.shape != (steps, setup.n_nodes):
                raise ValueError(
                    f"delays must be ({steps}, {setup.n_nodes}), "
                    f"got {delays.shape}"
                )
            if delays.size and delays.min() < 0:
                raise ValueError("delays must be non-negative")

        def jit_counted(ms):
            def counted(p, m, b, *w):
                nonlocal n_traces
                n_traces += 1
                if retrace_guard is not None:
                    retrace_guard.record("run_segments.multi_step")
                return ms(p, m, b, *w)

            return jax.jit(counted)

        msj = jit_counted(setup.multi_step_fn(rollout))
        pool = setup.pool
        mix = _as_mix_operand(mix, setup, pool)

        def stale_stream(base, d_seg):
            # resolve this segment's delay slice against the policy into
            # per-step stacked scan operands (host-side control plane)
            pol = setup.staleness
            if isinstance(base, ScheduleArrays):
                g, p, eff = straggler_stream(pol, base, d_seg)
                return ScheduleArrays(gammas=g, perms=p), eff
            arr = np.asarray(base)
            if arr.ndim == 1:
                g, eff = straggler_pool_stream(pol, base, pool, d_seg)
                return g, eff
            raise ValueError(
                "staleness needs a ScheduleArrays or pool-gamma mixing "
                "operand: a dense (n, n) W has no per-sender payload to "
                "delay (decompose it with schedule_from_matrix)"
            )

        meter = CommMeter(per_step_bytes=setup.comm_bytes_per_step or 0)
        losses, swaps, segment_s = [], [], []
        probe_names = (
            setup.probes.names() if setup.probes is not None else ()
        )
        health_l: dict[str, list] = {nm: [] for nm in probe_names}
        recompiles = 0
        t0 = 0
        resumed_from = None
        stopped_at = None
        if checkpoint_dir is not None and resume:
            last = latest_step(checkpoint_dir)
            if last is not None:
                like = {"params": params, "opt": opt_state, "mix": mix}
                tree, _meta = restore_checkpoint(checkpoint_dir, last, like)
                params, opt_state, mix = tree["params"], tree["opt"], tree["mix"]
                t0 = int(last)
                resumed_from = t0

        def save(t: int) -> None:
            with tracer.span("segment.checkpoint", t=int(t)):
                save_checkpoint(
                    checkpoint_dir,
                    t,
                    {"params": params, "opt": opt_state, "mix": mix},
                    metadata={"t": int(t)},
                )

        seg_idx = 0
        while t0 < steps:
            k = min(segment_len, steps - t0)
            seg = jax.tree_util.tree_map(lambda x: x[t0 : t0 + k], batches)
            tic = time.perf_counter()
            with tracer.span("segment.rollout", t0=t0, k=k):
                if setup.staleness is not None:
                    d_seg = delays[t0 : t0 + k]
                    w_stack, eff = stale_stream(mix, d_seg)
                    params, opt_state, loss = msj(
                        params, opt_state, seg, w_stack, eff
                    )
                else:
                    params, opt_state, loss = msj(params, opt_state, seg, mix)
                # segment wall time is the overlap probe (loss may be the
                # probes dict -- block on the whole tree)
                loss = jax.block_until_ready(loss)
            segment_s.append(time.perf_counter() - tic)
            if quarantine is not None:
                h = int(np.asarray(quarantine.mask(), bool).sum())
                n = setup.n_nodes
                q_share = (
                    1.0 - (n - h) * (n - h - 1) / (n * (n - 1))
                    if n > 1 and h > 0 else 0.0
                )
            else:
                q_share = 0.0
            if setup.staleness is not None:
                fates = [
                    staleness_transfer_fracs(
                        d_seg[j], setup.staleness.tau_max, setup.staleness.mode
                    )
                    for j in range(k)
                ]
                on_time = float(np.mean([f[0] for f in fates]))
                deferred = float(np.mean([f[1] for f in fates]))
                delivered = on_time + deferred
                meter.tick(
                    k, delivered_frac=delivered, deferred_frac=deferred,
                    quarantined_frac=delivered * q_share,
                )
            else:
                meter.tick(k, quarantined_frac=q_share)
            if probe_names:
                losses.append(np.asarray(loss["loss"]))
                for nm in probe_names:
                    health_l[nm].append(np.asarray(loss[nm]))
            else:
                losses.append(np.asarray(loss))
            t0 += k
            seg_idx += 1
            # no hook after the final segment (nothing executes it)
            if on_segment is not None and t0 < steps:
                update = on_segment(t0 - 1)
                if update is not None:
                    swaps.append(t0 - 1)
                    if isinstance(update, PoolSwap) and update.restaged:
                        pool = update.pool
                        if setup.sharded_transport == "pool":
                            # pool miss: the new atoms are not compiled in
                            # -- rebuild the step around the restaged pool
                            # (the ONE counted recompile)
                            with tracer.span("segment.restage", t=t0 - 1):
                                setup = setup._rebuild(pool)
                                msj = jit_counted(setup.multi_step_fn(rollout))
                            recompiles += 1
                            meter.set_rate(
                                setup.comm_bytes_per_step or 0, step=t0
                            )
                        # on the all-gather transport the restaged atoms
                        # execute as ScheduleArrays data: no rebuild, no
                        # recompile
                    mix = _as_mix_operand(update, setup, pool)
            if checkpoint_dir is not None and (
                seg_idx % checkpoint_every == 0 or t0 >= steps
            ):
                save(t0)
            if (
                stop_after_segments is not None
                and seg_idx >= stop_after_segments
                and t0 < steps
            ):
                if checkpoint_dir is not None and seg_idx % checkpoint_every != 0:
                    save(t0)  # the crash drill must leave a resumable state
                stopped_at = t0
                break
        out = {
            "params": params,
            "opt_state": opt_state,
            "losses": np.concatenate(losses) if losses else np.zeros((0,)),
            "n_traces": n_traces,
            "swaps": swaps,
            "recompiles": recompiles,
            "segment_s": segment_s,
            "comm": meter.summary(),
            "setup": setup,
            "mix": mix,
            "resumed_from": resumed_from,
            "stopped_at": stopped_at,
        }
        if quarantine is not None:
            out["quarantine"] = quarantine.summary()
        if probe_names:
            empty = np.zeros((0,))
            out["health"] = {
                nm: (np.concatenate(v) if v else empty)
                for nm, v in health_l.items()
            }
        return out

    # rebuilds this setup around a restaged PermPool (set by
    # make_train_setup; a manually constructed TrainSetup cannot restage)
    _rebuild: Callable | None = dataclasses.field(
        default=None, repr=False, compare=False
    )

    # builds the initial opt/comm state (set by make_train_setup, which
    # knows the momentum/gossip_every/compression carry convention)
    _init_opt_state: Callable | None = dataclasses.field(
        default=None, repr=False, compare=False
    )

    # cached jax.jit of train_step for the "loop" rollout (recompiling it
    # per multi_step call would defeat the A/B comparison)
    _jitted_step: Callable | None = dataclasses.field(
        default=None, repr=False, compare=False
    )


def _as_mix_operand(update, setup: "TrainSetup", pool: PermPool | None):
    """Normalize a hook return / initial mix into the step's operand.

    ``pool`` is the CURRENTLY staged pool (tracked by ``run_segments``
    across restages). Pool-coordinate gammas are accepted on either
    transport: the pool transport consumes them directly; the
    all-gather transport (e.g. ``sharded_transport="auto"`` resolving
    against the pool) executes them as ``pool.arrays_for(gammas)`` --
    the bitwise-equal ScheduleArrays twin -- so the same controller
    drives both without caring which transport won the autotune.
    """
    if isinstance(update, PoolSwap):
        update = update.gammas
    if isinstance(update, ScheduleArrays):
        return update
    arr = np.asarray(update, np.float32)
    if setup.sharded_transport == "pool":
        if arr.shape != (setup.pool.capacity,):
            raise ValueError(
                f"pool transport expects ({setup.pool.capacity},) gammas, "
                f"got {arr.shape}"
            )
        return jnp.asarray(arr)
    if pool is not None and arr.ndim == 1:
        if arr.shape != (pool.capacity,):
            raise ValueError(
                f"pool-coordinate gammas must be ({pool.capacity},), "
                f"got {arr.shape}"
            )
        return pool.arrays_for(arr)
    return jnp.asarray(arr)


def gossip_fn(
    mesh: Mesh, schedule: BirkhoffSchedule | None, axis: str, param_specs: PyTree
) -> Callable[[PyTree], PyTree]:
    """Mixing transport over ``axis``: Birkhoff ppermute schedule, or pmean
    when ``schedule`` is None (complete graph / C-PSGD)."""

    node_specs = jax.tree_util.tree_map(
        lambda s: P(axis), param_specs, is_leaf=lambda x: isinstance(x, P)
    )

    def mix(params: PyTree) -> PyTree:
        def inner(p):
            if schedule is None:
                # f32 reduction: numerics + XLA-CPU bf16 all-reduce workaround
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.pmean(x.astype(jnp.float32), axis).astype(x.dtype),
                    p,
                )
            return mix_ppermute(p, schedule, axis)

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(node_specs,),
            out_specs=node_specs,
            axis_names={axis},
            check_vma=False,
        )(params)

    return mix


def _sgd_update(params, grads, momentum_state, lr, momentum):
    if momentum > 0.0:
        new_m = jax.tree_util.tree_map(
            lambda m, g: momentum * m + g, momentum_state, grads
        )
        new_p = jax.tree_util.tree_map(lambda p, m: p - lr * m, params, new_m)
        return new_p, new_m
    new_p = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return new_p, momentum_state


def make_train_setup(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    mode: str = "dsgd",
    schedule: BirkhoffSchedule | None = None,
    lr: float = 1e-3,
    momentum: float = 0.0,
    impl: str = "xla",
    grad_accum: int = 1,
    gossip_every: int = 1,
    online_w: bool = False,
    sharded_transport: str = "auto",
    pool: PermPool | None = None,
    compression: "Compressor | str | None" = None,
    staleness: "StragglerPolicy | None" = None,
    probes: "HealthProbes | None" = None,
) -> TrainSetup:
    """Build the distributed train step for (cfg, mesh, mode).

    ``schedule=None`` in dsgd/dsgd_pod modes means complete-graph mixing.
    ``online_w=True`` builds the *online-adaptation* step: the mixing
    operand is a trailing data argument (``train_step(params,
    opt_state, batch, mix_w)``) instead of a baked-in schedule, so a
    mid-training topology refresh swaps it with zero retraces. In dsgd
    mode the per-node mixing transport is then picked by
    ``sharded_transport``:

    * ``"allgather"`` -- ``mix_w`` is an (n, n) W (``mix_dense_sharded``)
      or a ``ScheduleArrays`` (``mix_arrays_sharded``): any topology
      swaps as data, at O(n P) bytes per node per step.
    * ``"pool"``      -- requires ``pool``; ``mix_w`` is the
      ``(pool.capacity,)`` gamma vector and mixing runs as
      ``mix_ppermute_pool``: O(pool.n_comm_slots x P) bytes -- the
      learned topology's sparse-communication payoff -- and in-pool
      swaps are pure value changes. Out-of-pool refreshes restage via
      ``TrainSetup.run_segments`` (one counted recompile).
    * ``"auto"``      -- the measured sharded autotune table when a
      bucket exists, else the ``preferred_sharded_transport`` closed
      form (``repro.core.mixing``); resolves to ``"allgather"`` when no
      pool is given. The resolved choice is recorded on
      ``TrainSetup.sharded_transport``.

    Incompatible with a static ``schedule`` and with fsdp mode (whose
    all-reduce has no W); ``pool`` requires online_w dsgd mode (the
    dsgd_pod online path mixes by GSPMD einsum, W as data).
    ``grad_accum > 1`` splits the per-step batch into microbatches and
    accumulates gradients in a scan -- same math, ~grad_accum x smaller
    live-activation footprint (the big lever for DeepSeek-V2 -- §Perf).
    ``gossip_every = k > 1`` mixes only every k-th step (time-varying
    W^(t) with W = I on off-steps -- covered by the paper's changing-
    topology analysis): amortizes gossip bytes by 1/k. The step function
    then takes a step counter through the momentum_state slot convention
    (see train_step signature below: ``step`` is carried in opt state).

    ``compression`` (a ``repro.core.compression.Compressor`` or a spec
    string -- ``"identity"``, ``"bf16"``, ``"topk:<frac>"``) turns the
    online mixing into CHOCO-style EF-compressed gossip: every
    transport's payload passes through the wire format, the per-node
    error-feedback memory travels in the opt-state dict under ``"ef"``
    (build it with ``TrainSetup.init_opt_state`` -- it rides the scan
    carry, so hot swaps stay zero-retrace), and
    ``TrainSetup.comm_bytes_per_step`` meters the compressed wire
    (bf16: exactly half; top-k: k value+index pairs). Only the
    retrace-free dsgd online transports compose: fsdp (all-reduce, no
    per-edge payload -- e.g. ``compression="topk:0.1"`` with
    ``mode="fsdp"`` is meaningless), dsgd_pod (GSPMD einsum, no EF
    carry), and offline (static-schedule) setups are rejected
    explicitly. The identity wire routes to the uncompressed transports
    at trace time, so it is bitwise the ``compression=None`` run -- the
    A/B control arm.

    ``staleness`` (a ``repro.core.mixing.StragglerPolicy``) turns the
    online mixing into bounded-delay gossip: every node keeps a
    sender-side ring of its last ``tau_max + 1`` wire payloads in the
    opt-state dict under ``"stale"`` (build it with
    ``TrainSetup.init_opt_state`` -- it rides the scan carry next to
    the EF memory, so hot swaps stay zero-retrace), and the step takes
    a per-step ``(n,)`` delay vector as a second trailing data argument
    after ``mix_w``. A straggler's payload is then consumed
    ``delays[i]`` pushes old; ``delays == 0`` reads back the value just
    pushed, reproducing the fresh transports bitwise. Only the
    per-sender-payload transports compose (ScheduleArrays on allgather,
    gammas on pool -- a dense (n, n) ``mix_w`` is rejected at mix
    time); fsdp/dsgd_pod (no per-node ring) and ``gossip_every > 1``
    (off-steps would desynchronize ring pushes from consumption) are
    rejected explicitly. Composes with ``compression``: the ring then
    stores the compressed wire payload and the EF memory stays local
    and fresh (see ``repro.core.compression``).

    ``probes`` (a ``repro.obs.HealthProbes``; ``consensus`` and
    ``grad_dev`` only) threads the paper's health quantities through
    the shard_map as collective value computations (``pmean`` /
    ``psum`` over the node axis -- same numbers as the stacked-host
    probes, asserted in tests): the step's loss output becomes the
    ``{"loss", <probe>...}`` dict of replicated scalars, per-step
    series land in ``run_segments``' ``"health"``, and the loss
    trajectory is BITWISE the probes-off run's. ``tau_bar`` is
    rejected here -- the pool transport never materializes W's
    coefficients in the carry; use the simulator drivers. Requires the
    online_w dsgd step (fsdp has one global model, so consensus is
    identically zero; dsgd_pod mixes by GSPMD einsum outside the
    manual node axis).
    """
    compressor = make_compressor(compression)
    if probes is not None:
        if not isinstance(probes, HealthProbes):
            raise TypeError(
                f"probes must be a HealthProbes, got {type(probes).__name__}"
            )
        if probes.tau_bar:
            raise ValueError(
                "the tau_bar probe needs the in-carry ScheduleArrays of the "
                "simulator drivers (run_mean_estimation / run_classification); "
                "the mesh transports never carry W's coefficients"
            )
        if mode != "dsgd":
            raise ValueError(
                f"health probes are incompatible with mode={mode!r}: they "
                "are collectives over the manual dsgd node axis (fsdp has "
                "one global model -- consensus is identically 0; dsgd_pod "
                "mixes by GSPMD einsum)"
            )
        if not online_w:
            raise ValueError(
                "health probes ride the online (retrace-free) step: build "
                "with online_w=True"
            )
    if staleness is not None:
        if not isinstance(staleness, StragglerPolicy):
            raise TypeError(
                f"staleness must be a StragglerPolicy, got {type(staleness)}"
            )
        if mode != "dsgd":
            raise ValueError(
                f"staleness is incompatible with mode={mode!r}: the "
                "bounded-delay ring is per-NODE sender state, which only "
                "the dsgd shard_map transports carry (fsdp all-reduces "
                "in-network; dsgd_pod mixes by GSPMD einsum)"
            )
        if not online_w:
            raise ValueError(
                "staleness rides the online (retrace-free) transports: "
                "build with online_w=True"
            )
        if gossip_every > 1:
            raise ValueError(
                f"staleness is incompatible with gossip_every={gossip_every}: "
                "off-steps would push no ring slot while delays keep "
                "counting pushes, silently re-basing every delay -- run "
                "bounded-delay gossip with gossip_every=1"
            )
    if compressor is not None:
        if mode == "fsdp":
            raise ValueError(
                f"compression={compressor.label!r} is incompatible with "
                "mode='fsdp': the C-PSGD baseline mixes by in-network "
                "all-reduce, so there is no per-edge gossip payload for a "
                "wire format to compress"
            )
        if mode == "dsgd_pod":
            raise ValueError(
                f"compression={compressor.label!r} is incompatible with "
                "mode='dsgd_pod': cross-pod mixing is a GSPMD einsum with "
                "no EF memory carry; use mode='dsgd'"
            )
        if not online_w:
            raise ValueError(
                "compression rides the online (retrace-free) transports: "
                "build with online_w=True"
            )
    if online_w and mode == "fsdp":
        raise ValueError("online_w needs a node axis (dsgd/dsgd_pod); fsdp has no W")
    if online_w and schedule is not None:
        raise ValueError(
            "online_w and a static schedule are mutually exclusive -- pass the "
            "initial W as the mix_w argument of the step instead"
        )
    if sharded_transport not in ("auto", "allgather", "pool"):
        raise ValueError(f"unknown sharded_transport {sharded_transport!r}")
    if pool is not None and not (online_w and mode == "dsgd"):
        raise ValueError("a PermPool requires online_w=True and mode='dsgd'")
    if sharded_transport == "pool" and pool is None:
        raise ValueError("sharded_transport='pool' requires a PermPool")
    axes = mesh.axis_names
    if mode == "dsgd":
        node_axis = "data"
        n_nodes = mesh.shape["data"]
        fsdp_axis = None
    elif mode == "dsgd_pod":
        if "pod" not in axes:
            raise ValueError("dsgd_pod requires a 'pod' mesh axis")
        node_axis = "pod"
        n_nodes = mesh.shape["pod"]
        fsdp_axis = "data"
    elif mode == "fsdp":
        node_axis = None
        n_nodes = 1
        fsdp_axis = "data"
    else:
        raise ValueError(f"unknown mode {mode}")

    if schedule is not None and node_axis is not None and schedule.n_nodes != n_nodes:
        raise ValueError(
            f"schedule has {schedule.n_nodes} nodes, mesh axis '{node_axis}' "
            f"provides {n_nodes}"
        )

    def init_single(rng):
        return registry.init_model(rng, cfg)

    if node_axis is not None:
        def init_params(rng):
            p = init_single(rng)
            # Algorithm 1: theta_i^(0) = theta^(0) -- same init on all nodes.
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n_nodes,) + x.shape), p
            )
    else:
        init_params = init_single

    if pool is not None and pool.n_nodes != n_nodes:
        raise ValueError(
            f"pool is staged for {pool.n_nodes} nodes, mesh axis "
            f"'{node_axis}' provides {n_nodes}"
        )

    params_proto = jax.eval_shape(init_params, jax.random.PRNGKey(0))
    param_specs = make_param_specs(
        params_proto, mesh, node_axis=node_axis, fsdp_axis=fsdp_axis
    )

    # per-NODE parameter count (leaves carry the leading node axis in
    # node modes) -- the P of the bytes/step accounting and the sharded
    # autotune bucket. TP over `model` divides the per-DEVICE share, not
    # the per-node collective volume modeled here.
    p_total = sum(
        int(np.prod(leaf.shape[1:] if node_axis is not None else leaf.shape,
                    dtype=np.int64))
        for leaf in jax.tree_util.tree_leaves(params_proto)
    )

    # Resolve the hot-swappable sharded transport (satellite of ISSUE 5:
    # consult the measured table / closed form instead of hardcoding the
    # all-gather). Lookup-only: unmeasured hardware falls back to the
    # conservative preferred_sharded_transport crossover.
    resolved_transport: str | None = None
    comm_bytes: int | None = None
    if mode == "dsgd":
        if online_w:
            if sharded_transport == "auto":
                resolved_transport = (
                    "allgather"
                    if pool is None
                    else autotune_sharded_transport(
                        n_nodes, pool.n_comm_slots, p_total
                    )
                )
            else:
                resolved_transport = sharded_transport
            comm_bytes = mix_bytes_per_step(
                "pool" if resolved_transport == "pool" else "allgather",
                n_nodes=n_nodes,
                p_total=p_total,
                n_comm_atoms=pool.n_comm_slots if resolved_transport == "pool" else None,
                compression=compressor,
            )
        elif schedule is not None:
            comm_bytes = mix_bytes_per_step(
                "ppermute", n_nodes=n_nodes, p_total=p_total,
                n_comm_atoms=schedule.n_communication_atoms,
            )
        else:
            comm_bytes = mix_bytes_per_step(
                "allreduce", n_nodes=n_nodes, p_total=p_total
            )

    # batch sharding:
    #   dsgd:      leaves (n_nodes, per_node, ...) -> P(data, None, ...)
    #   dsgd_pod:  leaves (n_pod, per_pod, ...)    -> P(pod, data, ...)
    #   fsdp:      leaves (batch, ...)             -> P((pod?, data), ...)
    if mode == "dsgd":
        batch_prefix = ("data", None)
    elif mode == "dsgd_pod":
        batch_prefix = ("pod", "data")
    else:
        # true-FSDP batch sharding: batch over data AND model (weights are
        # gathered per layer-group; grads reduce-scatter back)
        dp = ("pod", "data", "model") if "pod" in axes else ("data", "model")
        batch_prefix = (tuple(dp),)

    def batch_spec_for(leaf_ndim: int) -> P:
        pad = [None] * (leaf_ndim - len(batch_prefix))
        return P(*batch_prefix, *pad)

    loss_of = lambda p, b: registry.loss_fn(p, cfg, b, impl=impl)[0]
    grad_of_single = jax.value_and_grad(loss_of)

    if grad_accum > 1:
        def grad_of(p, b):
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum, *x.shape[1:]),
                b,
            )

            def body(acc, mb):
                loss_acc, g_acc = acc
                loss, g = grad_of_single(p, mb)
                g_new = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (loss_acc + loss, g_new), None

            zeros = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p
            )
            (loss_sum, g_sum), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros), micro)
            g_mean = jax.tree_util.tree_map(
                lambda g, x: (g / grad_accum).astype(x.dtype), g_sum, p
            )
            return loss_sum / grad_accum, g_mean
    else:
        grad_of = grad_of_single

    def _step_impl(params, momentum_state, batch, mix_w=None, delays=None):
        if node_axis is None:
            with jax.named_scope("dsgd.grad"):
                loss, grads = grad_of(params, batch)
            with jax.named_scope("dsgd.update"):
                new_params, new_m = _sgd_update(
                    params, grads, momentum_state, lr, momentum
                )
            return new_params, new_m, loss

        if mode == "dsgd_pod":
            # Cross-pod gossip as a dense mixing einsum over the (tiny) pod
            # axis: GSPMD lowers the contraction over the pod-sharded axis
            # to cross-pod collectives. (A partial-manual shard_map over
            # `pod` with auto data/model axes crashes this XLA version's
            # SPMD partitioner -- see EXPERIMENTS.md.)
            import numpy as _np

            with jax.named_scope("dsgd.grad"):
                losses, grads = jax.vmap(grad_of)(params, batch)
            with jax.named_scope("dsgd.update"):
                half, new_m = _sgd_update(
                    params, grads, momentum_state, lr, momentum
                )
            if online_w:
                if isinstance(mix_w, ScheduleArrays) or getattr(mix_w, "ndim", 2) != 2:
                    raise TypeError(
                        "dsgd_pod online mixing is a GSPMD einsum over the pod "
                        "axis: pass mix_w as a dense (n, n) W (pool gammas / "
                        "ScheduleArrays are dsgd-mode operands)"
                    )
            with jax.named_scope("dsgd.gossip"):
                if online_w:
                    W_pod = mix_w.astype(jnp.float32)
                else:
                    W_pod = (
                        jnp.asarray(schedule.to_matrix(), jnp.float32)
                        if schedule is not None
                        else jnp.full((n_nodes, n_nodes), 1.0 / n_nodes, jnp.float32)
                    )
                mixed = jax.tree_util.tree_map(
                    lambda x: jnp.einsum(
                        "pq,q...->p...", W_pod, x.astype(jnp.float32)
                    ).astype(x.dtype),
                    half,
                )
            return mixed, new_m, losses.mean()

        # The node axis is *manual* (shard_map over `node_axis`): each shard
        # owns exactly one node's replica, so node-local activations can
        # never silently replicate across nodes. TP over `model` (and, in
        # dsgd_pod mode, data-parallel grads over `data`) stays automatic
        # inside the shard.
        squeeze = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
        unsqueeze = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)

        def per_node(p, m, b, *w_args):
            p1, b1 = squeeze(p), squeeze(b)
            step = m.get("step") if isinstance(m, dict) else None
            m_tree = m.get("m") if isinstance(m, dict) else m
            m1 = squeeze(m_tree) if momentum > 0.0 else None
            ef_tree = m.get("ef") if isinstance(m, dict) else None
            if compressor is not None and ef_tree is None:
                raise ValueError(
                    "compressed mixing carries its error-feedback memory in "
                    "the opt state: pass momentum_state including an 'ef' "
                    "entry (build it with TrainSetup.init_opt_state)"
                )
            e1 = squeeze(ef_tree) if ef_tree is not None else None
            stale_tree = m.get("stale") if isinstance(m, dict) else None
            if staleness is not None and stale_tree is None:
                raise ValueError(
                    "bounded-delay mixing carries its sender-side ring in "
                    "the opt state: pass momentum_state including a 'stale' "
                    "entry (build it with TrainSetup.init_opt_state)"
                )
            st1 = (
                ShardStaleState(
                    rings=squeeze(stale_tree["buf"]), head=stale_tree["head"]
                )
                if stale_tree is not None
                else None
            )
            # In dsgd_pod mode the within-pod `data` axis stays automatic:
            # GSPMD data-parallelizes the loss/grad over it (the batch input
            # sharding carries P(pod, data, ...)).
            with jax.named_scope("dsgd.grad"):
                loss, grads = grad_of(p1, b1)
            with jax.named_scope("dsgd.update"):
                half, new_m = _sgd_update(p1, grads, m1, lr, momentum)

            def do_mix(h):
                if online_w:
                    w = w_args[0]
                    if resolved_transport == "pool":
                        return mix_ppermute_pool(h, w, pool, node_axis)
                    if isinstance(w, ScheduleArrays):
                        return mix_arrays_sharded(h, w, node_axis)
                    return mix_dense_sharded(h, w, node_axis)
                if schedule is None:
                    return jax.tree_util.tree_map(
                        lambda x: jax.lax.pmean(x.astype(jnp.float32), node_axis).astype(x.dtype),
                        h,
                    )
                return mix_ppermute(h, schedule, node_axis)

            def do_mix_ef(he):
                # EF-compressed online transports: same dispatch as
                # do_mix, with the wire format static and the EF memory
                # threaded as data (the hot-swap story is unchanged)
                h, e = he
                w = w_args[0]
                if resolved_transport == "pool":
                    return mix_ppermute_pool_ef(
                        h, e, w, pool, node_axis, compressor
                    )
                if isinstance(w, ScheduleArrays):
                    return mix_arrays_sharded_ef(h, e, w, node_axis, compressor)
                return mix_dense_sharded_ef(h, e, W=w, axis_name=node_axis,
                                            compressor=compressor)

            if gossip_every > 1 and step is None:
                raise ValueError(
                    "gossip_every > 1 needs a step counter: pass "
                    "momentum_state={'step': jnp.zeros((), jnp.int32), 'm': ...}"
                )
            with jax.named_scope("dsgd.gossip"):
                new_e1 = None
                new_st1 = None
                if staleness is not None:
                    # bounded-delay dispatch: same transport fork as do_mix,
                    # with the sender-side ring and this step's delay vector
                    # threaded as data (gossip_every > 1 was rejected at
                    # build time, so every step both pushes and mixes)
                    w, d = w_args
                    stale_dense_msg = (
                        "staleness needs a per-sender payload to delay: pass "
                        "mix_w as ScheduleArrays (allgather) or pool gammas, "
                        "not a dense (n, n) W"
                    )
                    if compressor is not None:
                        if resolved_transport == "pool":
                            mixed, new_e1, new_st1 = mix_ppermute_pool_stale_ef(
                                half, e1, st1, w, pool, d, node_axis, compressor
                            )
                        elif isinstance(w, ScheduleArrays):
                            mixed, new_e1, new_st1 = mix_arrays_sharded_stale_ef(
                                half, e1, st1, w, d, node_axis, compressor
                            )
                        else:
                            raise TypeError(stale_dense_msg)
                    else:
                        if resolved_transport == "pool":
                            mixed, new_st1 = mix_ppermute_pool_stale(
                                half, st1, w, pool, d, node_axis
                            )
                        elif isinstance(w, ScheduleArrays):
                            mixed, new_st1 = mix_arrays_sharded_stale(
                                half, st1, w, d, node_axis
                            )
                        else:
                            raise TypeError(stale_dense_msg)
                elif compressor is not None:
                    if gossip_every > 1:
                        mixed, new_e1 = jax.lax.cond(
                            jnp.mod(step, gossip_every) == 0,
                            do_mix_ef,
                            lambda he: he,
                            (half, e1),
                        )
                    else:
                        mixed, new_e1 = do_mix_ef((half, e1))
                elif gossip_every > 1:
                    mixed = jax.lax.cond(
                        jnp.mod(step, gossip_every) == 0, do_mix, lambda h: h, half
                    )
                else:
                    mixed = do_mix(half)
            loss_mean = jax.lax.pmean(loss, node_axis)
            if probes is not None:
                with jax.named_scope("dsgd.probes"):
                    # collective twins of the stacked-host probes: psum over
                    # nodes of this shard's squared distance to the pmean.
                    # Pure value computations on this step's mixed params /
                    # grads -- extra replicated outputs, zero extra traces.
                    def spread_sq(tree):
                        tot = jnp.zeros((), jnp.float32)
                        for x in jax.tree_util.tree_leaves(tree):
                            xf = x.astype(jnp.float32)
                            mu = jax.lax.pmean(xf, node_axis)
                            tot = tot + jax.lax.psum(
                                jnp.sum(jnp.square(xf - mu)), node_axis
                            )
                        return tot

                    loss_out = {"loss": loss_mean}
                    if probes.consensus:
                        loss_out["consensus"] = spread_sq(mixed)
                    if probes.grad_dev:
                        loss_out["grad_dev"] = spread_sq(grads) / n_nodes
            else:
                loss_out = loss_mean
            new_m_tree = unsqueeze(new_m) if momentum > 0.0 else m_tree
            if isinstance(m, dict):
                new_m_out = {}
                if "step" in m:
                    new_m_out["step"] = step + 1
                if "m" in m:
                    new_m_out["m"] = new_m_tree
                if "ef" in m:
                    new_m_out["ef"] = (
                        unsqueeze(new_e1) if new_e1 is not None else ef_tree
                    )
                if "stale" in m:
                    new_m_out["stale"] = (
                        {"buf": unsqueeze(new_st1.rings), "head": new_st1.head}
                        if new_st1 is not None
                        else stale_tree
                    )
            else:
                new_m_out = new_m_tree
            return unsqueeze(mixed), new_m_out, loss_out

        node_specs = jax.tree_util.tree_map(
            lambda s: P(node_axis), param_specs, is_leaf=lambda x: isinstance(x, P)
        )
        m_inner = node_specs if momentum > 0.0 else None
        if isinstance(momentum_state, dict):
            key_spec = {
                "step": P(),
                "m": m_inner,
                "ef": node_specs,
                # ring leaves carry (n, depth, *shape): node-sharded like
                # params; the head counter is a replicated scalar
                "stale": {"buf": node_specs, "head": P()},
            }
            mom_specs = {k: key_spec[k] for k in momentum_state}
        else:
            mom_specs = m_inner
        bspec = jax.tree_util.tree_map(lambda _: P(node_axis), batch)
        in_specs = (node_specs, mom_specs, bspec)
        args = (params, momentum_state, batch)
        if online_w:
            # mixing operand replicated to every node shard; tree-mapped
            # so ScheduleArrays (a 2-leaf pytree) and flat gammas/W all fit
            w_specs = jax.tree_util.tree_map(lambda _: P(), mix_w)
            in_specs = in_specs + (w_specs,)
            args = args + (mix_w,)
            if staleness is not None:
                # the (n,) delay vector is replicated; each node picks
                # its own entry by axis_index inside the transport
                in_specs = in_specs + (P(),)
                args = args + (delays,)
        loss_specs = (
            {"loss": P(), **{nm: P() for nm in probes.names()}}
            if probes is not None
            else P()
        )
        return jax.shard_map(
            per_node,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(node_specs, mom_specs, loss_specs),
            axis_names={node_axis},
            check_vma=False,
        )(*args)

    if online_w and staleness is not None:
        def train_step(params, momentum_state, batch, mix_w, delays):
            return _step_impl(params, momentum_state, batch, mix_w, delays)
    elif online_w:
        def train_step(params, momentum_state, batch, mix_w):
            return _step_impl(params, momentum_state, batch, mix_w)
    else:
        def train_step(params, momentum_state, batch):
            return _step_impl(params, momentum_state, batch)

    def rebuild(new_pool: PermPool) -> TrainSetup:
        # pool-miss fallback: same setup, new staged atoms (the one
        # counted recompile of TrainSetup.run_segments)
        return make_train_setup(
            cfg, mesh, mode=mode, schedule=schedule, lr=lr, momentum=momentum,
            impl=impl, grad_accum=grad_accum, gossip_every=gossip_every,
            online_w=online_w, sharded_transport="pool", pool=new_pool,
            compression=compressor, staleness=staleness, probes=probes,
        )

    def init_opt_state(params: PyTree):
        # the momentum_state the step expects for this configuration:
        # a dict of the present slots ({'step','m','ef'} keys), a bare
        # momentum tree when only momentum is on, None when stateless
        out: dict = {}
        if gossip_every > 1:
            out["step"] = jnp.zeros((), jnp.int32)
        if momentum > 0.0:
            out["m"] = jax.tree_util.tree_map(jnp.zeros_like, params)
        if compressor is not None:
            out["ef"] = ef_init(params)
        if staleness is not None:
            # per-node sender-side ring, all ring_depth slots primed with
            # the initial payload (a day-one straggler reads the shared
            # init, never garbage); leaves (n, depth, *shape) in f32, the
            # wire dtype
            out["stale"] = {
                "buf": jax.tree_util.tree_map(
                    lambda x: jnp.tile(
                        x.astype(jnp.float32)[:, None],
                        (1, staleness.ring_depth) + (1,) * (x.ndim - 1),
                    ),
                    params,
                ),
                "head": jnp.zeros((), jnp.int32),
            }
        if not out:
            return None
        if set(out) == {"m"}:
            return out["m"]
        return out

    return TrainSetup(
        train_step=train_step,
        init_params=init_params,
        param_specs=param_specs,
        batch_spec=batch_spec_for,
        mode=mode,
        n_nodes=n_nodes,
        online_w=online_w,
        sharded_transport=resolved_transport,
        pool=pool,
        comm_bytes_per_step=comm_bytes,
        compression=compressor,
        staleness=staleness,
        probes=probes,
        _rebuild=rebuild,
        _init_opt_state=init_opt_state,
    )
