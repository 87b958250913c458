"""Online topology adaptation benchmarks: the subsystem's three headline
claims, measured (and asserted) rather than asserted in prose.

1. **Warm refresh latency** -- at n=512/budget=64 (the ISSUE 4 acceptance
   point), a warm ``TopologyRefresher.refresh`` (previous Birkhoff atoms
   + persistent LMO duals + 1/4-budget cap + duality-gap stop) versus a
   cold ``learn_topology`` at full budget, under repeated abrupt
   node-permutation drifts. Steady-state MEDIANS over the drift rounds;
   the non-smoke run asserts the >= 3x acceptance bar and records the
   refreshed-vs-cold objective honestly (the warm solve's extra atom
   capacity usually makes it slightly BETTER, not worse).
   Measured on this 2-vCPU container: ~3.9x (cold ~3.2 s, warm
   ~0.84 s; the warm solve always hits its 16-iteration cap because a
   full node permutation relocates the optimum -- milder drifts stop
   earlier on the gap certificate).

2. **Post-drift convergence recovery** -- the abrupt label-swap scenario
   on the Section 6.1 mean-estimation task: frozen-W vs oracle-W
   (cold-solved on the true post-drift Pi, swapped exactly at the drift
   step) vs the full online pipeline (streaming Pi_hat -> drift detector
   -> warm refresh -> hot swap), all three on the SAME precomputed
   observation stream at equal iteration count. Recovery of the
   frozen->oracle error gap is reported in log space (strict: compares
   convergence floors) and linear space; the non-smoke run asserts
   log-recovery >= 0.8 (acceptance criterion a).

3. **Zero retraces** -- every online run asserts
   ``result["n_traces"] == 1``: the scanned rollout is compiled once
   and schedule hot-swaps reach it as data. This assertion runs in
   --smoke too, so CI catches any regression that turns a swap back
   into a retrace (acceptance criterion c).

ISSUE 5 adds two more measured claims:

4. **Staged-pool sharded mixing** (subprocess, forced host devices) --
   the pre-staged ppermute atom pool vs the all-gather on the online
   MESH trainer: bytes/step from the comm counter (the pool must move
   <= (d_max+1)/n of the all-gather's bytes -- asserted), median
   segment wall time for both transports, zero retraces across >= 3
   consecutive in-pool gamma swaps (asserted, smoke included), and the
   pool-miss fallback costing exactly ONE counted recompile (asserted).
   Also runs the sharded-transport autotuner once on the forced-device
   mesh, memoizing the ``sh_`` bucket into the autotune table.

5. **Overlapped refresh** -- the background-thread refresh on the
   n=512/budget=64 simulator rollout: wall clock of frozen vs
   synchronous-refresh vs overlapped-refresh runs on identical data,
   hidden-latency fraction = (wall_sync - wall_async) / solve_total.
   Asserts (smoke included) that every in-run refresh was collected
   with ``blocked_s == 0`` (the hook never waits on the solver) and
   that segment-time jitter while a solve is in flight stays bounded
   (no rollout serialization behind the solve). The >= 50% hidden
   target is recorded honestly (``target_met``) rather than asserted:
   on a 2-vCPU container the solver and the rollout share cores, and
   the floor is explained in the JSON when missed.

ISSUE 7 adds the compressed-gossip claims:

6. **Bytes-vs-convergence frontier** -- the W-budget x wire-format grid
   under ``data/drift.py`` scenarios. Mean estimation (abrupt label
   swap, full online pipeline) sweeps budgets x {uncompressed,
   identity, bf16}: identity must be BITWISE equal to the uncompressed
   run (the trace-time routing rot detector), bf16 must move exactly
   half the bytes (CommMeter-verified) and, non-smoke, still recover
   >= 0.8 of the frozen->oracle gap. Label-skew classification (vector
   payloads, where top-k is meaningful) sweeps {uncompressed, bf16,
   topk:0.25, topk:0.1} with a mid-run schedule hot-swap, asserting
   zero retraces per wire and the metered bytes against each wire's
   closed-form ratio. The sharded-pool bench (4) additionally runs the
   compressed pool transport in-subprocess: identity bitwise vs the
   uncompressed pool across in-pool swaps, bf16 pool <= 0.55x the
   uncompressed pool's bytes/step, zero retraces in every compressed
   run -- all asserted in --smoke too.

Writes experiments/bench/BENCH_online.json.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from .common import emit, result_dir
from repro.core.mixing import schedule_from_result, schedule_to_arrays
from repro.core.stl_fw import learn_topology
from repro.core.compression import make_compressor
from repro.data.drift import AbruptLabelSwap, labels_stream, partition_from_pi
from repro.data.synthetic import gaussian_blobs, mean_estimation_clusters
from repro.online import (
    DriftDetector,
    OnlineTopologyController,
    RefreshConfig,
    StreamingPiEstimator,
    TopologyRefresher,
)
from repro.train.trainer import run_classification, run_mean_estimation

LAM = 0.1


def _bench_refresh_speed(results: dict, smoke: bool) -> None:
    """Warm refresh vs cold solve under repeated abrupt drifts."""
    n, K, budget = (32, 8, 8) if smoke else (512, 64, 64)
    refresh_budget = max(4, budget // 4)
    rounds = 3 if smoke else 5
    rng = np.random.default_rng(0)
    Pi0 = rng.dirichlet(0.1 * np.ones(K), size=n)

    t0 = time.perf_counter()
    res0 = learn_topology(Pi0, budget=budget, lam=LAM)
    t_initial = time.perf_counter() - t0
    ref = TopologyRefresher(res0, RefreshConfig(budget=refresh_budget, lam=LAM))

    colds, warms, warm_iters, obj_pairs = [], [], [], []
    Pi_t = Pi0
    for _ in range(rounds):
        Pi_t = Pi_t[rng.permutation(n)]  # abrupt node-permutation drift
        t0 = time.perf_counter()
        cold = learn_topology(Pi_t, budget=budget, lam=LAM)
        colds.append(time.perf_counter() - t0)
        warm = ref.refresh(Pi_t)
        warms.append(ref.last_refresh_s)
        warm_iters.append(ref.last_iters)
        obj_pairs.append(
            (float(cold.objective_trace[-1]), float(warm.objective_trace[-1]))
        )

    cold_med, warm_med = float(np.median(colds)), float(np.median(warms))
    speedup = cold_med / warm_med
    results["refresh_speed"] = {
        "n": n, "K": K, "budget": budget, "refresh_budget": refresh_budget,
        "lam": LAM, "rounds": rounds,
        "initial_cold_s": t_initial,
        "gap_ref": ref.gap_ref,
        "cold_s": colds, "warm_s": warms,
        "cold_median_s": cold_med, "warm_median_s": warm_med,
        "speedup_warm_vs_cold": speedup,
        "warm_iters": warm_iters,
        "l_max": ref.l_max,
        "objective_cold_vs_warm": obj_pairs,
        # honesty note: warm objectives benefit from l_max > budget+1 atom
        # capacity; the comparison point is "topology you actually deploy"
        "warm_objective_worse_than_cold": max(
            w - c for c, w in obj_pairs
        ),
    }
    emit(
        f"online_refresh_n{n}_b{budget}", warm_med * 1e6,
        f"{speedup:.2f}x_vs_cold_{cold_med * 1e3:.0f}ms_iters={warm_iters}",
    )
    if not smoke:
        assert speedup >= 3.0, (
            f"acceptance (b) failed: warm refresh only {speedup:.2f}x faster "
            f"than cold at n={n}/budget={budget}"
        )


def _bench_recovery_and_retrace(results: dict, smoke: bool) -> None:
    """Abrupt label-swap: frozen vs oracle vs online-refreshed D-SGD."""
    if smoke:
        n, K, steps, seg, t_drift, budget = 12, 4, 120, 10, 40, 4
    else:
        n, K, steps, seg, t_drift, budget = 64, 8, 600, 20, 200, 8
    lam, lr, batch, beta = 0.5, 0.05, 4, 0.2
    task = mean_estimation_clusters(n_nodes=n, K=K, m=5.0, sigma_tilde2=1.0)
    Pi0 = np.eye(K)[np.arange(n) % K].astype(float)
    # seeded random node permutation (the half-rotation default is a
    # symmetry of cyclic one-hot Pi -- see AbruptLabelSwap docstring)
    perm = np.random.default_rng(11).permutation(n)
    scenario = AbruptLabelSwap(Pi0, t_drift=t_drift, node_perm=perm)
    labels = labels_stream(scenario, steps, batch, seed=0)
    means = np.asarray(task.cluster_means)
    zs = means[labels] + np.sqrt(task.sigma_tilde2) * np.random.default_rng(
        1
    ).normal(size=labels.shape)

    res0 = learn_topology(Pi0, budget=budget, lam=lam)
    oracle_res = learn_topology(scenario.Pi(t_drift), budget=budget, lam=lam)
    ref = TopologyRefresher(res0, RefreshConfig(budget=budget, lam=lam))
    sa0 = schedule_to_arrays(schedule_from_result(res0), ref.l_max)
    sa_oracle = schedule_to_arrays(schedule_from_result(oracle_res), ref.l_max)

    def run(hook):
        return run_mean_estimation(
            task, None, steps=steps, lr=lr, batch=batch, seed=2,
            schedule=sa0, zs=zs, on_segment=hook, segment_len=seg,
        )

    out_frozen = run(None)

    # first segment boundary at/after the drift step (robust to seg
    # values that don't divide t_drift -- an exact-match hook would
    # silently never swap and the oracle arm would measure frozen-W)
    oracle_done = {"swapped": False}

    def oracle_hook(t):
        if not oracle_done["swapped"] and t >= t_drift - 1:
            oracle_done["swapped"] = True
            return sa_oracle
        return None

    out_oracle = run(oracle_hook)
    assert oracle_done["swapped"], "oracle arm never swapped -- check seg/t_drift"

    ctl = OnlineTopologyController(
        ref, estimator=StreamingPiEstimator(n, K, beta=beta, init=Pi0)
    )
    fed = {"t": 0}

    def online_hook(t):
        while fed["t"] <= t:
            ctl.observe(labels[fed["t"]])
            fed["t"] += 1
        return ctl.on_segment(t)

    out_online = run(online_hook)

    # acceptance (c): swaps reached the compiled rollout as data -- the
    # scan traced exactly once per run, drift or no drift. Asserted in
    # smoke too: this is the CI jit-cache-miss detector.
    for name, out in (("frozen", out_frozen), ("oracle", out_oracle),
                      ("online", out_online)):
        assert out["n_traces"] == 1, (
            f"hot-swap retraced the rollout in the {name} run: "
            f"n_traces={out['n_traces']}"
        )
    assert ref.n_refreshes >= 1, "drift never detected -- no swap exercised"
    assert out_online["swaps"], "refresh fired but no schedule swap landed"

    tail = slice(-max(10, steps // 12), None)
    e_frozen = float(np.median(out_frozen["mean_sq_error"][tail]))
    e_oracle = float(np.median(out_oracle["mean_sq_error"][tail]))
    e_online = float(np.median(out_online["mean_sq_error"][tail]))
    log_rec = (np.log(e_frozen) - np.log(e_online)) / (
        np.log(e_frozen) - np.log(e_oracle)
    )
    lin_rec = (e_frozen - e_online) / (e_frozen - e_oracle)
    results["recovery"] = {
        "n": n, "K": K, "steps": steps, "segment_len": seg,
        "t_drift": t_drift, "budget": budget, "lam": lam, "lr": lr,
        "batch": batch, "estimator_beta": beta,
        "err_frozen": e_frozen, "err_oracle": e_oracle, "err_online": e_online,
        "recovery_log": float(log_rec), "recovery_linear": float(lin_rec),
        "n_refreshes": ref.n_refreshes,
        "swap_steps": out_online["swaps"],
        "detector_events": ctl.events[-6:],
        "n_traces": {"frozen": out_frozen["n_traces"],
                     "oracle": out_oracle["n_traces"],
                     "online": out_online["n_traces"]},
    }
    emit(
        f"online_recovery_n{n}", 0.0,
        f"log={log_rec:.3f}_lin={lin_rec:.3f}_refreshes={ref.n_refreshes}"
        f"_retraces=0",
    )
    if not smoke:
        assert log_rec >= 0.8, (
            f"acceptance (a) failed: online refresh recovered only "
            f"{log_rec:.3f} of the frozen->oracle gap (log space)"
        )


def _bench_frontier(results: dict, smoke: bool) -> None:
    """Bytes-vs-convergence frontier: W budget x wire format under drift.

    Two sweeps, one artifact. (a) Mean estimation under the abrupt
    label swap with the FULL online pipeline (estimator -> detector ->
    warm refresh -> hot swap) per arm: budgets x {none, identity,
    bf16}. The task's payload is scalar (P=1 per node), so top-k is
    degenerate there -- a k=1-of-1 wire would CHARGE 8 bytes against
    f32's 4, which the meter would report honestly but the frontier
    would learn nothing from. (b) Label-skew classification (linear
    model: P = d*C + C per node) where top-k earns its row: wires
    {none, bf16, topk:0.25:g0.25, topk:0.1:g0.25} with a mid-run hot
    swap to the post-drift topology (top-k rides CHOCO's damped
    consensus step -- see the gamma note at the wire loop). Every run
    asserts n_traces == 1 (smoke too).
    """
    if smoke:
        n, K, steps, seg, t_drift = 12, 4, 120, 10, 40
        budgets = (4,)
    else:
        n, K, steps, seg, t_drift = 32, 8, 400, 20, 120
        budgets = (4, 8)
    lam, lr, batch, beta = 0.5, 0.05, 4, 0.2
    task = mean_estimation_clusters(n_nodes=n, K=K, m=5.0, sigma_tilde2=1.0)
    Pi0 = np.eye(K)[np.arange(n) % K].astype(float)
    perm = np.random.default_rng(11).permutation(n)
    scenario = AbruptLabelSwap(Pi0, t_drift=t_drift, node_perm=perm)
    labels = labels_stream(scenario, steps, batch, seed=0)
    means = np.asarray(task.cluster_means)
    zs = means[labels] + np.sqrt(task.sigma_tilde2) * np.random.default_rng(
        1
    ).normal(size=labels.shape)
    tail = slice(-max(10, steps // 12), None)

    points = []
    for budget in budgets:
        res0 = learn_topology(Pi0, budget=budget, lam=lam)
        oracle_res = learn_topology(scenario.Pi(t_drift), budget=budget, lam=lam)
        l_max = TopologyRefresher(
            res0, RefreshConfig(budget=budget, lam=lam)
        ).l_max
        sa0 = schedule_to_arrays(schedule_from_result(res0), l_max)
        sa_oracle = schedule_to_arrays(schedule_from_result(oracle_res), l_max)

        def run(hook, wire):
            return run_mean_estimation(
                task, None, steps=steps, lr=lr, batch=batch, seed=2,
                schedule=sa0, zs=zs, on_segment=hook, segment_len=seg,
                compression=wire,
            )

        out_frozen = run(None, None)
        swapped = {"done": False}

        def oracle_hook(t):
            if not swapped["done"] and t >= t_drift - 1:
                swapped["done"] = True
                return sa_oracle
            return None

        out_oracle = run(oracle_hook, None)
        e_frozen = float(np.median(out_frozen["mean_sq_error"][tail]))
        e_oracle = float(np.median(out_oracle["mean_sq_error"][tail]))

        base_bytes = None
        base_mse = None
        for wire in (None, "identity", "bf16"):
            # fresh pipeline state per arm: the refresher/estimator are
            # stateful, and each arm must solve from the same start
            ref = TopologyRefresher(res0, RefreshConfig(budget=budget, lam=lam))
            # the low-budget arms start from a W that fits Pi0 loosely,
            # so the permutation's relative proxy jump is smaller than
            # the 1.5x default trigger (1.47x at n=32/K=8/budget=4) --
            # the frontier measures bytes vs convergence, not detector
            # calibration, so pin a more sensitive trigger explicitly
            ctl = OnlineTopologyController(
                ref,
                estimator=StreamingPiEstimator(n, K, beta=beta, init=Pi0),
                detector=DriftDetector(threshold=1.3),
            )
            fed = {"t": 0}

            def online_hook(t):
                while fed["t"] <= t:
                    ctl.observe(labels[fed["t"]])
                    fed["t"] += 1
                return ctl.on_segment(t)

            out = run(online_hook, wire)
            assert out["n_traces"] == 1, (wire, out["n_traces"])
            assert out["swaps"], (wire, "no swap landed")
            e = float(np.median(out["mean_sq_error"][tail]))
            rec = (np.log(e_frozen) - np.log(e)) / (
                np.log(e_frozen) - np.log(e_oracle)
            )
            bps = out["comm"]["per_step_bytes"]
            if wire is None:
                base_bytes, base_mse = bps, out["mean_sq_error"]
            elif wire == "identity":
                # trace-time routing rot detector: the identity wire IS
                # the uncompressed transport, bit for bit
                assert bps == base_bytes
                assert np.array_equal(out["mean_sq_error"], base_mse), (
                    "identity wire diverged from the uncompressed run"
                )
            elif wire == "bf16":
                assert bps * 2 == base_bytes, (bps, base_bytes)
                if not smoke:
                    assert rec >= 0.8, (
                        f"bf16 frontier recovery {rec:.3f} < 0.8 at "
                        f"budget={budget}"
                    )
            points.append({
                "task": "mean_estimation", "budget": budget,
                "wire": wire or "none", "bytes_per_step": bps,
                "total_bytes": out["comm"]["total_bytes"],
                "err_tail": e, "err_frozen": e_frozen,
                "err_oracle": e_oracle, "recovery_log": float(rec),
                "n_refreshes": ref.n_refreshes, "swaps": out["swaps"],
            })

    # --- classification sweep: vector payloads make top-k meaningful
    if smoke:
        nc, C, d, steps_c, spn = 8, 4, 16, 60, 64
    else:
        nc, C, d, steps_c, spn = 16, 8, 32, 240, 256
    X, y = gaussian_blobs(
        n_samples=40 * spn, num_classes=C, dim=d, seed=3
    )
    Pi_pre = np.eye(C)[np.arange(nc) % C].astype(float)
    Pi_post = Pi_pre[np.random.default_rng(13).permutation(nc)]
    idx = partition_from_pi(y, Pi_post, samples_per_node=spn, seed=4)
    res_pre = learn_topology(Pi_pre, budget=4, lam=lam)
    res_post = learn_topology(Pi_post, budget=4, lam=lam)
    cap = max(
        schedule_from_result(res_pre).n_atoms,
        schedule_from_result(res_post).n_atoms,
    )
    sa_pre = schedule_to_arrays(schedule_from_result(res_pre), cap)
    sa_post = schedule_to_arrays(schedule_from_result(res_post), cap)
    p_total = d * C + C
    cls_points = []
    base_cls_bytes = None
    eval_every_c = max(10, steps_c // 6)
    # traces == distinct scan segment lengths (the t=0 eval point makes
    # a length-1 prefix segment) -- swaps and compression must add NONE
    from repro.train.trainer import _eval_segments

    expected_traces = len({l for l, _ in _eval_segments(steps_c, eval_every_c, True)})
    # top-k needs CHOCO's consensus step size: at gamma=1 the sparsifier's
    # error feedback through (W - I) has no contraction and the run
    # diverges (measured: loss_tail 7.9e6 at topk:0.25, 1.0e11 at
    # topk:0.1 on this sweep) -- gamma=0.25 converges at both fractions
    for wire in (None, "bf16", "topk:0.25:g0.25", "topk:0.1:g0.25"):
        swapped_c = {"done": False}

        def cls_hook(t):
            if not swapped_c["done"] and t >= steps_c // 3:
                swapped_c["done"] = True
                return sa_post
            return None

        logger = run_classification(
            X, y, idx, None, model="linear", steps=steps_c,
            batch_size=8, lr=0.2, eval_every=eval_every_c,
            seed=5, schedule=sa_pre, on_segment=cls_hook, compression=wire,
        )
        assert logger.aux["n_traces"] == expected_traces, (
            wire, logger.aux["n_traces"], expected_traces
        )
        assert logger.aux["swaps"], (wire, "no swap landed")
        bps = logger.aux["comm"]["per_step_bytes"]
        comp = make_compressor(wire)
        if wire is None:
            base_cls_bytes = bps
            expect_ratio = 1.0
        else:
            wire_elems, wire_item = comp.wire_layout(p_total)
            expect_ratio = (wire_elems * wire_item) / (p_total * 4)
            got_ratio = bps / base_cls_bytes
            assert abs(got_ratio - expect_ratio) < 1e-9, (
                wire, got_ratio, expect_ratio
            )
        loss_tail = float(np.median(logger.column("loss")[-20:]))
        if wire is None:
            base_cls_loss = loss_tail
        elif not smoke:
            # convergence bar: a compressed wire may trade bytes for
            # accuracy but not blow up -- stay within 1.5x of dense
            assert loss_tail <= 1.5 * base_cls_loss, (
                wire, loss_tail, base_cls_loss
            )
        cls_points.append({
            "task": "classification", "wire": wire or "none",
            "p_total": p_total, "bytes_per_step": bps,
            "bytes_ratio": bps / base_cls_bytes,
            "expected_ratio": expect_ratio,
            "loss_tail": loss_tail, "swaps": logger.aux["swaps"],
        })
        assert np.isfinite(loss_tail), wire

    results["frontier"] = {
        "mean_estimation": points,
        "classification": cls_points,
        "note": (
            "mean-estimation payloads are scalar (P=1), where a top-k "
            "value+index wire costs MORE than f32 -- the classification "
            "sweep owns the top-k rows; those ride gamma=0.25 (CHOCO "
            "consensus step size) because undamped top-k EF gossip "
            "diverges on this task"
        ),
    }
    best_bf = max(
        (p for p in points if p["wire"] == "bf16"),
        key=lambda p: p["recovery_log"],
    )
    emit(
        "online_frontier", 0.0,
        f"bf16_recovery={best_bf['recovery_log']:.3f}"
        f"_bytes=0.5x_topk_rows={len(cls_points) - 2}",
    )


_SHARDED_SCRIPT = """
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from jax.sharding import AxisType
    from repro.configs import get_smoke_config
    from repro.core import learn_topology
    from repro.core.mixing import (BirkhoffSchedule, PermPool, PoolSwap,
                                   autotune_sharded_transport,
                                   schedule_from_result)
    from repro.online import RefreshConfig, TopologyRefresher
    from repro.train.lm_trainer import make_train_setup

    cfgd = json.loads(%r)
    n, K, steps, seg = cfgd["n"], cfgd["K"], cfgd["steps"], cfgd["seg"]

    rng = np.random.default_rng(0)
    Pi = rng.dirichlet(0.2 * np.ones(K), size=n)
    res0 = learn_topology(Pi, budget=cfgd["budget"], lam=0.1)
    ref = TopologyRefresher(res0, RefreshConfig(budget=2, lam=0.1))
    sched = ref.schedule
    pool = PermPool.from_schedule(sched, capacity=ref.l_max)
    g0, _ = pool.project(sched)
    W = sched.to_matrix()
    d_max = int(max((np.abs(W[i]) > 1e-9).sum() - (W[i, i] > 1e-9)
                    for i in range(n)))

    mesh = jax.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = get_smoke_config("qwen3-0.6b")
    mk = lambda tr, pl, comp=None: make_train_setup(
        cfg, mesh, mode="dsgd", online_w=True, sharded_transport=tr,
        pool=pl, lr=1e-2, compression=comp)
    s_pool, s_ag = mk("pool", pool), mk("allgather", None)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), s_pool.param_specs,
                      is_leaf=lambda x: isinstance(x, P))
    out = {"n": n, "d_max": d_max, "pool_capacity": pool.capacity,
           "pool_comm_slots": pool.n_comm_slots,
           "pool_bytes_per_step": s_pool.comm_bytes_per_step,
           "allgather_bytes_per_step": s_ag.comm_bytes_per_step}

    with jax.set_mesh(mesh):
        params = jax.jit(s_pool.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (steps, n, 1, 32), 0,
                                  cfg.vocab_size)
        batches = {"tokens": toks, "labels": toks}

        # (a) >= 3 consecutive in-pool gamma swaps: zero retraces
        g1 = np.roll(g0, 1).astype(np.float32); g1 /= max(g1.sum(), 1e-9)
        swaps = iter([PoolSwap(gammas=g1), PoolSwap(gammas=g0),
                      PoolSwap(gammas=g1)])
        r_pool = s_pool.run_segments(params, None, batches, g0, segment_len=seg,
                                     on_segment=lambda t: next(swaps, None))
        assert r_pool["n_traces"] == 1 and r_pool["recompiles"] == 0, r_pool
        assert len(r_pool["swaps"]) >= 3
        assert np.isfinite(r_pool["losses"]).all()

        # (b) pool miss: exactly one counted recompile
        new_perm = tuple(int(v) for v in np.roll(np.arange(n), n // 2 + 1))
        ns = BirkhoffSchedule(coeffs=(0.5, 0.5),
                              perms=(tuple(range(n)), new_perm))
        np2 = PermPool.from_schedule(ns, capacity=pool.capacity)
        ng, _ = np2.project(ns)
        miss = iter([PoolSwap(gammas=ng, pool=np2)])
        r_miss = s_pool.run_segments(r_pool["params"], None, batches, g0,
                                     segment_len=seg,
                                     on_segment=lambda t: next(miss, None))
        assert r_miss["recompiles"] == 1 and r_miss["n_traces"] == 2, r_miss

        # (c) wall clock: same batches, no swaps, both transports
        r_p = s_pool.run_segments(params, None, batches, g0, segment_len=seg)
        Wj = jnp.asarray(W, jnp.float32)
        r_a = s_ag.run_segments(params, None, batches, Wj, segment_len=seg)
        out["pool_segment_s"] = r_p["segment_s"]
        out["allgather_segment_s"] = r_a["segment_s"]
        out["pool_comm"] = r_p["comm"]
        out["allgather_comm"] = r_a["comm"]
        out["in_pool_swaps"] = len(r_pool["swaps"])
        out["miss_recompiles"] = r_miss["recompiles"]

        # (d) sharded autotune: measure once on this forced-device mesh
        p_total = out["allgather_bytes_per_step"] // ((n - 1) * 4)
        out["autotune_winner"] = autotune_sharded_transport(
            n, pool.n_comm_slots, p_total, measure=True, mesh=mesh)

        # (e) compressed pool transports: the EF wire on the staged
        # ppermutes. Identity is the trace-time-routing rot detector
        # (must be BITWISE the uncompressed pool, swaps included);
        # bf16/top-k assert zero retraces across in-pool swaps and the
        # metered bytes against each wire's closed-form ratio.
        from repro.core.compression import make_compressor
        s_id = mk("pool", pool, "identity")
        s_bf = mk("pool", pool, "bf16")
        s_tk = mk("pool", pool, "topk:0.25")
        out["pool_bf16_bytes_per_step"] = s_bf.comm_bytes_per_step
        out["pool_topk25_bytes_per_step"] = s_tk.comm_bytes_per_step
        assert s_id.comm_bytes_per_step == s_pool.comm_bytes_per_step
        assert s_bf.comm_bytes_per_step * 2 == s_pool.comm_bytes_per_step
        assert s_bf.comm_bytes_per_step <= 0.55 * s_pool.comm_bytes_per_step
        pp = s_pool.comm_bytes_per_step // (pool.n_comm_slots * 4)
        k_elems, k_item = make_compressor("topk:0.25").wire_layout(pp)
        assert s_tk.comm_bytes_per_step == pool.n_comm_slots * k_elems * k_item
        compressed = {}
        for wname, s_c in (("identity", s_id), ("bf16", s_bf),
                           ("topk:0.25", s_tk)):
            sw = iter([PoolSwap(gammas=g1), PoolSwap(gammas=g0),
                       PoolSwap(gammas=g1)])
            r_c = s_c.run_segments(params, s_c.init_opt_state(params),
                                   batches, g0, segment_len=seg,
                                   on_segment=lambda t: next(sw, None))
            assert r_c["n_traces"] == 1 and r_c["recompiles"] == 0, (wname, r_c)
            assert len(r_c["swaps"]) >= 3
            assert np.isfinite(r_c["losses"]).all(), wname
            compressed[wname] = {
                "bytes_per_step": s_c.comm_bytes_per_step,
                "comm": r_c["comm"],
                "losses_vs_uncompressed_max_abs": float(
                    np.abs(r_c["losses"] - r_pool["losses"]).max()),
            }
            if wname == "identity":
                assert np.array_equal(r_c["losses"], r_pool["losses"]), (
                    "identity wire diverged from the uncompressed pool")
        out["compressed_pool"] = compressed

    print("RESULT_JSON " + json.dumps(out))
"""


def _bench_sharded_pool(results: dict, smoke: bool) -> None:
    """Staged-pool vs all-gather on the online mesh trainer (subprocess:
    the main process must keep its single-device view)."""
    n = 8
    cfgd = {"n": n, "K": 4, "budget": 3,
            "steps": 8 if smoke else 24, "seg": 2 if smoke else 4}
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    # the sharded autotune entry lands next to the other bench artifacts
    # (the committed table on full runs, the smoke dir in CI)
    os.makedirs(result_dir(), exist_ok=True)
    env["REPRO_TRANSPORT_AUTOTUNE"] = os.path.join(
        result_dir(), "transport_autotune.json"
    )
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SHARDED_SCRIPT % json.dumps(cfgd))],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    assert proc.returncode == 0, f"sharded bench failed:\n{proc.stderr[-4000:]}"
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT_JSON ")]
    out = json.loads(line[0][len("RESULT_JSON "):])

    ratio = out["pool_bytes_per_step"] / out["allgather_bytes_per_step"]
    bound = (out["d_max"] + 1) / out["n"]
    out["bytes_ratio_pool_vs_allgather"] = ratio
    out["bytes_ratio_bound"] = bound
    # acceptance: the staged pool moves <= (d_max + eps)/n of the
    # all-gather's bytes/step, from the comm counters (eps = 1 atom)
    assert ratio <= bound, (ratio, bound)
    # steady-state medians, first segment (compile) excluded
    pool_med = float(np.median(out["pool_segment_s"][1:]))
    ag_med = float(np.median(out["allgather_segment_s"][1:]))
    out["pool_segment_median_s"] = pool_med
    out["allgather_segment_median_s"] = ag_med
    # acceptance (ISSUE 7): the bf16 pool moves <= 0.55x the
    # uncompressed pool's bytes/step, from the RUN meter (not just the
    # setup's static rate) -- asserted in smoke too
    bf_rate = out["compressed_pool"]["bf16"]["comm"]["per_step_bytes"]
    bf_ratio = bf_rate / out["pool_comm"]["per_step_bytes"]
    out["bytes_ratio_bf16_vs_pool"] = bf_ratio
    assert bf_ratio <= 0.55, bf_ratio
    results["sharded_pool"] = out
    emit(
        f"online_pool_mix_n{out['n']}", pool_med * 1e6,
        f"bytes_ratio={ratio:.3f}<=bound_{bound:.3f}_bf16={bf_ratio:.2f}x"
        f"_retraces=0_miss_recompiles={out['miss_recompiles']}"
        f"_vs_allgather_{ag_med * 1e6:.0f}us",
    )


def _bench_overlap(results: dict, smoke: bool) -> None:
    """Overlapped (background-thread) refresh vs inline refresh on the
    n=512/budget=64 rollout: how much solve latency the rollout hides.

    The three arms (frozen / sync / overlap) run the SAME precomputed
    observation stream -- this measures scheduling, not learning (the
    recovery bench above owns the quality claim). Drifts are scripted
    ``request_refresh`` calls on an estimator snapshotted from drifted
    labels, so all arms solve comparable problems deterministically.
    """
    if smoke:
        n, K, budget, rbudget = 32, 8, 8, 4
        steps, seg, batch = 600, 50, 4
        drift_segs = (3, 7)
    else:
        n, K, budget, rbudget = 512, 64, 64, 16
        steps, seg, batch = 40000, 1000, 1
        drift_segs = (8, 20, 32)
    rng = np.random.default_rng(0)
    Pi0 = rng.dirichlet(0.1 * np.ones(K), size=n)
    task = mean_estimation_clusters(n_nodes=n, K=K, m=5.0, sigma_tilde2=1.0)
    zs = np.stack([task.sample(batch, rng) for _ in range(steps)]).astype(np.float32)

    t0 = time.perf_counter()
    res0 = learn_topology(Pi0, budget=budget, lam=LAM)
    t_initial = time.perf_counter() - t0
    # the initial arrays MUST use the refresher's l_max (zero-weight
    # atoms dropped + refresh-budget headroom): any other capacity would
    # make the first swap a shape change, i.e. a retrace
    sched0 = schedule_from_result(res0)
    sa0 = schedule_to_arrays(sched0, sched0.n_atoms + rbudget)

    # drifted Pi per scripted refresh + a label batch that imprints it on
    # a beta=1 estimator (empirical snapshot) at the drift boundary
    drift_pis = []
    Pi_t = Pi0
    for _ in drift_segs:
        Pi_t = Pi_t[rng.permutation(n)]
        drift_pis.append(Pi_t)
    label_rng = np.random.default_rng(7)
    drift_labels = [
        np.stack([label_rng.choice(K, size=256, p=Pi_d[i]) for i in range(n)])
        for Pi_d in drift_pis
    ]

    def run_arm(overlap: bool | None) -> dict:
        """overlap=None => frozen arm (no controller at all)."""
        arm: dict = {}
        hook = None
        ctl = None
        seg_times: list[tuple[float, bool]] = []
        if overlap is not None:
            ref = TopologyRefresher(res0, RefreshConfig(budget=rbudget, lam=LAM))
            ctl = OnlineTopologyController(
                ref, estimator=StreamingPiEstimator(n, K, beta=1.0, init=Pi0),
                overlap=overlap,
            )
            state = {"seg": 0, "drift": 0, "last": None}

            def hook(t):
                now = time.perf_counter()
                if state["last"] is not None:
                    seg_times.append((now - state["last"], ctl.refresh_pending))
                state["seg"] += 1
                if (state["drift"] < len(drift_segs)
                        and state["seg"] == drift_segs[state["drift"]]):
                    ctl.observe(drift_labels[state["drift"]])
                    state["drift"] += 1
                    ctl.request_refresh()
                ret = ctl.on_segment(t)
                state["last"] = time.perf_counter()
                return ret

        t0 = time.perf_counter()
        out = run_mean_estimation(
            task, None, steps=steps, lr=0.05, batch=batch, seed=2,
            schedule=sa0, zs=zs, on_segment=hook, segment_len=seg,
        )
        if ctl is not None:
            ctl.flush()
            ctl.close()
        arm["wall_s"] = time.perf_counter() - t0
        arm["n_traces"] = out["n_traces"]
        assert out["n_traces"] == 1, out["n_traces"]
        if ctl is not None:
            arm["refresh_log"] = ctl.refresh_log
            arm["solve_total_s"] = float(
                sum(r["solve_s"] for r in ctl.refresh_log)
            )
            arm["n_refreshes"] = ctl.refresher.n_refreshes
            idle = [s for s, pending in seg_times if not pending]
            busy = [s for s, pending in seg_times if pending]
            arm["segment_median_idle_s"] = float(np.median(idle)) if idle else None
            arm["segment_max_pending_s"] = float(max(busy)) if busy else None
        return arm

    frozen = run_arm(None)
    sync = run_arm(False)
    over = run_arm(True)

    solve_total = sync["solve_total_s"]
    hidden = (sync["wall_s"] - over["wall_s"]) / max(solve_total, 1e-9)
    hidden = float(np.clip(hidden, -1.0, 1.0))
    # the >= 0.5 target is a FULL-SIZE claim: at smoke sizes the solves
    # are ~ms, so the wall-clock difference is scheduling noise divided
    # by a tiny denominator -- record it, but only judge the target
    # where the measurement is meaningful (CI smoke still asserts the
    # non-blocking contract below, which is size-independent)
    target_met = None if smoke else hidden >= 0.5

    # the overlap contract, asserted in smoke too: every in-run refresh
    # was COLLECTED at a boundary, never waited for (blocked_s == 0 --
    # a final flush after the last segment is the only legal wait), and
    # no segment serialized behind a full solve (bounded jitter).
    in_run = [r for r in over["refresh_log"] if r["t_collect"] >= 0]
    assert in_run, "no overlapped refresh landed inside the run"
    for r in in_run:
        assert r["blocked_s"] == 0.0, r
    if over["segment_max_pending_s"] is not None:
        solve_med = float(np.median([r["solve_s"] for r in in_run]))
        jitter_bound = 5.0 * over["segment_median_idle_s"] + 0.8 * solve_med + 0.1
        assert over["segment_max_pending_s"] <= jitter_bound, (
            f"rollout serialized behind the solve: pending segment took "
            f"{over['segment_max_pending_s']:.3f}s > bound {jitter_bound:.3f}s"
        )

    results["overlap"] = {
        "n": n, "K": K, "budget": budget, "refresh_budget": rbudget,
        "steps": steps, "segment_len": seg, "drift_segments": list(drift_segs),
        "initial_cold_solve_s": t_initial,
        "wall_frozen_s": frozen["wall_s"],
        "wall_sync_s": sync["wall_s"],
        "wall_overlap_s": over["wall_s"],
        "solve_total_sync_s": solve_total,
        "solve_total_overlap_s": over["solve_total_s"],
        "hidden_latency_fraction": hidden,
        "target_met": target_met,
        "overlap_refresh_log": over["refresh_log"],
        "sync_refresh_log": sync["refresh_log"],
        "segment_median_idle_s": over["segment_median_idle_s"],
        "segment_max_pending_s": over["segment_max_pending_s"],
        # honesty note kept in the artifact, not only in prose: on a
        # 2-vCPU container the BLAS solve and the XLA rollout share
        # cores, so "hidden" latency is bounded by the spare-core time;
        # the >= 0.5 target assumes at least one core is free for the
        # solver while the rollout computes.
        "floor_note": (
            "hidden fraction is bounded by spare-core availability; "
            "solver (BLAS, GIL released) and rollout (XLA CPU) share "
            f"{os.cpu_count()} cores here"
        ),
    }
    emit(
        f"online_overlap_n{n}_b{budget}", over["wall_s"] * 1e6,
        f"hidden={hidden:.2f}_of_{solve_total * 1e3:.0f}ms"
        f"_sync_{sync['wall_s']:.2f}s_overlap_{over['wall_s']:.2f}s"
        f"_target_met={target_met}",
    )


def main(smoke: bool = False) -> None:
    results: dict = {"smoke": smoke}
    _bench_refresh_speed(results, smoke)
    _bench_recovery_and_retrace(results, smoke)
    _bench_frontier(results, smoke)
    _bench_sharded_pool(results, smoke)
    _bench_overlap(results, smoke)
    os.makedirs(result_dir(), exist_ok=True)
    path = os.path.join(result_dir(), "BENCH_online.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    emit("bench_online_json", 0.0, path)


if __name__ == "__main__":
    main()
