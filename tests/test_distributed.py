"""Multi-device distribution tests (subprocess: forced host devices).

These run in subprocesses because the main pytest process must keep seeing
exactly 1 device (jax locks device count at first init).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(
    code: str, n_devices: int = 8, timeout: int = 480, xla_flags: str = ""
) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} {xla_flags}"
    )
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_ppermute_gossip_equals_dense_mixing():
    """The sharded Birkhoff-ppermute transport must equal the dense W-matmul
    transport (same mixing matrix) on real multi-device buffers."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.core import topology as T
        from repro.core.mixing import schedule_from_matrix, mix_ppermute, mix_dense

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        W = T.ring(8)
        sched = schedule_from_matrix(W)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)), jnp.float32)

        def gossip(v):
            def inner(p):
                return mix_ppermute(p, sched, "data")
            return jax.shard_map(inner, mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P("data"), axis_names={"data"},
                                 check_vma=False)(v)

        with jax.set_mesh(mesh):
            got = np.asarray(jax.jit(gossip)(x))
        want = np.asarray(mix_dense(x, jnp.asarray(W, jnp.float32)))
        assert np.allclose(got, want, atol=1e-5), np.abs(got - want).max()
        print("PPERMUTE_OK")
    """)
    assert "PPERMUTE_OK" in out


def test_sharded_dsgd_step_runs_and_learns():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import learn_topology, schedule_from_result
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        Pi = np.eye(2)[np.arange(4) % 2].astype(float)
        sched = schedule_from_result(learn_topology(Pi, budget=2, lam=0.5))
        setup = make_train_setup(cfg, mesh, mode="dsgd", schedule=sched, lr=2e-2)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), setup.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            params = jax.jit(setup.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
            batch = {k: jnp.zeros((4, 2, 32), jnp.int32) for k in ("tokens", "labels")}
            step = jax.jit(setup.train_step)
            losses = []
            for _ in range(6):
                params, _, loss = step(params, None, batch)
                losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        print("DSGD_SHARDED_OK", losses[0], losses[-1])
    """)
    assert "DSGD_SHARDED_OK" in out


def test_gossip_every_k_amortization():
    """gossip_every=k: consensus collapses exactly on gossip steps and
    drifts on local-only steps (time-varying W^(t), EXPERIMENTS.md §Perf A)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import topology as T
        from repro.core.mixing import schedule_from_matrix
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        sched = schedule_from_matrix(T.complete(4))
        setup = make_train_setup(cfg, mesh, mode="dsgd", schedule=sched,
                                 lr=1e-2, gossip_every=3)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), setup.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            params = jax.jit(setup.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 32), 0, cfg.vocab_size)
            batch = {"tokens": toks, "labels": toks}
            opt = {"step": jnp.zeros((), jnp.int32), "m": None}
            step = jax.jit(setup.train_step)
            cons = []
            for t in range(4):
                params, opt, loss = step(params, opt, batch)
                leaf = jax.tree_util.tree_leaves(params)[1]
                mean = jnp.mean(leaf, 0, keepdims=True)
                cons.append(float(jnp.sum(((leaf - mean).astype(jnp.float32))**2)))
        assert cons[0] < 1e-9 and cons[3] < 1e-9, cons  # gossip steps
        assert cons[1] > 1e-9 and cons[2] > 1e-9, cons  # local-only steps
        print("GOSSIP_EVERY_OK")
    """)
    assert "GOSSIP_EVERY_OK" in out


def test_multi_step_scan_bitwise_equals_loop():
    """The scanned multi-step train fn (lax.scan over k inner steps, mix in
    the carry, gossip_every + grad-accum inside) must be bitwise-equivalent
    to stepping the same jitted train_step from Python."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import topology as T
        from repro.core.mixing import schedule_from_matrix
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        sched = schedule_from_matrix(T.ring(4))
        setup = make_train_setup(cfg, mesh, mode="dsgd", schedule=sched,
                                 lr=1e-2, momentum=0.9, gossip_every=2,
                                 grad_accum=2)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), setup.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        k = 4
        with jax.set_mesh(mesh):
            params = jax.jit(setup.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (k, 4, 4, 32), 0, cfg.vocab_size)
            batches = {"tokens": toks, "labels": toks}
            zeros_m = jax.tree.map(jnp.zeros_like, params)
            opt = {"step": jnp.zeros((), jnp.int32), "m": zeros_m}

            scan_fn = jax.jit(setup.multi_step_fn("scan"))
            p_scan, opt_scan, loss_scan = scan_fn(params, opt, batches)

            loop_fn = setup.multi_step_fn("loop")
            p_loop, opt_loop, loss_loop = loop_fn(params, opt, batches)

        for a, b in zip(jax.tree.leaves(p_scan), jax.tree.leaves(p_loop)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), "params diverged"
        for a, b in zip(jax.tree.leaves(opt_scan), jax.tree.leaves(opt_loop)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), "opt state diverged"
        assert np.array_equal(np.asarray(loss_scan), np.asarray(loss_loop)), "losses"
        assert int(opt_scan["step"]) == k
        print("MULTI_STEP_BITWISE_OK", [float(x) for x in np.asarray(loss_scan)])
    """)
    assert "MULTI_STEP_BITWISE_OK" in out


def test_fsdp_step_matches_loss_of_dsgd_complete():
    """fsdp (C-PSGD) and dsgd-with-complete-graph start from the same init
    and identical data => identical first-step loss."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("gemma-2b")
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (8, 32), 0, cfg.vocab_size))
        with jax.set_mesh(mesh):
            s_f = make_train_setup(cfg, mesh, mode="fsdp", lr=1e-2)
            p_f = jax.jit(s_f.init_params)(jax.random.PRNGKey(0))
            bf = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
            _, _, loss_f = jax.jit(s_f.train_step)(p_f, None, bf)

            s_d = make_train_setup(cfg, mesh, mode="dsgd", schedule=None, lr=1e-2)
            sh = jax.tree.map(lambda s: NamedSharding(mesh, s), s_d.param_specs,
                              is_leaf=lambda x: isinstance(x, P))
            # init unsharded then device_put: out_shardings= would partition
            # the threefry calls, which changes the drawn values on JAX
            # installs where jax_threefry_partitionable defaults to False --
            # and this test needs bit-identical init across both modes.
            p_d = jax.device_put(jax.jit(s_d.init_params)(jax.random.PRNGKey(0)), sh)
            bd = {"tokens": jnp.asarray(toks.reshape(4, 2, 32)),
                  "labels": jnp.asarray(toks.reshape(4, 2, 32))}
            _, _, loss_d = jax.jit(s_d.train_step)(p_d, None, bd)
        assert abs(float(loss_f) - float(loss_d)) < 1e-2, (float(loss_f), float(loss_d))
        print("MODES_CONSISTENT", float(loss_f), float(loss_d))
    """)
    assert "MODES_CONSISTENT" in out


def test_online_w_matches_static_schedule_and_swaps_without_retrace():
    """The online-adaptation step (W as data, all-gather mixing) must equal
    the static ppermute-schedule step on the same W, and a W hot-swap
    through the scanned multi-step must compile nothing new."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import learn_topology, schedule_from_result
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        Pi = np.eye(2)[np.arange(4) % 2].astype(float)
        sched = schedule_from_result(learn_topology(Pi, budget=2, lam=0.5))
        W = jnp.asarray(sched.to_matrix(), jnp.float32)
        # the hot-swap target is built beside W: same type, new value (an
        # array made under set_mesh carries the mesh in its type)
        W2 = jnp.full((4, 4), 0.25, jnp.float32)   # uniform W

        s_static = make_train_setup(cfg, mesh, mode="dsgd", schedule=sched, lr=2e-2)
        s_online = make_train_setup(cfg, mesh, mode="dsgd", online_w=True, lr=2e-2)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), s_static.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            params = jax.jit(s_static.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
            toks = np.random.default_rng(0).integers(0, 50, size=(4, 2, 32))
            batch = {k: jnp.asarray(toks, jnp.int32) for k in ("tokens", "labels")}
            p1, _, l1 = jax.jit(s_static.train_step)(params, None, batch)
            p2, _, l2 = jax.jit(s_online.train_step)(params, None, batch, W)
            d = max(float(jnp.abs(a - b).max())
                    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
            assert d < 2e-5, d
            assert abs(float(l1) - float(l2)) < 1e-5

            n_traces = [0]
            ms = s_online.multi_step_fn("scan")
            def counted(p, m, b, w):
                n_traces[0] += 1
                return ms(p, m, b, w)
            msj = jax.jit(counted)
            batches = {k: jnp.stack([batch[k]] * 3) for k in batch}
            p, _, _ = msj(params, None, batches, W)
            p, _, losses2 = msj(p, None, batches, W2)   # hot swap
            assert n_traces[0] == 1, n_traces          # swap retraced nothing
            assert np.isfinite(np.asarray(losses2)).all()
        print("ONLINE_W_OK", d)
    """)
    assert "ONLINE_W_OK" in out


def test_staged_pool_bitwise_equals_allgather_and_swaps_without_retrace():
    """The staged-ppermute pool transport must equal the all-gather
    ScheduleArrays transport BITWISE on the same schedule (slot-for-slot
    identical accumulation), and >= 3 consecutive in-pool gamma swaps
    through run_segments must compile nothing; a forced pool miss must
    cost exactly one counted recompile."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import topology as T
        from repro.core.mixing import (BirkhoffSchedule, PermPool, PoolSwap,
                                       schedule_from_matrix, mix_ppermute_pool,
                                       mix_arrays_sharded)
        from repro.train.lm_trainer import make_train_setup

        mesh1 = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        sched = schedule_from_matrix(T.ring(8))
        pool = PermPool.from_schedule(sched, capacity=6)
        g, dropped = pool.project(sched)
        assert dropped == 0.0
        arrays = pool.arrays_for(g)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 37)), jnp.float32)
        gj = jnp.asarray(g)

        def run(fn):
            return jax.jit(jax.shard_map(fn, mesh=mesh1, in_specs=(P("data"),),
                                         out_specs=P("data"), axis_names={"data"},
                                         check_vma=False))(x)

        got_pool = np.asarray(run(lambda v: mix_ppermute_pool(v, gj, pool, "data")))
        got_ag = np.asarray(run(lambda v: mix_arrays_sharded(v, arrays, "data")))
        assert np.array_equal(got_pool, got_ag), np.abs(got_pool - got_ag).max()
        want = T.ring(8) @ np.asarray(x)
        assert np.allclose(got_pool, want, atol=1e-5)

        mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        setup = make_train_setup(cfg, mesh, mode="dsgd", online_w=True,
                                 sharded_transport="pool", pool=pool, lr=1e-2)
        assert setup.sharded_transport == "pool"
        assert setup.comm_bytes_per_step > 0
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), setup.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            params = jax.jit(setup.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (10, 8, 2, 32), 0,
                                      cfg.vocab_size)
            batches = {"tokens": toks, "labels": toks}
            g2 = np.roll(g, 1).astype(np.float32); g2 /= g2.sum()
            swaps = iter([PoolSwap(gammas=g2), PoolSwap(gammas=g),
                          PoolSwap(gammas=g2)])
            out = setup.run_segments(params, None, batches, g, segment_len=2,
                                     on_segment=lambda t: next(swaps, None))
            assert out["n_traces"] == 1, out["n_traces"]   # 3 in-pool swaps: 0 retraces
            assert out["recompiles"] == 0
            assert len(out["swaps"]) == 3
            assert np.isfinite(out["losses"]).all()

            # the all-gather transport must accept the SAME pool-coordinate
            # updates (gammas execute as their ScheduleArrays twin) and
            # produce bitwise-identical losses -- the autotune can then pick
            # either transport under one controller
            setup_ag = make_train_setup(cfg, mesh, mode="dsgd", online_w=True,
                                        sharded_transport="allgather",
                                        pool=pool, lr=1e-2)
            swaps_ag = iter([PoolSwap(gammas=g2), PoolSwap(gammas=g),
                             PoolSwap(gammas=g2)])
            out_ag = setup_ag.run_segments(params, None, batches, g,
                                           segment_len=2,
                                           on_segment=lambda t: next(swaps_ag, None))
            assert np.array_equal(out["losses"], out_ag["losses"]), "transports diverged"

            # out-of-pool atom => restage => exactly ONE counted recompile
            new_perm = tuple(int(v) for v in np.roll(np.arange(8), 3))
            ns = BirkhoffSchedule(coeffs=(0.5, 0.5),
                                  perms=(tuple(range(8)), new_perm))
            new_pool = PermPool.from_schedule(ns, capacity=6)
            ng, _ = new_pool.project(ns)
            miss = iter([PoolSwap(gammas=ng, pool=new_pool)])
            out2 = setup.run_segments(out["params"], None, batches, g,
                                      segment_len=5,
                                      on_segment=lambda t: next(miss, None))
            assert out2["recompiles"] == 1, out2
            assert out2["n_traces"] == 2, out2
            assert out2["setup"].pool is new_pool  # continue from the LIVE setup
            assert np.isfinite(out2["losses"]).all()

            # same restage on the all-gather transport: pure data, NO recompile
            miss_ag = iter([PoolSwap(gammas=ng, pool=new_pool)])
            out3 = setup_ag.run_segments(out_ag["params"], None, batches, g,
                                         segment_len=5,
                                         on_segment=lambda t: next(miss_ag, None))
            assert out3["recompiles"] == 0 and out3["n_traces"] == 1, out3
            assert np.array_equal(out2["losses"], out3["losses"]), "restage diverged"
        print("POOL_TRANSPORT_OK", out["comm"]["per_step_bytes"])
    """)
    assert "POOL_TRANSPORT_OK" in out


def test_mix_dense_sharded_serialized_peak_memory():
    """The serialized all-gather contraction must never hold the gathered
    (n, P_total) stack live: compiled per-device temp memory stays within
    ~one gathered leaf (the PR-4 peak-memory fix, checked on the compiled
    HLO's buffer assignment). XLA:CPU expands optimization barriers before
    it schedules, so this CPU stand-in depends on an XLA flag: it turns
    that pass off (a compiler setting no deployment uses) to see the order
    the TPU scheduler keeps; the unserialized map must hold the whole
    stack. The guard on the real compiler is
    test_tpu_compile.py::test_serialized_gather_holds_one_leaf_on_four_chips."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.core.mixing import mix_dense_sharded

        n, n_leaves = 8, 6
        mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
        leaves = {f"w{i}": jnp.zeros((n, 64, 257), jnp.float32)
                  for i in range(n_leaves)}
        W = jnp.eye(n, dtype=jnp.float32)

        def temp_bytes(serialize):
            def f(p, w):
                return jax.shard_map(
                    lambda q: mix_dense_sharded(q, w, "data", serialize=serialize),
                    mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
                    axis_names={"data"}, check_vma=False)(p)
            stats = jax.jit(f).lower(leaves, W).compile().memory_analysis()
            return stats.temp_size_in_bytes

        one_gathered_leaf = n * 64 * 257 * 4      # bytes, f32
        full_stack = n_leaves * one_gathered_leaf
        temp = temp_bytes(True)
        # one live gather (+ slack for the contraction buffer), NOT the stack
        assert temp <= 2 * one_gathered_leaf, (temp, one_gathered_leaf)
        assert temp < full_stack // 2, (temp, full_stack)
        assert temp_bytes(False) >= full_stack
        print("PEAK_MEMORY_OK", temp, one_gathered_leaf, full_stack)
    """, xla_flags="--xla_disable_hlo_passes=cse_barrier_expander")
    assert "PEAK_MEMORY_OK" in out


def test_node_churn_end_to_end_online_mesh_trainer():
    """NodeChurn drift (node replacement + offline windows) driven through
    the ONLINE MESH TRAINER: streamed labels -> drift detector -> warm
    refresh -> pool-coordinate hot swap at a run_segments boundary, with
    zero retraces unless the refresh restages (counted)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import learn_topology
        from repro.core.mixing import PermPool, schedule_from_result
        from repro.data.drift import NodeChurn, labels_stream
        from repro.online import (DriftDetector, OnlineTopologyController,
                                  RefreshConfig, StreamingPiEstimator,
                                  TopologyRefresher)
        from repro.train.lm_trainer import make_train_setup

        n, K, steps, seg = 8, 4, 24, 4
        Pi0 = np.eye(K)[np.arange(n) % K].astype(float)
        churn = NodeChurn(Pi0, events=((6, 1, 4), (6, 4), (6, 6)), alpha=0.3,
                          seed=3)
        labels = labels_stream(churn, steps, batch=16, seed=0)

        res0 = learn_topology(Pi0, budget=3, lam=0.5)
        ref = TopologyRefresher(res0, RefreshConfig(budget=3, lam=0.5))
        pool = PermPool.from_schedule(ref.schedule, capacity=ref.l_max)
        ctl = OnlineTopologyController(
            ref, estimator=StreamingPiEstimator(n, K, beta=0.5, init=Pi0),
            detector=DriftDetector(threshold=1.05, warmup=1),
            pool=pool, pool_miss_tol=0.25)

        mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        setup = make_train_setup(cfg, mesh, mode="dsgd", online_w=True,
                                 sharded_transport="pool", pool=pool, lr=1e-2)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), setup.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        fed = {"t": 0}
        def hook(t):
            while fed["t"] <= t:
                ctl.observe(labels[fed["t"]])
                fed["t"] += 1
            return ctl.on_segment(t)

        g0, _ = pool.project(ref.schedule)
        with jax.set_mesh(mesh):
            params = jax.jit(setup.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (steps, 8, 2, 32),
                                      0, cfg.vocab_size)
            out = setup.run_segments(params, None,
                                     {"tokens": toks, "labels": toks}, g0,
                                     segment_len=seg, on_segment=hook)
        assert ref.n_refreshes >= 1, "churn never detected"
        assert out["swaps"], "refresh fired but no swap landed"
        # every trace is accounted: 1 initial + 1 per counted restage
        assert out["n_traces"] == 1 + out["recompiles"], out
        assert np.isfinite(out["losses"]).all()
        assert out["comm"]["total_bytes"] > 0
        print("NODE_CHURN_MESH_OK", len(out["swaps"]), out["recompiles"],
              ctl.pool_misses)
    """)
    assert "NODE_CHURN_MESH_OK" in out


def test_online_w_rejects_invalid_configs():
    from repro.configs import get_smoke_config  # noqa: F401  (import-path smoke)
    code = """
        import jax, numpy as np, pytest
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import learn_topology, schedule_from_result
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        Pi = np.eye(2)[np.arange(4) % 2].astype(float)
        sched = schedule_from_result(learn_topology(Pi, budget=2, lam=0.5))
        for kwargs in ({"mode": "fsdp", "online_w": True},
                       {"mode": "dsgd", "online_w": True, "schedule": sched}):
            try:
                make_train_setup(cfg, mesh, lr=1e-2, **kwargs)
            except ValueError:
                continue
            raise AssertionError(f"{kwargs} should have been rejected")
        setup = make_train_setup(cfg, mesh, mode="dsgd", online_w=True, lr=1e-2)
        ms = setup.multi_step_fn("scan")
        try:
            ms(None, None, {"tokens": np.zeros((1, 4, 2, 32))})  # missing mix_w
        except TypeError:
            pass
        else:
            raise AssertionError("missing mix_w should raise")
        print("ONLINE_W_VALIDATION_OK")
    """
    out = run_with_devices(code)
    assert "ONLINE_W_VALIDATION_OK" in out


def test_compressed_sharded_transports_agree_and_validate():
    """ISSUE 7: the EF-compressed pool and all-gather transports must be
    bitwise twins on the same schedule and wire (like their uncompressed
    counterparts); the identity wire must route to the PLAIN transports
    bitwise; and make_train_setup must reject the combos that have no
    compressed wire (fsdp all-reduce, dsgd_pod einsum, offline runs)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import topology as T
        from repro.core.compression import (Compressor, make_compressor,
                                            mix_arrays_sharded_ef,
                                            mix_dense_sharded_ef,
                                            mix_ppermute_pool_ef)
        from repro.core.mixing import (PermPool, mix_arrays_sharded,
                                       mix_ppermute_pool, schedule_from_matrix)
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        sched = schedule_from_matrix(T.ring(8))
        pool = PermPool.from_schedule(sched, capacity=6)
        g, dropped = pool.project(sched)
        assert dropped == 0.0
        arrays = pool.arrays_for(g)
        gj = jnp.asarray(g)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 37)), jnp.float32)
        e = jnp.asarray(rng.normal(size=(8, 37), scale=0.2), jnp.float32)

        def run(fn):
            return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("data"), P("data")),
                                         out_specs=(P("data"), P("data")),
                                         axis_names={"data"}, check_vma=False))(x, e)

        for wire in ("bf16", "topk:0.25"):
            comp = make_compressor(wire)
            mp, ep = run(lambda v, m: mix_ppermute_pool_ef(v, m, gj, pool,
                                                           "data", comp))
            ma, ea = run(lambda v, m: mix_arrays_sharded_ef(v, m, arrays,
                                                            "data", comp))
            assert np.array_equal(np.asarray(mp), np.asarray(ma)), wire
            assert np.array_equal(np.asarray(ep), np.asarray(ea)), wire
            # dense reference on the reconstructed W: same EF bitwise,
            # mixed equal up to accumulation order
            Wj = jnp.asarray(sched.to_matrix(), jnp.float32)
            md, ed = run(lambda v, m: mix_dense_sharded_ef(v, m, Wj,
                                                           "data", comp))
            assert np.array_equal(np.asarray(ep), np.asarray(ed)), wire
            assert np.allclose(np.asarray(mp), np.asarray(md), atol=1e-5), wire

        ident = Compressor("identity")
        mi, ei = run(lambda v, m: mix_ppermute_pool_ef(v, m, gj, pool,
                                                       "data", ident))
        plain = jax.jit(jax.shard_map(
            lambda v: mix_ppermute_pool(v, gj, pool, "data"), mesh=mesh,
            in_specs=(P("data"),), out_specs=P("data"), axis_names={"data"},
            check_vma=False))(x)
        assert np.array_equal(np.asarray(mi), np.asarray(plain))
        assert np.array_equal(np.asarray(ei), np.asarray(e))  # ef untouched

        mesh2 = jax.make_mesh((8, 1), ("data", "model"),
                              axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke_config("qwen3-0.6b")
        for kwargs in ({"mode": "fsdp"},
                       {"mode": "dsgd_pod"},
                       {"mode": "dsgd", "online_w": False}):
            try:
                make_train_setup(cfg, mesh2, lr=1e-2, compression="bf16",
                                 **kwargs)
            except ValueError:
                continue
            raise AssertionError(f"{kwargs} + compression should be rejected")
        s = make_train_setup(cfg, mesh2, mode="dsgd", online_w=True, lr=1e-2,
                             sharded_transport="pool", pool=pool,
                             compression="topk:0.25")
        assert s.compression.label == "topk:0.25"
        assert s.comm_bytes_per_step < make_train_setup(
            cfg, mesh2, mode="dsgd", online_w=True, lr=1e-2,
            sharded_transport="pool", pool=pool).comm_bytes_per_step
        print("COMPRESSED_SHARDED_OK")
    """)
    assert "COMPRESSED_SHARDED_OK" in out


def test_run_segments_checkpoint_resume_bitwise():
    """Crash recovery for the mesh trainer: stop after 2 segments (the
    scripted crash), resume from the checkpoint, and land bitwise on the
    uninterrupted run -- including a pre-crash hot swap, which rides the
    checkpoint as the saved mixing operand."""
    out = run_with_devices("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import topology as T
        from repro.core.mixing import schedule_from_matrix, schedule_to_arrays
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        cfg = get_smoke_config("qwen3-0.6b")
        setup = make_train_setup(cfg, mesh, mode="dsgd", online_w=True, lr=1e-2)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), setup.param_specs,
                          is_leaf=lambda x: isinstance(x, P))
        mix0 = schedule_to_arrays(schedule_from_matrix(T.ring(8)), 4)
        mix1 = schedule_to_arrays(
            schedule_from_matrix(0.5 * T.ring(8) + 0.5 * np.eye(8)), 4)
        hook = lambda t: mix1 if t == 3 else None   # swap BEFORE the crash
        with jax.set_mesh(mesh):
            params = jax.jit(setup.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (8, 8, 2, 32), 0,
                                      cfg.vocab_size)
            batches = {"tokens": toks, "labels": toks}
            full = setup.run_segments(params, None, batches, mix0,
                                      segment_len=2, on_segment=hook)
            assert full["stopped_at"] is None and full["resumed_from"] is None
            with tempfile.TemporaryDirectory() as d:
                head = setup.run_segments(params, None, batches, mix0,
                                          segment_len=2, on_segment=hook,
                                          checkpoint_dir=d,
                                          stop_after_segments=2)
                assert head["stopped_at"] == 4, head["stopped_at"]
                assert head["swaps"] == [3]
                tail = setup.run_segments(params, None, batches, mix0,
                                          segment_len=2, checkpoint_dir=d,
                                          resume=True)
                assert tail["resumed_from"] == 4, tail["resumed_from"]
                assert tail["n_traces"] == 1      # resume retraces nothing new
        glued = np.concatenate([head["losses"], tail["losses"]])
        assert np.array_equal(glued, full["losses"]), "resume diverged"
        for a, b in zip(jax.tree.leaves(tail["params"]),
                        jax.tree.leaves(full["params"])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("CKPT_RESUME_OK")
    """)
    assert "CKPT_RESUME_OK" in out
