"""Bounded-delay (straggler-tolerant) gossip property suite (ISSUE 8).

The invariants under test:

* ``StragglerPolicy`` semantics: *wait* clamps delays to the deadline
  and never repairs the schedule for staleness; *degrade* zeroes
  past-deadline delays and repairs the schedule on the on-time support
  (late nodes isolated, W exactly doubly stochastic); dead nodes always
  carry effective delay 0.
* degrade repair preserves the node MEAN (column sums stay 1) at both
  the cycle level (``degrade_schedule`` via the policy) and the pool
  level (``degrade_pool_gammas`` stays an exact convex combination).
* ``delays == 0`` reduces every stale transport BITWISE to its fresh
  counterpart -- the flat simulator path, the sharded all-gather path,
  and the staged-pool path (the latter two on a forced-8-device mesh).
* the stale ring (and the EF memory, under compression) ride ONE scan
  carry: a mid-run hot swap under staleness retraces nothing.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import topology as T
from repro.core.mixing import (
    PermPool,
    ScheduleArrays,
    StragglerPolicy,
    degrade_pool_gammas,
    schedule_from_matrix,
    schedule_to_arrays,
    straggler_pool_stream,
    straggler_stream,
)
from repro.data.synthetic import mean_estimation_clusters
from repro.faults import FaultPlan
from repro.train.trainer import run_mean_estimation

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


def _arrays(n: int, l_max: int = 8) -> ScheduleArrays:
    sched = schedule_from_matrix(0.6 * T.ring(n) + 0.4 * np.eye(n))
    return schedule_to_arrays(sched, l_max)


def _dense(arrays: ScheduleArrays) -> np.ndarray:
    g = np.asarray(arrays.gammas, np.float64)
    g = g / g.sum()
    P = np.asarray(arrays.perms)
    n = P.shape[1]
    W = np.zeros((n, n))
    for l in range(len(g)):
        W[np.arange(n), P[l]] += g[l]
    return W


# ----------------------------------------------------------------- policy


def test_policy_wait_clamps_and_never_repairs():
    arrays = _arrays(8)
    pol = StragglerPolicy(mode="wait", tau_max=2)
    assert pol.ring_depth == 3
    delays = np.array([0, 1, 2, 3, 7, 0, 1, 5])
    sa, eff = pol.apply(arrays, delays)
    assert eff.dtype == np.int32
    assert np.array_equal(eff, [0, 1, 2, 2, 2, 0, 1, 2])  # clamped
    # wait never repairs for staleness: schedule untouched
    assert np.array_equal(np.asarray(sa.perms), np.asarray(arrays.perms))
    assert np.array_equal(np.asarray(sa.gammas), np.asarray(arrays.gammas))


def test_policy_degrade_cuts_late_nodes():
    n = 8
    arrays = _arrays(n)
    pol = StragglerPolicy(mode="degrade", tau_max=2)
    delays = np.array([0, 1, 2, 3, 7, 0, 1, 5])
    sa, eff = pol.apply(arrays, delays)
    late = delays > 2
    assert np.array_equal(eff, np.where(late, 0, delays))
    perms = np.asarray(sa.perms)
    for i in np.flatnonzero(late):
        assert (perms[:, i] == i).all()  # late node isolated in every atom
    W = _dense(sa)
    assert np.abs(W.sum(axis=0) - 1.0).max() < 1e-12
    assert np.abs(W.sum(axis=1) - 1.0).max() < 1e-12


def test_policy_dead_nodes_get_zero_delay():
    arrays = _arrays(4)
    pol = StragglerPolicy(mode="wait", tau_max=3)
    alive = np.array([True, False, True, False])
    _, eff = pol.apply(arrays, np.array([2, 2, 0, 3]), alive_mask=alive)
    assert np.array_equal(eff, [2, 0, 0, 0])  # the alive mask governs them


def test_policy_validation():
    with pytest.raises(ValueError):
        StragglerPolicy(mode="barrier")
    with pytest.raises(ValueError):
        StragglerPolicy(tau_max=-1)
    pol = StragglerPolicy()
    arrays = _arrays(4)
    with pytest.raises(ValueError):
        pol.apply(arrays, np.array([0, -1, 0, 0]))
    with pytest.raises(ValueError):
        pol.apply(arrays, np.zeros(5, np.int32))
    hash(pol)  # frozen/hashable: usable as a jit static or dict key


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(4, 12))
def test_degrade_repair_preserves_node_mean(seed, n):
    """Column sums of the repaired W stay exactly 1, so degrade never
    biases the consensus mean: mean(W' x) == mean(x)."""
    rng = np.random.default_rng(seed)
    arrays = _arrays(n)
    pol = StragglerPolicy(mode="degrade", tau_max=1)
    delays = rng.integers(0, 5, size=n)
    sa, _ = pol.apply(arrays, delays)
    W = _dense(sa)
    x = rng.normal(size=(n, 3))
    assert np.abs((W @ x).mean(axis=0) - x.mean(axis=0)).max() < 1e-12


# ------------------------------------------------------------ pool repair


def _pool_and_gammas(n: int = 8, capacity: int = 8):
    sched = schedule_from_matrix(0.6 * T.ring(n) + 0.4 * np.eye(n))
    pool = PermPool.from_schedule(sched, capacity=capacity)
    gammas, residual = pool.project(sched)
    assert residual < 1e-6
    return pool, gammas


def _pool_dense(pool: PermPool, gammas) -> np.ndarray:
    g = np.asarray(gammas, np.float64)
    n = pool.n_nodes
    W = np.zeros((n, n))
    for l, p in enumerate(pool.perms):
        W[np.arange(n), list(p)] += g[l]
    return W


def test_degrade_pool_gammas_mean_preserving():
    pool, gammas = _pool_and_gammas()
    off = np.zeros(8, bool)
    off[[2, 5]] = True
    g2 = degrade_pool_gammas(pool, gammas, off)
    assert abs(g2.sum() - np.asarray(gammas).sum()) < 1e-6  # mass conserved
    W = _pool_dense(pool, g2)
    assert np.abs(W.sum(axis=0) - 1.0).max() < 1e-6
    assert np.abs(W.sum(axis=1) - 1.0).max() < 1e-6
    # offline nodes are fixed points: row/col collapse to the self-loop
    for i in (2, 5):
        e = np.zeros(8)
        e[i] = 1.0
        assert np.allclose(W[i], e, atol=1e-6)
        assert np.allclose(W[:, i], e, atol=1e-6)


# ---------------------------------------------------------------- streams


def test_straggler_stream_zero_delays_is_identity():
    arrays = _arrays(8)
    pol = StragglerPolicy(mode="degrade", tau_max=2)
    g, p, eff = straggler_stream(pol, arrays, np.zeros((5, 8), np.int32))
    assert g.shape == (5, 8) and p.shape == (5, 8, 8) and eff.shape == (5, 8)
    assert not np.asarray(eff).any()
    for t in range(5):
        assert np.array_equal(np.asarray(g[t]), np.asarray(arrays.gammas))
        assert np.array_equal(np.asarray(p[t]), np.asarray(arrays.perms))


def test_straggler_pool_stream_wait_and_degrade():
    pool, gammas = _pool_and_gammas()
    delays = np.zeros((4, 8), np.int64)
    delays[1, 3] = 5  # past any deadline below
    delays[2, 0] = 1  # within deadline
    wait = StragglerPolicy(mode="wait", tau_max=2)
    g_w, e_w = straggler_pool_stream(wait, gammas, pool, delays)
    assert g_w.shape == (4, pool.capacity) and e_w.shape == (4, 8)
    # wait: base gammas every step, delays clamped
    for t in range(4):
        assert np.array_equal(np.asarray(g_w[t]), np.asarray(gammas, np.float32))
    assert int(e_w[1, 3]) == 2 and int(e_w[2, 0]) == 1
    deg = StragglerPolicy(mode="degrade", tau_max=2)
    g_d, e_d = straggler_pool_stream(deg, gammas, pool, delays)
    assert int(e_d[1, 3]) == 0  # late node self-loops with fresh params
    # step 1's repaired gammas isolate node 3; steps 0/3 keep the base
    W1 = _pool_dense(pool, np.asarray(g_d[1], np.float64))
    e3 = np.zeros(8)
    e3[3] = 1.0
    assert np.allclose(W1[3], e3, atol=1e-6)
    assert np.array_equal(np.asarray(g_d[0]), np.asarray(gammas, np.float32))
    with pytest.raises(ValueError):
        straggler_pool_stream(deg, gammas, pool, np.zeros((4, 7), np.int64))
    with pytest.raises(ValueError):
        straggler_pool_stream(deg, gammas, pool, -np.ones((4, 8), np.int64))


# --------------------------------------------- simulator: delays=0 bitwise


@pytest.fixture(scope="module")
def me_problem():
    n = 8
    task = mean_estimation_clusters(n_nodes=n, K=4)
    return task, _arrays(n)


@pytest.mark.parametrize("mode", ["wait", "degrade"])
def test_mean_estimation_zero_delays_bitwise_fresh(me_problem, mode):
    task, arrays = me_problem
    kw = dict(steps=24, schedule=arrays, lr=0.1, seed=7, segment_len=8)
    base = run_mean_estimation(task, None, **kw)
    stale = run_mean_estimation(
        task, None, staleness=StragglerPolicy(mode=mode, tau_max=3), **kw
    )
    for key in ("mean_sq_error", "max_sq_error", "min_sq_error"):
        assert np.array_equal(base[key], stale[key]), key
    assert np.array_equal(base["theta"], stale["theta"])
    assert stale["n_traces"] == 1
    assert stale["comm"]["deferred_bytes"] == 0
    assert stale["comm"]["dropped_bytes"] == 0


def test_mean_estimation_zero_delays_bitwise_with_ef(me_problem):
    """Staleness composed with EF compression: zero delays + identity
    routing still leave the bf16 EF trajectory bitwise unchanged."""
    task, arrays = me_problem
    kw = dict(steps=24, schedule=arrays, lr=0.1, seed=7, segment_len=8,
              compression="bf16")
    base = run_mean_estimation(task, None, **kw)
    stale = run_mean_estimation(
        task, None, staleness=StragglerPolicy(mode="wait", tau_max=2), **kw
    )
    for key in ("mean_sq_error", "max_sq_error", "min_sq_error"):
        assert np.array_equal(base[key], stale[key]), key
    assert stale["n_traces"] == 1


def test_mean_estimation_stale_hot_swap_single_trace(me_problem):
    """Live delays + EF memory + a mid-run topology swap, one trace:
    the stale ring and the EF memory share one scan carry and the swap
    is a pure value change."""
    task, arrays = me_problem
    plan = FaultPlan(n_nodes=8, steps=30, seed=2, straggler_rate=0.5, tau_max=3)
    swapped = schedule_to_arrays(
        schedule_from_matrix(0.5 * T.ring(8) + 0.5 * np.eye(8)),
        int(np.asarray(arrays.gammas).shape[0]),
    )
    hooks = iter([None, swapped])
    out = run_mean_estimation(
        task, None, steps=30, schedule=arrays, lr=0.1, seed=7,
        segment_len=10, compression="bf16",
        staleness=StragglerPolicy(mode="wait", tau_max=3),
        delays=plan.delays, on_segment=lambda t: next(hooks, None),
    )
    assert out["n_traces"] == 1, out["n_traces"]
    assert out["swaps"] == [19]
    assert np.isfinite(out["mean_sq_error"]).all()
    assert out["comm"]["deferred_bytes"] > 0   # stragglers were metered late
    assert out["comm"]["dropped_bytes"] == 0   # wait drops nothing
    deg = run_mean_estimation(
        task, None, steps=30, schedule=arrays, lr=0.1, seed=7,
        segment_len=10, staleness=StragglerPolicy(mode="degrade", tau_max=1),
        delays=plan.delays,
    )
    assert deg["n_traces"] == 1
    assert deg["comm"]["dropped_bytes"] > 0    # degrade converts late to lost
    assert np.isfinite(deg["mean_sq_error"]).all()


def test_mean_estimation_staleness_validation(me_problem):
    task, arrays = me_problem
    with pytest.raises(ValueError, match="delays without staleness"):
        run_mean_estimation(
            task, None, steps=4, schedule=arrays,
            delays=np.zeros((4, 8), np.int32),
        )
    with pytest.raises(ValueError, match="ScheduleArrays"):
        run_mean_estimation(
            task, np.full((8, 8), 1 / 8), steps=4,
            staleness=StragglerPolicy(),
        )
    with pytest.raises(ValueError, match="delays must be"):
        run_mean_estimation(
            task, None, steps=4, schedule=arrays,
            staleness=StragglerPolicy(), delays=np.zeros((3, 8), np.int32),
        )


# -------------------------------------------- sharded transports (8 dev)


def test_sharded_stale_transports_zero_delay_bitwise():
    """On a forced-8-device mesh, both sharded stale transports reduce
    bitwise to their fresh twins at delays=0, and nonzero delays match
    the flat single-host stale reference row-for-row."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.core import topology as T
        from repro.core.mixing import (
            PermPool, mix_arrays_sharded, mix_arrays_sharded_stale,
            mix_ppermute_pool, mix_ppermute_pool_stale,
            mix_schedule_arrays_stale, schedule_from_matrix,
            schedule_to_arrays, shard_stale_init, stale_buffer_init,
            stale_push,
        )

        n, Pdim, depth, steps = 8, 16, 3, 4
        mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
        sched = schedule_from_matrix(0.6 * T.ring(n) + 0.4 * np.eye(n))
        arrays = schedule_to_arrays(sched, 8)
        pool = PermPool.from_schedule(sched, capacity=8)
        gammas, _ = pool.project(sched)
        gammas = jnp.asarray(gammas, jnp.float32)
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.normal(size=(steps, n, Pdim)), jnp.float32)
        delays = jnp.asarray([0, 1, 2, 0, 1, 2, 0, 1], jnp.int32)
        zeros = jnp.zeros((n,), jnp.int32)

        def rollout(xs_loc, d):
            # xs_loc (steps, 1, Pdim) per shard; d (n,) replicated
            st_ag = shard_stale_init(xs_loc[0] * 0.0, depth)
            st_pool = shard_stale_init(xs_loc[0] * 0.0, depth)
            f_ag, s_ag, f_pl, s_pl = [], [], [], []
            for t in range(steps):
                x = xs_loc[t]
                f_ag.append(mix_arrays_sharded(x, arrays, "data"))
                m, st_ag = mix_arrays_sharded_stale(x, st_ag, arrays, zeros, "data")
                s_ag.append(m)
                f_pl.append(mix_ppermute_pool(x, gammas, pool, "data"))
                m, st_pool = mix_ppermute_pool_stale(
                    x, st_pool, gammas, pool, zeros, "data"
                )
                s_pl.append(m)
            # one more push, read at NONZERO source-indexed delays
            late_ag, st_ag = mix_arrays_sharded_stale(
                xs_loc[-1], st_ag, arrays, d, "data"
            )
            late_pl, st_pool = mix_ppermute_pool_stale(
                xs_loc[-1], st_pool, gammas, pool, d, "data"
            )
            return (jnp.stack(f_ag), jnp.stack(s_ag), jnp.stack(f_pl),
                    jnp.stack(s_pl), late_ag, late_pl)

        with jax.set_mesh(mesh):
            run = jax.jit(jax.shard_map(
                rollout, mesh=mesh,
                in_specs=(P(None, "data"), P()),
                out_specs=tuple(P(None, "data") for _ in range(4))
                          + (P("data"), P("data")),
                axis_names={"data"}, check_vma=False,
            ))
            f_ag, s_ag, f_pl, s_pl, late_ag, late_pl = run(xs, delays)

        # delays == 0: bitwise the fresh transports, every step
        assert np.array_equal(np.asarray(f_ag), np.asarray(s_ag))
        assert np.array_equal(np.asarray(f_pl), np.asarray(s_pl))
        print("ZERO_DELAY_BITWISE_OK")

        # nonzero delays: match the flat single-host stale reference
        buf = stale_buffer_init(jnp.zeros((n, Pdim)), depth)
        for t in range(steps):
            buf = stale_push(buf, xs[t])
        buf = stale_push(buf, xs[-1])  # the rollout's extra push
        want = mix_schedule_arrays_stale(buf, arrays, delays)
        assert np.allclose(np.asarray(late_ag), np.asarray(want), atol=1e-6), \\
            np.abs(np.asarray(late_ag) - np.asarray(want)).max()
        # and the two sharded transports agree on the same delayed W x
        assert np.allclose(np.asarray(late_ag), np.asarray(late_pl), atol=1e-5)
        print("NONZERO_DELAY_REFERENCE_OK")
    """)
    assert "ZERO_DELAY_BITWISE_OK" in out
    assert "NONZERO_DELAY_REFERENCE_OK" in out


def test_lm_stale_ring_and_ef_share_one_carry():
    """End-to-end LM trainer on a forced-8-device mesh: staleness + EF
    compression + a mid-rollout hot swap run in ONE compiled trace, and
    the delays=0 arm is bitwise the fresh run (losses AND bytes)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core import topology as T
        from repro.core.mixing import (
            StragglerPolicy, schedule_from_matrix, schedule_to_arrays,
        )
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke_config("qwen3-0.6b")
        sched = schedule_from_matrix(0.6 * T.ring(8) + 0.4 * np.eye(8))
        arrays = schedule_to_arrays(sched, 8)
        swapped = schedule_to_arrays(
            schedule_from_matrix(0.5 * T.ring(8) + 0.5 * np.eye(8)), 8
        )
        steps, seg = 8, 4
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (steps, 8, 2, 32), 0, cfg.vocab_size
        )
        batches = {"tokens": toks, "labels": toks}

        def build(**kw):
            s = make_train_setup(cfg, mesh, mode="dsgd", lr=1e-2,
                                 online_w=True, sharded_transport="allgather",
                                 **kw)
            sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                              s.param_specs,
                              is_leaf=lambda x: isinstance(x, P))
            with jax.set_mesh(mesh):
                p = jax.jit(s.init_params, out_shardings=sh)(jax.random.PRNGKey(0))
                o = s.init_opt_state(p)
            return s, p, o

        pol = StragglerPolicy(mode="wait", tau_max=2)

        # fresh vs staleness-at-zero-delays: bitwise
        s0, p0, o0 = build(compression="bf16")
        with jax.set_mesh(mesh):
            base = s0.run_segments(p0, o0, batches, arrays, segment_len=seg)
        s1, p1, o1 = build(compression="bf16", staleness=pol)
        with jax.set_mesh(mesh):
            zero = s1.run_segments(p1, o1, batches, arrays, segment_len=seg)
        assert np.array_equal(base["losses"], zero["losses"])
        assert base["comm"]["total_bytes"] == zero["comm"]["total_bytes"]
        assert zero["comm"]["deferred_bytes"] == 0
        print("LM_ZERO_BITWISE_OK", zero["n_traces"])

        # live delays + EF + mid-rollout hot swap: one trace
        rng = np.random.default_rng(3)
        delays = (rng.random((steps, 8)) < 0.4) * rng.integers(
            1, 3, size=(steps, 8)
        )
        s2, p2, o2 = build(compression="bf16", staleness=pol)
        hooks = iter([swapped])
        with jax.set_mesh(mesh):
            live = s2.run_segments(
                p2, o2, batches, arrays, segment_len=seg,
                delays=delays.astype(np.int32),
                on_segment=lambda t: next(hooks, None),
            )
        assert live["n_traces"] == 1, live["n_traces"]
        assert live["swaps"] == [3]
        assert np.isfinite(live["losses"]).all()
        assert live["comm"]["deferred_bytes"] > 0
        print("LM_STALE_EF_SWAP_OK")
    """, timeout=600)
    assert "LM_ZERO_BITWISE_OK" in out and "LM_STALE_EF_SWAP_OK" in out


def test_lm_staleness_validation():
    out = run_with_devices("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.core.mixing import StragglerPolicy
        from repro.train.lm_trainer import make_train_setup

        mesh = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke_config("qwen3-0.6b")
        pol = StragglerPolicy(mode="wait", tau_max=2)
        for kw, exc in (
            (dict(mode="fsdp", staleness=pol), ValueError),
            (dict(mode="dsgd", online_w=True, gossip_every=2, staleness=pol),
             ValueError),
            (dict(mode="dsgd", staleness=pol), ValueError),  # needs online_w
            (dict(mode="dsgd", online_w=True, staleness="wait"), TypeError),
        ):
            try:
                make_train_setup(cfg, mesh, lr=1e-2, **kw)
            except exc:
                pass
            else:
                raise AssertionError(f"{kw} did not raise {exc}")
        print("LM_VALIDATION_OK")
    """)
    assert "LM_VALIDATION_OK" in out
