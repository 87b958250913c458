"""Transport equivalence: every mixing execution of the same W must agree.

Covers the tentpole surface of the sparse Birkhoff mixing engine:
  * mix_dense == mix_schedule_stacked (single-buffer, per-leaf, and Pallas
    gossip_schedule kernel paths) on random doubly-stochastic W and on
    learned STL-FW schedules;
  * mix_ppermute == mix_dense on real multi-device buffers (subprocess,
    forced host devices -- reuses the test_distributed harness);
  * ravel_stack/unravel_stack round-trip incl. pad-once edge cases
    (P not a multiple of 128, n = 1);
  * scan-compiled rollouts match the per-step loop bit-for-bit;
  * the preferred_transport cost model's shape.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology as T
from repro.core.mixing import (
    BirkhoffSchedule,
    mix_dense,
    mix_schedule_stacked,
    mix_stacked,
    preferred_transport,
    ravel_stack,
    schedule_from_matrix,
    schedule_from_result,
    unravel_stack,
)
from repro.core.stl_fw import learn_topology
from repro.data.synthetic import mean_estimation_clusters, gaussian_blobs
from repro.data.partition import shard_partition
from repro.train.trainer import run_classification, run_mean_estimation

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _sinkhorn(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    M = rng.random((n, n)) + 0.05
    for _ in range(400):
        M /= M.sum(1, keepdims=True)
        M /= M.sum(0, keepdims=True)
    return M


def _random_tree(n: int, seed: int):
    rng = np.random.default_rng(seed)
    return {
        "w1": jnp.asarray(rng.normal(size=(n, 13, 7)), jnp.float32),
        "b1": jnp.asarray(rng.normal(size=(n, 7)), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(n, 7, 3)), jnp.float32),
    }


def _assert_trees_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol)


@pytest.mark.parametrize("n", [4, 9, 16])
def test_schedule_matches_dense_on_sinkhorn_W(n):
    W = _sinkhorn(n, seed=n)
    sched = schedule_from_matrix(W)
    Wj = jnp.asarray(sched.to_matrix(), jnp.float32)  # exact atoms' matrix
    tree = _random_tree(n, seed=n + 1)
    dense = mix_dense(tree, Wj)
    for kwargs in (
        {"single_buffer": True},
        {"single_buffer": False},
        {"use_kernel": True, "block_p": 128},
    ):
        _assert_trees_close(dense, mix_schedule_stacked(tree, sched, **kwargs))


@pytest.mark.parametrize("budget", [2, 6])
def test_schedule_matches_dense_on_learned_topology(budget):
    n, K = 12, 4
    rng = np.random.default_rng(budget)
    Pi = rng.dirichlet(np.ones(K) * 0.5, size=n)
    res = learn_topology(Pi, budget=budget, lam=0.2)
    sched = schedule_from_result(res)
    assert sched.n_communication_atoms <= budget  # Theorem 2 sparsity
    tree = _random_tree(n, seed=budget + 10)
    dense = mix_dense(tree, jnp.asarray(res.W, jnp.float32))
    _assert_trees_close(dense, mix_schedule_stacked(tree, sched))
    _assert_trees_close(dense, mix_stacked(tree, schedule=sched, transport="schedule"))


def test_mix_stacked_auto_picks_and_agrees():
    n = 16
    W = T.ring(n)
    sched = schedule_from_matrix(W)  # ring: 3 atoms << n -> schedule
    assert preferred_transport(n, sched.n_atoms) == "schedule"
    assert preferred_transport(n, n) == "dense"
    tree = _random_tree(n, seed=3)
    Wj = jnp.asarray(W, jnp.float32)
    _assert_trees_close(
        mix_dense(tree, Wj),
        mix_stacked(tree, W=Wj, schedule=sched, transport="auto"),
    )


def test_ppermute_matches_schedule_stacked_multidevice():
    """All three transports agree on real multi-device buffers."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import topology as T
        from repro.core.mixing import (schedule_from_matrix, mix_ppermute,
                                       mix_dense, mix_schedule_stacked)

        n = 8
        mesh = jax.make_mesh((n,), ("data",))
        W = T.random_d_regular(n, 3, seed=4)
        sched = schedule_from_matrix(W)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(n, 24)), jnp.float32)

        def gossip(v):
            return jax.shard_map(lambda p: mix_ppermute(p, sched, "data"),
                                 mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P("data"), axis_names={"data"},
                                 check_vma=False)(v)

        got = np.asarray(jax.jit(gossip)(x))
        Wj = jnp.asarray(sched.to_matrix(), jnp.float32)
        dense = np.asarray(mix_dense(x, Wj))
        stacked = np.asarray(mix_schedule_stacked(x, sched))
        assert np.allclose(got, dense, atol=1e-5), np.abs(got - dense).max()
        assert np.allclose(stacked, dense, atol=1e-5), np.abs(stacked - dense).max()
        print("TRANSPORTS_AGREE")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=480, env=env,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert "TRANSPORTS_AGREE" in proc.stdout


# ---------------------------------------------------------------------------
# single-buffer ravel/unravel + pad-once edge cases
# ---------------------------------------------------------------------------

def test_ravel_roundtrip_pads_once():
    tree = _random_tree(5, seed=0)
    flat, spec = ravel_stack(tree, pad_to=128)
    assert flat.shape[1] % 128 == 0
    assert spec.pad == spec.padded - spec.total
    _assert_trees_close(tree, unravel_stack(flat, spec), atol=0.0)


@pytest.mark.parametrize("n,sizes", [(1, (37,)), (3, (5, 130)), (2, (128, 1))])
def test_schedule_kernel_shape_edge_cases(n, sizes):
    """P not a multiple of 128 and n = 1 must both work through the kernel
    path (padding happens once, at flatten time)."""
    rng = np.random.default_rng(n)
    tree = {f"p{i}": jnp.asarray(rng.normal(size=(n, s)), jnp.float32) for i, s in enumerate(sizes)}
    if n == 1:
        sched = BirkhoffSchedule(coeffs=(1.0,), perms=((0,),))
    else:
        sched = schedule_from_matrix(_sinkhorn(n, seed=n + 7))
    dense = mix_dense(tree, jnp.asarray(sched.to_matrix(), jnp.float32))
    kern = mix_schedule_stacked(tree, sched, use_kernel=True, block_p=128)
    _assert_trees_close(dense, kern)


def test_mixed_dtype_single_buffer():
    rng = np.random.default_rng(0)
    n = 4
    tree = {
        "a": jnp.asarray(rng.normal(size=(n, 40)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(n, 24)), jnp.bfloat16),
    }
    sched = schedule_from_matrix(T.ring(n))
    out = mix_schedule_stacked(tree, sched)
    assert out["a"].dtype == jnp.float32 and out["b"].dtype == jnp.bfloat16
    dense = mix_dense(tree, jnp.asarray(sched.to_matrix(), jnp.float32))
    np.testing.assert_allclose(
        np.asarray(out["a"]), np.asarray(dense["a"]), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out["b"], np.float32), np.asarray(dense["b"], np.float32), atol=5e-2
    )


# ---------------------------------------------------------------------------
# scan rollout == python loop, bit for bit
# ---------------------------------------------------------------------------

def test_mean_estimation_scan_matches_loop_bitwise():
    task = mean_estimation_clusters(n_nodes=12, K=4, m=3.0)
    W = T.ring(12)
    a = run_mean_estimation(task, W, steps=40, lr=0.2, seed=3, rollout="scan")
    b = run_mean_estimation(task, W, steps=40, lr=0.2, seed=3, rollout="loop")
    assert np.array_equal(a["theta"], b["theta"])
    for k in ("mean_sq_error", "max_sq_error", "min_sq_error"):
        assert np.array_equal(a[k], b[k]), k


def test_mean_estimation_scan_matches_loop_with_schedule_transport():
    task = mean_estimation_clusters(n_nodes=10, K=5, m=2.0)
    res = learn_topology(task.Pi, budget=4, lam=0.5)
    sched = schedule_from_result(res)
    a = run_mean_estimation(task, None, steps=25, lr=0.2, seed=1,
                            schedule=sched, transport="schedule", rollout="scan")
    b = run_mean_estimation(task, None, steps=25, lr=0.2, seed=1,
                            schedule=sched, transport="schedule", rollout="loop")
    assert np.array_equal(a["theta"], b["theta"])
    assert np.array_equal(a["mean_sq_error"], b["mean_sq_error"])


def test_classification_scan_matches_loop_trace():
    X, y = gaussian_blobs(n_samples=800, num_classes=5, dim=12, seed=2)
    idx, Pi = shard_partition(y, 8, seed=0)
    kwargs = dict(steps=33, batch_size=8, lr=0.3, eval_every=10,
                  X_test=X[:100], y_test=y[:100], seed=5)
    la = run_classification(X, y, idx, T.ring(8), rollout="scan", **kwargs)
    lb = run_classification(X, y, idx, T.ring(8), rollout="loop", **kwargs)
    assert la.history == lb.history


# ---------------------------------------------------------------------------
# measured transport autotune table
# ---------------------------------------------------------------------------

def test_autotune_transport_fallback_and_memoize(tmp_path, monkeypatch):
    from repro.core import mixing as M

    path = str(tmp_path / "transport_autotune.json")
    monkeypatch.setenv("REPRO_TRANSPORT_AUTOTUNE", path)
    M._autotune_cache = None  # drop any table cached from other tests

    # miss without measure => closed-form fallback, nothing written
    assert M.autotune_transport(64, 4, 512) == M.preferred_transport(64, 4)
    assert M.autotune_transport(64, 60, 512) == M.preferred_transport(64, 60)
    assert not os.path.exists(path)

    # miss with measure => record written at the power-of-two bucket,
    # keyed by a hardware fingerprint so one machine's measurements
    # never decide transports on different hardware
    w = M.autotune_transport(60, 3, 500, measure=True)
    assert w in ("schedule", "dense")
    import json
    key = M._bucket_key(60, 3, 500)
    assert key.endswith("_n64_L4_P512") and key.startswith(M._hw_tag())
    table = json.load(open(path))
    assert key in table
    for k in ("schedule_us", "dense_us", "winner", "backend", "hw"):
        assert k in table[key]

    # same bucket now resolves from the table even when the closed form
    # would disagree (force disagreement via an absurd dense_speedup)
    forced = M.autotune_transport(64, 4, 512, dense_speedup=1e9)
    assert forced == w
    M._autotune_cache = None  # don't leak the tmp table to other tests


def test_mix_stacked_autotune_transport_matches_dense(tmp_path, monkeypatch):
    from repro.core import mixing as M

    monkeypatch.setenv("REPRO_TRANSPORT_AUTOTUNE", str(tmp_path / "t.json"))
    M._autotune_cache = None
    rng = np.random.default_rng(7)
    n = 12
    W = T.ring(n)
    sched = M.schedule_from_matrix(W)
    params = {"w": jnp.asarray(rng.normal(size=(n, 96)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(n, 7)), jnp.float32)}
    got = mix_stacked(params, W=jnp.asarray(W, jnp.float32), schedule=sched,
                      transport="autotune")
    want = mix_dense(params, jnp.asarray(W, jnp.float32))
    for k in params:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), atol=1e-5
        )
    M._autotune_cache = None


# ---------------------------------------------------------------------------
# sharded hot-swap transports: PermPool + cost model + autotune schema
# ---------------------------------------------------------------------------

def test_perm_pool_staging_projection_and_restage():
    from repro.core.mixing import PermPool

    sched = schedule_from_matrix(T.ring(8))
    pool = PermPool.from_schedule(sched, capacity=6)
    assert pool.capacity == 6 and pool.n_nodes == 8
    # ring = 0.5 I + 0.25 shift + 0.25 shift^-1: 2 comm slots, identity
    # headroom pads the rest (free until staged)
    assert pool.n_comm_slots == sched.n_communication_atoms
    g, dropped = pool.project(sched)
    assert dropped == 0.0 and pool.contains(sched)
    np.testing.assert_allclose(pool.to_matrix(g), T.ring(8), atol=1e-12)

    # out-of-pool atom: its mass is dropped, the rest renormalized (the
    # executed W stays doubly stochastic)
    new_perm = tuple(int(v) for v in np.roll(np.arange(8), 3))
    drifted = BirkhoffSchedule(
        coeffs=(0.6,) + tuple(0.4 * c for c in sched.coeffs),
        perms=(new_perm,) + sched.perms,
    )
    g2, dropped2 = pool.project(drifted)
    assert abs(dropped2 - 0.6) < 1e-12 and not pool.contains(drifted)
    assert abs(g2.sum() - 1.0) < 1e-6
    W2 = pool.to_matrix(g2)
    np.testing.assert_allclose(W2.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(W2.sum(axis=1), 1.0, atol=1e-6)

    # restage fits everything again
    restaged = PermPool.from_schedule(drifted, capacity=6)
    assert restaged.contains(drifted)
    # over-capacity schedules are truncated, largest coefficients kept
    many = BirkhoffSchedule(
        coeffs=tuple(np.full(8, 1 / 8)),
        perms=tuple(tuple(int(v) for v in np.roll(np.arange(8), k)) for k in range(8)),
    )
    small = PermPool.from_schedule(many, capacity=3)
    assert small.capacity == 3

    with pytest.raises(ValueError):
        PermPool(perms=((0, 0, 1),))  # not a permutation
    with pytest.raises(ValueError):
        pool.project(schedule_from_matrix(T.ring(4)))  # node-count mismatch


def test_perm_pool_arrays_for_matches_slots():
    from repro.core.mixing import PermPool, arrays_to_matrix

    sched = schedule_from_matrix(T.ring(8))
    pool = PermPool.from_schedule(sched, capacity=5)
    g, _ = pool.project(sched)
    arrays = pool.arrays_for(g)
    assert arrays.l_max == pool.capacity
    np.testing.assert_allclose(arrays_to_matrix(arrays), T.ring(8), atol=1e-6)
    with pytest.raises(ValueError):
        pool.arrays_for(np.ones(3, np.float32))  # wrong gamma shape


def test_preferred_sharded_transport_crossover():
    from repro.core.mixing import preferred_sharded_transport

    # bytes: pool moves K*P per node, all-gather (n-1)*P discounted by
    # the fused-collective advantage => pool iff K <= (n-1)/advantage
    assert preferred_sharded_transport(8, 3) == "pool"
    assert preferred_sharded_transport(8, 4) == "allgather"
    assert preferred_sharded_transport(512, 64) == "pool"
    assert preferred_sharded_transport(4, 3, allgather_speedup=1.0) == "pool"
    with pytest.raises(ValueError):
        preferred_sharded_transport(8, 3, allgather_speedup=0.0)


def test_autotune_sharded_transport_schema_and_fallback(tmp_path, monkeypatch):
    import json

    from repro.core import mixing as M

    path = str(tmp_path / "transport_autotune.json")
    monkeypatch.setenv("REPRO_TRANSPORT_AUTOTUNE", path)
    M._autotune_cache = None

    # lookup-only miss => closed form, nothing written, nothing timed
    assert M.autotune_sharded_transport(8, 3, 4096) == "pool"
    assert M.autotune_sharded_transport(8, 7, 4096) == "allgather"
    assert not os.path.exists(path)
    # measure without a mesh cannot time => still the closed form
    assert M.autotune_sharded_transport(8, 7, 4096, measure=True) == "allgather"

    # a measured entry (the "sh_" schema extension of the same table)
    # overrides the closed form at its bucket -- and ONLY there
    key = M._sharded_bucket_key(8, 3, 4096)
    assert key.startswith("sh_") and key.endswith("_n8_K4_P4096")
    with open(path, "w") as f:
        json.dump({key: {"winner": "allgather"}}, f)
    M._autotune_cache = None
    assert M.autotune_sharded_transport(8, 3, 4096) == "allgather"
    assert M.autotune_sharded_transport(8, 3, 1 << 20) == "pool"  # other bucket
    # stacked-transport lookups never see sharded keys (disjoint prefix)
    assert M.autotune_transport(8, 3, 4096) == M.preferred_transport(8, 3)
    M._autotune_cache = None


def test_mix_bytes_per_step_model():
    from repro.train.metrics import CommMeter, mix_bytes_per_step

    P_, n = 1000, 8
    ag = mix_bytes_per_step("allgather", n_nodes=n, p_total=P_)
    pool = mix_bytes_per_step("pool", n_nodes=n, p_total=P_, n_comm_atoms=2)
    assert ag == (n - 1) * P_ * 4 and pool == 2 * P_ * 4
    assert mix_bytes_per_step("dense", n_nodes=n, p_total=P_) == 0
    assert mix_bytes_per_step(
        "ppermute", n_nodes=n, p_total=P_, n_comm_atoms=3
    ) == 3 * P_ * 4
    with pytest.raises(ValueError):
        mix_bytes_per_step("pool", n_nodes=n, p_total=P_)  # needs n_comm_atoms
    with pytest.raises(ValueError):
        mix_bytes_per_step("warp", n_nodes=n, p_total=P_)

    meter = CommMeter(per_step_bytes=ag)
    meter.tick(10)
    meter.set_rate(pool, step=10)
    meter.tick(5)
    s = meter.summary()
    assert s["total_bytes"] == 10 * ag + 5 * pool
    assert s["steps"] == 15 and s["rate_changes"] == [
        {"step": 10, "per_step_bytes": pool}
    ]
