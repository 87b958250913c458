"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than present. Whatever it refuses here -- a kernel slice
the tiling cannot prove aligned, a step that does not fit in HBM -- would
fail on the chip. Nothing runs: these tests say nothing about values or
times. The topology is described inside a fixture, never at import, and
all such compiles live in this one file (one process may hold libtpu).
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import learn_topology, schedule_from_result
from repro.core.mixing import mix_dense_sharded
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gossip_mix import gossip_mix, gossip_schedule
from repro.train.lm_trainer import make_train_setup

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip can be written to the persistent
        # cache but never read back here: keep the cache off meanwhile
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_schedule_compiles(one_chip, dtype):
    n, L, Pdim = 100, 6, 32768

    def mix(theta, coeffs, perms):
        return gossip_schedule(theta, coeffs, perms, interpret=False)

    compiled = jax.jit(mix).lower(
        _shape((n, Pdim), dtype, one_chip),
        _shape((L,), jnp.float32, one_chip),
        _shape((L, n), jnp.int32, one_chip),
    ).compile()
    _assert_kernel(compiled)


def test_gossip_mix_compiles(one_chip):
    n, Pdim = 100, 32768

    def mix(theta, W):
        return gossip_mix(theta, W, interpret=False)

    compiled = jax.jit(mix).lower(
        _shape((n, Pdim), jnp.float32, one_chip),
        _shape((n, n), jnp.float32, one_chip),
    ).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles_at_qwen3_shapes(one_chip):
    """Forward and gradient at the qwen3 cell's shape: 10 x 4096 tokens,
    16 q heads and 8 kv heads of 128, in the shape-chosen 1024 blocks."""
    cfg = get_config("qwen3-0.6b")
    B, S, D = 10, 4096, cfg.head_dim
    q = _shape((B, S, cfg.num_heads, D), jnp.bfloat16, one_chip)
    kv = _shape((B, S, cfg.num_kv_heads, D), jnp.bfloat16, one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    forward = jax.jit(attend).lower(q, kv, kv).compile().as_text()
    assert "flash_fwd" in forward and "flash_bwd" not in forward
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    for name in _FLASH_KERNELS:
        assert name in grad.as_text(), name


_FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def test_qwen3_dsgd_step_fits_one_chip(topo):
    """The launcher's step on one chip: qwen3-0.6b at published widths, one
    D-SGD node, the STL-FW schedule of one node, per-node batch 1 x 2048."""
    cfg = get_config("qwen3-0.6b")
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    Pi = np.array([[0.9, 0.1 / 3, 0.1 / 3, 0.1 / 3]])
    schedule = schedule_from_result(learn_topology(Pi, budget=2, lam=0.1))
    setup = make_train_setup(cfg, mesh, mode="dsgd", schedule=schedule, lr=5e-3)
    params = jax.tree_util.tree_map(
        lambda a, s: _shape(a.shape, a.dtype, NamedSharding(mesh, s)),
        setup.abstract_params(), setup.param_specs,
    )
    tokens = _shape((1, 1, 2048), jnp.int32, NamedSharding(mesh, P("data")))
    compiled = jax.jit(setup.train_step).lower(
        params, None, {"tokens": tokens, "labels": tokens}
    ).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def test_qwen3_dsgd_step_through_flash_kernel_fits_one_chip(topo, monkeypatch):
    """The qwen3 cell's step, one node x 10 x 4096, with attention through
    the Pallas kernel: what ``attention()`` selects on a TPU. The test steers
    ``impl`` and interpret mode itself, since the compile runs on the CPU
    backend. The XLA path's step takes 11.80 GiB (PERF.md)."""
    fa_mod = importlib.import_module("repro.kernels.flash_attention.flash_attention")
    monkeypatch.setattr(fa_mod, "resolve_interpret", lambda interpret: False)
    jax.clear_caches()  # no trace that resolved interpret mode on the CPU
    cfg = get_config("qwen3-0.6b")
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    schedule = schedule_from_result(learn_topology(
        np.array([[0.9, 0.1 / 3, 0.1 / 3, 0.1 / 3]]), budget=2, lam=0.1))
    setup = make_train_setup(cfg, mesh, mode="dsgd", schedule=schedule,
                             lr=5e-3, impl="pallas")
    params = jax.tree_util.tree_map(
        lambda a, s: _shape(a.shape, a.dtype, NamedSharding(mesh, s)),
        setup.abstract_params(), setup.param_specs,
    )
    tokens = _shape((1, 10, 4096), jnp.int32, NamedSharding(mesh, P("data")))
    compiled = jax.jit(setup.train_step).lower(
        params, None, {"tokens": tokens, "labels": tokens}
    ).compile()
    jax.clear_caches()
    text = compiled.as_text()
    _assert_kernel(compiled)
    for name in _FLASH_KERNELS:
        assert name in text, name
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def test_serialized_gather_holds_one_leaf_on_four_chips(topo):
    """The all-gather mixing of four nodes, one per chip: serialized, the
    TPU schedule keeps one leaf's (n, P_leaf) gather live, not the stack."""
    n, n_leaves = 4, 6
    mesh = Mesh(np.array(topo.devices).reshape(n), ("data",),
                axis_types=(AxisType.Auto,))
    leaf = _shape((n, 1024, 8192), jnp.float32, NamedSharding(mesh, P("data")))
    leaves = {f"w{i}": leaf for i in range(n_leaves)}
    W = _shape((n, n), jnp.float32, NamedSharding(mesh, P()))
    one_gathered_leaf = n * 1024 * 8192 * 4

    def temp_bytes(serialize):
        def f(p, w):
            return jax.shard_map(
                lambda q: mix_dense_sharded(q, w, "data", serialize=serialize),
                mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
                axis_names={"data"}, check_vma=False)(p)
        compiled = jax.jit(f).lower(leaves, W).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    assert temp_bytes(True) <= 2 * one_gathered_leaf
    assert temp_bytes(False) > 2 * one_gathered_leaf
