"""Each cell's step, and its reference, compiled at the real size for a
described TPU v5e (``v5e:2x2``), with no chip attached.

Nothing runs: this says nothing of values or times. It shows that the
program the window drives fits the chip's memory and, with gossip, holds
its collective-permutes. The topology is described inside a fixture,
never at import, and every such compile of the benchmark lives in this
one file (one process may hold the TPU's library).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import program, reference, weights

HBM = 16 * 2**30
BENCH = Path(__file__).resolve().parent
# (configuration, traffic mix): the two cells, and the four-node mix that
# PERF.md keeps out of the benchmark for now (its gossip rounds in bf16)
CASES = [("qwen3-0.6b", "1node.10x4k"), ("qwen2.5-14b.cut4", "1node.4x4k"),
         ("qwen3-0.6b", "4node.stlfw2")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _case_on(topo, case):
    cfg = json.loads((BENCH / "configs" / f"{case[0]}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{case[1]}.json").read_text())
    n = mix["nodes"]
    mesh = Mesh(np.array(topo.devices[:n]).reshape(n, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return cfg, mix, mesh


def _bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes


@pytest.mark.parametrize("case", CASES, ids="-".join)
def test_step_fits_and_holds_its_collectives(topo, case, monkeypatch):
    cfg, tp, mesh = _case_on(topo, case)
    # the program builds its mesh from jax.devices(); hand it the described one
    monkeypatch.setattr(program, "make_device_mesh", lambda d, m: mesh)
    prog = program.build(cfg, tp)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        prog.setup.abstract_params(), prog.param_shardings)
    rows = jax.ShapeDtypeStruct((tp["nodes"], tp["rows_per_node"], tp["seq_len"]),
                                jnp.int32, sharding=prog.batch_sharding)
    compiled = jax.jit(prog.setup.train_step).lower(
        params, None, {"tokens": rows, "labels": rows}).compile()
    assert _bytes(compiled) < HBM
    has_permute = "collective-permute" in compiled.as_text()
    assert has_permute == (tp["nodes"] > 1)


@pytest.mark.parametrize("case", CASES, ids="-".join)
def test_reference_step_fits(topo, case):
    cfg, tp, mesh = _case_on(topo, case)
    n = tp["nodes"]
    by_node = NamedSharding(mesh, P("data"))
    theta = {k: jax.ShapeDtypeStruct((n,) + shape, jnp.dtype(cfg["torch_dtype"]),
                                     sharding=by_node)
             for k, (shape, _) in weights.shapes(cfg).items()}
    rows = jax.ShapeDtypeStruct((n, tp["rows_per_node"], tp["seq_len"]), jnp.int32,
                                sharding=by_node)
    W = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=NamedSharding(mesh, P()))
    step = reference.make_step(cfg, tp["lr"])
    compiled = jax.jit(step, out_shardings=(by_node, None, None)).lower(
        theta, {"tokens": rows, "labels": rows}, W).compile()
    assert _bytes(compiled) < HBM
