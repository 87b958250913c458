"""The system under test, as the benchmark drives it.

Builds ``make_train_setup(cfg, mesh, mode="dsgd", schedule=...)`` from
the pieces ``repro.launch.train.run`` uses, and maps the benchmark's
weight layout (``weights.py``) onto the program's parameter tree by the
paths of the model's family (``families/<family>.py``). This module and
the families are the only ones that know the program's names.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_device_mesh
from repro.launch.train import build_topology
from repro.train.lm_trainer import make_train_setup

from . import traffic as traffic_mod

@dataclasses.dataclass
class Program:
    setup: object  # repro.train.lm_trainer.TrainSetup
    mesh: object
    W: np.ndarray  # (n, n) the mixing matrix of the schedule the program learned
    param_shardings: object
    batch_sharding: NamedSharding


def build(model_cfg, traffic: dict) -> Program:
    """The program of the ModelConfig ``model_cfg`` (a family's
    ``model_config``) over the devices present, one node per device."""
    n = traffic["nodes"]
    mesh = make_device_mesh(n, 1)
    pi = traffic_mod.node_mix(traffic)
    schedule = build_topology(traffic["topology"], pi, traffic["budget"],
                              traffic["lam"])
    setup = make_train_setup(model_cfg, mesh, mode="dsgd",
                             schedule=schedule, lr=traffic["lr"])
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), setup.param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    W = schedule.to_matrix() if schedule is not None else np.full((n, n), 1.0 / n)
    return Program(setup, mesh, np.asarray(W), shardings,
                   NamedSharding(mesh, P("data")))


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def to_program(stacked: dict, setup, paths: dict) -> dict:
    """Benchmark layout with a leading node axis -> the program's tree, by
    ``paths`` (name -> path in the tree, a family's ``paths``).

    Raises when the program's tree holds leaves the benchmark does not
    make, or shapes that differ: the mapping must cover the whole model.
    """
    proto = jax.eval_shape(setup.init_params, jax.random.PRNGKey(0))
    tree = jax.tree_util.tree_map(lambda _: None, proto)
    for name, x in stacked.items():
        leaf = _get(proto, paths[name])
        if leaf.shape != x.shape or leaf.dtype != x.dtype:
            raise ValueError(f"{name}: program has {leaf.shape} {leaf.dtype}, "
                             f"benchmark made {x.shape} {x.dtype}")
        parent = _get(tree, paths[name][:-1])
        parent[paths[name][-1]] = x
    missing = [jax.tree_util.keystr(p) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   tree, is_leaf=lambda v: v is None)[0] if v is None]
    if missing:
        raise ValueError(f"program leaves the benchmark does not make: {missing}")
    return tree


def from_program(tree: dict, paths: dict) -> dict:
    """The program's tree -> benchmark layout (node axis first)."""
    return {name: _get(tree, path) for name, path in paths.items()}


def place_batch(rows: jax.Array) -> dict:
    """(nodes, rows, seq_len + 1) tokens -> the step's batch."""
    return {"tokens": rows[..., :-1], "labels": rows[..., 1:]}


def broadcast_nodes(w: dict, n: int) -> dict:
    return {k: jnp.broadcast_to(v[None], (n,) + v.shape) for k, v in w.items()}
