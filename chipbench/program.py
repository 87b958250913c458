"""The system under test, as the benchmark drives it.

Builds ``make_train_setup(cfg, mesh, mode="dsgd", schedule=...)`` from
the pieces ``repro.launch.train.run`` uses, and maps the benchmark's
weight layout (``weights.py``) onto the program's parameter tree. This
is the only module that knows the program's names.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_device_mesh
from repro.launch.train import build_topology
from repro.train.lm_trainer import make_train_setup

from . import traffic as traffic_mod

# benchmark name -> path in the program's tree (after its node axis); the
# stacked layer leaves sit in the one pattern group of a dense model
PATHS = {
    "embed": ("embed", "table"),
    "lm_head": ("embed", "unembed"),
    "final_norm": ("final_norm", "scale"),
    "ln1": ("stages", 0, "ln1", "scale"),
    "wq": ("stages", 0, "attn", "wq"),
    "wk": ("stages", 0, "attn", "wk"),
    "wv": ("stages", 0, "attn", "wv"),
    "wo": ("stages", 0, "attn", "wo"),
    "bq": ("stages", 0, "attn", "bq"),
    "bk": ("stages", 0, "attn", "bk"),
    "bv": ("stages", 0, "attn", "bv"),
    "q_norm": ("stages", 0, "attn", "q_norm", "scale"),
    "k_norm": ("stages", 0, "attn", "k_norm", "scale"),
    "ln2": ("stages", 0, "ln2", "scale"),
    "w_gate": ("stages", 0, "mlp", "w_gate"),
    "w_up": ("stages", 0, "mlp", "w_up"),
    "w_down": ("stages", 0, "mlp", "w_down"),
}


def model_config(cfg: dict):
    """The program's ModelConfig for the benchmark's configuration file."""
    arch = cfg["implied_by_architecture"]
    return dataclasses.replace(
        get_config(cfg["arch"]),
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        attn_bias=arch["qkv_bias"],
        qk_norm=arch["qk_norm"],
        mlp_type="swiglu",
        layer_pattern=("attn",),
        dtype=cfg["torch_dtype"],
    )


@dataclasses.dataclass
class Program:
    setup: object  # repro.train.lm_trainer.TrainSetup
    mesh: object
    W: np.ndarray  # (n, n) the mixing matrix of the schedule the program learned
    param_shardings: object
    batch_sharding: NamedSharding


def build(cfg: dict, traffic: dict) -> Program:
    """The program over the devices present, one node per device."""
    n = traffic["nodes"]
    mesh = make_device_mesh(n, 1)
    pi = traffic_mod.node_mix(traffic)
    schedule = build_topology(traffic["topology"], pi, traffic["budget"],
                              traffic["lam"])
    setup = make_train_setup(model_config(cfg), mesh, mode="dsgd",
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


def to_program(stacked: dict, setup) -> dict:
    """Benchmark layout with a leading node axis -> the program's tree.

    Raises when the program's tree holds leaves the benchmark does not
    make, or shapes that differ: the mapping must cover the whole model.
    """
    proto = jax.eval_shape(setup.init_params, jax.random.PRNGKey(0))
    tree = jax.tree_util.tree_map(lambda _: None, proto)
    for name, x in stacked.items():
        leaf = _get(proto, PATHS[name])
        if leaf.shape != x.shape or leaf.dtype != x.dtype:
            raise ValueError(f"{name}: program has {leaf.shape} {leaf.dtype}, "
                             f"benchmark made {x.shape} {x.dtype}")
        parent = _get(tree, PATHS[name][:-1])
        parent[PATHS[name][-1]] = x
    missing = [jax.tree_util.keystr(p) for p, v in
               jax.tree_util.tree_flatten_with_path(
                   tree, is_leaf=lambda v: v is None)[0] if v is None]
    if missing:
        raise ValueError(f"program leaves the benchmark does not make: {missing}")
    return tree


def from_program(tree: dict, names) -> dict:
    """The program's tree -> benchmark layout (node axis first)."""
    return {name: _get(tree, PATHS[name]) for name in names}


def place_batch(rows: jax.Array) -> dict:
    """(nodes, rows, seq_len + 1) tokens -> the step's batch."""
    return {"tokens": rows[..., :-1], "labels": rows[..., 1:]}


def broadcast_nodes(w: dict, n: int) -> dict:
    return {k: jnp.broadcast_to(v[None], (n,) + v.shape) for k, v in w.items()}
