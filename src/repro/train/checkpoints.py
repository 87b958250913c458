"""Checkpointing: msgpack-serialized pytrees with a manifest.

Layout of a checkpoint directory::

    <dir>/
      manifest.json       # step, tree structure, shapes/dtypes, metadata
      arrays.msgpack      # flat list of raw array buffers

In ``dsgd`` mode the trainer checkpoints the stacked per-node parameters, so
a single checkpoint holds every node's replica (restorable onto a different
node count only through explicit re-mixing, which we deliberately do not do
silently).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import msgpack
import numpy as np
from jax.sharding import NamedSharding

PyTree = Any

__all__ = ["save_checkpoint", "restore_checkpoint", "latest_step", "CheckpointManager"]

_MANIFEST = "manifest.json"
_ARRAYS = "arrays.msgpack"


def _tree_paths(tree: PyTree) -> list[str]:
    paths, _ = zip(*jax.tree_util.tree_flatten_with_path(tree)[0]) if jax.tree_util.tree_leaves(tree) else ((), None)
    return [jax.tree_util.keystr(p) for p in paths]


def save_checkpoint(directory: str, step: int, tree: PyTree, metadata: dict | None = None) -> str:
    """Write ``tree`` under ``directory/step_<step>``; returns the path."""
    path = os.path.join(directory, f"step_{step:08d}")
    os.makedirs(path, exist_ok=True)
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = [jax.tree_util.keystr(kp) for kp, _ in leaves_with_paths]
    leaves = [np.asarray(leaf) for _, leaf in leaves_with_paths]
    manifest = {
        "step": step,
        "keys": keys,
        "shapes": [list(x.shape) for x in leaves],
        "dtypes": [str(x.dtype) for x in leaves],
        "treedef": str(treedef),
        "metadata": metadata or {},
    }
    packed = msgpack.packb([x.tobytes() for x in leaves], use_bin_type=True)
    with open(os.path.join(path, _ARRAYS), "wb") as f:
        f.write(packed)
    with open(os.path.join(path, _MANIFEST), "w") as f:
        json.dump(manifest, f)
    return path


def restore_checkpoint(directory: str, step: int, like: PyTree) -> tuple[PyTree, dict]:
    """Restore into the structure of ``like`` (shapes/dtypes validated).

    Leaves whose template is sharded over a mesh are placed with the
    template's sharding; the others come back as numpy arrays.
    """
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, _MANIFEST)) as f:
        manifest = json.load(f)
    with open(os.path.join(path, _ARRAYS), "rb") as f:
        raw = msgpack.unpackb(f.read(), raw=False)
    leaves_like, treedef = jax.tree_util.tree_flatten(like)
    if len(raw) != len(leaves_like):
        raise ValueError(
            f"checkpoint has {len(raw)} leaves, template has {len(leaves_like)}"
        )
    leaves = []
    for buf, shape, dtype, tmpl in zip(raw, manifest["shapes"], manifest["dtypes"], leaves_like):
        arr = np.frombuffer(buf, dtype=np.dtype(dtype)).reshape(shape)
        t_shape = tuple(np.shape(tmpl))
        if t_shape != tuple(shape):
            raise ValueError(f"shape mismatch: checkpoint {shape} vs template {t_shape}")
        if isinstance(getattr(tmpl, "sharding", None), NamedSharding):
            # land on the template's mesh: a leaf restored to the host
            # would reach a jitted step as a different input type than
            # the live state it replaces, and retrace it
            arr = jax.device_put(arr, tmpl.sharding)
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves), manifest["metadata"]


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(name.split("_")[1])
        for name in os.listdir(directory)
        if name.startswith("step_")
    ]
    return max(steps) if steps else None


@dataclasses.dataclass
class CheckpointManager:
    """Keeps the most recent ``max_to_keep`` checkpoints."""

    directory: str
    max_to_keep: int = 3

    def save(self, step: int, tree: PyTree, metadata: dict | None = None) -> str:
        path = save_checkpoint(self.directory, step, tree, metadata)
        self._gc()
        return path

    def restore_latest(self, like: PyTree) -> tuple[int, PyTree, dict] | None:
        step = latest_step(self.directory)
        if step is None:
            return None
        tree, meta = restore_checkpoint(self.directory, step, like)
        return step, tree, meta

    def _gc(self) -> None:
        steps = sorted(
            int(name.split("_")[1])
            for name in os.listdir(self.directory)
            if name.startswith("step_")
        )
        for s in steps[: -self.max_to_keep]:
            p = os.path.join(self.directory, f"step_{s:08d}")
            for fn in os.listdir(p):
                os.remove(os.path.join(p, fn))
            os.rmdir(p)
