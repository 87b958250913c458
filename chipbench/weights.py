"""Initial weights, made by the benchmark from the seed.

One layout serves the reference and, through ``program.to_program``, the
program: the leaves the model's family names in its ``shapes`` (each
family's docstring lists them), layer leaves stacked on a leading layer
axis. Matrices are N(0, 1/fan_in), fan-in the second-to-last axis (a
stack of experts ``(L, E, d, f)`` too), the embedding N(0, 0.02^2),
norm scales 1 + N(0, 0.1^2), biases N(0, 0.02^2), all stored in the
configuration's dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STREAM_WEIGHTS, STREAM_TRAFFIC = 0, 1


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for one stream of the run; any whole ``seed`` below 2**64."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    return jax.random.fold_in(key, stream)


def spread(shape: tuple[int, ...], kind: str) -> float:
    """Standard deviation of a leaf's initial values."""
    return {"matrix": lambda: shape[-2] ** -0.5, "embed": lambda: 0.02,
            "norm": lambda: 0.1, "bias": lambda: 0.02}[kind]()


def init(key: jax.Array, cfg: dict, family) -> dict[str, jax.Array]:
    """The weights of one node of ``family``'s model ``cfg`` (jit it: it is
    one call on the device)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(family.shapes(cfg).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = (1.0 if kind == "norm" else 0.0) + spread(shape, kind) * z
        out[name] = x.astype(dtype)
    return out
