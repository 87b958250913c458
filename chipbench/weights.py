"""Initial weights, made by the benchmark from the seed.

One layout serves the reference and, through ``program.to_program``, the
program: ``embed`` (V, D), ``lm_head`` (D, V) when untied,
``final_norm`` (D,), and per layer, stacked on a leading layer axis,
``ln1``, ``wq``, ``wk``, ``wv``, ``wo``, ``bq``/``bk``/``bv`` (QKV bias),
``q_norm``/``k_norm`` (qk-norm), ``ln2``, ``w_gate``, ``w_up``,
``w_down``. Projections are N(0, 1/fan_in), the embedding N(0, 0.02^2),
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


def shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind); kind is matrix, embed, norm or bias."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    arch = cfg["implied_by_architecture"]
    out = {
        "embed": ((v, d), "embed"),
        "final_norm": ((d,), "norm"),
        "ln1": ((L, d), "norm"),
        "wq": ((L, d, q), "matrix"),
        "wk": ((L, d, kv), "matrix"),
        "wv": ((L, d, kv), "matrix"),
        "wo": ((L, q, d), "matrix"),
        "ln2": ((L, d), "norm"),
        "w_gate": ((L, d, f), "matrix"),
        "w_up": ((L, d, f), "matrix"),
        "w_down": ((L, f, d), "matrix"),
    }
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), "matrix")
    if arch["qkv_bias"]:
        out.update(bq=((L, q), "bias"), bk=((L, kv), "bias"), bv=((L, kv), "bias"))
    if arch["qk_norm"]:
        out.update(q_norm=((L, hd), "norm"), k_norm=((L, hd), "norm"))
    return out


def spread(shape: tuple[int, ...], kind: str) -> float:
    """Standard deviation of a leaf's initial values."""
    return {"matrix": lambda: shape[-2] ** -0.5, "embed": lambda: 0.02,
            "norm": lambda: 0.1, "bias": lambda: 0.02}[kind]()


def init(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    """The weights of one node (jit it: it is one call on the device)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = (1.0 if kind == "norm" else 0.0) + spread(shape, kind) * z
        out[name] = x.astype(dtype)
    return out
