"""Model registry: uniform init / loss / serve entry points per family.

Dispatches on ``cfg.arch_type``:

* decoder-only families (dense / moe / ssm / hybrid / vlm) -> transformer.py
* audio (whisper) -> whisper.py

``make_inputs`` builds concrete (or ShapeDtypeStruct) example inputs for a
config + shape, shared by smoke tests and the dry-run launcher.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import transformer, whisper
from .common import ModelConfig, dtype_of

PyTree = Any

__all__ = ["init_model", "loss_fn", "model_forward", "make_inputs"]


def init_model(rng: jax.Array, cfg: ModelConfig) -> PyTree:
    if cfg.arch_type == "audio":
        return whisper.init_whisper(rng, cfg)
    return transformer.init_lm(rng, cfg)


def model_forward(
    params: PyTree,
    cfg: ModelConfig,
    batch: dict,
    *,
    cache: PyTree | None = None,
    positions: jax.Array | None = None,
    window_override: int | None = None,
    impl: str = "xla",
):
    """Uniform forward: batch keys depend on the family (see make_inputs)."""
    if cfg.arch_type == "audio":
        return whisper.whisper_forward(
            params, cfg, batch.get("frames"), batch["tokens"],
            cache=cache, positions=positions,
        )
    return transformer.forward(
        params, cfg, batch["tokens"],
        image_embeds=batch.get("image_embeds"),
        cache=cache, positions=positions,
        window_override=window_override, impl=impl,
    )


def loss_fn(params: PyTree, cfg: ModelConfig, batch: dict, impl: str = "xla"):
    """Cross-entropy loss for any family. Returns (loss, metrics)."""
    if cfg.arch_type == "audio":
        logits, _, _ = whisper.whisper_forward(
            params, cfg, batch["frames"], batch["tokens"]
        )
        with jax.named_scope("lm_head"):
            loss = transformer.softmax_xent(logits, batch["labels"])
        return loss, {"nll": loss, "aux": jnp.zeros((), jnp.float32)}
    return transformer.lm_loss(
        params, cfg, batch["tokens"], batch["labels"],
        image_embeds=batch.get("image_embeds"), impl=impl,
    )


def make_inputs(
    cfg: ModelConfig,
    batch_size: int,
    seq_len: int,
    *,
    abstract: bool = False,
    seed: int = 0,
) -> dict:
    """Example training inputs for (cfg, shape).

    For VLM configs the text length is ``seq_len - num_patches`` so the total
    sequence budget matches the assigned shape. For audio, ``seq_len`` is the
    decoder length (labels) and the encoder consumes the stub frames.
    """
    dt = dtype_of(cfg)

    def arr(shape, dtype, maxval=None):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        if jnp.issubdtype(dtype, jnp.integer):
            key = jax.random.PRNGKey(seed)
            return jax.random.randint(key, shape, 0, maxval or cfg.vocab_size, dtype)
        return jnp.zeros(shape, dtype)

    if cfg.arch_type == "audio":
        dec_len = min(seq_len, 448)  # whisper max target positions
        return {
            "frames": arr((batch_size, cfg.encoder.num_frames, cfg.d_model), dt),
            "tokens": arr((batch_size, dec_len), jnp.int32),
            "labels": arr((batch_size, dec_len), jnp.int32),
        }
    if cfg.arch_type == "vlm":
        p = cfg.vision.num_patches
        text_len = max(seq_len - p, 16)
        return {
            "image_embeds": arr((batch_size, p, cfg.d_model), dt),
            "tokens": arr((batch_size, text_len), jnp.int32),
            "labels": arr((batch_size, text_len), jnp.int32),
        }
    return {
        "tokens": arr((batch_size, seq_len), jnp.int32),
        "labels": arr((batch_size, seq_len), jnp.int32),
    }
