"""Jitted public wrapper for flash attention (padding + dispatch).

Pads the head dim to an MXU-aligned multiple of 128 and the sequence to a
multiple of the q/kv block sizes (padded kv positions are masked out by the
causal mask since they sit in the "future"), then calls the Pallas kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import (
    DEFAULT_BLOCK_KV,
    DEFAULT_BLOCK_Q,
    flash_attention_pallas,
)
from .ref import flash_attention_ref

__all__ = ["flash_attention"]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv", "interpret", "use_ref"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float = 0.0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """Flash attention with GQA. q: (B,S,H,D); k/v: (B,S,Hkv,D)."""
    if use_ref:
        return flash_attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    B, S, H, D = q.shape
    if S < block_q:  # tiny sequences: kernel tiling is pure overhead
        return flash_attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)

    pad_d = (-D) % 128
    pad_s = (-S) % max(block_q, block_kv)
    # NOTE: scale must use the TRUE head dim, not the padded one; the kernel
    # applies D_padded**-0.5, so pre-scale q to compensate.
    if pad_d:
        Dp = D + pad_d
        q = q * ((Dp / D) ** 0.5)  # undo the kernel's padded scaling
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))

    out = flash_attention_pallas(
        q, k, v,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out[:, :S, :, :D]
