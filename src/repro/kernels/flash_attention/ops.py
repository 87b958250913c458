"""Jitted public wrapper for flash attention (padding, block sizes, dispatch).

Pads the head dim to an MXU-aligned multiple of 128 and the sequence to a
multiple of the q/kv block sizes (padded kv positions are masked out by the
causal mask since they sit in the "future"), then calls the Pallas kernel.
Differentiable: the kernel carries its own backward pass, and the padding's
transpose slices the padded head dims off dq, dk and dv.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_pallas
from .ref import flash_attention_ref

__all__ = ["flash_attention", "block_for"]


def block_for(seq: int, head_dim: int) -> int:
    """The q and kv block for ``seq`` tokens of ``head_dim``: the whole
    (128-padded) sequence up to the largest block, else the largest block of
    1024, 512, 256, 128 that divides it. The largest block is 1024 up to a
    128 head dim (timed on a v5e at S = 4096: PERF.md) and 512 above, where
    1024-row tiles of the backward outgrow VMEM."""
    top = 1024 if head_dim <= 128 else 512
    padded = -(-seq // 128) * 128
    if padded <= top:
        return padded
    return next(b for b in (1024, 512, 256, 128) if b <= top and padded % b == 0)


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
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """Flash attention with GQA. q: (B,S,H,D); k/v: (B,S,Hkv,D).

    ``block_q`` / ``block_kv`` default to :func:`block_for` of the shape.
    """
    if use_ref:
        return flash_attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    B, S, H, D = q.shape
    block_q = block_q or block_for(S, D)
    block_kv = block_kv or block_for(S, D)
    if S < block_q:  # tiny sequences: kernel tiling is pure overhead
        return flash_attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)

    pad_d = (-D) % 128
    pad_s = (-S) % math.lcm(block_q, block_kv)
    if pad_s and not causal:
        raise ValueError("padding the sequence needs the causal mask to hide it")
    if pad_d or pad_s:
        pad = ((0, 0), (0, pad_s), (0, 0), (0, pad_d))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))

    out = flash_attention_pallas(
        q, k, v,
        causal=causal, window=window, softcap=softcap, scale=D**-0.5,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out[:, :S, :, :D]
