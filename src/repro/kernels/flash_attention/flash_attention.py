"""Pallas TPU flash attention (forward): causal / sliding-window, GQA.

Online-softmax tiling (Dao et al., adapted to TPU):

* grid = (B * H, num_q_blocks, num_kv_blocks); the kv axis is the innermost
  ("arbitrary") dimension so the running (m, l, acc) state carries across kv
  steps in VMEM scratch.
* Per grid step the kernel holds one (BQ, D) q tile, one (BKV, D) k tile and
  one (BKV, D) v tile in VMEM; BQ = BKV = 128 and D <= 256 keeps the working
  set < 1 MiB -- far below the ~16 MiB v5e VMEM, leaving room for double
  buffering of the streamed k/v tiles.
* MXU alignment: BQ/BKV are multiples of 128; D is padded to a multiple of
  128 by the ops.py wrapper.
* Causal / window block skipping happens at trace time: out-of-range kv
  blocks are masked entirely (their contribution is exp(-inf) = 0); fully
  in-range blocks skip the mask computation.

GQA is expressed through the k/v BlockSpec index maps: q head ``h`` reads kv
head ``h // (H // Hkv)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
_NEG_INF = -2.0e9


def _fa_kernel(
    q_ref, k_ref, v_ref, out_ref,
    m_scratch, l_scratch, acc_scratch,
    *, scale: float, causal: bool, window: int | None, softcap: float,
    block_q: int, block_kv: int, num_kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_kv

    # Trace-time reasoning is impossible (qi/ki are dynamic), so compute a
    # cheap runtime block-relevance predicate instead.
    relevant = jnp.asarray(True)
    if causal:
        relevant = relevant & (k_start <= q_start + block_q - 1)
    if window is not None:
        relevant = relevant & (k_start + block_kv - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
        k = k_ref[0].astype(jnp.float32)  # (BKV, D)
        v = v_ref[0].astype(jnp.float32)  # (BKV, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BKV)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scratch[...]  # (BQ, 1)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (BQ, BKV)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_scratch[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_scratch[...] / l_safe).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_kv", "interpret"
    ),
)
def flash_attention_pallas(
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
) -> jax.Array:
    """q: (B, S, H, D); k/v: (B, S, Hkv, D); S % block == 0, D MXU-aligned."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    if S % block_q or S % block_kv:
        raise ValueError(f"S={S} must be divisible by block sizes")
    nq = S // block_q
    nkv = S // block_kv

    # layout: fold heads into the batch grid axis; keep (S, D) per block
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b = bh // H
        h = bh % H
        return (b * Hkv + h // groups, ki, 0)

    kernel = functools.partial(
        _fa_kernel,
        scale=D**-0.5,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nkv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_kv, D), kv_map),
            pl.BlockSpec((1, block_kv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
