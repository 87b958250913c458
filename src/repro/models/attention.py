"""Attention blocks: GQA/MQA (qk-norm, bias, softcap, sliding window), MLA,
and cross-attention, with full-sequence and cached-decode paths.

Layout conventions: activations (B, S, D); q/k/v (B, S, H, Dh). Keys are
rotated (RoPE) before caching. The full-sequence causal path runs the Pallas
flash-attention kernel when ``impl='pallas'``, and by default (``impl='xla'``)
above 2048 tokens on a TPU when the head dim is a multiple of 128; otherwise
plain XLA einsums (chunked above 2048 tokens).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.kernels.flash_attention import flash_attention

from .common import MLAConfig, ModelConfig, dtype_of, truncated_normal
from .kvcache import (
    init_full_cache,
    init_window_cache,
    update_full_cache,
    update_window_cache,
)
from .layers import apply_rope, rms_norm, rotary_embedding

PyTree = Any

_NEG_INF = -2.0e9

__all__ = [
    "init_attention",
    "attention",
    "init_mla_attention",
    "mla_attention",
    "init_cross_attention",
    "cross_attention",
    "init_attention_cache",
    "init_mla_cache",
]


# ---------------------------------------------------------------------------
# Standard multi-head attention with GQA / MQA
# ---------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig) -> PyTree:
    dt = dtype_of(cfg)
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    std = d**-0.5
    params = {
        "wq": truncated_normal(ks[0], (d, h * dh), std, dt),
        "wk": truncated_normal(ks[1], (d, hkv * dh), std, dt),
        "wv": truncated_normal(ks[2], (d, hkv * dh), std, dt),
        "wo": truncated_normal(ks[3], (h * dh, d), (h * dh) ** -0.5, dt),
    }
    if cfg.attn_bias:
        params["bq"] = jnp.zeros((h * dh,), dt)
        params["bk"] = jnp.zeros((hkv * dh,), dt)
        params["bv"] = jnp.zeros((hkv * dh,), dt)
    if cfg.qk_norm:
        params["q_norm"] = {"scale": jnp.ones((dh,), dt)}
        params["k_norm"] = {"scale": jnp.ones((dh,), dt)}
    return params


def _project_qkv(params: PyTree, cfg: ModelConfig, x: jax.Array):
    B, S, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.attn_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, h, dh)
    k = k.reshape(B, S, hkv, dh)
    v = v.reshape(B, S, hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None,
    cfg: ModelConfig,
) -> jax.Array:
    """Grouped scaled-dot-product attention. q: (B,Sq,H,Dh); k/v: (B,Sk,Hkv,Dh).

    mask: broadcastable to (B, 1, Sq, Sk) boolean (True = attend) or None.
    """
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    scale = Dh**-0.5
    qg = q.reshape(B, Sq, Hkv, groups, Dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    if cfg.attn_logit_softcap > 0.0:
        cap = cfg.attn_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    if mask is not None:
        logits = jnp.where(mask[:, :, None, :, :] if mask.ndim == 4 else mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


_CHUNK_THRESHOLD = 2048  # full-seq lengths above this use the chunked path
_CHUNK_Q = 512


def _sdpa_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: ModelConfig,
    window: int | None,
    chunk_q: int = _CHUNK_Q,
) -> jax.Array:
    """Flash-style causal attention in pure XLA: scan over q chunks with a
    full-k online-softmax per chunk. Peak temp is O(B*H*chunk_q*S) instead of
    O(B*H*S^2) -- the stand-in for the Pallas kernel off the TPU and for
    head dims that are not a multiple of 128 (same tiling idea, executed by
    XLA).
    """
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    scale = Dh**-0.5
    assert S % chunk_q == 0
    nq = S // chunk_q
    qg = q.reshape(B, S, Hkv, groups, Dh)
    kpos = jnp.arange(S)

    def one_chunk(ci):
        q_chunk = jax.lax.dynamic_slice_in_dim(qg, ci * chunk_q, chunk_q, axis=1)
        # bf16 inputs, f32 accumulation -- no full-tensor f32 copies
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q_chunk, k,
            preferred_element_type=jnp.float32,
        ) * scale
        if cfg.attn_logit_softcap > 0.0:
            cap = cfg.attn_logit_softcap
            logits = cap * jnp.tanh(logits / cap)
        qpos = ci * chunk_q + jnp.arange(chunk_q)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        out = out / jnp.sum(p, axis=-1).transpose(0, 3, 1, 2)[..., None]
        return out.reshape(B, chunk_q, H, Dh).astype(q.dtype)

    # checkpoint each chunk: the map's backward recomputes chunk logits
    # instead of stacking every chunk's probs (O(S^2) residuals otherwise)
    chunks = jax.lax.map(jax.checkpoint(one_chunk), jnp.arange(nq))
    return chunks.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dh)


def _flash(
    q: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig, window: int | None
) -> jax.Array:
    """Causal attention through the Pallas flash kernel (differentiable).

    XLA cannot partition a Mosaic kernel, so under a mesh the call runs in a
    shard_map over the axes not yet manual, replicated over them.
    """
    attend = functools.partial(
        flash_attention, causal=True, window=window,
        softcap=cfg.attn_logit_softcap,
    )
    auto = _auto_axes()
    if auto:
        attend = jax.shard_map(
            attend, in_specs=(P(),) * 3, out_specs=P(), axis_names=set(auto),
            check_vma=False,
        )
    return attend(q, k, v)


def _auto_axes() -> dict[str, int]:
    """Size of each axis of the current mesh that is not manual."""
    mesh = jax.sharding.get_abstract_mesh()
    return {
        name: size
        for name, size, kind in zip(mesh.axis_names, mesh.axis_sizes, mesh.axis_types)
        if kind != AxisType.Manual
    }


def _unpartitioned() -> bool:
    """Whether the step runs attention on one device as it stands: every
    automatic mesh axis has size 1, or there is no mesh and one device."""
    if jax.sharding.get_abstract_mesh().empty:
        return jax.device_count() == 1
    return all(size == 1 for size in _auto_axes().values())


def _causal_mask(Sq: int, Sk: int, window: int | None) -> jax.Array:
    """(1, 1, Sq, Sk) boolean mask; Sk == Sq for full-sequence paths."""
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (kpos > qpos - window)
    return mask[None, None]


def attention(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    local: bool = False,
    window: int | None = None,
    cache: PyTree | None = None,
    causal: bool = True,
    impl: str = "xla",
) -> tuple[jax.Array, PyTree | None]:
    """Self-attention. Returns (output, updated_cache).

    Full-sequence when ``cache is None``; cached decode/append otherwise.
    ``local=True`` applies the layer's sliding window (``window`` overrides
    ``cfg.sliding_window`` -- used by the long_500k sub-quadratic mode).
    """
    B, S, _ = x.shape
    dh = cfg.resolved_head_dim
    eff_window = window if window is not None else (cfg.sliding_window if local else None)
    with jax.named_scope("qkv"):
        q, k, v = _project_qkv(params, cfg, x)
        cos, sin = rotary_embedding(positions, dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    with jax.named_scope("sdpa"):
        if cache is None:
            if impl == "pallas" and causal:
                out = _flash(q, k, v, cfg, eff_window)
            elif causal and S > _CHUNK_THRESHOLD and S % _CHUNK_Q == 0:
                # on a TPU the Pallas kernel, where the head dim fills MXU
                # lanes and no mesh axis would have to partition the call
                if (jax.default_backend() == "tpu" and dh % 128 == 0
                        and _unpartitioned()):
                    out = _flash(q, k, v, cfg, eff_window)
                else:
                    out = _sdpa_chunked(q, k, v, cfg, eff_window)
            else:
                mask = _causal_mask(S, S, eff_window) if causal else None
                out = _sdpa(q, k, v, mask, cfg)
            new_cache = None
        elif S > 1:
            # Prefill (multi-token append, assumed from a fresh cache): compute
            # the chunk's attention on the full-sequence path -- the chunked
            # flash-style implementation, NOT a quadratic attend against the
            # (possibly much larger) cache buffer -- then write the cache.
            # (A window ring also cannot serve as the source while being
            # filled: early keys may be evicted before later queries need them.)
            if causal and S > _CHUNK_THRESHOLD and S % _CHUNK_Q == 0:
                out = _sdpa_chunked(q, k, v, cfg, eff_window)
            else:
                mask = _causal_mask(S, S, eff_window) if causal else None
                out = _sdpa(q, k, v, mask, cfg)
            if local or window is not None:
                new_cache = update_window_cache(cache, k, v)
            else:
                new_cache = update_full_cache(cache, k, v)
        else:
            # positions: (B, S) absolute positions of the new tokens.
            qpos = positions[:, :, None]  # (B, Sq, 1)
            if not (local or window is not None):
                new_cache = update_full_cache(cache, k, v)
                Sk = new_cache["k"].shape[1]
                kpos = jnp.arange(Sk)[None, None, :]  # (1, 1, Sk)
                mask = kpos <= qpos  # (B, Sq, Sk)
                out = _sdpa(q, new_cache["k"], new_cache["v"], mask[:, None], cfg)
            else:  # window ring buffer
                new_cache = update_window_cache(cache, k, v)
                W = new_cache["k"].shape[1]
                slot = jnp.arange(W)
                idx = new_cache["index"]  # absolute positions written so far
                # absolute position held by each ring slot after the write:
                # largest value < idx congruent to the slot modulo W.
                abs_pos = (idx - 1) - jnp.mod(idx - 1 - slot, W)  # (W,)
                abs_pos = abs_pos[None, None, :]  # (1, 1, W)
                mask = (abs_pos >= 0) & (abs_pos <= qpos)
                if eff_window is not None:
                    mask = mask & (abs_pos > qpos - eff_window)
                out = _sdpa(q, new_cache["k"], new_cache["v"], mask[:, None], cfg)
    with jax.named_scope("out"):
        B, Sq = out.shape[:2]
        out = out.reshape(B, Sq, -1) @ params["wo"]
    return out, new_cache


def init_attention_cache(
    cfg: ModelConfig, batch: int, max_len: int, *, local: bool, window: int | None = None
) -> PyTree:
    dt = dtype_of(cfg)
    dh = cfg.resolved_head_dim
    if local or window is not None:
        w = window if window is not None else cfg.sliding_window
        w = min(w, max_len)
        return init_window_cache(batch, w, cfg.num_kv_heads, dh, dt)
    return init_full_cache(batch, max_len, cfg.num_kv_heads, dh, dt)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla_attention(key: jax.Array, cfg: ModelConfig) -> PyTree:
    assert cfg.mla is not None
    m: MLAConfig = cfg.mla
    dt = dtype_of(cfg)
    d, h = cfg.d_model, cfg.num_heads
    dq = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    std = d**-0.5
    params = {
        "wq": truncated_normal(ks[0], (d, h * dq), std, dt),
        "w_dkv": truncated_normal(ks[1], (d, m.kv_lora_rank), std, dt),
        "w_krope": truncated_normal(ks[2], (d, m.qk_rope_head_dim), std, dt),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), dt)},
        "w_uk": truncated_normal(ks[3], (m.kv_lora_rank, h * m.qk_nope_head_dim), m.kv_lora_rank**-0.5, dt),
        "w_uv": truncated_normal(ks[4], (m.kv_lora_rank, h * m.v_head_dim), m.kv_lora_rank**-0.5, dt),
        "wo": truncated_normal(ks[5], (h * m.v_head_dim, d), (h * m.v_head_dim) ** -0.5, dt),
    }
    return params


@jax.named_scope("sdpa")
def _mla_attend(
    params: PyTree,
    cfg: ModelConfig,
    q_nope: jax.Array,
    q_rope: jax.Array,
    c_kv: jax.Array,
    k_rope: jax.Array,
    mask: jax.Array | None,
) -> jax.Array:
    """Attention over compressed latents. q_*: (B,Sq,H,*); c_kv: (B,Sk,r);
    k_rope: (B,Sk,dr)."""
    m = cfg.mla
    B, Sq, H, dn = q_nope.shape
    Sk = c_kv.shape[1]
    k_nope = (c_kv @ params["w_uk"]).reshape(B, Sk, H, m.qk_nope_head_dim)
    v = (c_kv @ params["w_uv"]).reshape(B, Sk, H, m.v_head_dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
        + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    ) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H * m.v_head_dim).astype(q_nope.dtype)


@jax.named_scope("sdpa")
def _mla_attend_chunked(
    params: PyTree,
    cfg: ModelConfig,
    q_nope: jax.Array,
    q_rope: jax.Array,
    c_kv: jax.Array,
    k_rope: jax.Array,
    window: int | None,
    chunk_q: int = _CHUNK_Q,
) -> jax.Array:
    """Chunked-causal MLA: decompress k/v once, scan q chunks (flash-style)
    so the (H, S, S) logits tensor never materializes."""
    m = cfg.mla
    B, S, H, dn = q_nope.shape
    k_nope = (c_kv @ params["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (c_kv @ params["w_uv"]).reshape(B, S, H, m.v_head_dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    nq = S // chunk_q
    kpos = jnp.arange(S)

    def one_chunk(ci):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, ci * chunk_q, chunk_q, 1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, ci * chunk_q, chunk_q, 1)
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope, preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope, preferred_element_type=jnp.float32)
        ) * scale
        qpos = ci * chunk_q + jnp.arange(chunk_q)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
        p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        out = out / jnp.sum(p, axis=-1).transpose(0, 2, 1)[..., None]
        return out.reshape(B, chunk_q, H * m.v_head_dim).astype(q_nope.dtype)

    chunks = jax.lax.map(jax.checkpoint(one_chunk), jnp.arange(nq))
    out = chunks.transpose(1, 0, 2, 3).reshape(B, S, H * m.v_head_dim)
    return out.astype(q_nope.dtype)


def mla_attention(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    cache: PyTree | None = None,
    window: int | None = None,
) -> tuple[jax.Array, PyTree | None]:
    """MLA self-attention; the cache stores (c_kv, roped k_rope) only."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q = (x @ params["wq"]).reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    cos, sin = rotary_embedding(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    c_kv = rms_norm(params["kv_norm"], x @ params["w_dkv"], cfg.norm_eps)
    k_rope = apply_rope((x @ params["w_krope"])[:, :, None, :], cos, sin)[:, :, 0, :]

    if cache is None and S > _CHUNK_THRESHOLD and S % _CHUNK_Q == 0:
        out = _mla_attend_chunked(params, cfg, q_nope, q_rope, c_kv, k_rope, window)
        new_cache = None
    elif cache is not None and S > 1:
        # MLA prefill from a fresh cache: full-sequence compute + cache write
        if S > _CHUNK_THRESHOLD and S % _CHUNK_Q == 0:
            out = _mla_attend_chunked(params, cfg, q_nope, q_rope, c_kv, k_rope, window)
        else:
            qpos = jnp.arange(S)[:, None]
            kpos = jnp.arange(S)[None, :]
            mask = kpos <= qpos
            if window is not None:
                mask = mask & (kpos > qpos - window)
            out = _mla_attend(params, cfg, q_nope, q_rope, c_kv, k_rope, mask[None, None])
        L = cache["c_kv"].shape[1]
        ck = c_kv if S <= L else c_kv[:, -L:]
        kr = k_rope if S <= L else k_rope[:, -L:]
        start = jnp.mod(cache["index"] + jnp.maximum(S - L, 0), L)
        ckv_buf = jax.lax.dynamic_update_slice(cache["c_kv"], ck.astype(cache["c_kv"].dtype), (0, start, 0))
        krope_buf = jax.lax.dynamic_update_slice(cache["k_rope"], kr.astype(cache["k_rope"].dtype), (0, start, 0))
        new_cache = {"c_kv": ckv_buf, "k_rope": krope_buf, "index": cache["index"] + S}
    elif cache is None:
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
        mask = mask[None, None]
        out = _mla_attend(params, cfg, q_nope, q_rope, c_kv, k_rope, mask)
        new_cache = None
    else:
        # Ring-buffer semantics: capacity L == buffer length. For
        # decode_32k the buffer covers the whole context (no wrap); for
        # long_500k the buffer is cfg.long_context_window and wraps.
        idx = cache["index"]
        L = cache["c_kv"].shape[1]
        slot0 = jnp.mod(idx, L)
        ckv_buf = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, slot0, 0)
        )
        krope_buf = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, slot0, 0)
        )
        new_cache = {"c_kv": ckv_buf, "k_rope": krope_buf, "index": idx + S}
        slot = jnp.arange(L)
        new_idx = idx + S
        abs_pos = (new_idx - 1) - jnp.mod(new_idx - 1 - slot, L)  # (L,)
        abs_pos = abs_pos[None, None, :]
        qpos = positions[:, :, None]  # (B, Sq, 1)
        mask = (abs_pos >= 0) & (abs_pos <= qpos)
        if window is not None:
            mask = mask & (abs_pos > qpos - window)
        out = _mla_attend(params, cfg, q_nope, q_rope, ckv_buf, krope_buf, mask[:, None])
    out = out @ params["wo"]
    return out, new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    dt = dtype_of(cfg)
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dt),
        "index": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key: jax.Array, cfg: ModelConfig) -> PyTree:
    return init_attention(key, cfg)


def cross_attention(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    encoder_out: jax.Array,
) -> jax.Array:
    """Query from decoder x, keys/values from encoder output (no RoPE --
    whisper uses learned/sinusoidal absolute positions)."""
    B, S, _ = x.shape
    Se = encoder_out.shape[1]
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, h, dh)
    k = (encoder_out @ params["wk"]).reshape(B, Se, hkv, dh)
    v = (encoder_out @ params["wv"]).reshape(B, Se, hkv, dh)
    out = _sdpa(q, k, v, None, cfg)
    return out.reshape(B, S, -1) @ params["wo"]
