"""Decoder-only language model assembly from a ModelConfig.

Handles every assigned decoder-only architecture through the per-layer block
pattern: 'attn' / 'local_attn' (GQA or MLA + dense-or-MoE MLP), 'mlstm',
'slstm' (self-contained xLSTM blocks), 'rglru' (Griffin recurrent block +
MLP). VLM (llava) inputs are handled by prepending stub patch embeddings.

Layer-stacking: layers are grouped into repetitions of ``cfg.layer_pattern``
and executed with ``jax.lax.scan`` over the repetitions (parameters for each
pattern position are stacked on a leading "group" axis). This keeps the HLO
size and compile time O(pattern) instead of O(num_layers), and bounds live
activation memory to one group (one layer's working set) with per-group
activation checkpointing. Layers that do not fill a whole pattern
repetition (e.g. recurrentgemma's 26 = 8x3 + 2) run unrolled as the "tail".

API:
  init_lm(rng, cfg)                      -> params
  forward(params, cfg, tokens, ...)      -> (logits|hidden, new_cache, aux)
  init_cache(cfg, batch, max_len, ...)   -> cache pytree
  lm_loss(params, cfg, batch)            -> (loss, metrics)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from .attention import (
    attention,
    init_attention,
    init_attention_cache,
    init_mla_attention,
    init_mla_cache,
    mla_attention,
)
from .common import ModelConfig, dtype_of
from .layers import (
    embed,
    init_embedding,
    init_mlp,
    init_rms_norm,
    mlp_forward,
    rms_norm,
    unembed,
)
from .moe import init_moe, moe_forward
from .rglru import init_rglru_block, init_rglru_state, rglru_block
from .xlstm import (
    init_mlstm_block,
    init_mlstm_state,
    init_slstm_block,
    init_slstm_state,
    mlstm_block,
    slstm_block,
)

PyTree = Any

__all__ = [
    "init_lm",
    "forward",
    "init_cache",
    "lm_loss",
    "softmax_xent",
    "fused_unembed_xent",
]

_ATTN_KINDS = ("attn", "local_attn")


# ---------------------------------------------------------------------------
# Per-layer init / forward (kind-static)
# ---------------------------------------------------------------------------

def _init_layer(key: jax.Array, cfg: ModelConfig, kind: str) -> PyTree:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    params: PyTree = {}
    if kind in _ATTN_KINDS:
        params["ln1"] = init_rms_norm(cfg.d_model, dt)
        if cfg.mla is not None:
            params["attn"] = init_mla_attention(ks[0], cfg)
        else:
            params["attn"] = init_attention(ks[0], cfg)
        if cfg.post_block_norms:
            params["post_ln1"] = init_rms_norm(cfg.d_model, dt)
    elif kind == "mlstm":
        params["block"] = init_mlstm_block(ks[0], cfg)
    elif kind == "slstm":
        params["block"] = init_slstm_block(ks[0], cfg)
    elif kind == "rglru":
        params["block"] = init_rglru_block(ks[0], cfg)
    else:
        raise ValueError(f"unknown layer kind {kind}")

    if cfg.d_ff > 0 and kind not in ("mlstm", "slstm"):
        params["ln2"] = init_rms_norm(cfg.d_model, dt)
        if cfg.moe is not None:
            params["mlp"] = init_moe(ks[1], cfg)
        else:
            params["mlp"] = init_mlp(ks[1], cfg)
        if cfg.post_block_norms:
            params["post_ln2"] = init_rms_norm(cfg.d_model, dt)
    return params


def _layer_forward(
    lp: PyTree,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    cache_layer: PyTree | None,
    window_override: int | None,
    impl: str,
) -> tuple[jax.Array, PyTree | None, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    if kind in _ATTN_KINDS:
        with jax.named_scope("attn"):
            h = rms_norm(lp["ln1"], x, cfg.norm_eps)
            local = kind == "local_attn" or window_override is not None
            if cfg.mla is not None:
                win = window_override if window_override is not None else (
                    cfg.sliding_window if kind == "local_attn" else None
                )
                attn_out, new_cache = mla_attention(
                    lp["attn"], cfg, h, positions=positions, cache=cache_layer,
                    window=win,
                )
            else:
                attn_out, new_cache = attention(
                    lp["attn"], cfg, h,
                    positions=positions,
                    local=local,
                    window=window_override,
                    cache=cache_layer,
                    impl=impl,
                )
            if cfg.post_block_norms:
                attn_out = rms_norm(lp["post_ln1"], attn_out, cfg.norm_eps)
            x = x + attn_out
    elif kind == "mlstm":
        x, new_cache = mlstm_block(lp["block"], cfg, x, cache_layer)
    elif kind == "slstm":
        x, new_cache = slstm_block(lp["block"], cfg, x, cache_layer)
    elif kind == "rglru":
        x, new_cache = rglru_block(lp["block"], cfg, x, cache_layer)

    if cfg.d_ff > 0 and kind not in ("mlstm", "slstm"):
        with jax.named_scope("mlp"):
            h = rms_norm(lp["ln2"], x, cfg.norm_eps)
            if cfg.moe is not None:
                mlp_out, aux = moe_forward(lp["mlp"], cfg, h)
            else:
                mlp_out = mlp_forward(lp["mlp"], h, cfg.mlp_type)
            if cfg.post_block_norms:
                mlp_out = rms_norm(lp["post_ln2"], mlp_out, cfg.norm_eps)
            x = x + mlp_out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Whole-model init: stacked pattern groups + tail
# ---------------------------------------------------------------------------

def _group_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(num_full_groups, num_tail_layers)."""
    plen = len(cfg.layer_pattern)
    return cfg.num_layers // plen, cfg.num_layers % plen


def init_lm(rng: jax.Array, cfg: ModelConfig) -> PyTree:
    reps, rem = _group_layout(cfg)
    plen = len(cfg.layer_pattern)
    keys = jax.random.split(rng, cfg.num_layers + 2)

    stages = []
    for j, kind in enumerate(cfg.layer_pattern):
        group_params = [
            _init_layer(keys[g * plen + j], cfg, kind) for g in range(reps)
        ]
        stages.append(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *group_params)
            if reps > 0
            else None
        )
    tail = [
        _init_layer(keys[reps * plen + t], cfg, cfg.layer_pattern[t % plen])
        for t in range(rem)
    ]
    return {
        "embed": init_embedding(keys[-1], cfg),
        "stages": stages,
        "tail": tail,
        "final_norm": init_rms_norm(cfg.d_model, dtype_of(cfg)),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    image_embeds: jax.Array | None = None,
    cache: PyTree | None = None,
    positions: jax.Array | None = None,
    window_override: int | None = None,
    impl: str = "xla",
    remat: bool = True,
    return_hidden: bool = False,
) -> tuple[jax.Array, PyTree | None, jax.Array]:
    """Decoder forward.

    Args:
      tokens: (B, S_text) int tokens.
      image_embeds: optional (B, P, D) stub patch embeddings (VLM) prepended
        to the text sequence (prefill / training only).
      cache: cache pytree from init_cache for decode; None = full sequence.
      positions: (B, S_total) absolute positions (required with cache).
      window_override: force all attention layers to a sliding window (the
        long_500k sub-quadratic serving mode).
      impl: 'xla' | 'pallas' attention implementation.
      remat: per-group activation checkpointing (training path).
      return_hidden: skip the unembedding (used by the fused loss).

    Returns (logits | hidden, new_cache, moe_aux_loss).
    """
    with jax.named_scope("embed"):
        x = embed(params["embed"], tokens, cfg)
    if image_embeds is not None:
        x = jnp.concatenate([image_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    pattern = cfg.layer_pattern
    reps, rem = _group_layout(cfg)

    # scan over the stacked groups
    aux_total = jnp.zeros((), jnp.float32)
    new_cache_stages = None
    if reps > 0:
        stage_params = [params["stages"][j] for j in range(len(pattern))]
        stage_caches = (
            [cache["stages"][j] for j in range(len(pattern))]
            if cache is not None
            else None
        )

        def body(carry, xs):
            x = carry["x"]
            aux = carry["aux"]
            sp = xs["params"]
            sc = xs.get("caches")
            new_caches = []
            for j, kind in enumerate(pattern):
                cl = sc[j] if sc is not None else None
                x, nc, a = _layer_forward(
                    sp[j], cfg, kind, x, positions, cl, window_override, impl
                )
                aux = aux + a
                new_caches.append(nc)
            out = {"caches": tuple(new_caches)} if sc is not None else {}
            return {"x": x, "aux": aux}, out

        if remat and cache is None:
            body = jax.checkpoint(body, prevent_cse=False)

        xs = {"params": stage_params}
        if stage_caches is not None:
            xs["caches"] = stage_caches
        carry, ys = jax.lax.scan(body, {"x": x, "aux": aux_total}, xs)
        x = carry["x"]
        aux_total = carry["aux"]
        if cache is not None:
            new_cache_stages = list(ys["caches"])

    # unrolled tail layers
    new_tail = []
    for t, lp in enumerate(params["tail"]):
        kind = pattern[t % len(pattern)]
        cl = cache["tail"][t] if cache is not None else None
        x, nc, a = _layer_forward(
            lp, cfg, kind, x, positions, cl, window_override, impl
        )
        aux_total = aux_total + a
        new_tail.append(nc)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    new_cache = (
        {"stages": new_cache_stages, "tail": new_tail} if cache is not None else None
    )
    if return_hidden:
        return x, new_cache, aux_total
    logits = unembed(params["embed"], x, cfg)
    return logits, new_cache, aux_total


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _init_layer_cache(
    cfg: ModelConfig, kind: str, batch: int, max_len: int, long_context: bool
) -> PyTree:
    if kind in _ATTN_KINDS:
        if cfg.mla is not None:
            L = cfg.long_context_window if long_context else max_len
            return init_mla_cache(cfg, batch, L)
        local = kind == "local_attn" or long_context
        window = cfg.long_context_window if long_context else None
        return init_attention_cache(cfg, batch, max_len, local=local, window=window)
    if kind == "mlstm":
        return init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return init_slstm_state(cfg, batch)
    if kind == "rglru":
        return init_rglru_state(cfg, batch)
    raise ValueError(kind)


def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    *,
    long_context: bool = False,
) -> PyTree:
    """Cache pytree matching the stacked-group layout of the model.

    ``long_context=True`` selects the sub-quadratic mode: every attention
    layer gets a ring-buffer window cache of ``cfg.long_context_window``.
    """
    reps, rem = _group_layout(cfg)
    pattern = cfg.layer_pattern
    stages = []
    for j, kind in enumerate(pattern):
        per_group = [
            _init_layer_cache(cfg, kind, batch, max_len, long_context)
            for _ in range(reps)
        ]
        stages.append(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_group)
            if reps > 0
            else None
        )
    tail = [
        _init_layer_cache(cfg, pattern[t % len(pattern)], batch, max_len, long_context)
        for t in range(rem)
    ]
    return {"stages": stages, "tail": tail}


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Memory-lean cross entropy: logits stay in compute dtype (bf16) and
    vocab-shardable; logsumexp reduces over V in f32; the label logit is a
    one-hot einsum (no gather -- GSPMD keeps the vocab axis sharded)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)  # (B, S)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    label_logit = jnp.einsum(
        "bsv,bsv->bs", logits.astype(jnp.float32), onehot.astype(jnp.float32)
    )
    return jnp.mean(lse - label_logit)


_XENT_CHUNK = 512


def fused_unembed_xent(
    params: PyTree, cfg: ModelConfig, hidden: jax.Array, labels: jax.Array
) -> jax.Array:
    """Unembed + cross-entropy fused over sequence chunks: the full (B,S,V)
    logits tensor never materializes -- peak extra memory is one
    (B, chunk, V) block (re-materialized in the backward pass via remat)."""
    B, S, D = hidden.shape
    if S % _XENT_CHUNK != 0:
        return softmax_xent(unembed(params["embed"], hidden, cfg), labels)
    nc = S // _XENT_CHUNK

    def chunk_nll(ci):
        h = jax.lax.dynamic_slice_in_dim(hidden, ci * _XENT_CHUNK, _XENT_CHUNK, 1)
        lab = jax.lax.dynamic_slice_in_dim(labels, ci * _XENT_CHUNK, _XENT_CHUNK, 1)
        logits = unembed(params["embed"], h, cfg)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(lab, logits.shape[-1], dtype=logits.dtype)
        label_logit = jnp.einsum(
            "bsv,bsv->bs", logits.astype(jnp.float32), onehot.astype(jnp.float32)
        )
        return jnp.sum(lse - label_logit)

    totals = jax.lax.map(jax.checkpoint(chunk_nll), jnp.arange(nc))
    return jnp.sum(totals) / (B * S)


def lm_loss(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,
    labels: jax.Array,
    *,
    image_embeds: jax.Array | None = None,
    impl: str = "xla",
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy (+ MoE aux). Labels align with text tokens."""
    hidden, _, aux = forward(
        params, cfg, tokens, image_embeds=image_embeds, impl=impl,
        return_hidden=True,
    )
    if image_embeds is not None:
        hidden = hidden[:, image_embeds.shape[1] :, :]
    with jax.named_scope("lm_head"):
        loss = fused_unembed_xent(params, cfg, hidden, labels)
    total = loss
    if cfg.moe is not None:
        total = total + cfg.moe.router_aux_coef * aux
    return total, {"nll": loss, "aux": aux}
