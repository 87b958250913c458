"""The dense Qwen2/Qwen3 decoder (HF ``modeling_qwen2`` / ``modeling_qwen3``).

Weight layout, made by ``weights.init`` from ``shapes``: ``embed`` (V, D),
``lm_head`` (D, V) when untied, ``final_norm`` (D,), and per layer,
stacked on a leading layer axis, ``ln1``, ``wq``, ``wk``, ``wv``, ``wo``,
``bq``/``bk``/``bv`` (QKV bias), ``q_norm``/``k_norm`` (qk-norm), ``ln2``,
``w_gate``, ``w_up``, ``w_down``. Which of the optional leaves a model has
follows from its class (``implied_by_architecture`` in its file).

Reference equations: RMSNorm (x * rsqrt(mean x^2 + eps) * w),
grouped-query attention with optional QKV bias and per-head q/k RMSNorm
before rotary embedding (rotate-half, theta from the config), causal
softmax, SwiGLU MLP. Everything but ``model_config`` imports nothing of
the program.
"""

from __future__ import annotations

import dataclasses

import jax

from chipbench.reference import BLOCK, attention, rms_norm, rope

ARCHITECTURES = ("Qwen2ForCausalLM", "Qwen3ForCausalLM")

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


def paths(cfg: dict) -> dict[str, tuple]:
    """Each leaf of ``shapes(cfg)`` -> its path in the program's tree."""
    return {name: PATHS[name] for name in shapes(cfg)}


def model_config(cfg: dict):
    """The program's ModelConfig for the benchmark's configuration file."""
    from repro.configs import get_config

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


def attention_block(x, p, cfg, mm):
    """x + attention(ln1(x)) on one row. x: (S, D) float32."""
    arch = cfg["implied_by_architecture"]
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    s = x.shape[0]
    h = rms_norm(x, p["ln1"], eps)
    q, k, v = (mm("sd,df->sf", h, p[n]) for n in ("wq", "wk", "wv"))
    if arch["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(s, -1, hd)
    k = k.reshape(s, -1, hd)
    v = v.reshape(s, -1, hd)
    if arch["qk_norm"]:
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
    theta = float(cfg["rope_theta"])
    a = attention(rope(q, theta), rope(k, theta), v, mm, min(BLOCK, s))
    return x + mm("sf,fd->sd", a.reshape(s, -1), p["wo"])


def mlp_block(x, p, cfg, mm):
    """x + SwiGLU(ln2(x)) on one row. x: (S, D) float32."""
    h = rms_norm(x, p["ln2"], cfg["rms_norm_eps"])
    g = mm("sd,df->sf", h, p["w_gate"])
    u = mm("sd,df->sf", h, p["w_up"])
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"])


def layer(x, p, cfg, mm, index):
    """One decoder layer on one row. x: (S, D) float32; p: its weights;
    ``index``: the layer's number (a traced scalar), the same for every
    layer of this family."""
    del index
    return mlp_block(attention_block(x, p, cfg, mm), p, cfg, mm)


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul: the layers' projections and
    the LM head (the embedding table itself when it is tied)."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * cfg["intermediate_size"]
    head = d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + head


def attention_flops(cfg: dict, tokens: int, seq_len: int) -> float:
    """Forward and backward of causal QK^T and PV over ``tokens`` tokens."""
    return (12.0 * tokens * cfg["num_attention_heads"] * cfg["head_dim"]
            * (seq_len / 2) * cfg["num_hidden_layers"])
