"""Model FLOPs of one training step, from a configuration's shapes.

Counted: 6 x matmul parameters x tokens (forward and backward of every
projection of every layer, and of the LM head), plus causal attention,
12 x tokens x heads x head_dim x (seq_len / 2) x layers. Not counted:
the input embedding (a lookup, no matmul), norms, biases, and what remat
computes twice.
"""

from __future__ import annotations


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


def step_flops(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one D-SGD step over all nodes of the cell."""
    seq = traffic["seq_len"]
    tokens = traffic["nodes"] * traffic["rows_per_node"] * seq
    return 6.0 * matmul_params(cfg) * tokens + attention_flops(cfg, tokens, seq)
