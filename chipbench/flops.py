"""Model FLOPs of one training step, from a configuration's shapes.

Counted: 6 x matmul parameters x tokens (forward and backward of every
projection of every layer, and of the LM head), plus causal attention;
the model's family counts both (``families/<family>.py``:
``matmul_params``, ``attention_flops``). Not counted: the input
embedding (a lookup, no matmul), norms, biases, and what remat computes
twice.
"""

from __future__ import annotations


def step_flops(cfg: dict, traffic: dict, family) -> float:
    """Model FLOPs of one D-SGD step over all nodes of the cell."""
    seq = traffic["seq_len"]
    tokens = traffic["nodes"] * traffic["rows_per_node"] * seq
    return (6.0 * family.matmul_params(cfg) * tokens
            + family.attention_flops(cfg, tokens, seq))
