"""Model FLOPs of training a dense decoder, from the configuration's shapes.

Counted per trained token, forward and backward (3x the forward), as in the
usual definition of model FLOP utilization: every matmul with a weight
(query, key, value and output projections, the gated FFN, the output
head; the embedding lookup is a gather, not a matmul) and causal attention
(scores and the weighted sum, half of the full square).  Recomputation is
not counted: a program that recomputes does more work for the same tokens.
"""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    d, ff = model["d_model"], model["d_ff"]
    head_dim = model.get("head_dim") or d // model["num_heads"]
    q_dim = model["num_heads"] * head_dim
    kv_dim = model["num_kv_heads"] * head_dim
    ffn = (3 if model["glu"] else 2) * d * ff
    layer = d * q_dim + 2 * d * kv_dim + q_dim * d + ffn
    return model["num_layers"] * layer + d * model["vocab_size"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6 FLOPs per matmul parameter, plus causal attention: 2 S q_dim
    forward per layer (QK^T and PV over half the S x S square), 3x that
    with the backward."""
    head_dim = model.get("head_dim") or model["d_model"] // model["num_heads"]
    q_dim = model["num_heads"] * head_dim
    attn = 6 * model["num_layers"] * seq_len * q_dim
    return 6.0 * matmul_params(model) + attn
