"""Model FLOPs of a decoder-only LM, counted from its shapes.

Training counts 6 FLOPs per matmul parameter per token (2 forward, 4
backward) and the causal attention products on top; recomputation
(remat) does not count, as model FLOP utilization requires.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that multiply activations: attention projections, the
    SwiGLU MLP and the LM head (the tied embedding counts once, as the
    head; the embedding lookup itself is a gather, not a matmul)."""
    d, hd = m["d_model"], m.get("head_dim") or m["d_model"] // m["n_heads"]
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
    mlp = 3 * d * m["d_ff"]
    return m["n_layers"] * (attn + mlp) + m["vocab"] * d


def train_flops_per_token(m: dict, seq: int) -> float:
    """6 x matmul parameters + 6 L S d_attn for causal self-attention.

    Per layer and token, QK^T and PV each take 2 S' d_attn FLOPs forward
    over the S' = S/2 keys a causal query sees on average: 2 S d_attn
    forward, three times that with the backward pass."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    d_attn = m["n_heads"] * hd
    return 6.0 * matmul_params(m) + 6.0 * m["n_layers"] * seq * d_attn
