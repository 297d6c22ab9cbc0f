"""Counts for a model of latent attention and routed experts (the
DeepSeek-V3 block), and the readers of its scopes.

FLOPs and bytes are computed from the configuration's shapes and the token
copies the program counted (``moe_rows_here``): a copy routed to an expert
that another chip holds is not work here, and a padding row is not work at
all.
"""
from __future__ import annotations

import sys
from typing import Optional, Tuple

from . import scopes

BF16 = 2            # bytes of a stored weight or activation


def _mla_params(m: dict) -> int:
    d, h = m["d_model"], m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r = m["kv_lora_rank"]
    q = (m["q_lora_rank"] * (d + h * qk) if m["q_lora_rank"]
         else d * h * qk)
    kv = d * (r + m["qk_rope_head_dim"]) \
        + r * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
    return q + kv + h * m["v_head_dim"] * d


def train_flops_per_token(m: dict, seq: int, rows_per_token: float) -> float:
    """Model FLOPs of one trained token: 6 x the matmul parameters every
    token passes through (latent attention's projections, the dense
    layers' SwiGLU, the routers, the shared experts, the head), 6 x one
    routed expert's 3 d d_expert for each copy computed here
    (``rows_per_token``: copies over tokens, summed over the layers), and
    the causal attention products: per layer and token QK^T over the
    qk_nope + qk_rope dims and PV over the v dims, 2 S/2 each forward,
    three times that with the backward pass.  Recomputation not counted."""
    d, n_layers = m["d_model"], m["n_layers"]
    dense, n_moe = m["first_k_dense"], n_layers - m["first_k_dense"]
    shared = 3 * d * m["n_shared_experts"] * m["d_expert"]
    every = (n_layers * _mla_params(m) + dense * 3 * d * m["d_ff"]
             + n_moe * (d * m["router_experts"] + shared)
             + d * m["vocab"])
    routed = 3 * d * m["d_expert"] * rows_per_token
    h = m["n_heads"]
    dims = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return 6.0 * (every + routed) + 3.0 * n_layers * seq * h * dims


def grouped_swiglu_cost(rows: float, m: dict) -> Tuple[float, float]:
    """(FLOPs, bytes) of the held experts' three grouped matmuls in one
    training step: forward, the remat recompute of the forward, and the
    backward (the input's and the weights' gradients of each), over
    ``rows`` counted copies summed over the expert layers.  A pass of one
    matmul (rows x k) @ (k x n) per expert reads its input and the held
    experts' weights and writes its output, in bfloat16; the backward
    takes twice the forward's FLOPs and bytes."""
    d, f = m["d_model"], m["d_expert"]
    n_moe = m["n_layers"] - m["first_k_dense"]
    weights = n_moe * m["n_experts"] * d * f
    fwd_flops = 3 * 2.0 * rows * d * f
    fwd_bytes = BF16 * (3.0 * rows * (d + f) + 3.0 * weights)
    return 4 * fwd_flops, 4 * fwd_bytes


def scope_ms(out, cell, scope: str, metric: str) -> Optional[float]:
    """Milliseconds of device self time in ``scope`` per traced step, or
    None where the program under test has no such scope."""
    got = scopes.of_run(out, cell, metric)
    if got is None:
        return None
    if scope not in got["scopes"]:
        print(f"{metric}: the program has no {scope!r} scope",
              file=sys.stderr, flush=True)
        return None
    return 1e3 * got["scopes"][scope] / cell.traffic["trace_steps"]
