"""Operations a training step requires, from the configuration's shapes.

Per token: 6 x the matmul parameters a token passes through (forward 2,
backward 4), the unembedding included and the embedding lookup not; for
a mixture of experts the router and only the top-k routed experts (and
any shared ones) count.  Attention adds 12 x layers x sequence x the
attention width (heads x head size): QK^T and AV, 2 operations per
multiply-add, forward and backward, over the full square (the causal half
is not discounted, as is usual).  Recomputation does not count.
"""
from __future__ import annotations

from typing import Dict


def matmul_params_per_token(a: Dict) -> int:
    d, hd = a["d_model"], a["head_dim"]
    attn = d * a["n_heads"] * hd + 2 * d * a["n_kv_heads"] * hd \
        + a["n_heads"] * hd * d
    moe = a.get("moe")
    if moe:
        mlp = d * moe["n_experts"] + (moe["top_k"] + moe.get("n_shared", 0)) \
            * 3 * d * moe["d_expert"]
    else:
        mlp = 3 * d * a["d_ff"]
    return a["n_layers"] * (attn + mlp) + a["vocab_size"] * d


def flops_per_token(a: Dict, seq_len: int) -> float:
    return float(6 * matmul_params_per_token(a)
                 + 12 * a["n_layers"] * seq_len * a["n_heads"] * a["head_dim"])


def step_flops(a: Dict, seq_len: int, rows: int) -> float:
    """Required operations of one step over ``rows`` sequences."""
    return flops_per_token(a, seq_len) * rows * seq_len
