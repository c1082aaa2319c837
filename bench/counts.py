"""Bytes and operations a step needs, from the configuration's shapes.

These are the least work, whatever implements it: every weight read once
at the served dtype and each active slot's KV context at the KV dtype for
bytes; 2 operations per weight per token (the LM head included) plus
4 x context x heads x head_dim per layer per token for operations. The
coded head's block mix and erasure solve count as nothing: they are
redundancy, not model work.
"""
from __future__ import annotations

from reference.dense_decoder import Dims

BYTES = {"bfloat16": 2, "float32": 4}


def matmul_params(d: Dims) -> int:
    """Weights that multiply a token's activations, the LM head included
    (tied: the embedding table counts once, as the head)."""
    attn = d.d * d.heads * d.hd * 2 + d.d * d.kv * d.hd * 2
    mlp = 3 * d.d * d.f
    return d.layers * (attn + mlp) + d.vocab * d.d


def params(d: Dims) -> int:
    """Every parameter: matmul weights plus the norm scales."""
    norms = 2 * d.d + (2 * d.hd if d.qk_norm else 0)
    return matmul_params(d) + d.layers * norms + d.d


def weight_bytes(d: Dims) -> int:
    return params(d) * BYTES[d.dtype]


def kv_bytes_per_token(d: Dims, kv_dtype: str = "bfloat16") -> int:
    return d.layers * 2 * d.kv * d.hd * BYTES[kv_dtype]


def token_flops(d: Dims, context: int) -> int:
    """Operations of one token that attends ``context`` positions."""
    return 2 * matmul_params(d) + 4 * context * d.heads * d.hd * d.layers


def decode_step_bytes(d: Dims, contexts) -> int:
    """Least bytes of one decode step over the active slots' contexts."""
    return weight_bytes(d) + sum(contexts) * kv_bytes_per_token(d)
