"""Operations and bytes each algorithm needs, from a configuration file's
shapes. These are the benchmark's own counts, never the program's
``cost_analysis``: work the program does beyond them (masked cache
positions, recomputation) does not count.

A matrix product of (m, k) by (k, n) is 2mkn operations. Causal attention
over a context of c positions is 4 * heads * head_dim * c operations per
query token (scores and the weighted sum of values).
"""
from __future__ import annotations

from bench.reference.dense import Dense

BYTES = {"bfloat16": 2, "float32": 4}


def spec(c: dict) -> Dense:
    return Dense.from_config(c)


def layer_matmul_params(d: Dense) -> int:
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    return d.d_model * (q + 2 * kv) + q * d.d_model + 3 * d.d_model * d.d_ff


def matmul_params(d: Dense) -> int:
    """Parameters that take part in a product for every token: the layers'
    projections and the output head (the embedding is a lookup)."""
    return d.n_layers * layer_matmul_params(d) + d.d_model * d.vocab


def weight_params(d: Dense) -> int:
    """Parameters a decode step reads once: all but the embedding table (a
    tied head is that table, read once as the head and counted once)."""
    per_layer = layer_matmul_params(d) + 2 * d.d_model + (
        (d.n_heads + 2 * d.n_kv_heads) * d.head_dim if d.qkv_bias else 0)
    return d.n_layers * per_layer + d.d_model * d.vocab + d.d_model


def attention_flops(d: Dense, context: int) -> int:
    """Per query token and layer stack, over ``context`` positions."""
    return d.n_layers * 4 * d.n_heads * d.head_dim * context


def prefill_flops(c: dict, s: int) -> int:
    """A prompt of ``s`` tokens: every layer over every token, causal
    attention, and the output head at the last position only."""
    d = spec(c)
    mm = 2 * s * d.n_layers * layer_matmul_params(d) + 2 * d.d_model * d.vocab
    return mm + d.n_layers * 4 * d.n_heads * d.head_dim * s * (s + 1) // 2


def decode_flops(c: dict, tokens: int, context: int) -> int:
    """One decode step of ``tokens`` sequences whose contexts (the new token
    included) add up to ``context`` positions."""
    d = spec(c)
    return 2 * tokens * matmul_params(d) + attention_flops(d, context)


def decode_bytes(c: dict, tokens: int, context: int) -> int:
    """One decode step: every weight but the embedding once, one embedding
    row per token, the keys and values of ``context`` positions read and of
    ``tokens`` positions written, and the float32 logits written."""
    d = spec(c)
    wb, ab = BYTES[c["param_dtype"]], BYTES[c["compute_dtype"]]
    kv = d.n_layers * 2 * d.n_kv_heads * d.head_dim * ab
    return (weight_params(d) * wb + tokens * d.d_model * wb
            + kv * (context + tokens) + tokens * d.vocab * 4)


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward and backward (three times the forward) of one token of a
    ``seq``-token row: 6 per product parameter, plus causal attention over
    (seq + 1) / 2 positions on average. Recomputation does not count."""
    d = spec(c)
    return 6 * matmul_params(d) + 3 * attention_flops(d, 1) * (seq + 1) // 2
