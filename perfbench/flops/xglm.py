"""Operations and bytes the XGLM decoder needs, computed from shapes.

These are the algorithm's own counts: what a forward (and backward)
pass has to do, whatever the program does to get there. Recomputed
activations are not counted, a causal attention counts the lower
triangle only, and a decode step has to read every matmul weight once
and each live token's keys and values once.
"""

from perfbench.reference.xglm import sizes


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication (the embedding is
    a gather and the LayerNorms and biases are elementwise)."""
    V, D, _H, L, F = sizes(cfg)
    return L * (4 * D * D + 2 * D * F) + D * V


def train_flops_per_token(cfg, seq_len):
    """(matmul, attention) FLOPs per trained token, forward + backward
    (backward = 2 x forward). Attention: QK^T and PV are 2*T*D each per
    token per layer, halved by causality."""
    _V, D, _H, L, _F = sizes(cfg)
    matmul = 6 * matmul_params(cfg)
    attention = 3 * L * (2 * seq_len * D)
    return matmul, attention


def forward_flops_per_token(cfg, context_len):
    """FLOPs of one token's forward pass attending ``context_len``
    cached positions (a decode step, or the mean position of a
    prefill)."""
    _V, D, _H, L, _F = sizes(cfg)
    return 2 * matmul_params(cfg) + L * 4 * context_len * D


def decode_step_bytes(cfg, weight_bytes, kv_bytes, cached_tokens):
    """(weights, kv) bytes one decode step streams: every matmul weight
    and bias once, and the keys and values of every cached token of
    every sequence in the batch (``cached_tokens`` = their sum)."""
    _V, D, _H, L, F = sizes(cfg)
    biases = L * (4 * D + F + D)
    weights = (matmul_params(cfg) + biases) * weight_bytes
    kv = 2 * L * D * kv_bytes * cached_tokens
    return weights, kv


def kv_bytes_per_token(cfg, kv_bytes):
    _V, D, _H, L, _F = sizes(cfg)
    return 2 * L * D * kv_bytes
