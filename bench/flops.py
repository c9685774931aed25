"""Operations and bytes the algorithms need, computed from shapes.

``dims`` is the plain description of a decoder that
``bench.reference.transformer.describe`` makes from a configuration
file.  A multiply-add counts 2 operations.  Only useful work is counted:
real prompt and output tokens, causal attention over the keys each token
may see; no padding, no recomputation.
"""
from __future__ import annotations


def matmul_params(d):
    """Weights a token passes through: attention, the SwiGLU MLP and the
    LM head."""
    D, F = d["d_model"], d["d_ff"]
    q, kv = d["n_heads"] * d["head_dim"], d["n_kv_heads"] * d["head_dim"]
    return d["n_layers"] * (D * q + 2 * D * kv + q * D + 3 * D * F) \
        + D * d["vocab"]


def attn_flops(d, keys):
    """Scores and weighted values of one query over ``keys`` keys, all
    layers."""
    return 4 * d["n_layers"] * d["n_heads"] * d["head_dim"] * keys


def prefill_flops(d, n):
    """Forward over an ``n``-token prompt (causal)."""
    return 2 * matmul_params(d) * n + attn_flops(d, n * (n + 1) // 2)


def decode_flops(d, kv_len):
    """Forward of one token that attends ``kv_len`` keys."""
    return 2 * matmul_params(d) + attn_flops(d, kv_len)


def decode_attn_cost(d, kv_len, kv_bytes):
    """(flops, bytes) of slot-decode attention for one token over
    ``kv_len`` cached positions: every key and value is read once."""
    flops = attn_flops(d, kv_len)
    byts = 2 * d["n_layers"] * d["n_kv_heads"] * d["head_dim"] * kv_len \
        * kv_bytes
    return flops, byts


def roofline_share(flops, byts, seconds, peak):
    """Least time the chip could take (the larger of flops over peak
    FLOP/s and bytes over peak bandwidth) as a share of ``seconds``."""
    least = max(flops / peak["bf16_flops_per_s"],
                byts / peak["hbm_bytes_per_s"])
    return least / seconds
