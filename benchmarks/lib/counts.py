"""Operations and bytes from shapes: what the algorithm NEEDS for a call,
whatever the program happens to execute.  Kept with the benchmark so no
later PR can move a roofline share by recounting.

All counts are for the block the program runs (``departures`` in the
configuration files): bias-free linear layers, a tied embedding used as
the output head, full causal attention, a GELU MLP.  A multiply-add is 2
FLOPs.  Causal attention needs half the score matrix.
"""

from __future__ import annotations

__all__ = [
    "Dims",
    "dims_of",
    "layer_matmul_params",
    "param_count",
    "train_flops_per_token",
    "flash_fwd_flops",
    "flash_bwd_flops",
    "flash_fwd_bytes",
    "flash_bwd_bytes",
    "kv_bytes_per_token",
    "decode_round_bytes",
]

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int  # hidden size
    heads: int
    head_dim: int
    ff: int  # MLP intermediate size
    vocab: int
    layers: int


def dims_of(config: dict) -> Dims:
    """Sizes from a configuration file (Hugging Face key names)."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if d % h:
        raise ValueError(f"hidden_size {d} not divisible by {h} heads")
    return Dims(
        d=d, heads=h, head_dim=d // h, ff=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        layers=int(config["num_hidden_layers"]),
    )


def layer_matmul_params(m: Dims) -> int:
    """Weights a token is multiplied with in one layer: q, k, v, o
    (4 d^2) and the MLP's two matrices (2 d ff)."""
    return 4 * m.d * m.d + 2 * m.d * m.ff


def param_count(m: Dims) -> int:
    """Parameters the program holds: the layers' matrices and two norm
    scales each, the tied embedding, the final norm scale."""
    return (
        m.layers * (layer_matmul_params(m) + 2 * m.d) + m.vocab * m.d + m.d
    )


def train_flops_per_token(m: Dims, seq_len: int) -> float:
    """FLOPs the forward and backward passes need for one token of a
    ``seq_len`` sequence, nothing recomputed: 6 per matmul weight (2
    forward, 4 backward) over the layers and the output head, plus causal
    attention: per layer the forward's QK^T and PV are 2 * 2*T*d / 2 and
    the backward's four matmuls (dP, dV, dK, dQ) twice that."""
    matmul = 6.0 * (m.layers * layer_matmul_params(m) + m.vocab * m.d)
    attention = m.layers * 6.0 * seq_len * m.d
    return matmul + attention


def _attn_unit(batch: int, heads: int, seq_len: int, head_dim: int) -> float:
    """FLOPs of ONE causal (T x T x D) matmul over the batch and heads:
    2 T^2 D per head, halved by the mask."""
    return float(batch) * heads * seq_len * seq_len * head_dim


def flash_fwd_flops(batch, heads, seq_len, head_dim) -> float:
    """Flash forward: QK^T and PV."""
    return 2.0 * _attn_unit(batch, heads, seq_len, head_dim)


def flash_bwd_flops(batch, heads, seq_len, head_dim) -> float:
    """Flash backward as the algorithm is defined: the score matrix is
    rebuilt once (it was never stored), then dP, dV, dK, dQ: 5 matmuls.
    A kernel pair that rebuilds it twice does 7; the extra 2 are the
    kernel's, not the algorithm's, and are not counted."""
    return 5.0 * _attn_unit(batch, heads, seq_len, head_dim)


def _qkv_bytes(batch, heads, seq_len, head_dim, itemsize) -> float:
    return float(batch) * heads * seq_len * head_dim * itemsize


def flash_fwd_bytes(batch, heads, seq_len, head_dim, itemsize=2) -> float:
    """Forward reads q, k, v and writes o once; the f32 row statistics
    (one per query row) are written too."""
    one = _qkv_bytes(batch, heads, seq_len, head_dim, itemsize)
    return 4.0 * one + float(batch) * heads * seq_len * 4


def flash_bwd_bytes(batch, heads, seq_len, head_dim, itemsize=2) -> float:
    """Backward reads q, k, v, o, do and the row statistics, and writes
    dq, dk, dv."""
    one = _qkv_bytes(batch, heads, seq_len, head_dim, itemsize)
    return 8.0 * one + 2.0 * float(batch) * heads * seq_len * 4


def kv_bytes_per_token(m: Dims, itemsize: int = 2) -> int:
    """K and V of one cached position over all layers."""
    return 2 * m.layers * m.heads * m.head_dim * itemsize


def decode_round_bytes(
    m: Dims, live_tokens: float, weight_itemsize: int = 4, kv_itemsize: int = 2
) -> float:
    """Bytes one decode round must read: every layer matrix and the
    output head (the tied embedding) once, in the type the engine HOLDS
    them in, and the K/V of every live cached position.  Activations,
    the embedding rows looked up and the K/V written are thousands of
    times smaller and left out."""
    weights = (m.layers * layer_matmul_params(m) + m.vocab * m.d)
    return (
        float(weights) * weight_itemsize
        + float(live_tokens) * kv_bytes_per_token(m, kv_itemsize)
    )
