"""Bytes a decode round of a ``laguna`` configuration NEEDS to read, from
the configuration's keys: what the algorithm asks for, whatever the
program happens to execute.  Kept with the benchmark, beside
``counts.py`` (the dense block's), so that no later PR can move
``kernels.moe_decode_roofline`` by recounting.

A round of S tokens multiplies each of them with every weight outside
the routed experts (attention projections and gates, the dense layer's
FFN, routers, shared experts, the untied head), with each routed expert
that at least one token picked AND that this chip holds, and reads the
K/V each sequence's attention can see: every cached position in a full
layer, at most ``sliding_window`` of them in a window layer.  The
embedding rows looked up, the activations and the K/V written are
thousands of times smaller and left out.
"""

from __future__ import annotations

__all__ = [
    "expert_bytes", "other_weight_bytes", "kv_bytes_per_position",
    "layer_kinds", "decode_round_bytes",
]

_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _gated_ffn_params(d: int, width: int) -> int:
    return 3 * d * width


def expert_bytes(c: dict) -> int:
    """One routed expert's three matrices, as held."""
    return _gated_ffn_params(
        int(c["hidden_size"]), int(c["moe_intermediate_size"])
    ) * _ITEMSIZE[c["param_dtype"]]


def other_weight_bytes(c: dict) -> int:
    """Every matrix a decoded token multiplies with outside the routed
    experts, the output head included, as held."""
    d, dh = int(c["hidden_size"]), int(c["head_dim"])
    kv = int(c["num_key_value_heads"])
    routed = int(c.get("published", {}).get("num_experts", c["num_experts"]))
    total = d * int(c["vocab_size"])  # the untied head
    for heads, mlp in zip(c["num_attention_heads_per_layer"],
                          c["mlp_layer_types"]):
        total += d * dh * (2 * heads + 2 * kv) + d * heads  # q, o, k, v, gate
        if mlp == "dense":
            total += _gated_ffn_params(d, int(c["intermediate_size"]))
        else:
            total += d * routed + _gated_ffn_params(
                d, int(c["shared_expert_intermediate_size"])
            )
    return total * _ITEMSIZE[c["param_dtype"]]


def kv_bytes_per_position(c: dict) -> int:
    """K and V of one cached position in ONE layer."""
    return 2 * int(c["num_key_value_heads"]) * int(c["head_dim"]) \
        * _ITEMSIZE[c["compute_dtype"]]


def layer_kinds(c: dict) -> tuple:
    """(full-attention layers, window layers)."""
    full = sum(k == "full_attention" for k in c["layer_types"])
    return full, len(c["layer_types"]) - full


def decode_round_bytes(c: dict, experts_hit: float, live: float,
                       live_capped: float) -> float:
    """Bytes one decode round must read.  ``experts_hit``: held routed
    experts that got a pick, summed over the sparse layers; ``live``:
    cached positions over all sequences; ``live_capped``: the same with
    each sequence's count capped at ``sliding_window``."""
    full, window = layer_kinds(c)
    return (
        float(other_weight_bytes(c))
        + float(experts_hit) * expert_bytes(c)
        + kv_bytes_per_position(c) * (full * float(live) + window * float(live_capped))
    )
