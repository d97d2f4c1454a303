"""What a ``pangu_ultra_moe`` configuration NEEDS to read and to multiply,
from the configuration's keys: what the algorithm asks for, whatever the
program happens to execute.  Kept with the benchmark, beside ``counts.py``
and ``counts_laguna.py``, so that no later PR can move
``kernels.mla_decode_roofline`` or ``kernels.mla_prefill_roofline`` by
recounting.

A decoded token multiplies with every matrix outside the routed experts
(the five attention projections: in the absorbed form ``q_nope W_uk^T``
and ``o_lat W_uv`` are ``W_kvb``'s own numbers; a dense layer's FFN;
routers; shared experts; the slice of the untied head held here), with
one routed expert for each pick that lands on an expert THIS CHIP HOLDS,
and against every cached row of its sequence in every layer: 128 heads x
(576 for the score + 512 for the weighted row).  A round reads each of
those matrices once, each routed expert that got a pick once, and each
live row once a layer.  A prompt multiplies as a decoded token does a
position (the head once), and attends in the expanded form, which is the
cheaper one for a prompt: 128 heads x (192 + 128) a pair of positions.
Norms, rotary, softmax, the embedding rows looked up, the activations and
the rows written are hundreds of times smaller and left out.
"""

from __future__ import annotations

__all__ = [
    "attention_params", "expert_params", "expert_bytes", "other_params",
    "other_weight_bytes", "cache_bytes_per_position", "expected_local_picks",
    "decode_round_bytes", "decode_round_flops", "prefill_flops",
]

_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _row(c: dict) -> int:
    return int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"])


def attention_params(c: dict) -> int:
    """One layer's five projections."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    q_rank, kv_rank, dv = (
        int(c["q_lora_rank"]), int(c["kv_lora_rank"]), int(c["v_head_dim"])
    )
    return (
        d * q_rank + q_rank * h * (nope + rope) + d * _row(c)
        + kv_rank * h * (nope + dv) + h * dv * d
    )


def expert_params(c: dict) -> int:
    """One routed expert's three matrices (the shared expert's too, a
    shared expert being ``n_shared_experts`` of them wide)."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def expert_bytes(c: dict) -> int:
    return expert_params(c) * _ITEMSIZE[c["param_dtype"]]


def _layers(c: dict) -> tuple:
    """(all, dense, sparse) layer counts."""
    n, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
    return n, dense, n - dense


def other_params(c: dict) -> int:
    """Every matrix number a decoded token multiplies with outside the
    routed experts, the head's slice included."""
    d = int(c["hidden_size"])
    n, dense, sparse = _layers(c)
    routed = int(c.get("published", {}).get(
        "n_routed_experts", c["n_routed_experts"]))
    return (
        d * int(c["vocab_size"]) + n * attention_params(c)
        + dense * 3 * d * int(c["intermediate_size"])
        + sparse * (d * routed + int(c["n_shared_experts"]) * expert_params(c))
    )


def other_weight_bytes(c: dict) -> int:
    return other_params(c) * _ITEMSIZE[c["param_dtype"]]


def cache_bytes_per_position(c: dict) -> int:
    """One cached position over ALL the layers: a row of ``kv_lora_rank +
    qk_rope_head_dim`` numbers a layer."""
    return _layers(c)[0] * _row(c) * _ITEMSIZE[c["compute_dtype"]]


def expected_local_picks(c: dict) -> float:
    """Picks a token makes, in one sparse layer, of experts held here,
    under a router that spreads evenly (seeded random weights do)."""
    routed = int(c.get("published", {}).get(
        "n_routed_experts", c["n_routed_experts"]))
    return int(c["num_experts_per_tok"]) * int(c["n_routed_experts"]) / routed


def _core_flops_per_pair(c: dict, absorbed: bool) -> int:
    """Multiply-adds x 2 of one query against one cached position, over
    the layers and heads."""
    h = int(c["num_attention_heads"])
    if absorbed:
        per_head = _row(c) + int(c["kv_lora_rank"])
    else:
        per_head = (
            int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])
            + int(c["v_head_dim"])
        )
    return _layers(c)[0] * h * per_head * 2


def decode_round_bytes(c: dict, experts_hit: float, live: float) -> float:
    """Bytes one decode round must read.  ``experts_hit``: held routed
    experts that got a pick, summed over the sparse layers; ``live``:
    cached positions over all sequences."""
    return (
        float(other_weight_bytes(c)) + float(experts_hit) * expert_bytes(c)
        + float(live) * cache_bytes_per_position(c)
    )


def decode_round_flops(c: dict, active: float, local_picks: float,
                       live: float) -> float:
    """FLOPs one decode round must do.  ``active``: slots that decoded;
    ``local_picks``: picks of held experts, summed over slots and sparse
    layers; ``live``: cached positions over all sequences."""
    return (
        2.0 * (other_params(c) * float(active)
               + expert_params(c) * float(local_picks))
        + float(live) * _core_flops_per_pair(c, absorbed=True)
    )


def prefill_flops(c: dict, prompt_len: int) -> float:
    """FLOPs the prefill of one prompt must do: every matrix but the
    head a position (routed experts by the local picks an even router
    gives), the head once, and the causal core in the expanded form."""
    t = int(prompt_len)
    head = int(c["hidden_size"]) * int(c["vocab_size"])
    per_token = (
        other_params(c) - head
        + _layers(c)[2] * expected_local_picks(c) * expert_params(c)
    )
    return (
        2.0 * (per_token * t + head)
        + _core_flops_per_pair(c, absorbed=False) * t * (t + 1) / 2
    )
