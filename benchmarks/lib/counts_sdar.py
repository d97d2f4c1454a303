"""What a pass and a prefill of an ``sdar_moe`` configuration NEED to read
and to multiply, from the configuration's keys: what the algorithm asks
for, whatever the program happens to execute.  Kept with the benchmark,
beside ``counts_laguna.py``, so that no later PR can move
``kernels.block_decode_roofline`` or ``kernels.block_prefill_roofline`` by
recounting.

A pass of a diffusion block computes B positions a slot.  Each of them
multiplies with every matrix outside the routed experts (the four
attention projections, the router, the untied head), with the experts it
picked that this chip holds, and attends its slot's committed positions
and its whole open block.  A pass reads each matrix outside the experts
once, each routed expert that at least one row picked (HIT) once, and each
slot's committed positions' K and V once a layer, and writes the open
block's rows.  A prefill multiplies its prompt's positions as a pass does
(no head: a prefill reveals nothing) and attends under the block-causal
mask.  Norms, rotary, softmax, the embedding rows looked up, the
activations and the router's top-k are hundreds of times smaller and left
out.
"""

from __future__ import annotations

__all__ = [
    "attention_params", "expert_params", "expert_bytes", "other_weight_bytes",
    "cache_bytes_per_position", "pass_bytes", "pass_flops",
    "block_causal_pairs", "prefill_flops",
]

_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _dims(c: dict) -> tuple:
    """(hidden, query heads, K/V heads, head width, layers)."""
    return (int(c["hidden_size"]), int(c["num_attention_heads"]),
            int(c["num_key_value_heads"]), int(c["head_dim"]),
            int(c["num_hidden_layers"]))


def attention_params(c: dict) -> int:
    """One layer's W_q, W_k, W_v, W_o and router."""
    d, h, hkv, dh, _ = _dims(c)
    routed = int(c.get("published", {}).get("num_experts", c["num_experts"]))
    return d * dh * (2 * h + 2 * hkv) + d * routed


def expert_params(c: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def expert_bytes(c: dict) -> int:
    return expert_params(c) * _ITEMSIZE[c["param_dtype"]]


def other_weight_bytes(c: dict) -> int:
    """Every matrix a pass multiplies with outside the routed experts, the
    head included, as held."""
    d, *_, n = _dims(c)
    return (n * attention_params(c) + d * int(c["vocab_size"])) \
        * _ITEMSIZE[c["param_dtype"]]


def cache_bytes_per_position(c: dict) -> int:
    """K and V of one position over all the layers."""
    _, _, hkv, dh, n = _dims(c)
    return n * 2 * hkv * dh * _ITEMSIZE[c["compute_dtype"]]


def pass_bytes(c: dict, experts_hit: float, live: float,
               active: float) -> float:
    """Bytes one pass must move.  ``experts_hit``: held routed experts that
    got a pick, summed over the layers; ``live``: committed positions over
    all slots; ``active``: slots in the pass, each of which writes its open
    block's B rows."""
    b = int(c["block_length"])
    return (
        float(other_weight_bytes(c)) + float(experts_hit) * expert_bytes(c)
        + (float(live) + float(active) * b) * cache_bytes_per_position(c)
    )


def pass_flops(c: dict, active: float, local_picks: float,
               live: float) -> float:
    """FLOPs one pass must do: ``active`` slots' B rows through every
    matrix outside the experts, ``local_picks`` (over the layers) rows
    through an expert each, and each row against its slot's committed
    positions (``live``, over all slots) and its block's B (the score and
    the weighted value, every layer and query head)."""
    d, h, _, dh, n = _dims(c)
    b = int(c["block_length"])
    rows = float(active) * b
    pairs = b * float(live) + rows * b
    return (
        2.0 * (n * attention_params(c) + d * int(c["vocab_size"])) * rows
        + 2.0 * expert_params(c) * float(local_picks)
        + pairs * n * h * 2 * dh * 2
    )


def block_causal_pairs(t: int, block: int) -> int:
    """(query, key) pairs of ``t`` positions (whole blocks) under the
    block-causal mask: block ``j``'s B queries see ``(j + 1) B`` keys."""
    blocks = int(t) // int(block)
    return block * block * blocks * (blocks + 1) // 2


def prefill_flops(c: dict, prompt_len: int) -> float:
    """FLOPs the prefill of one prompt's whole blocks must do: every matrix
    outside the experts and ``num_experts_per_tok`` experts a position (no
    head), and the attention over the block-causal triangle."""
    d, h, _, dh, n = _dims(c)
    b = int(c["block_length"])
    t = int(prompt_len) // b * b
    per_position = n * (
        attention_params(c) + int(c["num_experts_per_tok"]) * expert_params(c)
    )
    return 2.0 * per_position * t + block_causal_pairs(t, b) * n * h * 2 * dh * 2
