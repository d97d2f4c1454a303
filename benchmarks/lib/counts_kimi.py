"""What a ``kimi_linear`` configuration NEEDS to read and to multiply, from
the configuration's keys: what the algorithm asks for, whatever the program
happens to execute (a Pallas kernel for the scan or the state update later
is read by the same yardstick).  Kept with the benchmark, beside
``counts_pangu.py``, so that no later PR can move
``kernels.kda_decode_roofline`` or ``kernels.kda_prefill_roofline`` by
recounting.

A decoded token multiplies with every matrix outside the routed experts (a
KDA layer's ``W_qkv``, ``W_o``, the two low-rank gates and ``W_beta``; an
MLA layer's four projections, ``q_nope W_uk^T`` and ``o_lat W_uv`` being
``W_kvb``'s own numbers; the dense layer's FFN; routers; shared experts;
the slice of the untied head held here), with one routed expert for each
pick that lands on an expert THIS CHIP HOLDS, against every cached row of
its sequence in every MLA layer (heads x (576 for the score + 512 for the
weighted row)), and it reads and writes its slot's state in every KDA
layer: 7 operations a number of the state (the decay, ``S^T k``, ``S^T
q``, the rank-one update).  A round reads each matrix once, each routed
expert that got a pick once, each live row once an MLA layer, and each
live slot's state once and writes it once.  A prompt multiplies as a
decoded token does a position (the head once), attends in the expanded
form in the MLA layers (heads x (192 + 128) a pair of positions), and runs
the recurrence in chunks of ``CHUNK`` tokens in the KDA layers
(:func:`kda_scan_flops_per_token`).  Norms, the convolution, softmax,
decays, the embedding rows looked up, the activations and the rows written
are hundreds of times smaller and left out.
"""

from __future__ import annotations

__all__ = [
    "layers", "kda_params", "mla_params", "expert_params", "expert_bytes",
    "other_params", "other_weight_bytes", "cache_bytes_per_position",
    "state_bytes_per_slot", "expected_local_picks", "decode_round_bytes",
    "decode_round_flops", "kda_scan_flops_per_token", "prefill_flops",
]

_ITEMSIZE = {"float32": 4, "bfloat16": 2}
CHUNK = 64  # tokens a chunk of the prefill's scan


def _lin(c: dict) -> tuple:
    """(heads, head width, convolution taps) of a KDA layer."""
    lin = c["linear_attn_config"]
    return (int(lin["num_heads"]), int(lin["head_dim"]),
            int(lin["short_conv_kernel_size"]))


def _row(c: dict) -> int:
    return int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"])


def layers(c: dict) -> dict:
    """Layer counts: all, KDA, MLA, dense FFN, sparse FFN."""
    n = int(c["num_hidden_layers"])
    kda = sum(1 for i in c["linear_attn_config"]["kda_layers"] if i <= n)
    dense = int(c["first_k_dense_replace"])
    return {"all": n, "kda": kda, "mla": n - kda, "dense": dense,
            "sparse": n - dense}


def kda_params(c: dict) -> int:
    """One KDA layer's matrices (the gates' inner width is the head's)."""
    d = int(c["hidden_size"])
    h, dim, _ = _lin(c)
    return d * 3 * h * dim + h * dim * d + 2 * (d * dim + dim * h * dim) + d * h


def mla_params(c: dict) -> int:
    """One MLA layer's four projections (queries uncompressed)."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    return (
        d * h * (nope + rope) + d * _row(c)
        + int(c["kv_lora_rank"]) * h * (nope + int(c["v_head_dim"]))
        + h * int(c["v_head_dim"]) * d
    )


def expert_params(c: dict) -> int:
    """One routed expert's three matrices (a shared expert's too)."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def expert_bytes(c: dict) -> int:
    return expert_params(c) * _ITEMSIZE[c["param_dtype"]]


def _routed(c: dict) -> int:
    return int(c.get("published", {}).get("num_experts", c["num_experts"]))


def other_params(c: dict) -> int:
    """Every matrix number a decoded token multiplies with outside the
    routed experts, the head's slice included."""
    d, n = int(c["hidden_size"]), layers(c)
    return (
        d * int(c["vocab_size"]) + n["kda"] * kda_params(c)
        + n["mla"] * mla_params(c)
        + n["dense"] * 3 * d * int(c["intermediate_size"])
        + n["sparse"] * (
            d * _routed(c) + int(c["num_shared_experts"]) * expert_params(c)
        )
    )


def other_weight_bytes(c: dict) -> int:
    return other_params(c) * _ITEMSIZE[c["param_dtype"]]


def cache_bytes_per_position(c: dict) -> int:
    """One cached position over ALL the layers: a row of ``kv_lora_rank +
    qk_rope_head_dim`` numbers an MLA layer, nothing a KDA layer."""
    return layers(c)["mla"] * _row(c) * _ITEMSIZE[c["compute_dtype"]]


def state_bytes_per_slot(c: dict) -> int:
    """What one sequence holds over the KDA layers, whatever its length:
    a float32 state of (heads, width, width) and the convolution's last
    ``taps - 1`` inputs over q, k and v."""
    h, dim, taps = _lin(c)
    return layers(c)["kda"] * (
        h * dim * dim * 4
        + (taps - 1) * 3 * h * dim * _ITEMSIZE[c["compute_dtype"]]
    )


def expected_local_picks(c: dict) -> float:
    """Picks a token makes, in one sparse layer, of experts held here,
    under a router that spreads evenly (seeded random weights do)."""
    return int(c["num_experts_per_token"]) * int(c["num_experts"]) / _routed(c)


def _mla_flops_per_pair(c: dict, absorbed: bool) -> int:
    """Multiply-adds x 2 of one query against one cached position, over
    the MLA layers and heads."""
    if absorbed:
        per_head = _row(c) + int(c["kv_lora_rank"])
    else:
        per_head = (
            int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])
            + int(c["v_head_dim"])
        )
    return layers(c)["mla"] * int(c["num_attention_heads"]) * per_head * 2


def _kda_state_numbers(c: dict) -> int:
    """Numbers of state a sequence holds over the KDA layers."""
    h, dim, _ = _lin(c)
    return layers(c)["kda"] * h * dim * dim


def decode_round_bytes(c: dict, experts_hit: float, live: float,
                       active: float) -> float:
    """Bytes one decode round must move.  ``experts_hit``: held routed
    experts that got a pick, summed over the sparse layers; ``live``:
    cached positions over all sequences; ``active``: slots that decoded,
    each of which reads its state and writes it."""
    return (
        float(other_weight_bytes(c)) + float(experts_hit) * expert_bytes(c)
        + float(live) * cache_bytes_per_position(c)
        + float(active) * state_bytes_per_slot(c) * 2
    )


def decode_round_flops(c: dict, active: float, local_picks: float,
                       live: float) -> float:
    """FLOPs one decode round must do.  ``active``: slots that decoded;
    ``local_picks``: picks of held experts, summed over slots and sparse
    layers; ``live``: cached positions over all sequences."""
    return (
        2.0 * (other_params(c) * float(active)
               + expert_params(c) * float(local_picks))
        + float(live) * _mla_flops_per_pair(c, absorbed=True)
        + float(active) * 7 * _kda_state_numbers(c)
    )


def kda_scan_flops_per_token(c: dict, chunk: int = CHUNK) -> float:
    """FLOPs a prompt's token costs the KDA layers' recurrence, run in
    chunks of ``chunk``: a head's token takes the lower half of the
    chunk's two (chunk x chunk) products over the head width (``A``,
    ``B``: 2 x chunk x width), the unit-triangular solve against the
    chunk's values and decayed keys (chunk x 2 width), three products
    with the (width, width) state (read for ``u``, read for the output,
    written: 3 x 2 width^2), and ``B u`` (chunk x width)."""
    h, dim, _ = _lin(c)
    per_head = (
        2 * chunk * dim + chunk * 2 * dim + 3 * 2 * dim * dim + chunk * dim
    )
    return float(layers(c)["kda"] * h * per_head)


def prefill_flops(c: dict, prompt_len: int) -> float:
    """FLOPs the prefill of one prompt must do: every matrix but the head
    a position (routed experts by the local picks an even router gives),
    the head once, the MLA layers' causal core in the expanded form, and
    the KDA layers' chunked scan."""
    t = int(prompt_len)
    head = int(c["hidden_size"]) * int(c["vocab_size"])
    per_token = (
        other_params(c) - head
        + layers(c)["sparse"] * expected_local_picks(c) * expert_params(c)
    )
    return (
        2.0 * (per_token * t + head)
        + _mla_flops_per_pair(c, absorbed=False) * t * (t + 1) / 2
        + kda_scan_flops_per_token(c) * t
    )
