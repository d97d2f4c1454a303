"""What an ``olmo_hybrid`` configuration NEEDS to read and to multiply,
from the configuration's keys: what the algorithm asks for, whatever the
program happens to execute (a Pallas kernel for the scan, the state update
or the paged walk later is read by the same yardstick).  Kept with the
benchmark, beside ``counts_kimi.py``, so that no later PR can move
``kernels.gdn_decode_roofline`` or ``kernels.gdn_prefill_roofline`` by
recounting.

A decoded token multiplies with every matrix (a linear layer's ``W_qkv``,
output gate ``W_g``, ``W_o``, ``W_a`` and ``W_b``; a full layer's four
projections; every layer's FFN; the untied head), against every cached
position of its sequence in every full layer (heads x (128 for the score +
128 for the weighted value)), and it reads and writes its slot's state in
every linear layer: 7 operations a number of the state (the decay, ``S^T
k``, ``S^T q``, the rank-one update).  A round reads each matrix once,
each live position's K and V once a full layer, and each live slot's state
and convolution inputs once and writes them once.  A prompt multiplies as
a decoded token does a position (the head once), attends causally in the
full layers, and runs the recurrence in chunks of ``CHUNK`` tokens in the
linear layers (:func:`scan_flops_per_token`).  Norms, the convolution,
softmax, decays, the embedding rows looked up, the activations and the rows
written are hundreds of times smaller and left out.
"""

from __future__ import annotations

__all__ = [
    "layers", "linear_params", "full_params", "ffn_params", "weight_params",
    "weight_bytes", "cache_bytes_per_position", "state_bytes_per_slot",
    "decode_round_bytes", "decode_round_flops", "scan_flops_per_token",
    "prefill_flops",
]

_ITEMSIZE = {"float32": 4, "bfloat16": 2}
CHUNK = 64  # tokens a chunk of the prefill's scan


def _lin(c: dict) -> tuple:
    """(heads, key width, value width, convolution taps) of a linear layer."""
    return (int(c["linear_num_value_heads"]), int(c["linear_key_head_dim"]),
            int(c["linear_value_head_dim"]), int(c["linear_conv_kernel_dim"]))


def _full(c: dict) -> tuple:
    """(query heads, K/V heads, head width) of a full layer."""
    heads = int(c["num_attention_heads"])
    return (heads, int(c.get("num_key_value_heads") or heads),
            int(c.get("head_dim") or int(c["hidden_size"]) // heads))


def layers(c: dict) -> dict:
    """Layer counts: all, linear, full."""
    kinds = list(c["layer_types"])[: int(c["num_hidden_layers"])]
    linear = sum(k == "linear_attention" for k in kinds)
    return {"all": len(kinds), "linear": linear, "full": len(kinds) - linear}


def linear_params(c: dict) -> int:
    """One linear layer's mixer: ``W_qkv``, the gate, ``W_o``, ``W_a``,
    ``W_b``."""
    d = int(c["hidden_size"])
    h, dk, dv, _ = _lin(c)
    return d * h * (2 * dk + dv) + 2 * d * h * dv + 2 * d * h


def full_params(c: dict) -> int:
    """One full layer's four projections."""
    d = int(c["hidden_size"])
    h, hkv, dh = _full(c)
    return 2 * d * h * dh + 2 * d * hkv * dh


def ffn_params(c: dict) -> int:
    return 3 * int(c["hidden_size"]) * int(c["intermediate_size"])


def weight_params(c: dict) -> int:
    """Every matrix number a decoded token multiplies with, the head's
    included (the embedding is looked up, not multiplied)."""
    n = layers(c)
    return (
        int(c["hidden_size"]) * int(c["vocab_size"])
        + n["linear"] * linear_params(c) + n["full"] * full_params(c)
        + n["all"] * ffn_params(c)
    )


def weight_bytes(c: dict) -> int:
    return weight_params(c) * _ITEMSIZE[c["param_dtype"]]


def cache_bytes_per_position(c: dict) -> int:
    """One cached position over ALL the layers: K and V, a row a K/V head,
    in a full layer; nothing in a linear layer."""
    _, hkv, dh = _full(c)
    return layers(c)["full"] * 2 * hkv * dh * _ITEMSIZE[c["compute_dtype"]]


def state_bytes_per_slot(c: dict) -> int:
    """What one sequence holds over the linear layers, whatever its
    length: a float32 state of (heads, key width, value width) and the
    convolution's last ``taps - 1`` inputs over q, k and v."""
    h, dk, dv, taps = _lin(c)
    return layers(c)["linear"] * (
        h * dk * dv * 4
        + (taps - 1) * h * (2 * dk + dv) * _ITEMSIZE[c["compute_dtype"]]
    )


def _attn_flops_per_pair(c: dict) -> int:
    """Multiply-adds x 2 of one query against one cached position, over
    the full layers and heads: the score and the weighted value."""
    h, _, dh = _full(c)
    return layers(c)["full"] * h * 2 * dh * 2


def _state_numbers(c: dict) -> int:
    """Numbers of state a sequence holds over the linear layers."""
    h, dk, dv, _ = _lin(c)
    return layers(c)["linear"] * h * dk * dv


def decode_round_bytes(c: dict, live: float, active: float) -> float:
    """Bytes one decode round must move.  ``live``: cached positions over
    all sequences; ``active``: slots that decoded, each of which reads its
    state and writes it."""
    return (
        float(weight_bytes(c)) + float(live) * cache_bytes_per_position(c)
        + float(active) * state_bytes_per_slot(c) * 2
    )


def decode_round_flops(c: dict, active: float, live: float) -> float:
    """FLOPs one decode round must do.  ``active``: slots that decoded;
    ``live``: cached positions over all sequences."""
    return (
        2.0 * weight_params(c) * float(active)
        + float(live) * _attn_flops_per_pair(c)
        + float(active) * 7 * _state_numbers(c)
    )


def scan_flops_per_token(c: dict, chunk: int = CHUNK) -> float:
    """FLOPs a prompt's token costs the linear layers' recurrence, run in
    chunks of ``chunk``: a head's token takes the lower half of the
    chunk's two (chunk x chunk) products over the key width (``A``, ``B``:
    2 x chunk x d_k), the unit-triangular solve against the chunk's values
    and decayed keys (chunk x (d_k + d_v)), three products with the (d_k,
    d_v) state (read for ``u``, read for the output, written: 3 x 2 d_k
    d_v), and ``B u`` (chunk x d_v)."""
    h, dk, dv, _ = _lin(c)
    per_head = (
        2 * chunk * dk + chunk * (dk + dv) + 3 * 2 * dk * dv + chunk * dv
    )
    return float(layers(c)["linear"] * h * per_head)


def prefill_flops(c: dict, prompt_len: int) -> float:
    """FLOPs the prefill of one prompt must do: every matrix but the head
    a position, the head once, the full layers' causal attention, and the
    linear layers' chunked scan."""
    t = int(prompt_len)
    head = int(c["hidden_size"]) * int(c["vocab_size"])
    return (
        2.0 * ((weight_params(c) - head) * t + head)
        + _attn_flops_per_pair(c) * t * (t + 1) / 2
        + scan_flops_per_token(c) * t
    )
