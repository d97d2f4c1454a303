"""Paged-decode cost estimates: the serving planner's predicted side.

The training stack prices every collective before it runs and PR 12
closed the loop on the residuals; serving had measured histograms
(round/TTFT) but no predictions to hold them against.  This module
supplies the predicted half — a decode round's and a prefill's cost
estimate, priced from the SAME calibratable constants the rest of the
planner uses (``TpuCostParams.bwd_GFLOPs`` as the achievable compute
throughput, ``reduce_bw_GBps`` as the HBM-bound byte-stream rate).  The
engine puts the prefill's beside its measured time on the
``serve_prefill`` event, for a recorder alone; migration's cost follows
the round's decomposition.

The estimate is deliberately first-order: dense projection FLOPs per
decoded token plus the attention walk's K/V byte traffic over the batch
causal frontier (the paged pools are read once per round up to the
frontier — exactly the quantity the fused kernel's win shrinks with).
It does not model dispatch overlap or sampling-host time, and it prices
a DENSE block (routed experts, latent rows and a recurrent state are not
in it); compute-bound and byte-bound terms are separate fields of the
prediction.
"""

from __future__ import annotations

import math

from ..models.configs import pool_layout, position_parts, slot_parts

__all__ = [
    "decode_round_flops",
    "decode_round_bytes",
    "predict_decode_round_us",
    "predict_prefill_us",
    "cache_bytes_per_position",
    "state_bytes_per_slot",
    "kv_migration_elems",
    "predict_migration_us",
    "plan_migration",
    "migration_crossover_tokens",
]


def _dense_flops_per_token(cfg) -> float:
    """Dense (projection + MLP + LM head) multiply-accumulate FLOPs to
    decode one token: 2·weights touched.  A configuration that knows
    its own count (per-layer heads, routed experts) says so."""
    active = getattr(cfg, "active_matmul_params", None)
    if active is not None:
        return 2.0 * active
    d, ff = cfg.d_model, cfg.d_ff
    per_layer = 4 * d * d + 2 * d * ff  # qkvo + in/out MLP
    return 2.0 * (cfg.n_layers * per_layer + d * cfg.vocab_size)


def decode_round_flops(cfg, n_active: int, max_len: int) -> float:
    """FLOPs for one decode round over ``n_active`` slots attending up to
    ``max_len`` positions (the batched walk runs to the batch frontier)."""
    attn = 4.0 * max_len * cfg.d_model * cfg.n_layers  # QK^T + AV per token
    return n_active * (_dense_flops_per_token(cfg) + attn)


def cache_bytes_per_position(cfg) -> int:
    """Bytes the cache holds a position over all the layers, from the
    model's pool layout and compute dtype: what ``init_pools`` allocates
    a position, and ``engine.report()["cache_bytes_per_position"]``."""
    try:
        import numpy as np

        itemsize = np.dtype(cfg.dtype).itemsize
    except TypeError:
        itemsize = 4
    return itemsize * sum(
        math.prod(row)
        for layer in pool_layout(cfg) for row in layer["position"].values()
    )


def state_bytes_per_slot(cfg) -> int:
    """Bytes the layers hold a SLOT, whatever its sequence's length, over
    all the layers (a recurrent layer's state): what ``init_state``
    allocates a slot, ``engine.report()["state_bytes_per_slot"]``, and
    what a swap or a migration moves besides the cached positions.  0 for
    a block that keeps nothing a slot."""
    import jax.numpy as jnp

    return sum(
        math.prod(shape) * jnp.dtype(dtype).itemsize * layers
        for (shape, dtype), layers in slot_parts(cfg).values()
    )


def decode_round_bytes(cfg, pcfg, n_active: int, frontier_blocks: int) -> float:
    """Cache bytes streamed in one decode round: every active slot reads
    the pools up to the batch frontier (blocks × block_size positions ×
    the pool's real bytes a position, :func:`cache_bytes_per_position`)."""
    return float(
        n_active * frontier_blocks * pcfg.block_size
        * cache_bytes_per_position(cfg)
    )


def predict_decode_round_us(
    cfg, pcfg, n_active: int, max_len: int, params=None
) -> dict:
    """Predicted decode-round time, split into the two attributable
    phases: ``compute_us`` (dense+attention FLOPs over the calibrated
    achievable throughput) and ``bytes_us`` (K/V streaming at the
    HBM-bound byte rate).  Returns ``{"predicted_us", "compute_us",
    "bytes_us"}`` — the per-term decomposition the serving residual
    stream attributes drift against."""
    from ..parallel.overlap import resolve_bwd_GFLOPs
    from ..planner.calibrate import default_params

    if params is None:
        params = default_params()
    if n_active <= 0:
        return {"predicted_us": 0.0, "compute_us": 0.0, "bytes_us": 0.0}
    frontier_blocks = min(
        (max(int(max_len), 1) + pcfg.block_size - 1) // pcfg.block_size,
        pcfg.blocks_per_seq,
    )
    gflops = max(resolve_bwd_GFLOPs(params), 1e-6)
    compute_us = decode_round_flops(cfg, n_active, max_len) / (gflops * 1e3)
    bytes_us = decode_round_bytes(cfg, pcfg, n_active, frontier_blocks) / (
        max(params.reduce_bw_GBps, 1e-6) * 1e3
    )
    return {
        "predicted_us": compute_us + bytes_us,
        "compute_us": compute_us,
        "bytes_us": bytes_us,
    }


def predict_prefill_us(cfg, prompt_len: int, params=None,
                       cached_tokens: int = 0) -> float:
    """Predicted prefill compute time for one prompt (the TTFT floor a
    non-queued request could hit): dense FLOPs for every prompt token
    plus the causal attention triangle.

    ``cached_tokens`` is the prefix-cache hit length: those tokens pay
    neither dense FLOPs nor their attention rows, but the suffix still
    attends over the FULL prefix — so the attention term is the triangle
    minus the cached sub-triangle (``t² − c²``), not ``(t − c)²``.
    Pricing a hit as a full prefill would poison the serving residual
    stream the feedback loop pools."""
    from ..parallel.overlap import resolve_bwd_GFLOPs
    from ..planner.calibrate import default_params

    if params is None:
        params = default_params()
    t = max(int(prompt_len), 1)
    c = min(max(int(cached_tokens), 0), t - 1)
    dense = _dense_flops_per_token(cfg) * (t - c)
    attn = 2.0 * (t * t - c * c) * cfg.d_model * cfg.n_layers
    gflops = max(resolve_bwd_GFLOPs(params), 1e-6)
    return (dense + attn) / (gflops * 1e3)


def kv_migration_elems(cfg, pcfg, prompt_len: int) -> list:
    """f32 elements of each tensor ONE layer of one migrated sequence
    ships, a tensor a part of the pool's layout (K and V, or one latent
    row): the block footprint of the prompt (``blocks_for``, whole blocks
    — migration ships the tail block too) × block positions × the part's
    numbers a position.  One sequence ships each as often as there are
    layers that cache the part (``position_parts``: every layer of the
    dense, Laguna and openPangu blocks, the latent or full layers alone of
    a block whose other layers hold a state a slot)."""
    n_blocks = pcfg.blocks_for(max(int(prompt_len), 1))
    return [
        n_blocks * pcfg.block_size * math.prod(row)
        for row, _ in position_parts(cfg).values()
    ]


def predict_migration_us(cfg, pcfg, prompt_len: int, codec="f32",
                         params=None) -> dict:
    """Predicted time to ship one sequence's KV to a decode replica: the
    α–β wire term (DCN latency + codec wire bytes over DCN bandwidth)
    plus, for lossy codecs, the encode+decode pass over the f32 payload
    at the calibrated codec throughput.  Returns ``{"predicted_us",
    "wire_us", "codec_us", "bytes_on_wire"}`` — the same per-term
    decomposition style as :func:`predict_decode_round_us`, so migration
    residuals stay phase-attributable."""
    from ..ops.quantize import get_codec
    from ..planner.calibrate import default_params

    if params is None:
        params = default_params()
    c = get_codec(codec)
    # (how often, f32 elements) of every tensor shipped: a part's rows a
    # layer that caches it, and, under the same codec, what the sequence
    # holds a slot, a part and layer at a time as the rows are
    tensors = [
        (layers, e) for (_, layers), e in zip(
            position_parts(cfg).values(),
            kv_migration_elems(cfg, pcfg, prompt_len),
        )
    ] + [
        (layers, math.prod(shape))
        for (shape, _), layers in slot_parts(cfg).values()
    ]
    bytes_on_wire = sum(n * c.wire_bytes(e) for n, e in tensors)
    wire_us = params.dcn.latency_us + bytes_on_wire / (
        max(params.dcn.bandwidth_GBps, 1e-6) * 1e3
    )
    codec_us = 0.0
    if c.hop_cost:
        codec_us = 2.0 * (sum(n * e for n, e in tensors) * 4) / (
            max(params.codec_bw_GBps, 1e-6) * 1e3
        )
    return {
        "predicted_us": wire_us + codec_us,
        "wire_us": wire_us,
        "codec_us": codec_us,
        "bytes_on_wire": bytes_on_wire,
    }


def plan_migration(cfg, pcfg, prompt_len: int, codec="f32",
                   params=None) -> dict:
    """The migrate-vs-local decision for one request: ship the quantized
    KV (``predict_migration_us``) or recompute the prefill on the decode
    replica (``predict_prefill_us``)?  Prefill FLOPs grow quadratically
    in the prompt while the wire term grows linearly, so short prompts
    recompute (never pay the hop) and long prompts ship.  Returns
    ``{"migrate", "migrate_us", "recompute_us", "bytes_on_wire"}``."""
    mig = predict_migration_us(cfg, pcfg, prompt_len, codec, params)
    recompute_us = predict_prefill_us(cfg, prompt_len, params)
    return {
        "migrate": mig["predicted_us"] < recompute_us,
        "migrate_us": mig["predicted_us"],
        "recompute_us": recompute_us,
        "bytes_on_wire": mig["bytes_on_wire"],
    }


def migration_crossover_tokens(cfg, pcfg, codec="f32", params=None):
    """Smallest prompt length at which shipping the KV beats recomputing
    the prefill (``None`` if no prompt admissible under ``pcfg.max_len``
    ever crosses).  The front door uses this as its routing threshold so
    the per-request decision is one integer compare, and the SERVING doc
    quotes it as the crossover the calibration constants imply."""
    from ..planner.calibrate import default_params

    if params is None:
        params = default_params()
    for t in range(1, pcfg.max_len + 1):
        if plan_migration(cfg, pcfg, t, codec, params)["migrate"]:
            return t
    return None
