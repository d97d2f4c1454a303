"""A model from its configuration: the ``model_type`` of a published
``config.json`` names the block, its keys give the sizes.  This is how
the serving path chooses a model (``ServingEngine.from_config``, ``python
-m flextree_tpu.serving --config``).

:data:`BLOCKS` is the one table of what a block brings: its configuration
object, seeded parameters, the two walks over its layers behind the
signatures the engine calls, and the layout of what it keeps.  The five
blocks are ``models.transformer`` (``gpt_neox``: the dense block at those
widths, see ``benchmarks/configs/pythia-*.json`` for what it departs in),
``models.laguna`` (``laguna``), ``models.pangu_ultra_moe``
(``pangu_ultra_moe``), ``models.kimi_linear`` (``kimi_linear``) and
``models.olmo_hybrid`` (``olmo_hybrid``).

**The layout is a layer's** (:func:`pool_layout`, one entry a layer):
``position`` names the parts the layer caches a POSITION and the shape of
one position's row: these are paged, ``(num_blocks, block_size, *row)``,
an array a layer that caches the part (:func:`position_parts`);
``slot`` names the parts the layer holds a SLOT, whatever the length of
the sequence in it, with their shape and dtype: these are the engine's
state, ``(slots, *shape)``, an array a layer that holds the part
(:func:`slot_parts`).  The dense and the Laguna block cache ``k`` and
``v`` of ``(K/V heads, head_dim)`` in every layer, openPangu one ``ckv``
row in every layer, none of the three anything a slot; a Kimi-Linear MLA
layer caches one ``ckv`` row and a KDA layer NOTHING a position, but a
float32 state ``s`` and its convolution's last inputs ``conv`` a slot; an
Olmo-Hybrid linear layer holds the same two parts a slot (keys narrower
than values) beside full layers that cache PLAIN ``k`` and ``v``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from ..ops.paged_attention import runs_kernel
from . import (
    kimi_linear as kimi,
    laguna,
    olmo_hybrid as olmo,
    pangu_ultra_moe as pangu,
)
from .generate import paged_decode_dense, prefill_dense
from .moe import expert_kernel_layers
from .transformer import TransformerConfig, init_params

__all__ = [
    "Block", "BLOCKS", "block_of", "config_from_dict", "init_model_params",
    "pool_layout", "position_parts", "slot_parts",
]


@dataclasses.dataclass(frozen=True)
class Block:
    """What a ``model_type`` brings."""

    config_type: type
    from_dict: Callable  # published keys -> config_type
    init_params: Callable  # (key, cfg) -> seeded parameters on the device
    prefill: Callable  # (params, tokens, cfg, max_len) -> (logits, cache)
    # (params, pools, tables, lengths, tokens, cfg, fused) -> (logits,
    # pools[, what its routers did])
    decode_step: Callable
    # cfg -> a layer at a time, {"position": {part: shape of one cached
    # position}, "slot": {part: (shape, dtype name) of what a slot holds,
    # whatever its sequence's length}}: the pools, a prefill's cache, a
    # swap and a migration payload hold the position parts of the layers
    # that have them, in blocks of positions; the engine's state, the same
    # four and the decode program hold the slot parts, an array a slot
    pool_layout: Callable
    # (cfg, pcfg) -> (attention layers of the fused decode program, those
    # of them that run the Pallas kernel): fixed by the backend and the
    # shapes (``pcfg``: the pool's ``num_blocks`` and ``block_size``)
    kernel_layers: Callable
    # cfg -> (layers of the decode program that hold a state a slot, those
    # of them whose update runs the Pallas kernel), fixed likewise
    state_kernel_layers: Callable = lambda cfg: (0, 0)
    # (cfg, slots) -> (expert layers of the decode program, those of them
    # whose grouped products run the Pallas kernel), fixed likewise
    expert_kernel_layers: Callable = lambda cfg, slots: (0, 0)


def _dense_from_dict(c: dict) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_layers=int(c["num_hidden_layers"]),
        d_ff=int(c["intermediate_size"]),
        rope_theta=float(c.get("rotary_emb_base", 10000.0)),
        dtype=getattr(jnp, c.get("compute_dtype", "float32")),
    )


def _kv_heads(cfg) -> tuple:
    """K and V, a row a K/V head, in every layer; nothing a slot."""
    row = (cfg.n_kv_heads, cfg.head_dim)
    return ({"position": {"k": row, "v": row}, "slot": {}},) * cfg.n_layers


def _kv_kernel_layers(cfg, pcfg) -> tuple:
    """A K and a V pool: a layer runs the kernel if its own count of query
    heads lets it (``layer_heads``: Laguna's window and full layers)."""
    heads = getattr(cfg, "layer_heads", None) or (cfg.n_heads,) * cfg.n_layers
    row = (cfg.n_kv_heads, cfg.head_dim)
    pool = jax.ShapeDtypeStruct(
        (pcfg.num_blocks, pcfg.block_size, *row), cfg.dtype
    )
    return len(heads), sum(
        runs_kernel(jax.ShapeDtypeStruct((1, h, row[-1]), cfg.dtype), pool)
        for h in heads
    )


BLOCKS = {
    "gpt_neox": Block(
        TransformerConfig, _dense_from_dict,
        lambda key, cfg: jax.jit(lambda k: init_params(k, cfg))(key),
        prefill_dense, paged_decode_dense, _kv_heads, _kv_kernel_layers,
    ),
    "laguna": Block(
        laguna.LagunaConfig, laguna.config_from_dict, laguna.init_params,
        laguna.prefill, laguna.paged_decode_step, _kv_heads,
        _kv_kernel_layers, expert_kernel_layers=expert_kernel_layers,
    ),
    "pangu_ultra_moe": Block(
        pangu.PanguConfig, pangu.config_from_dict, pangu.init_params,
        pangu.prefill, pangu.paged_decode_step, pangu.pool_layout,
        pangu.kernel_layers, expert_kernel_layers=expert_kernel_layers,
    ),
    "kimi_linear": Block(
        kimi.KimiLinearConfig, kimi.config_from_dict, kimi.init_params,
        kimi.prefill, kimi.paged_decode_step, kimi.pool_layout,
        kimi.kernel_layers, kimi.state_kernel_layers,
        expert_kernel_layers=expert_kernel_layers,
    ),
    "olmo_hybrid": Block(
        olmo.OlmoHybridConfig, olmo.config_from_dict, olmo.init_params,
        olmo.prefill, olmo.paged_decode_step, olmo.pool_layout,
        olmo.kernel_layers, olmo.state_kernel_layers,
    ),
}


def block_of(cfg) -> Block:
    """The table's row for a configuration object."""
    for block in BLOCKS.values():
        if isinstance(cfg, block.config_type):
            return block
    raise TypeError(f"no block for a {type(cfg).__name__}")


def config_from_dict(config: dict):
    """The program's configuration object for a published configuration
    (plus the keys this repository's files add: ``compute_dtype``,
    ``param_dtype``, and for a share of the experts ``published`` and
    ``experts_held``)."""
    kind = config.get("model_type")
    if kind not in BLOCKS:
        raise ValueError(
            f"model_type {kind!r} is not implemented (known: "
            f"{sorted(BLOCKS)})"
        )
    return BLOCKS[kind].from_dict(config)


def init_model_params(key, cfg):
    """Seeded random parameters for ``cfg``, made on the device."""
    return block_of(cfg).init_params(key, cfg)


def pool_layout(cfg) -> tuple:
    """What ``cfg``'s block keeps, a layer at a time: ``{"position":
    {part: shape of one cached position}, "slot": {part: (shape, dtype
    name)}}`` (:class:`Block`)."""
    return block_of(cfg).pool_layout(cfg)


def _parts(cfg, kind: str) -> dict:
    """``{part: (what the layout states of it, layers that hold it)}``; a
    part is the same in every layer that holds it."""
    parts: dict = {}
    for layer in pool_layout(cfg):
        for part, what in layer[kind].items():
            seen, count = parts.get(part, (what, 0))
            if seen != what:
                raise ValueError(
                    f"part {part!r} is {seen} in one layer and {what} in "
                    f"another"
                )
            parts[part] = (what, count + 1)
    return parts


def position_parts(cfg) -> dict:
    """``{part: (row shape, layers that cache it)}``: what the paged pools
    hold, ``{part: [(num_blocks, block_size, *row) a layer that caches
    it]}``, in the compute dtype."""
    return _parts(cfg, "position")


def slot_parts(cfg) -> dict:
    """``{part: ((shape, dtype name), layers that hold it)}``: what the
    engine's state holds, ``{part: [(slots, *shape) a layer that holds
    it]}``.  Empty for a block that keeps nothing a slot."""
    return _parts(cfg, "slot")
