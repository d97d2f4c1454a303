"""A model from its configuration: the ``model_type`` of a published
``config.json`` names the block, its keys give the sizes.  This is how
the serving path chooses a model (``ServingEngine.from_config``, ``python
-m flextree_tpu.serving --config``).

:data:`BLOCKS` is the one table of what a block brings: its configuration
object, seeded parameters, the two walks over its layers behind the
signatures the engine calls, and the layout of what it caches a position.
The blocks themselves are ``models.transformer`` (``gpt_neox``: the dense
block at those widths, see ``benchmarks/configs/pythia-*.json`` for what
it departs in), ``models.laguna`` (``laguna``) and
``models.pangu_ultra_moe`` (``pangu_ultra_moe``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from ..ops.paged_attention import runs_kernel
from . import laguna, pangu_ultra_moe as pangu
from .generate import paged_decode_dense, prefill_dense
from .transformer import TransformerConfig, init_params

__all__ = [
    "Block", "BLOCKS", "block_of", "config_from_dict", "init_model_params",
    "pool_layout",
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
    # cfg -> {part: shape of one cached position of one layer}: the pools,
    # a prefill's cache, a swap and a migration payload hold these parts,
    # in this order
    pool_layout: Callable
    # (cfg, pcfg) -> (attention layers of the fused decode program, those
    # of them that run the Pallas kernel): fixed by the backend and the
    # shapes (``pcfg``: the pool's ``num_blocks`` and ``block_size``)
    kernel_layers: Callable


def _dense_from_dict(c: dict) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_layers=int(c["num_hidden_layers"]),
        d_ff=int(c["intermediate_size"]),
        rope_theta=float(c.get("rotary_emb_base", 10000.0)),
        dtype=getattr(jnp, c.get("compute_dtype", "float32")),
    )


def _kv_heads(cfg) -> dict:
    """K and V, a row a K/V head."""
    row = (cfg.n_kv_heads, cfg.head_dim)
    return {"k": row, "v": row}


def _kv_kernel_layers(cfg, pcfg) -> tuple:
    """A K and a V pool: a layer runs the kernel if its own count of query
    heads lets it (``layer_heads``: Laguna's window and full layers)."""
    heads = getattr(cfg, "layer_heads", None) or (cfg.n_heads,) * cfg.n_layers
    row = _kv_heads(cfg)["k"]
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
        _kv_kernel_layers,
    ),
    "pangu_ultra_moe": Block(
        pangu.PanguConfig, pangu.config_from_dict, pangu.init_params,
        pangu.prefill, pangu.paged_decode_step, pangu.pool_layout,
        pangu.kernel_layers,
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


def pool_layout(cfg) -> dict:
    """``{part: shape of one cached position of one layer}`` for ``cfg``."""
    return block_of(cfg).pool_layout(cfg)
