"""A model from its configuration: the ``model_type`` of a published
``config.json`` names the block, its keys give the sizes.  This is how
the serving path chooses a model (``ServingEngine.from_config``, ``python
-m flextree_tpu.serving --config``); the blocks themselves are
``models.transformer`` (``gpt_neox``: the dense block at those widths,
see ``benchmarks/configs/pythia-*.json`` for what it departs in) and
``models.laguna`` (``laguna``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import laguna
from .transformer import TransformerConfig, init_params

__all__ = ["config_from_dict", "init_model_params"]


def _dense_from_dict(c: dict) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_layers=int(c["num_hidden_layers"]),
        d_ff=int(c["intermediate_size"]),
        rope_theta=float(c.get("rotary_emb_base", 10000.0)),
        dtype=getattr(jnp, c.get("compute_dtype", "float32")),
    )


_BY_MODEL_TYPE = {
    "gpt_neox": _dense_from_dict,
    "laguna": laguna.config_from_dict,
}


def config_from_dict(config: dict):
    """The program's configuration object for a published configuration
    (plus the keys this repository's files add: ``compute_dtype``,
    ``param_dtype``, and for a share of the experts ``published`` and
    ``experts_held``)."""
    kind = config.get("model_type")
    if kind not in _BY_MODEL_TYPE:
        raise ValueError(
            f"model_type {kind!r} is not implemented (known: "
            f"{sorted(_BY_MODEL_TYPE)})"
        )
    return _BY_MODEL_TYPE[kind](config)


def init_model_params(key, cfg):
    """Seeded random parameters for ``cfg``, made on the device."""
    if isinstance(cfg, laguna.LagunaConfig):
        return laguna.init_params(key, cfg)  # leaf by leaf, in its own dtype
    return jax.jit(lambda k: init_params(k, cfg))(key)
