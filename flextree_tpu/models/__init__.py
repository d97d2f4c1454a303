"""Model substrate the collectives serve: dense transformer LM + MoE LM,
and the five served blocks that ``models.configs.BLOCKS`` chooses among by
``model_type``: ``transformer`` (dense), ``laguna`` (window + full
grouped-query attention, softmax-routed experts), ``pangu_ultra_moe``
(latent attention, sigmoid-routed experts, sandwich norms),
``kimi_linear`` (delta-rule linear-attention layers that hold a recurrent
state a slot, beside NoPE latent-attention layers that cache a row a
position) and ``olmo_hybrid`` (delta-rule layers with a decay a head and
keys narrower than values, beside full-attention layers that cache plain K
and V; a sublayer's output is normed).  What each keeps is its layout, a layer at a time: parts a
position, paged in blocks, and parts a slot, one array a sequence
(``models.configs.pool_layout``)."""

from .generate import (
    cached_attention,
    decode_step,
    generate,
    init_kv_cache,
    prefill,
    prefill_ragged,
    sample_token,
)
from .moe import (
    MoEConfig,
    init_moe_params,
    moe_forward,
    moe_layer,
    moe_param_specs,
)
from .transformer import (
    TransformerConfig,
    attention_block,
    cross_entropy_loss,
    forward,
    init_params,
    layer_forward,
    mlp_block,
    param_specs,
)

__all__ = [
    "TransformerConfig",
    "cross_entropy_loss",
    "forward",
    "layer_forward",
    "attention_block",
    "mlp_block",
    "init_params",
    "param_specs",
    "MoEConfig",
    "init_moe_params",
    "moe_forward",
    "moe_layer",
    "moe_param_specs",
    "generate",
    "prefill",
    "prefill_ragged",
    "decode_step",
    "init_kv_cache",
    "sample_token",
    "cached_attention",
]
