"""The SDAR-MoE block (``model_type`` ``sdar_moe``): a pre-norm decoder of
grouped-query attention with a per-head q/k RMSNorm and rotary on the
whole head, and a softmax-routed expert layer (no shared expert, no dense
layer) in every layer, that GENERATES BY DIFFUSION OVER BLOCKS.

The layer is the one of the config's keys (``decoder_sparse_step``,
``mlp_only_layers``, ``norm_topk_prob``), on the residual stream ``x``
(reference: ``benchmarks/reference/sdar_moe_decoder.py``)::

    h = RMSNorm(x);  q, k, v = h W_q, h W_k, h W_v   (heads of head_dim)
    q, k = rotary(RMSNorm_head(q)), rotary(RMSNorm_head(k))
    x = x + W_o attention(q, k, v)
    x = x + sum_top_k  w_e  W_down_e (silu(W_gate_e h') * W_up_e h'),
        h' = RMSNorm(x), w = softmax(h' W_router) over ALL the experts,
        the top_k of it divided by their sum

The attention mask is BLOCK-CAUSAL: positions are grouped in blocks of
``block_len`` B aligned on 0, B, 2B, ...; position ``i`` sees ``j`` iff
``j // B <= i // B`` (bidirectional inside a block, causal across blocks),
the prompt's whole blocks too.

How a block is generated (the engine's round, ``serving.engine``): a block
opens as B copies of ``mask_id`` (the prompt's last ``len % B`` tokens
fill its leading places, fixed); a DENOISING pass runs the open block over
the committed cache, takes the greedy token and its softmax probability at
every masked place, and reveals the most confident ones
(``serving.engine.unmask_greedy_ids``), ``denoise_steps`` passes a block;
then one COMMIT pass runs the clean block, and only its K/V are kept.
Every pass is :func:`block_pass`: B query positions a slot at positions
``lengths .. lengths + B - 1``, whose K/V it writes, attending the
committed ``lengths`` positions and the whole open block.

Served only: :func:`prefill` (the prompt's whole blocks under the
block-causal mask: the flash forward's ``kvgrid`` variant on a TPU) and
:func:`block_pass` (the paged Mosaic kernel over the B positions folded
into the query-head axis) are the two walks, over one description of the
layer (:func:`_attention_inputs`, :func:`_ffn`).

Scopes (``jax.named_scope``, never one inside another): ``ft_embed``,
``ft_norm`` (``rms_norm``'s own: the two pre-norms and the final one; the
q/k norms are computed INSIDE ``ft_attn_block``), ``ft_attn_block``
(projections, q/k norms, rotary, the K/V write, attention, ``W_o``),
``ft_moe_router``, ``ft_moe_experts``, ``ft_head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.paged_attention import (
    paged_attention,
    paged_attention_gather,
    put_rows,
    runs_kernel,
)
from ..ops.pallas_attention import flash_attention, kvgrid_tiles
from ..utils import backend
from .moe import expert_kernel_layers as _expert_kernel_layers
from .moe import expert_layer, round_counts, stack_router
from .olmo_hybrid import _scaled_rms
from .pangu_ultra_moe import _embed, head_logits, seeded_tree
from .transformer import apply_rope, rms_norm

__all__ = [
    "SdarMoeConfig",
    "config_from_dict",
    "init_params",
    "pool_layout",
    "kernel_layers",
    "expert_kernel_layers",
    "prefill",
    "block_pass",
]

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int  # experts the router scores (the published count)
    experts_held: tuple  # (lo, hi): the range of them this chip holds
    top_k: int
    d_expert: int
    rope_theta: float
    block_len: int  # B: positions a pass computes a slot
    denoise_steps: int  # denoising passes a block
    mask_id: int  # the token an unrevealed place holds
    norm_topk: bool = True
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute and K/V
    param_dtype: Any = jnp.bfloat16  # how the weights are held

    def __post_init__(self):
        b, steps = self.block_len, self.denoise_steps
        refusals = (
            (self.n_heads % self.n_kv_heads,
             f"{self.n_heads} query heads over {self.n_kv_heads} K/V heads"),
            (b < 1 or b & (b - 1), f"a block of {b} positions (a power of "
             "two: the flash forward's mask takes one)"),
            (not 1 <= steps <= b, f"{steps} denoising passes over a block of "
             f"{b}"),
            (not 0 <= self.mask_id < self.vocab_size,
             f"mask id {self.mask_id} outside the vocabulary"),
        )
        for refused, what in refusals:
            if refused:
                raise ValueError(f"sdar_moe: {what}")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of "
                f"{self.n_experts} experts"
            )

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_sparse(self) -> int:
        return self.n_layers  # every layer routes

    @property
    def active_matmul_params(self) -> int:
        """Weights one position multiplies with (a pick's expert counted
        whether or not it is held): ``serving.costs`` prices a round from
        it."""
        d, dh = self.d_model, self.head_dim
        attn = d * dh * (2 * self.n_heads + 2 * self.n_kv_heads)
        ffn = d * self.n_experts + 3 * d * self.top_k * self.d_expert
        return d * self.vocab_size + self.n_layers * (attn + ffn)


def pool_layout(cfg: SdarMoeConfig) -> tuple:
    """K and V, a row a K/V head, in every layer; nothing a slot."""
    row = (cfg.n_kv_heads, cfg.head_dim)
    return ({"position": {"k": row, "v": row}, "slot": {}},) * cfg.n_layers


def kernel_layers(cfg: SdarMoeConfig, pcfg) -> tuple:
    """``(attention layers of the pass program, those of them that run the
    paged Mosaic kernel)``: every layer alike, its B positions folded into
    ``n_heads * B`` query rows over the pool."""
    q = jax.ShapeDtypeStruct(
        (1, cfg.n_heads * cfg.block_len, cfg.head_dim), cfg.dtype
    )
    pool = jax.ShapeDtypeStruct(
        (pcfg.num_blocks, pcfg.block_size, cfg.n_kv_heads, cfg.head_dim),
        cfg.dtype,
    )
    return cfg.n_layers, cfg.n_layers * runs_kernel(q, pool)


def expert_kernel_layers(cfg: SdarMoeConfig, slots: int) -> tuple:
    """``moe.expert_kernel_layers`` for a pass of B rows a slot."""
    return _expert_kernel_layers(cfg, slots * cfg.block_len)


def config_from_dict(c: dict) -> SdarMoeConfig:
    """The configuration from the published keys, and the three the
    published config leaves to the generation procedure (``block_length``,
    ``denoising_steps``, ``mask_token_id``: the file's ``assumed`` says
    why each).  ``num_experts`` counts the experts HELD here; where that is
    a share, ``published`` gives the count the router scores and
    ``experts_held`` the range held."""
    n = int(c["num_hidden_layers"])
    refusals = (
        (int(c.get("decoder_sparse_step", 1)) != 1, "a dense layer between "
         "expert layers (decoder_sparse_step)"),
        (bool(c.get("mlp_only_layers")), "dense layers (mlp_only_layers)"),
        (bool(c.get("attention_bias", False)), "attention_bias"),
        (bool(c.get("tie_word_embeddings", False)), "a tied head"),
        (c.get("hidden_act", "silu") != "silu", "an activation other than "
         "silu"),
        (bool(c.get("use_sliding_window", False)), "window layers"),
        (c.get("rope_scaling") is not None, "scaled rotary"),
    )
    for refused, what in refusals:
        if refused:
            raise ValueError(f"sdar_moe: {what} is not implemented")
    held = int(c["num_experts"])
    routed = int(c.get("published", {}).get("num_experts", held))
    lo, hi = c.get("experts_held", (0, held))
    if hi - lo != held:
        raise ValueError(
            f"experts_held {[lo, hi]} does not hold num_experts={held}"
        )
    heads = int(c["num_attention_heads"])
    return SdarMoeConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_layers=n, n_heads=heads,
        n_kv_heads=int(c.get("num_key_value_heads") or heads),
        head_dim=int(c["head_dim"]), n_experts=routed,
        experts_held=(int(lo), int(hi)),
        top_k=int(c["num_experts_per_tok"]),
        d_expert=int(c["moe_intermediate_size"]),
        rope_theta=float(c["rope_theta"]),
        block_len=int(c["block_length"]),
        denoise_steps=int(c["denoising_steps"]),
        mask_id=int(c["mask_token_id"]),
        norm_topk=bool(c.get("norm_topk_prob", True)),
        rms_eps=float(c.get("rms_norm_eps", 1e-6)),
        dtype=getattr(jnp, c.get("compute_dtype", "bfloat16")),
        param_dtype=getattr(jnp, c.get("param_dtype", "bfloat16")),
    )


# ------------------------------------------------------------- parameters


def _leaf_shapes(cfg: SdarMoeConfig) -> dict:
    """``{path: (shape, std)}`` of every matrix, in a fixed order.  A
    token's row is drawn at the input scale, ``1 / sqrt(hidden)`` (0.022 at
    2,048, the published family's ``initializer_range`` of 0.02)."""
    d, dh, n = cfg.d_model, cfg.head_dim, cfg.n_layers
    hq, hkv = cfg.n_heads * dh, cfg.n_kv_heads * dh
    inp = 1.0 / math.sqrt(d)
    res = lambda width: 1.0 / math.sqrt(width * 2 * n)  # noqa: E731
    leaves = {
        ("embed",): ((cfg.vocab_size, d), inp),
        ("head",): ((d, cfg.vocab_size), inp),
    }
    for i in range(n):
        at = ("layers", i)
        leaves[at + ("wq",)] = ((d, hq), inp)
        leaves[at + ("wk",)] = ((d, hkv), inp)
        leaves[at + ("wv",)] = ((d, hkv), inp)
        leaves[at + ("wo",)] = ((hq, d), res(hq))
        leaves[at + ("router",)] = ((d, cfg.n_experts), inp)
        lead = (cfg.n_held,)
        leaves[at + ("experts", "w_gate")] = (lead + (d, cfg.d_expert), inp)
        leaves[at + ("experts", "w_up")] = (lead + (d, cfg.d_expert), inp)
        leaves[at + ("experts", "w_down")] = (
            lead + (cfg.d_expert, d), res(cfg.d_expert)
        )
    return leaves


def init_params(key, cfg: SdarMoeConfig) -> dict:
    """The parameter tree, made leaf by leaf in ``param_dtype``
    (``pangu_ultra_moe.seeded_tree``: no float32 copy of the tree exists at
    any moment; norm scales drawn near 1)."""
    norms = [(("ln_f",), cfg.d_model)]
    for i in range(cfg.n_layers):
        norms += [
            (("layers", i, name), width) for name, width in [
                ("ln1", cfg.d_model), ("ln2", cfg.d_model),
                ("q_norm", cfg.head_dim), ("k_norm", cfg.head_dim),
            ]
        ]
    params, _, _ = seeded_tree(
        key, _leaf_shapes(cfg), norms, cfg.n_layers, cfg.param_dtype
    )
    return params


# ------------------------------------------------------------------ layer


def _attention_inputs(layer, h, positions, cfg: SdarMoeConfig):
    """``(q, k, v)`` for normed inputs ``h`` (B, T, d): q (B, T, H, D) and
    k (B, T, Hkv, D) each normed a head and rotated at ``positions`` ((T,)
    shared or (B, T)), v (B, T, Hkv, D)."""
    b, t, _ = h.shape
    dh = cfg.head_dim

    def heads(w, n, norm=None):
        a = (h @ w).reshape(b, t, n, dh)
        if norm is not None:
            a = _scaled_rms(a, norm, cfg.rms_eps).astype(cfg.dtype)
        return a

    q = heads(layer["wq"], cfg.n_heads, layer["q_norm"])
    k = heads(layer["wk"], cfg.n_kv_heads, layer["k_norm"])
    return (
        apply_rope(q, positions, cfg.rope_theta),
        apply_rope(k, positions, cfg.rope_theta),
        heads(layer["wv"], cfg.n_kv_heads),
    )


def _block_causal_attention(q, k, v, cfg: SdarMoeConfig):
    """A prompt's attention under the block-causal mask, (B, T, H, D)
    queries over (B, T, Hkv, D) keys and values.  Which implementation
    runs is decided from what can be observed: on a TPU the flash forward
    (no score leaves VMEM), a K/V head a query head; elsewhere the scores
    whole, a K/V head's group of query heads together."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    g = cfg.n_heads // cfg.n_kv_heads
    if backend.kernel_platform() == "tpu":
        rep = lambda a: jnp.repeat(a, g, axis=2) if g > 1 else a  # noqa: E731
        return flash_attention(
            q, rep(k), rep(v), causal=True, scale=scale, variant="kvgrid",
            block_len=cfg.block_len,
            **kvgrid_tiles(cfg.head_dim, cfg.head_dim, v.dtype),
        )
    b, t, h, d = q.shape
    s = jnp.einsum(
        "btkgd,bskd->bkgts", q.reshape(b, t, cfg.n_kv_heads, g, d), k,
        preferred_element_type=jnp.float32,
    ) * scale
    pos = jnp.arange(t) // cfg.block_len
    s = jnp.where(pos[None, :] <= pos[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(b, t, h, d).astype(q.dtype)


def _ffn(layer, x, cfg: SdarMoeConfig, rows=None):
    """The residual after the expert layer, and what its router did.
    ``rows`` (N,) bool: rows whose picks are dispatched and counted."""
    b, t, d = x.shape
    h = rms_norm(x, layer["ln2"], cfg.rms_eps)
    y, moe = expert_layer(
        layer, h.reshape(b * t, d), top_k=cfg.top_k, scale=1.0,
        normalize=cfg.norm_topk, held=cfg.experts_held, rows=rows,
    )
    return x + y.reshape(b, t, d), moe


# ------------------------------------------------------------------ walks


def prefill(params, tokens, cfg: SdarMoeConfig, max_len: int):
    """The prompt's WHOLE blocks ``tokens`` (B, T), T a multiple of
    ``block_len``, through the model once under the block-causal mask.
    Returns ``(None, cache)``: a prefill reveals nothing, so it hands out
    no logits; ``cache["k"]`` / ``cache["v"]`` per layer (B, max_len, Hkv,
    D), zeros past the prompt, for ``kv_cache.write_prefill``, and
    ``cache["moe"]`` the layers' router scores (L, B*T, E) and choices (L,
    B*T, k)."""
    b, t = tokens.shape
    if t > max_len or t % cfg.block_len:
        raise ValueError(
            f"a prefill takes whole blocks of {cfg.block_len} within "
            f"max_len {max_len}, not {t} positions"
        )
    positions = jnp.arange(t)
    pad = ((0, 0), (0, max_len - t), (0, 0), (0, 0))
    x = _embed(params, tokens, cfg)
    ks, vs, moes = [], [], []
    for layer in params["layers"]:
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        with jax.named_scope("ft_attn_block"):
            q, k, v = _attention_inputs(layer, h, positions, cfg)
            attn = _block_causal_attention(q, k, v, cfg)
            x = x + attn.reshape(b, t, -1) @ layer["wo"]
            ks.append(jnp.pad(k, pad))
            vs.append(jnp.pad(v, pad))
        x, moe = _ffn(layer, x, cfg)
        moes.append(moe)
    cache = {
        "k": ks, "v": vs, "length": jnp.full((b,), t, jnp.int32),
        "moe": stack_router(moes),
    }
    return None, cache


def block_pass(params, pools, tables, lengths, tokens, cfg: SdarMoeConfig,
               fused: bool = False, *, active):
    """One pass over every slot's open block: ``tokens`` (S, B) the block
    as it stands (revealed ids, ``mask_id`` elsewhere) at positions
    ``lengths .. lengths + B - 1`` (``lengths`` (S,): the COMMITTED
    positions, a multiple of B), ``active`` (S,) bool.  Each layer writes
    the block's K/V at its positions, then attends the committed positions
    and the whole block: under the block-causal mask every query of a
    block sees the same keys, so the B positions fold into the query-head
    axis, ``(S, H * B, D)`` head-major (a K/V head's ``H / Hkv * B`` rows
    together), and one walk over ``lengths + B - 1`` pool positions plus
    the block's last row (:func:`ops.paged_attention.paged_attention`)
    serves them all.  Returns ``(logits, pools, moe)``: (S, B, vocab) f32,
    the pools with the block's rows written (a commit pass's are the
    ones kept: the next pass over that block's places is the next block's
    and writes past them), and the layers' ``scores`` (L, S*B, E),
    ``choices`` (L, S*B, k) and ``counts`` (:data:`moe.MOE_COUNTS`, over
    the active slots' rows).  An inactive slot's rows go to the null
    block."""
    s, b = tokens.shape
    positions = lengths[:, None] + jnp.arange(b, dtype=lengths.dtype)[None]
    bs = pools["k"][0].shape[1]
    blk = jnp.take_along_axis(tables, positions // bs, axis=1).reshape(-1)
    off = (positions % bs).reshape(-1)
    seen = lengths + (b - 1)  # pool positions the walk reads
    rows = jnp.repeat(active, b)
    attend = paged_attention if fused else paged_attention_gather
    h_all, dh = cfg.n_heads, cfg.head_dim
    x = _embed(params, tokens, cfg)
    new_k, new_v, moes = [], [], []
    for layer, pk, pv in zip(params["layers"], pools["k"], pools["v"]):
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        with jax.named_scope("ft_attn_block"):
            q, k, v = _attention_inputs(layer, h, positions, cfg)
            pk = put_rows(pk, blk, off, k.reshape(s * b, *k.shape[2:]))
            pv = put_rows(pv, blk, off, v.reshape(s * b, *v.shape[2:]))
            folded = q.transpose(0, 2, 1, 3).reshape(s, h_all * b, dh)
            attn = attend(folded, k[:, -1], v[:, -1], pk, pv, tables, seen)
            attn = attn.reshape(s, h_all, b, dh).transpose(0, 2, 1, 3)
            x = x + attn.reshape(s, b, h_all * dh) @ layer["wo"]
            new_k.append(pk)
            new_v.append(pv)
        x, moe = _ffn(layer, x, cfg, rows=rows)
        moes.append(moe)
    logits = head_logits(params, x, cfg)
    out = stack_router(moes)
    out["counts"] = round_counts(
        moes, rows, top_k=cfg.top_k, held=cfg.experts_held,
        n_experts=cfg.n_experts,
    )
    return logits, {"k": new_k, "v": new_v}, out
