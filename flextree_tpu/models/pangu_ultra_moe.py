"""The openPangu-Ultra-MoE block (``model_type`` ``pangu_ultra_moe``):
latent attention over ONE compressed row a token, sandwich norms, and a
gated-SiLU FFN that is dense in the leading layers and a sigmoid-routed
dropless expert layer (plus one shared expert) after them.

Served only, like ``models.laguna``: :func:`prefill` and
:func:`paged_decode_step` are the two walks over the layers and share one
description of the block (:func:`_latent_inputs`, :func:`_after_attention`,
:func:`head_logits`).  The table in ``models.configs`` hands a
:class:`PanguConfig` to them, which is how the one ``ServingEngine`` runs
this model through the programs, pool functions and round of the others.
The latent attention of a layer (:func:`latent_prefill`,
:func:`latent_decode`) and its FFN (:func:`ffn_layer`) take any
configuration that has their sizes: ``models.kimi_linear`` runs the same
two, with uncompressed queries (``q_rank`` None) and no rotary (``rope``
false).

The equations, ``H`` heads of ``d_nope + d_rope`` (queries and keys) and
``d_v`` (values); every norm is RMSNorm with a learned scale, no bias
anywhere (reference: ``benchmarks/reference/pangu_ultra_moe_decoder.py``):

- ``a = norm_in(h)``.  Queries ``q = norm_q(a W_qa) W_qb`` to ``H x
  (d_nope + d_rope)``, the last ``d_rope`` rotated.  The cached row
  ``[c, kr] = a W_kva`` (``kv_rank + d_rope``), ``c = norm_kv(c)``, ``kr``
  rotated: one rotary key shared by all heads.  **The cache holds ``[c,
  kr]`` and nothing else** (``pool_row``: 576 numbers at the published
  widths, where K and V a head would be 49,152).
- *Expanded* (:func:`prefill`): ``[k_nope, v] = c W_kvb`` to ``H x (d_nope
  + d_v)``, scores ``(q_nope . k_nope + q_rope . kr) / sqrt(d_nope +
  d_rope)``, causal softmax, weighted values, ``W_o``.  The scores never
  exist whole (at 128 heads the (H, T, T) array in f32 is 34 GB at 8,192
  tokens): on a TPU they stay in VMEM (the flash kernel), elsewhere they
  are taken a block of queries at a time
  (:func:`blocked_causal_attention`); :func:`_prefill_core` chooses.
- *Absorbed* (:func:`paged_decode_step`): with ``W_kvb`` split by head into
  ``W_uk`` (kv_rank x d_nope) and ``W_uv`` (kv_rank x d_v), ``q_lat =
  q_nope W_uk^T``, scores ``[q_lat, q_rope] . [c, kr]`` against the cached
  rows themselves, ``o_lat = softmax(s) c``, ``o = o_lat W_uv``: the same
  numbers, and no cached position is ever expanded
  (``ops.paged_attention.paged_attention_latent``).
- Sandwich norms: ``h += norm_post_attn(attn)``; ``m = norm_pre_mlp(h)``;
  ``h += norm_post_mlp(ffn(m))``.
- ``ffn``: gated SiLU at ``d_ff`` in the first ``n_dense`` layers; after
  them ``moe.expert_layer`` with ``score="sigmoid"``: sigmoid scores in f32
  over ALL ``n_experts``, the ``top_k`` largest, their scores over their
  sum times ``routed_scale``, the part of the sum that the experts HELD
  here give, plus the shared expert, ungated.
- final norm, untied head, logits in f32.

Scopes (``jax.named_scope``, never one inside another): ``ft_embed``,
``ft_norm`` (``rms_norm``'s own: the four sandwich norms, the two inner
ones and the final one), ``ft_mla_proj`` (the projections, rotary, the
absorb products, the output projection, the cache write), ``ft_mla_core``
(scores, softmax, weighted rows), ``ft_mlp``, ``ft_moe_router``, ``ft_moe_experts``,
``ft_moe_shared``, ``ft_head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.paged_attention import (
    paged_attention_latent,
    paged_attention_latent_gather,
    runs_latent_kernel,
)
from ..ops.pallas_attention import flash_attention, kvgrid_tiles
from ..utils import backend
from .moe import expert_layer, gated_ffn, round_counts, stack_router
from .transformer import apply_rope, rms_norm

__all__ = [
    "PanguConfig",
    "config_from_dict",
    "init_params",
    "pool_layout",
    "kernel_layers",
    "blocked_causal_attention",
    "latent_prefill",
    "latent_decode",
    "ffn_layer",
    "head_logits",
    "prefill",
    "paged_decode_step",
]

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class PanguConfig:
    vocab_size: int
    d_model: int
    n_heads: int
    q_rank: int | None  # the queries' compressed width (None: uncompressed)
    kv_rank: int  # the cached row's compressed part
    d_nope: int  # a head's unrotated query/key width
    d_rope: int  # the rotary key, shared by all heads
    d_v: int
    n_layers: int
    n_dense: int  # leading layers whose FFN is dense
    d_ff: int  # their FFN width
    n_experts: int  # experts the router scores (the published count)
    experts_held: tuple  # (lo, hi): the range of them this chip holds
    top_k: int
    d_expert: int
    d_shared: int
    routed_scale: float
    rope_theta: float
    norm_topk: bool = True
    rms_eps: float = 1e-5
    rope: bool = True  # rotate the queries' and the row's last d_rope
    dtype: Any = jnp.bfloat16  # compute and the cached rows
    param_dtype: Any = jnp.bfloat16  # how the weights are held
    # prefill attention: rows of queries whose scores exist at once, and
    # rows of queries that share one static extent of keys
    q_block: int = 128
    kv_group: int = 1024
    # prefill FFN: token rows one pass of a layer's FFN takes.  Every pass
    # of an expert layer reads all the experts held (1.5 GB a layer), so
    # few passes; but its sorted picks are top_k rows a token, in f32 on
    # the way out (1 GB at 4,096 rows)
    ffn_rows: int = 4096

    def __post_init__(self):
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of "
                f"{self.n_experts} experts"
            )
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError(
                f"{self.n_dense} leading dense layers of {self.n_layers}"
            )
        if self.d_rope % 2:
            raise ValueError(f"rotary width {self.d_rope} must be even")

    @property
    def pool_row(self) -> int:
        """Numbers the cache holds a position a layer."""
        return self.kv_rank + self.d_rope

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.d_nope + self.d_rope)

    def is_dense(self, i: int) -> bool:
        return i < self.n_dense

    @property
    def n_sparse(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def active_matmul_params(self) -> int:
        """Weights one decoded token multiplies with (a pick's expert
        counted whether or not it is held): ``serving.costs`` prices a
        round from it."""
        d, h = self.d_model, self.n_heads
        attn = (
            d * self.q_rank + self.q_rank * h * (self.d_nope + self.d_rope)
            + d * self.pool_row + self.kv_rank * h * (self.d_nope + self.d_v)
            + h * self.d_v * d
        )
        sparse = d * self.n_experts + 3 * d * (
            self.top_k * self.d_expert + self.d_shared
        )
        return (
            d * self.vocab_size + self.n_layers * attn
            + self.n_dense * 3 * d * self.d_ff + self.n_sparse * sparse
        )


def pool_layout(cfg: PanguConfig) -> tuple:
    """What the block keeps, a layer at a time: one cached row a position
    that is key and value at once, no heads axis; nothing a slot."""
    return (
        {"position": {"ckv": (cfg.pool_row,)}, "slot": {}},
    ) * cfg.n_layers


def kernel_layers(cfg: PanguConfig, pcfg) -> tuple:
    """``(attention layers of the fused decode program, those of them that
    run the latent Mosaic kernel)`` over a pool of ``pcfg.num_blocks``
    blocks of ``pcfg.block_size``: every layer alike."""
    q = jax.ShapeDtypeStruct((1, cfg.n_heads, cfg.pool_row), cfg.dtype)
    pool = jax.ShapeDtypeStruct(
        (pcfg.num_blocks, pcfg.block_size, cfg.pool_row), cfg.dtype
    )
    return cfg.n_layers, cfg.n_layers * runs_latent_kernel(
        q, pool, cfg.kv_rank
    )


def config_from_dict(c: dict) -> PanguConfig:
    """The configuration from the published keys.  ``n_routed_experts``
    counts the experts HELD here; where that is a share, ``published``
    gives the count the router scores and ``experts_held`` the range."""
    held = int(c["n_routed_experts"])
    routed = int(c.get("published", {}).get("n_routed_experts", held))
    lo, hi = c.get("experts_held", (0, held))
    if hi - lo != held:
        raise ValueError(
            f"experts_held {[lo, hi]} does not hold n_routed_experts={held}"
        )
    if c.get("attention_bias", False):
        raise ValueError("attention biases are not implemented")
    if not c.get("sandwich_norm", True):
        raise ValueError("only the sandwich-norm block is implemented")
    if c.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only the sigmoid router is implemented")
    if int(c.get("num_nextn_predict_layers", 0)):
        raise ValueError(
            "a next-token-prediction layer drafts a second token a round, "
            "which the scheduler does not take: set "
            "num_nextn_predict_layers to 0 (and list it in reduced)"
        )
    return PanguConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
        d_nope=int(c["qk_nope_head_dim"]), d_rope=int(c["qk_rope_head_dim"]),
        d_v=int(c["v_head_dim"]), n_layers=int(c["num_hidden_layers"]),
        n_dense=int(c["first_k_dense_replace"]),
        d_ff=int(c["intermediate_size"]),
        n_experts=routed, experts_held=(int(lo), int(hi)),
        top_k=int(c["num_experts_per_tok"]),
        d_expert=int(c["moe_intermediate_size"]),
        d_shared=int(c["n_shared_experts"]) * int(c["moe_intermediate_size"]),
        routed_scale=float(c["routed_scaling_factor"]),
        rope_theta=float(c["rope_theta"]),
        norm_topk=bool(c.get("norm_topk_prob", True)),
        rms_eps=float(c.get("rms_norm_eps", 1e-5)),
        dtype=getattr(jnp, c.get("compute_dtype", "bfloat16")),
        param_dtype=getattr(jnp, c.get("param_dtype", "bfloat16")),
    )


# ------------------------------------------------------------- parameters

#: the six norms of a layer and the width each scales
_NORMS = (
    ("ln_in", "d_model"), ("ln_post_attn", "d_model"),
    ("ln_pre_mlp", "d_model"), ("ln_post_mlp", "d_model"),
    ("ln_q", "q_rank"), ("ln_kv", "kv_rank"),
)


def _leaf_shapes(cfg: PanguConfig) -> dict:
    """``{path: (shape, std)}`` of every matrix, in a fixed order."""
    d, h, n = cfg.d_model, cfg.n_heads, cfg.n_layers
    inp = 1.0 / math.sqrt(d)
    leaves = {
        ("embed",): ((cfg.vocab_size, d), inp),
        ("head",): ((d, cfg.vocab_size), inp),
    }

    def ffn(prefix, lead, width):
        out = 1.0 / math.sqrt(width * 2 * n)
        leaves[prefix + ("w_gate",)] = (lead + (d, width), inp)
        leaves[prefix + ("w_up",)] = (lead + (d, width), inp)
        leaves[prefix + ("w_down",)] = (lead + (width, d), out)

    for i in range(n):
        at = ("layers", i)
        leaves[at + ("wq_a",)] = ((d, cfg.q_rank), inp)
        leaves[at + ("wq_b",)] = (
            (cfg.q_rank, h * (cfg.d_nope + cfg.d_rope)),
            1.0 / math.sqrt(cfg.q_rank),
        )
        leaves[at + ("wkv_a",)] = ((d, cfg.pool_row), inp)
        leaves[at + ("wkv_b",)] = (
            (cfg.kv_rank, h * (cfg.d_nope + cfg.d_v)),
            1.0 / math.sqrt(cfg.kv_rank),
        )
        leaves[at + ("wo",)] = (
            (h * cfg.d_v, d), 1.0 / math.sqrt(h * cfg.d_v * 2 * n)
        )
        if cfg.is_dense(i):
            ffn(at + ("mlp",), (), cfg.d_ff)
        else:
            leaves[at + ("router",)] = ((d, cfg.n_experts), inp)
            held = cfg.experts_held[1] - cfg.experts_held[0]
            ffn(at + ("experts",), (held,), cfg.d_expert)
            ffn(at + ("shared",), (), cfg.d_shared)
    return leaves


def seeded_tree(key, leaves: dict, norms: list, n_layers: int, dtype,
                spare: int = 0) -> tuple:
    """``(params, put, spare keys)``: a parameter tree with every matrix
    of ``leaves`` (``{path: (shape, std)}``) drawn about 0 and every norm
    scale of ``norms`` (``[(path, width)]``) about 1 with a spread of 0.1,
    leaf by leaf in ``dtype`` (each one jitted call that draws, scales and
    rounds it, so no float32 copy of the tree exists at any moment).
    ``put(path, value)`` sets further leaves, for which ``spare`` more keys
    are split off."""

    def draw(k, shape, std, mean):
        return (mean + jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    draw = jax.jit(draw, static_argnums=(1, 2, 3))
    keys = jax.random.split(key, len(leaves) + len(norms) + spare)
    params = {"layers": [{} for _ in range(n_layers)]}

    def put(path, value):
        node = params
        for name in path[:-1]:
            node = node[name] if isinstance(node, list) else node.setdefault(name, {})
        node[path[-1]] = value

    for k, (path, (shape, std)) in zip(keys, leaves.items()):
        put(path, draw(k, shape, std, 0.0))
    for k, (path, width) in zip(keys[len(leaves):], norms):
        put(path, draw(k, (width,), 0.1, 1.0))
    return params, put, keys[len(leaves) + len(norms):]


def init_params(key, cfg: PanguConfig) -> dict:
    """The parameter tree, made leaf by leaf in ``param_dtype``
    (:func:`seeded_tree`).  The norms' scales start at 1 as published
    checkpoints' do not: a seeded tree's are drawn near 1, so that a norm
    left out or misplaced moves the result."""
    norms = [(("ln_f",), cfg.d_model)] + [
        (("layers", i, name), getattr(cfg, width))
        for i in range(cfg.n_layers) for name, width in _NORMS
    ]
    return seeded_tree(
        key, _leaf_shapes(cfg), norms, cfg.n_layers, cfg.param_dtype
    )[0]


# ------------------------------------------------------------------ block


def _latent_inputs(layer, a, positions, cfg):
    """``(q_nope, q_rope, row)`` of one layer for normed inputs ``a``
    (B, T, d): queries (B, T, H, d_nope) and (B, T, H, d_rope), the latter
    rotated at ``positions``, and the row to cache (B, T, kv_rank +
    d_rope): the normed compressed vector and the rotated shared key.
    The two inner norms sit between the ``ft_mla_proj`` stretches (a
    scope never holds another).  ``cfg`` is any configuration with the
    latent attention's sizes (``models.kimi_linear``'s too): where its
    ``q_rank`` is None the queries are ``a W_q`` with no compression and
    no inner norm, and where its ``rope`` is false nothing is rotated (the
    row's last ``d_rope`` numbers are a plain shared key part)."""
    b, t, _ = a.shape
    turn = (
        (lambda x: apply_rope(x, positions, cfg.rope_theta)) if cfg.rope
        else (lambda x: x)
    )
    with jax.named_scope("ft_mla_proj"):
        cq = a @ (layer["wq"] if cfg.q_rank is None else layer["wq_a"])
        ckr = a @ layer["wkv_a"]
    if cfg.q_rank is not None:
        cq = rms_norm(cq, layer["ln_q"], cfg.rms_eps)
    c = rms_norm(ckr[..., : cfg.kv_rank], layer["ln_kv"], cfg.rms_eps)
    with jax.named_scope("ft_mla_proj"):
        q = (cq if cfg.q_rank is None else cq @ layer["wq_b"]).reshape(
            b, t, cfg.n_heads, cfg.d_nope + cfg.d_rope
        )
        q_nope, q_rope = q[..., : cfg.d_nope], q[..., cfg.d_nope :]
        q_rope = turn(q_rope)
        kr = turn(ckr[..., cfg.kv_rank :][:, :, None, :])[:, :, 0]
        return q_nope, q_rope, jnp.concatenate([c, kr], axis=-1)


def _split_kvb(layer, cfg):
    """``W_kvb`` by head: ``(W_uk, W_uv)``, (kv_rank, H, d_nope) and
    (kv_rank, H, d_v)."""
    w = layer["wkv_b"].reshape(cfg.kv_rank, cfg.n_heads, cfg.d_nope + cfg.d_v)
    return w[..., : cfg.d_nope], w[..., cfg.d_nope :]


def blocked_causal_attention(q_nope, q_rope, k_nope, kr, v, scale: float,
                             q_block: int, kv_group: int):
    """Causal attention of the EXPANDED form over positions ``0..T-1``,
    a block of queries at a time: ``q_nope``/``k_nope`` (B, T, H, d_nope),
    ``q_rope`` (B, T, H, d_rope), ``kr`` (B, T, d_rope) the one rotary key
    of a position, ``v`` (B, T, H, d_v).  Queries go ``kv_group`` rows at
    a time against the keys up to their group's end (a static slice: the
    keys past it are never multiplied), and inside a group ``q_block``
    rows at a time, so that the largest score array is (B, H, q_block,
    group end) in f32.  A group that ``q_block`` does not divide goes
    whole.  Returns (B, T, H, d_v) in ``v``'s dtype."""
    b, t, h, _ = q_nope.shape
    f32 = jnp.float32
    outs = []
    for g0 in range(0, t, kv_group):
        g1 = min(g0 + kv_group, t)
        rows = g1 - g0
        qb = q_block if rows % q_block == 0 else rows
        kn, kro, vg = k_nope[:, :g1], kr[:, :g1], v[:, :g1]
        kpos = jnp.arange(g1)

        def one(args, kn=kn, kro=kro, vg=vg, kpos=kpos, qb=qb):
            qn, qr, start = args  # (B, qb, H, .), first query position
            s = jnp.einsum("bqhd,bkhd->bhqk", qn, kn, preferred_element_type=f32)
            s += jnp.einsum("bqhr,bkr->bhqk", qr, kro, preferred_element_type=f32)
            seen = kpos[None, :] <= (start + jnp.arange(qb))[:, None]
            p = jax.nn.softmax(jnp.where(seen, s * scale, _NEG_INF), axis=-1)
            return jnp.einsum(
                "bhqk,bkhd->bqhd", p.astype(vg.dtype), vg,
                preferred_element_type=f32,
            ).astype(vg.dtype)

        def blocks(x):  # (B, rows, H, D) -> (rows // qb, B, qb, H, D)
            return x.reshape(b, rows // qb, qb, *x.shape[2:]).swapaxes(0, 1)

        out = lax.map(one, (
            blocks(q_nope[:, g0:g1]), blocks(q_rope[:, g0:g1]),
            g0 + qb * jnp.arange(rows // qb),
        ))
        outs.append(out.swapaxes(0, 1).reshape(b, rows, h, -1))
    return jnp.concatenate(outs, axis=1)


def _prefill_core(q_nope, q_rope, k_nope, kr, v, cfg):
    """The prefill's causal attention, expanded form.  Which
    implementation runs is decided from what can be observed, as
    ``ops.paged_attention`` decides: on a TPU the flash kernel
    (``ops.pallas_attention``, the ``kvgrid`` forward, whose values may be
    narrower than its keys: no score leaves VMEM, where the blocks below
    write and read each f32 score block several times over HBM), the rotary
    key repeated to every head; elsewhere (tier 1, a replica on the CPU)
    :func:`blocked_causal_attention`."""
    if backend.kernel_platform() != "tpu":
        return blocked_causal_attention(
            q_nope, q_rope, k_nope, kr, v, cfg.softmax_scale, cfg.q_block,
            cfg.kv_group,
        )
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :], q_rope.shape)], axis=-1
    )
    return flash_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1), k, v, causal=True,
        scale=cfg.softmax_scale, variant="kvgrid",
        **kvgrid_tiles(k.shape[-1], v.shape[-1], v.dtype),
    )


def _in_row_blocks(fn, flat, rows: int):
    """``fn`` over (N, d) ``flat``, ``rows`` at a time where they divide
    N (one pass otherwise).  ``fn`` returns a pytree of arrays whose
    leading axis is its input's."""
    n = flat.shape[0]
    if n <= rows or n % rows:
        return fn(flat)
    out = lax.map(fn, flat.reshape(n // rows, rows, -1))
    return jax.tree.map(lambda a: a.reshape(n, *a.shape[2:]), out)


def latent_prefill(layer, a, positions, cfg, max_len: int):
    """One layer's latent attention over a whole prompt, expanded form:
    normed inputs ``a`` (B, T, d) -> ``(attn, row)``, the output (B, T, d)
    after ``W_o`` and the rows to cache (B, max_len, kv_rank + d_rope),
    zeros past the prompt."""
    b, t, _ = a.shape
    q_nope, q_rope, row = _latent_inputs(layer, a, positions, cfg)
    with jax.named_scope("ft_mla_proj"):
        w_uk, w_uv = _split_kvb(layer, cfg)
        c = row[..., : cfg.kv_rank]
        k_nope = jnp.einsum("btr,rhn->bthn", c, w_uk)
        v = jnp.einsum("btr,rhv->bthv", c, w_uv)
        padded = jnp.pad(row, ((0, 0), (0, max_len - t), (0, 0)))
    with jax.named_scope("ft_mla_core"):
        o = _prefill_core(
            q_nope, q_rope, k_nope, row[..., cfg.kv_rank :], v, cfg
        )
    with jax.named_scope("ft_mla_proj"):
        return o.reshape(b, t, -1) @ layer["wo"], padded


def latent_decode(layer, a, positions, pool, tables, lengths, cfg,
                  fused: bool):
    """One layer's latent attention for one token a slot, absorbed form,
    over the paged latent ``pool`` (N, bs, kv_rank + d_rope): normed
    inputs ``a`` (S, 1, d) -> ``(attn, pool)``, the output (S, 1, d) and
    the pool with each slot's new row written at position ``lengths``."""
    s = a.shape[0]
    bs = pool.shape[1]
    attend = paged_attention_latent if fused else paged_attention_latent_gather
    q_nope, q_rope, row = _latent_inputs(layer, a, positions, cfg)
    with jax.named_scope("ft_mla_proj"):
        w_uk, w_uv = _split_kvb(layer, cfg)
        q_lat = jnp.einsum("shn,rhn->shr", q_nope[:, 0], w_uk)
        q = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)
    with jax.named_scope("ft_mla_core"):
        o_lat = attend(
            q, row[:, 0], pool, tables, lengths,
            value_dim=cfg.kv_rank, scale=cfg.softmax_scale,
        )
    with jax.named_scope("ft_mla_proj"):
        blk = tables[jnp.arange(s), lengths // bs]
        pool = pool.at[blk, lengths % bs].set(row[:, 0])
        o = jnp.einsum("shr,rhv->shv", o_lat, w_uv)
        return (o.reshape(s, -1) @ layer["wo"])[:, None], pool


def ffn_layer(layer, m, cfg, i: int, rows=None):
    """Layer ``i``'s FFN on normed rows ``m`` (N, d): gated SiLU where
    the layer is dense, else ``moe.expert_layer`` with sigmoid scores; and
    what the layer's router did (``None`` for a dense layer).  A prompt
    (``rows`` None) goes ``cfg.ffn_rows`` rows at a time; ``rows`` (N,)
    bool: rows whose picks are dispatched and counted (a decode round's
    inactive slots are not)."""
    routed = dict(
        top_k=cfg.top_k, scale=cfg.routed_scale, normalize=cfg.norm_topk,
        held=cfg.experts_held, score="sigmoid",
    )
    if cfg.is_dense(i):
        with jax.named_scope("ft_mlp"):
            y = _in_row_blocks(
                lambda r: gated_ffn(layer["mlp"], r), m, cfg.ffn_rows
            )
        return y, None
    if rows is not None:
        return expert_layer(layer, m, rows=rows, **routed)

    def experts(r):
        y, moe = expert_layer(layer, r, **routed)
        return y, moe["scores"], moe["choices"]

    y, scores, choices = _in_row_blocks(experts, m, cfg.ffn_rows)
    return y, {"scores": scores, "choices": choices}


def _after_attention(layer, x, attn, cfg: PanguConfig, i: int, rows=None):
    """The residual after layer ``i``, given the attention's output
    ``attn`` (B, T, d) before its sandwich norm; and what the layer's
    router did (:func:`ffn_layer`)."""
    b, t, d = x.shape
    x = x + rms_norm(attn, layer["ln_post_attn"], cfg.rms_eps)
    m = rms_norm(x, layer["ln_pre_mlp"], cfg.rms_eps).reshape(b * t, d)
    y, moe = ffn_layer(layer, m, cfg, i, rows)
    y = rms_norm(y.reshape(b, t, d), layer["ln_post_mlp"], cfg.rms_eps)
    return x + y, moe


def head_logits(params, x, cfg):
    """Final norm and the untied head on (..., d): float32 logits from a
    product in the held type with float32 accumulation."""
    h = rms_norm(x, params["ln_f"], cfg.rms_eps)
    with jax.named_scope("ft_head"):
        return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def _embed(params, tokens, cfg):
    with jax.named_scope("ft_embed"):
        return params["embed"][tokens].astype(cfg.dtype)


# ------------------------------------------------------------------ walks


def prefill(params, tokens, cfg: PanguConfig, max_len: int):
    """Run the prompt ``tokens`` (B, T) through the model once, in the
    expanded form.  Returns ``(last_logits, cache)``: (B, vocab) f32
    logits of the LAST position only, and per layer the rows to cache,
    ``cache["ckv"]`` (B, max_len, kv_rank + d_rope) (zeros past the
    prompt) for ``kv_cache.write_prefill``.  ``cache["moe"]`` holds the
    sparse layers' router scores (L_s, B*T, E) and choices (L_s, B*T, k)."""
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(f"prompt length {t} exceeds max_len {max_len}")
    positions = jnp.arange(t)
    x = _embed(params, tokens, cfg)
    rows, moes = [], []
    for i, layer in enumerate(params["layers"]):
        a = rms_norm(x, layer["ln_in"], cfg.rms_eps)
        attn, row = latent_prefill(layer, a, positions, cfg, max_len)
        rows.append(row)
        x, moe = _after_attention(layer, x, attn, cfg, i)
        if moe is not None:
            moes.append(moe)
    logits = head_logits(params, x[:, -1], cfg)
    cache = {
        "ckv": rows, "length": jnp.full((b,), t, jnp.int32),
        "moe": stack_router(moes),
    }
    return logits, cache


def paged_decode_step(params, pools, tables, lengths, tokens,
                      cfg: PanguConfig, fused: bool = False):
    """One decode step for S slots over the paged latent pool, in the
    absorbed form: the counterpart of ``kv_cache.paged_decode_step`` (same
    arguments; ``pools["ckv"]`` one (N, bs, kv_rank + d_rope) array a
    layer).  Returns ``(logits, pools, moe)``; ``moe`` holds the sparse
    layers' ``scores`` (L_s, S, E) and ``choices`` (L_s, S, k), and
    ``counts``, int32 in the order of ``moe.MOE_COUNTS``, over the sparse
    layers and the ACTIVE slots (``lengths > 0``)."""
    positions = lengths[:, None].astype(jnp.int32)
    active = lengths > 0
    x = _embed(params, tokens[:, None], cfg)
    new_rows, moes = [], []
    for i, (layer, pool) in enumerate(zip(params["layers"], pools["ckv"])):
        a = rms_norm(x, layer["ln_in"], cfg.rms_eps)
        attn, pool = latent_decode(
            layer, a, positions, pool, tables, lengths, cfg, fused
        )
        new_rows.append(pool)
        x, moe = _after_attention(layer, x, attn, cfg, i, rows=active)
        if moe is not None:
            moes.append(moe)
    logits = head_logits(params, x[:, 0], cfg)
    out = stack_router(moes)
    out["counts"] = round_counts(
        moes, active, top_k=cfg.top_k, held=cfg.experts_held,
        n_experts=cfg.n_experts,
    )
    return logits, {"ckv": new_rows}, out
