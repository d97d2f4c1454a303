"""The Laguna block: window and full attention layers with their own query
head counts over shared K/V heads, a per-head output gate, two rotary
schemes, and a gated-SiLU FFN that is dense in the leading layer and a
dropless expert layer (plus one shared expert) after it.

Served only: :func:`prefill` and :func:`paged_decode_step` are the two
walks over the layers, and they share one description of the block
(:func:`_attention_inputs`, :func:`_ffn`, :func:`head_logits`), so the
prefill that fills the cache and the decode that reads it cannot drift
apart.  ``models.generate.prefill`` and ``serving.kv_cache.
paged_decode_step`` hand a :class:`LagunaConfig` to them, which is how
the one ``ServingEngine`` runs this model through the programs, pools and
round it runs the dense model through.

The equations, layer ``l`` with ``H_l`` query heads over ``n_kv_heads``
K/V heads of ``head_dim`` (reference: ``benchmarks/reference/
laguna_decoder.py``):

- pre-norm RMSNorm; ``q = h W_q`` to ``H_l x D``, ``k``, ``v`` to
  ``n_kv x D``, no bias; rotary by layer type (:class:`RopeSpec`: YaRN
  frequencies on the first ``rotary_dim`` dimensions with cos and sin
  scaled by the attention factor on full layers, plain rotary over the
  whole head on window layers); causal grouped-query attention, a window
  layer's query at ``p`` seeing keys ``p - window + 1 .. p``; the gate
  ``g = sigmoid(h W_g)``, one per head, multiplies the head's output
  before ``W_o``.
- FFN ``W_down(silu(W_gate h) * W_up h)``; a sparse layer routes over ALL
  ``n_experts`` (softmax in f32, the ``top_k`` largest, their weights
  divided by their sum and scaled by ``routed_scale``), computes the part
  of the result that the experts it HOLDS give (``experts_held``; picks
  of absent experts keep their weight and add nothing), and adds the
  shared expert, ungated.  No capacity and no dropped pick
  (``moe.dropless_experts``).
- final norm, untied head, logits in f32.

Weights are held in ``param_dtype`` (bfloat16 as published): the head
multiplies in that type with f32 accumulation, and a prefill returns the
last position's logits only.

Scopes (``jax.named_scope``, never one inside another): ``ft_embed``,
``ft_norm``, ``ft_attn_window``, ``ft_attn_full``, ``ft_mlp`` (the dense
layer's FFN), ``ft_moe_router``, ``ft_moe_experts``, ``ft_moe_shared``,
``ft_head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import (
    paged_attention,
    paged_attention_gather,
    put_rows,
)
from .generate import cached_attention
from .moe import expert_layer, gated_ffn, round_counts, stack_router
from .transformer import apply_rope, rms_norm

__all__ = [
    "RopeSpec",
    "LagunaConfig",
    "config_from_dict",
    "init_params",
    "apply_rope_spec",
    "head_logits",
    "prefill",
    "paged_decode_step",
]

FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One layer type's rotary scheme.  ``factor`` set means YaRN: each
    frequency is a blend of ``theta**(-2i/rotary_dim)`` and the same over
    ``factor``, ramped between the ``beta_fast`` and ``beta_slow``
    correction dimensions for ``original_max`` positions, and cos and sin
    are scaled by ``attention_factor``."""

    theta: float
    rotary_dim: int
    factor: float | None = None
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self) -> np.ndarray:
        """(rotary_dim // 2,) float32 angular frequencies."""
        dim = self.rotary_dim
        pos = self.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        if self.factor is None:
            return (1.0 / pos).astype(np.float32)

        def correction_dim(rotations):
            return (
                dim * math.log(self.original_max / (rotations * 2 * math.pi))
                / (2 * math.log(self.theta))
            )

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), dim - 1)
        if low == high:
            high += 0.001  # the ramp's own guard against a zero width
        ramp = np.clip(
            (np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1
        )
        keep = 1.0 - ramp  # share of the unscaled frequency
        inv = (1.0 / (self.factor * pos)) * (1.0 - keep) + (1.0 / pos) * keep
        return inv.astype(np.float32)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int
    d_model: int
    head_dim: int
    n_kv_heads: int
    layer_heads: tuple  # query heads of each layer
    layer_types: tuple  # FULL or WINDOW, each layer
    mlp_types: tuple  # DENSE or SPARSE, each layer
    window: int
    d_ff: int  # the dense layers' FFN width
    n_experts: int  # experts the router scores (the published count)
    experts_held: tuple  # (lo, hi): the range of them this chip holds
    top_k: int
    d_expert: int
    d_shared: int
    routed_scale: float
    rope_full: RopeSpec
    rope_window: RopeSpec
    norm_topk: bool = True
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute and K/V
    param_dtype: Any = jnp.bfloat16  # how the weights are held

    def __post_init__(self):
        n = len(self.layer_heads)
        if not (len(self.layer_types) == len(self.mlp_types) == n):
            raise ValueError("per-layer lists differ in length")
        if any(h % self.n_kv_heads for h in self.layer_heads):
            raise ValueError(
                f"query heads {self.layer_heads} must be multiples of "
                f"n_kv_heads {self.n_kv_heads}"
            )
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of "
                f"{self.n_experts} experts"
            )

    @property
    def n_layers(self) -> int:
        return len(self.layer_heads)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_sparse(self) -> int:
        return sum(m == SPARSE for m in self.mlp_types)

    def rope(self, i: int) -> RopeSpec:
        return self.rope_full if self.layer_types[i] == FULL else self.rope_window

    @property
    def active_matmul_params(self) -> int:
        """Weights one decoded token multiplies with (a pick's expert
        counted whether or not it is held): ``serving.costs`` prices a
        round from it."""
        d, dh = self.d_model, self.head_dim
        total = d * self.vocab_size
        for heads, mlp in zip(self.layer_heads, self.mlp_types):
            total += d * dh * (2 * heads + 2 * self.n_kv_heads) + d * heads
            if mlp == DENSE:
                total += 3 * d * self.d_ff
            else:
                total += d * self.n_experts + 3 * d * (
                    self.top_k * self.d_expert + self.d_shared
                )
        return total


def _rope_spec(group: dict, head_dim: int) -> RopeSpec:
    rotary = int(round(head_dim * float(group.get("partial_rotary_factor", 1))))
    if group.get("rope_type", "default") == "yarn":
        return RopeSpec(
            theta=float(group["rope_theta"]), rotary_dim=rotary,
            factor=float(group["factor"]),
            original_max=int(group["original_max_position_embeddings"]),
            beta_fast=float(group["beta_fast"]),
            beta_slow=float(group["beta_slow"]),
            attention_factor=float(group["attention_factor"]),
        )
    return RopeSpec(theta=float(group["rope_theta"]), rotary_dim=rotary)


def config_from_dict(c: dict) -> LagunaConfig:
    """The configuration from the published keys.  ``num_experts`` counts
    the experts HELD here; where that is a share, ``published`` gives the
    count the router scores and ``experts_held`` the range held."""
    held = int(c["num_experts"])
    routed = int(c.get("published", {}).get("num_experts", held))
    lo, hi = c.get("experts_held", (0, held))
    if hi - lo != held:
        raise ValueError(
            f"experts_held {[lo, hi]} does not hold num_experts={held}"
        )
    n = int(c["num_hidden_layers"])
    lists = {
        k: tuple(c[k]) for k in
        ("num_attention_heads_per_layer", "layer_types", "mlp_layer_types")
    }
    for k, v in lists.items():
        if len(v) != n:
            raise ValueError(f"{k} has {len(v)} entries for {n} layers")
    if any(g != "per_head" for g in c.get("gating_types", ())):
        raise ValueError("only per-head output gates are implemented")
    head_dim = int(c["head_dim"])
    ropes = c["rope_parameters"]
    return LagunaConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        head_dim=head_dim, n_kv_heads=int(c["num_key_value_heads"]),
        layer_heads=tuple(int(h) for h in lists["num_attention_heads_per_layer"]),
        layer_types=lists["layer_types"], mlp_types=lists["mlp_layer_types"],
        window=int(c["sliding_window"]), d_ff=int(c["intermediate_size"]),
        n_experts=routed, experts_held=(int(lo), int(hi)),
        top_k=int(c["num_experts_per_tok"]),
        d_expert=int(c["moe_intermediate_size"]),
        d_shared=int(c["shared_expert_intermediate_size"]),
        routed_scale=float(c["moe_routed_scaling_factor"]),
        rope_full=_rope_spec(ropes[FULL], head_dim),
        rope_window=_rope_spec(ropes[WINDOW], head_dim),
        norm_topk=bool(c.get("norm_topk_prob", True)),
        rms_eps=float(c.get("rms_norm_eps", 1e-6)),
        dtype=getattr(jnp, c.get("compute_dtype", "bfloat16")),
        param_dtype=getattr(jnp, c.get("param_dtype", "bfloat16")),
    )


# ------------------------------------------------------------- parameters


def _leaf_shapes(cfg: LagunaConfig) -> dict:
    """``{path: (shape, std)}`` of every matrix, in a fixed order."""
    d, dh, kv = cfg.d_model, cfg.head_dim, cfg.n_kv_heads
    n = cfg.n_layers
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

    for i, (heads, mlp) in enumerate(zip(cfg.layer_heads, cfg.mlp_types)):
        at = ("layers", i)
        leaves[at + ("wq",)] = ((d, heads * dh), inp)
        leaves[at + ("wk",)] = ((d, kv * dh), inp)
        leaves[at + ("wv",)] = ((d, kv * dh), inp)
        leaves[at + ("wg",)] = ((d, heads), inp)
        leaves[at + ("wo",)] = (
            (heads * dh, d), 1.0 / math.sqrt(heads * dh * 2 * n)
        )
        if mlp == DENSE:
            ffn(at + ("mlp",), (), cfg.d_ff)
        else:
            leaves[at + ("router",)] = ((d, cfg.n_experts), inp)
            ffn(at + ("experts",), (cfg.n_held,), cfg.d_expert)
            ffn(at + ("shared",), (), cfg.d_shared)
    return leaves


def init_params(key, cfg: LagunaConfig) -> dict:
    """The parameter tree, made leaf by leaf in ``param_dtype``: each
    matrix is one jitted call that draws, scales and rounds it, so no
    float32 copy of the tree (twice its size) exists at any moment."""
    dt = cfg.param_dtype
    ones = jnp.ones((cfg.d_model,), dt)
    params = {
        "ln_f": ones,
        "layers": [{"ln1": ones, "ln2": ones} for _ in range(cfg.n_layers)],
    }

    def draw(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    draw = jax.jit(draw, static_argnums=(1, 2))
    leaves = _leaf_shapes(cfg)
    for k, (path, (shape, std)) in zip(
        jax.random.split(key, len(leaves)), leaves.items()
    ):
        node = params
        for name in path[:-1]:
            node = node[name] if isinstance(node, list) else node.setdefault(name, {})
        node[path[-1]] = draw(k, shape, std)
    return params


# ------------------------------------------------------------------ block


def apply_rope_spec(x, positions, spec: RopeSpec):
    """Rotary embedding on (B, T, H, D) under ``spec``: the first
    ``rotary_dim`` dimensions rotated in the half-split layout, the rest
    passed through.  Plain rotary over the whole head is
    ``transformer.apply_rope`` itself."""
    if spec.factor is None and spec.rotary_dim == x.shape[-1]:
        return apply_rope(x, positions, spec.theta)
    rot, half = spec.rotary_dim, spec.rotary_dim // 2
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(spec.inv_freq())
    if ang.ndim == 2:
        ang = ang[None]  # shared positions broadcast over the batch
    cos = (jnp.cos(ang) * spec.attention_factor)[:, :, None, :]
    sin = (jnp.sin(ang) * spec.attention_factor)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], axis=-1
    )
    return rotated.astype(x.dtype)


def _attn_scope(cfg: LagunaConfig, i: int):
    return jax.named_scope(
        "ft_attn_full" if cfg.layer_types[i] == FULL else "ft_attn_window"
    )


def _attention_inputs(layer, h, positions, cfg: LagunaConfig, i: int):
    """``(q, k, v, gate)`` of layer ``i`` for normed inputs ``h``
    (B, T, d): q (B, T, H_l, D) and k (B, T, n_kv, D) rotated at
    ``positions``, v, and the per-head gate (B, T, H_l) in f32."""
    b, t, _ = h.shape
    dh = cfg.head_dim
    q = (h @ layer["wq"]).reshape(b, t, cfg.layer_heads[i], dh)
    k = (h @ layer["wk"]).reshape(b, t, cfg.n_kv_heads, dh)
    v = (h @ layer["wv"]).reshape(b, t, cfg.n_kv_heads, dh)
    spec = cfg.rope(i)
    q = apply_rope_spec(q, positions, spec)
    k = apply_rope_spec(k, positions, spec)
    gate = jax.nn.sigmoid(
        jnp.dot(h, layer["wg"], preferred_element_type=jnp.float32)
    )
    return q, k, v, gate


def _attention_output(layer, x, attn, gate):
    """The residual after attention: each head's output times its gate,
    through ``W_o``."""
    b, t = attn.shape[:2]
    gated = (attn.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    return x + gated.reshape(b, t, -1) @ layer["wo"]


def _ffn(layer, x, cfg: LagunaConfig, i: int, rows=None):
    """The residual after layer ``i``'s FFN, and what its router did
    (``None`` for a dense layer; ``moe.expert_layer``'s otherwise).
    ``rows`` (N,) bool: rows whose picks are dispatched and counted (a
    decode round's inactive slots are not)."""
    b, t, d = x.shape
    h = rms_norm(x, layer["ln2"], cfg.rms_eps)
    if cfg.mlp_types[i] == DENSE:
        with jax.named_scope("ft_mlp"):
            return x + gated_ffn(layer["mlp"], h), None
    y, moe = expert_layer(
        layer, h.reshape(b * t, d), top_k=cfg.top_k, scale=cfg.routed_scale,
        normalize=cfg.norm_topk, held=cfg.experts_held, rows=rows,
    )
    return x + y.reshape(b, t, d), moe


def head_logits(params, x, cfg: LagunaConfig):
    """Final norm and the untied head on (..., d): float32 logits from a
    product in the held type with float32 accumulation (no f32 copy of
    the head is made)."""
    h = rms_norm(x, params["ln_f"], cfg.rms_eps)
    with jax.named_scope("ft_head"):
        return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def _embed(params, tokens, cfg: LagunaConfig):
    with jax.named_scope("ft_embed"):
        return params["embed"][tokens].astype(cfg.dtype)


# ------------------------------------------------------------------ walks


def prefill(params, tokens, cfg: LagunaConfig, max_len: int):
    """Run the prompt ``tokens`` (B, T) through the model once: the
    counterpart of ``models.generate.prefill``.  Returns ``(last_logits,
    cache)``: (B, vocab) f32 logits of the LAST position only, and per
    layer (B, max_len, n_kv, D) K/V (zeros past the prompt) for
    ``kv_cache.write_prefill``.  ``cache["moe"]`` holds the sparse
    layers' router scores (L_s, B*T, E) and choices (L_s, B*T, k)."""
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(f"prompt length {t} exceeds max_len {max_len}")
    positions = jnp.arange(t)
    pad = ((0, 0), (0, max_len - t), (0, 0), (0, 0))
    x = _embed(params, tokens, cfg)
    ks, vs, moes = [], [], []
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        with _attn_scope(cfg, i):
            q, k, v, gate = _attention_inputs(layer, h, positions, cfg, i)
            attn = cached_attention(
                q, k, v, positions,
                window=cfg.window if cfg.layer_types[i] == WINDOW else None,
            )
            x = _attention_output(layer, x, attn, gate)
            ks.append(jnp.pad(k, pad))
            vs.append(jnp.pad(v, pad))
        x, moe = _ffn(layer, x, cfg, i)
        if moe is not None:
            moes.append(moe)
    logits = head_logits(params, x[:, -1], cfg)
    cache = {
        "k": ks, "v": vs, "length": jnp.full((b,), t, jnp.int32),
        "moe": stack_router(moes),
    }
    return logits, cache


def paged_decode_step(params, pools, tables, lengths, tokens,
                      cfg: LagunaConfig, fused: bool = False):
    """One decode step for S slots over the paged pool: the counterpart
    of ``serving.kv_cache.paged_decode_step`` (same arguments, same pool
    layout, ``n_kv_heads`` wide).  A window layer attends only through
    the table columns that meet its window.  Returns ``(logits, pools,
    moe)``; ``moe`` holds the sparse layers' ``scores`` (L_s, S, E) and
    ``choices`` (L_s, S, k), and ``counts``, int32 in the order of
    :data:`moe.MOE_COUNTS`, over the sparse layers and the ACTIVE slots
    (``lengths > 0``; an empty slot's row dispatches nothing)."""
    s = tokens.shape[0]
    positions = lengths[:, None].astype(jnp.int32)
    bs = pools["k"][0].shape[1]
    row = jnp.arange(s)
    blk = tables[row, lengths // bs]
    off = lengths % bs
    active = lengths > 0
    attend = paged_attention if fused else paged_attention_gather
    x = _embed(params, tokens[:, None], cfg)
    new_k, new_v, moes = [], [], []
    for i, (layer, pk, pv) in enumerate(
        zip(params["layers"], pools["k"], pools["v"])
    ):
        h = rms_norm(x, layer["ln1"], cfg.rms_eps)
        with _attn_scope(cfg, i):
            q, k, v, gate = _attention_inputs(layer, h, positions, cfg, i)
            attn = attend(
                q[:, 0], k[:, 0], v[:, 0], pk, pv, tables, lengths,
                window=cfg.window if cfg.layer_types[i] == WINDOW else None,
            )[:, None]
            x = _attention_output(layer, x, attn, gate)
            new_k.append(put_rows(pk, blk, off, k[:, 0]))
            new_v.append(put_rows(pv, blk, off, v[:, 0]))
        x, moe = _ffn(layer, x, cfg, i, rows=active)
        if moe is not None:
            moes.append(moe)
    logits = head_logits(params, x[:, 0], cfg)
    out = stack_router(moes)
    out["counts"] = round_counts(
        moes, active, top_k=cfg.top_k, held=cfg.experts_held,
        n_experts=cfg.n_experts,
    )
    return logits, {"k": new_k, "v": new_v}, out
