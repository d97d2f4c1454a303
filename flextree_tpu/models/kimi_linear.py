"""The Kimi-Linear block (``model_type`` ``kimi_linear``): delta-rule
linear-attention (KDA) layers that carry ONE recurrent state a sequence,
whatever its length, beside NoPE latent-attention (MLA) layers that cache
one compressed row a position; pre-norm residuals; a gated-SiLU FFN that
is dense in the leading layers and a sigmoid-routed dropless expert layer
(plus one shared expert) after them.

Served only, like ``models.laguna`` and ``models.pangu_ultra_moe``:
:func:`prefill` and :func:`paged_decode_step` are the two walks over the
layers.  The MLA layers ARE openPangu's latent attention
(``pangu_ultra_moe.latent_prefill`` / ``latent_decode``: the same expanded
prefill, the same absorbed decode over the same paged pool and kernels),
with uncompressed queries (``q_rank`` None) and no rotary (``rope``
false); the FFN is its ``ffn_layer``.  What is new is what a KDA layer
holds: no row a position at all, but a SLOT's state, which the decode
program takes and hands back beside the pools (:func:`pool_layout`).

A KDA layer, ``H = kda_heads`` heads of ``kda_dim`` (keys and values
alike), on a token's normed input ``x`` (reference:
``benchmarks/reference/kimi_linear_decoder.py``):

- ``[q, k, v] = silu(conv(x W_qkv))``: a causal depthwise convolution over
  the last ``conv_taps`` positions of each of the ``3 H kda_dim``
  channels; ``q``, ``k`` L2-normalised a head, ``q`` times ``kda_dim **
  -0.5``;
- log decay a CHANNEL ``g = -exp(A_log[head]) softplus(x W_fa W_fb +
  dt_bias)``; ``beta = sigmoid(x W_beta)`` a head;
- in float32, ``S <- Diag(exp(g)) S; u = beta (v - S^T k); S <- S + k
  u^T; o = S^T q`` (``ops.linear_attention``: chunks of ``kda_chunk``
  tokens for a prompt, one token a slot for a decode round);
- ``W_o (rmsnorm_head(o) * sigmoid(x W_ga W_gb + b_gb))``.

What a sequence carries through such a layer: ``s`` (H, kda_dim, kda_dim)
float32 and ``conv`` (conv_taps - 1, 3 H kda_dim), the convolution's last
inputs, in the compute dtype.

Scopes (``jax.named_scope``, never one inside another): ``ft_embed``,
``ft_norm`` (``rms_norm``'s own), ``ft_kda_proj`` (the projections, the
convolution, both gates, beta, the output norm, gate and ``W_o``),
``ft_kda_core`` (the recurrence: the chunked scan, or the state's read,
update, write and readout: on a TPU the kernel ``kda_state_update``),
``ft_mla_proj``, ``ft_mla_core``, ``ft_mlp``, ``ft_moe_router``,
``ft_moe_experts``, ``ft_moe_shared``, ``ft_head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.linear_attention import (
    causal_conv,
    delta_rule_chunked,
    delta_rule_step,
    runs_step_kernel,
)
from ..ops.paged_attention import runs_latent_kernel
from .moe import round_counts, stack_router
from .pangu_ultra_moe import (
    _embed,
    ffn_layer,
    head_logits,
    latent_decode,
    latent_prefill,
    seeded_tree,
)
from .transformer import rms_norm

__all__ = [
    "KimiLinearConfig",
    "config_from_dict",
    "init_params",
    "pool_layout",
    "kernel_layers",
    "state_kernel_layers",
    "kda_prefill",
    "kda_decode",
    "prefill",
    "paged_decode_step",
]

_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int
    d_model: int
    n_layers: int
    kda: tuple  # per layer: True for a KDA layer, False for an MLA layer
    # KDA
    kda_heads: int
    kda_dim: int  # a head's key width and value width
    conv_taps: int
    gate_rank: int  # inner width of the two low-rank gates (assumed)
    # MLA (the names ``pangu_ultra_moe``'s latent attention reads)
    n_heads: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    # FFN (the names ``pangu_ultra_moe.ffn_layer`` reads)
    n_dense: int
    d_ff: int
    n_experts: int  # experts the router scores (the published count)
    experts_held: tuple  # (lo, hi): the range of them this chip holds
    top_k: int
    d_expert: int
    d_shared: int
    routed_scale: float
    norm_topk: bool = True
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute, the cached rows, the conv tail
    param_dtype: Any = jnp.bfloat16  # how the weights are held
    kda_chunk: int = 64
    q_block: int = 128
    kv_group: int = 1024
    ffn_rows: int = 4096
    # what the latent attention asks of a configuration besides its sizes
    q_rank = None  # queries are not compressed
    rope = False  # mla_use_nope: no rotary anywhere
    rope_theta = 0.0

    def __post_init__(self):
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of "
                f"{self.n_experts} experts"
            )
        if len(self.kda) != self.n_layers:
            raise ValueError(
                f"{len(self.kda)} layer kinds for {self.n_layers} layers"
            )
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError(
                f"{self.n_dense} leading dense layers of {self.n_layers}"
            )

    @property
    def pool_row(self) -> int:
        """Numbers an MLA layer caches a position."""
        return self.kv_rank + self.d_rope

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.d_nope + self.d_rope)

    @property
    def kda_channels(self) -> int:
        """Channels the convolution runs over: q, k and v of every head."""
        return 3 * self.kda_heads * self.kda_dim

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
        d, hk = self.d_model, self.kda_heads * self.kda_dim
        kda = (
            d * self.kda_channels + hk * d + d * self.kda_heads
            + 2 * self.gate_rank * (d + hk)
        )
        h = self.n_heads
        mla = (
            d * h * (self.d_nope + self.d_rope) + d * self.pool_row
            + self.kv_rank * h * (self.d_nope + self.d_v) + h * self.d_v * d
        )
        sparse = d * self.n_experts + 3 * d * (
            self.top_k * self.d_expert + self.d_shared
        )
        n_kda = sum(self.kda)
        return (
            d * self.vocab_size + n_kda * kda + (self.n_layers - n_kda) * mla
            + self.n_dense * 3 * d * self.d_ff + self.n_sparse * sparse
        )


def pool_layout(cfg: KimiLinearConfig) -> tuple:
    """A layer at a time: an MLA layer caches one row a position, key and
    value at once, and holds nothing a slot; a KDA layer caches NOTHING a
    position and holds a slot's state and its convolution's last inputs."""
    mla = {"position": {"ckv": (cfg.pool_row,)}, "slot": {}}
    kda = {"position": {}, "slot": {
        "s": ((cfg.kda_heads, cfg.kda_dim, cfg.kda_dim), "float32"),
        "conv": ((cfg.conv_taps - 1, cfg.kda_channels),
                 jnp.dtype(cfg.dtype).name),
    }}
    return tuple(kda if k else mla for k in cfg.kda)


def kernel_layers(cfg: KimiLinearConfig, pcfg) -> tuple:
    """``(attention layers of the fused decode program that read a paged
    pool, those of them that run the latent Mosaic kernel)``: the MLA
    layers, every one alike."""
    n_mla = cfg.n_layers - sum(cfg.kda)
    q = jax.ShapeDtypeStruct((1, cfg.n_heads, cfg.pool_row), cfg.dtype)
    pool = jax.ShapeDtypeStruct(
        (pcfg.num_blocks, pcfg.block_size, cfg.pool_row), cfg.dtype
    )
    return n_mla, n_mla * runs_latent_kernel(q, pool, cfg.kv_rank)


def state_kernel_layers(cfg: KimiLinearConfig) -> tuple:
    """``(layers of the decode program that hold a state a slot, those of
    them whose one-token update runs the Pallas kernel)``: the KDA layers,
    every one alike."""
    n_kda = sum(cfg.kda)
    state = jax.ShapeDtypeStruct(
        (1, cfg.kda_heads, cfg.kda_dim, cfg.kda_dim), jnp.float32
    )
    return n_kda, n_kda * runs_step_kernel(state)


def config_from_dict(c: dict) -> KimiLinearConfig:
    """The configuration from the published keys.  ``num_experts`` counts
    the experts HELD here; where that is a share, ``published`` gives the
    count the router scores and ``experts_held`` the range.  The two layer
    lists of ``linear_attn_config`` number the layers from 1."""
    held = int(c["num_experts"])
    routed = int(c.get("published", {}).get("num_experts", held))
    lo, hi = c.get("experts_held", (0, held))
    if hi - lo != held:
        raise ValueError(
            f"experts_held {[lo, hi]} does not hold num_experts={held}"
        )
    refusals = (
        (c.get("q_lora_rank") is not None, "compressed queries (q_lora_rank)"),
        (not c.get("mla_use_nope", False), "rotary latent attention "
         "(mla_use_nope false)"),
        (c.get("rope_scaling") is not None, "rope_scaling"),
        (c.get("moe_router_activation_func", "sigmoid") != "sigmoid",
         "a router that is not sigmoid"),
        (int(c.get("moe_layer_freq", 1)) != 1, "moe_layer_freq other than 1"),
        (int(c.get("num_expert_group", 1)) != 1
         or int(c.get("topk_group", 1)) != 1, "more than one expert group"),
        (bool(int(c.get("num_nextn_predict_layers", 0))),
         "a next-token-prediction layer"),
    )
    for refused, what in refusals:
        if refused:
            raise ValueError(f"kimi_linear: {what} is not implemented")
    n = int(c["num_hidden_layers"])
    lin = c["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError(
            f"kda_layers and full_attn_layers do not split layers 1..{n}"
        )
    return KimiLinearConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_layers=n, kda=tuple(i + 1 in kda for i in range(n)),
        kda_heads=int(lin["num_heads"]), kda_dim=int(lin["head_dim"]),
        conv_taps=int(lin["short_conv_kernel_size"]),
        gate_rank=int(lin["head_dim"]),
        n_heads=int(c["num_attention_heads"]),
        kv_rank=int(c["kv_lora_rank"]), d_nope=int(c["qk_nope_head_dim"]),
        d_rope=int(c["qk_rope_head_dim"]), d_v=int(c["v_head_dim"]),
        n_dense=int(c["first_k_dense_replace"]),
        d_ff=int(c["intermediate_size"]),
        n_experts=routed, experts_held=(int(lo), int(hi)),
        top_k=int(c["num_experts_per_token"]),
        d_expert=int(c["moe_intermediate_size"]),
        d_shared=int(c["num_shared_experts"]) * int(c["moe_intermediate_size"]),
        routed_scale=float(c["routed_scaling_factor"]),
        norm_topk=bool(c.get("moe_renormalize", True)),
        rms_eps=float(c.get("rms_norm_eps", 1e-5)),
        dtype=getattr(jnp, c.get("compute_dtype", "bfloat16")),
        param_dtype=getattr(jnp, c.get("param_dtype", "bfloat16")),
    )


# ------------------------------------------------------------- parameters


def _leaf_shapes(cfg: KimiLinearConfig) -> dict:
    """``{path: (shape, std)}`` of every matrix, in a fixed order."""
    d, n = cfg.d_model, cfg.n_layers
    hk, r = cfg.kda_heads * cfg.kda_dim, cfg.gate_rank
    inp = 1.0 / math.sqrt(d)
    res = lambda width: 1.0 / math.sqrt(width * 2 * n)  # noqa: E731
    # A token's row is drawn at a quarter a number: the larger part of the
    # first layers' input, as in a trained model.  At ``inp`` (0.02) the
    # layers' outputs, 0.1 a number each, drown it; every position's
    # hidden state is then mostly the same running sums, deep layers' keys
    # lie at a cosine of 0.9, and the delta rule, which subtracts what the
    # state already holds along a key, turns bf16's rounding of such keys
    # into a tenth of the largest logit on some seeds (PERF.md section 6,
    # PR 34)
    leaves = {
        ("embed",): ((cfg.vocab_size, d), 0.25),
        ("head",): ((d, cfg.vocab_size), inp),
    }

    def ffn(prefix, lead, width):
        leaves[prefix + ("w_gate",)] = (lead + (d, width), inp)
        leaves[prefix + ("w_up",)] = (lead + (d, width), inp)
        leaves[prefix + ("w_down",)] = (lead + (width, d), res(width))

    for i in range(n):
        at = ("layers", i)
        if cfg.kda[i]:
            leaves[at + ("wqkv",)] = ((d, cfg.kda_channels), inp)
            leaves[at + ("conv",)] = (
                (cfg.conv_taps, cfg.kda_channels), 1.0 / math.sqrt(cfg.conv_taps)
            )
            leaves[at + ("w_fa",)] = ((d, r), inp)
            leaves[at + ("w_fb",)] = ((r, hk), 1.0 / math.sqrt(r))
            leaves[at + ("w_beta",)] = ((d, cfg.kda_heads), inp)
            leaves[at + ("w_ga",)] = ((d, r), inp)
            leaves[at + ("w_gb",)] = ((r, hk), 1.0 / math.sqrt(r))
            leaves[at + ("b_gb",)] = ((hk,), 0.5)
            leaves[at + ("wo",)] = ((hk, d), res(hk))
        else:
            h = cfg.n_heads
            leaves[at + ("wq",)] = ((d, h * (cfg.d_nope + cfg.d_rope)), inp)
            leaves[at + ("wkv_a",)] = ((d, cfg.pool_row), inp)
            leaves[at + ("wkv_b",)] = (
                (cfg.kv_rank, h * (cfg.d_nope + cfg.d_v)),
                1.0 / math.sqrt(cfg.kv_rank),
            )
            leaves[at + ("wo",)] = ((h * cfg.d_v, d), res(h * cfg.d_v))
        if cfg.is_dense(i):
            ffn(at + ("mlp",), (), cfg.d_ff)
        else:
            leaves[at + ("router",)] = ((d, cfg.n_experts), inp)
            held = cfg.experts_held[1] - cfg.experts_held[0]
            ffn(at + ("experts",), (held,), cfg.d_expert)
            ffn(at + ("shared",), (), cfg.d_shared)
    return leaves


def init_params(key, cfg: KimiLinearConfig) -> dict:
    """The parameter tree, made leaf by leaf in ``param_dtype``
    (``pangu_ultra_moe.seeded_tree``: no float32 copy of the tree exists at
    any moment; norm scales drawn near 1).  The decay's two parameters
    follow the published initialiser, so that seeded decays lie where
    trained ones do: ``A_log = log U(1, 16)`` a head, ``dt_bias`` the
    inverse softplus of a step log-uniform in [1e-3, 1e-1] a channel; both
    are held in float32."""
    norms = [(("ln_f",), cfg.d_model)]
    for i in range(cfg.n_layers):
        inner = ("ln_o", cfg.kda_dim) if cfg.kda[i] else ("ln_kv", cfg.kv_rank)
        norms += [
            (("layers", i, name), width)
            for name, width in (
                ("ln_in", cfg.d_model), ("ln_mlp", cfg.d_model), inner
            )
        ]
    params, put, spare = seeded_tree(
        key, _leaf_shapes(cfg), norms, cfg.n_layers, cfg.param_dtype,
        spare=2 * sum(cfg.kda),
    )
    decay_keys = iter(spare)
    hk = cfg.kda_heads * cfg.kda_dim
    for i in range(cfg.n_layers):
        if not cfg.kda[i]:
            continue
        put(("layers", i, "a_log"), jnp.log(jax.random.uniform(
            next(decay_keys), (cfg.kda_heads,), jnp.float32, 1.0, 16.0)))
        step = jnp.exp(jax.random.uniform(
            next(decay_keys), (hk,), jnp.float32,
            math.log(1e-3), math.log(1e-1)))
        put(("layers", i, "dt_bias"), step + jnp.log(-jnp.expm1(-step)))
    return params


# ------------------------------------------------------------------ block


def _kda_inputs(layer, a, tail, cfg: KimiLinearConfig):
    """What the recurrence takes, from normed inputs ``a`` (B, T, d) and
    the convolution's inputs before them ``tail`` (B, taps - 1, channels;
    None: zeros): ``(q, k, v, g, beta, gate, tail)``, ``q``/``k``/``g``
    (B, T, H, dim) and ``beta`` (B, T, H) in float32, ``v`` (B, T, H, dim),
    ``gate`` (B, T, H, dim) the output gate, and the new ``tail``."""
    b, t, _ = a.shape
    h, dim = cfg.kda_heads, cfg.kda_dim
    f32 = jnp.float32
    mixed, tail = causal_conv(a @ layer["wqkv"], layer["conv"], tail)
    q, k, v = (
        x.reshape(b, t, h, dim)
        for x in jnp.split(jax.nn.silu(mixed), 3, axis=-1)
    )

    def unit(x):
        x = x.astype(f32)
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + _L2_EPS)

    step = jnp.dot(
        a @ layer["w_fa"], layer["w_fb"], preferred_element_type=f32
    ) + layer["dt_bias"]
    g = -jnp.exp(layer["a_log"])[:, None] * jax.nn.softplus(
        step.reshape(b, t, h, dim)
    )
    beta = jax.nn.sigmoid(
        jnp.dot(a, layer["w_beta"], preferred_element_type=f32)
    )
    gate = jax.nn.sigmoid(
        jnp.dot(a @ layer["w_ga"], layer["w_gb"], preferred_element_type=f32)
        + layer["b_gb"].astype(f32)
    ).reshape(b, t, h, dim)
    return unit(q) * dim ** -0.5, unit(k), v, g, beta, gate, tail


def _kda_output(layer, o, gate, cfg: KimiLinearConfig):
    """``W_o (rmsnorm_head(o) * gate)`` for the recurrence's readout ``o``
    (B, T, H, dim) float32."""
    b, t = o.shape[:2]
    normed = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps
    ) * layer["ln_o"].astype(jnp.float32)
    return (normed * gate).astype(cfg.dtype).reshape(b, t, -1) @ layer["wo"]


def kda_prefill(layer, a, cfg: KimiLinearConfig):
    """One KDA layer over a whole prompt from a zero state: normed inputs
    ``a`` (B, T, d) -> ``(attn, state)``: the output (B, T, d), and what
    the sequence carries on, ``{"s": (B, H, dim, dim) float32, "conv": (B,
    taps - 1, channels)}``."""
    b = a.shape[0]
    with jax.named_scope("ft_kda_proj"):
        q, k, v, g, beta, gate, tail = _kda_inputs(layer, a, None, cfg)
    with jax.named_scope("ft_kda_core"):
        zero = jnp.zeros(
            (b, cfg.kda_heads, cfg.kda_dim, cfg.kda_dim), jnp.float32
        )
        o, s = delta_rule_chunked(
            q, k, v, g, beta, zero, chunk=cfg.kda_chunk,
            sub=math.gcd(cfg.kda_chunk, 16),
        )
    with jax.named_scope("ft_kda_proj"):
        return _kda_output(layer, o, gate, cfg), {"s": s, "conv": tail}


def kda_decode(layer, a, state: dict, active, cfg: KimiLinearConfig):
    """One KDA layer for one token a slot: normed inputs ``a`` (S, 1, d),
    the slots' ``state`` (``s`` (S, H, dim, dim), ``conv`` (S, taps - 1,
    channels)) -> ``(attn, state)``.  A slot that is not ``active`` keeps
    its state bit for bit."""
    with jax.named_scope("ft_kda_proj"):
        q, k, v, g, beta, gate, tail = _kda_inputs(
            layer, a, state["conv"], cfg
        )
        tail = jnp.where(active[:, None, None], tail, state["conv"])
    with jax.named_scope("ft_kda_core"):
        o, s = delta_rule_step(
            q[:, 0], k[:, 0], v[:, 0].astype(jnp.float32), g[:, 0],
            beta[:, 0], state["s"], active,
        )
    with jax.named_scope("ft_kda_proj"):
        return _kda_output(layer, o[:, None], gate, cfg), {"s": s, "conv": tail}


def _after_attention(layer, x, attn, cfg: KimiLinearConfig, i: int, rows=None):
    """The pre-norm residual after layer ``i``; and what its router did."""
    b, t, d = x.shape
    x = x + attn
    m = rms_norm(x, layer["ln_mlp"], cfg.rms_eps).reshape(b * t, d)
    y, moe = ffn_layer(layer, m, cfg, i, rows)
    return x + y.reshape(b, t, d), moe


# ------------------------------------------------------------------ walks


def prefill(params, tokens, cfg: KimiLinearConfig, max_len: int):
    """Run the prompt ``tokens`` (B, T) through the model once.  Returns
    ``(last_logits, cache)``: (B, vocab) f32 logits of the LAST position,
    and what the sequence leaves behind: ``cache["ckv"]`` the MLA layers'
    rows (B, max_len, kv_rank + d_rope), zeros past the prompt, for
    ``kv_cache.write_prefill``; ``cache["state"]`` the KDA layers' final
    state, ``{"s": [...], "conv": [...]}``, an array a KDA layer, for
    ``kv_cache.write_state``; ``cache["moe"]`` the sparse layers' router
    scores (L_s, B*T, E) and choices (L_s, B*T, k)."""
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(f"prompt length {t} exceeds max_len {max_len}")
    positions = jnp.arange(t)
    x = _embed(params, tokens, cfg)
    rows, moes = [], []
    state = {"s": [], "conv": []}
    for i, layer in enumerate(params["layers"]):
        a = rms_norm(x, layer["ln_in"], cfg.rms_eps)
        if cfg.kda[i]:
            attn, carried = kda_prefill(layer, a, cfg)
            for part, value in carried.items():
                state[part].append(value)
        else:
            attn, row = latent_prefill(layer, a, positions, cfg, max_len)
            rows.append(row)
        x, moe = _after_attention(layer, x, attn, cfg, i)
        if moe is not None:
            moes.append(moe)
    logits = head_logits(params, x[:, -1], cfg)
    cache = {
        "ckv": rows, "state": state, "length": jnp.full((b,), t, jnp.int32),
        "moe": stack_router(moes),
    }
    return logits, cache


def paged_decode_step(params, pools, tables, lengths, tokens,
                      cfg: KimiLinearConfig, fused: bool = False, *, state):
    """One decode step for S slots: the MLA layers over the paged latent
    pool (``pools["ckv"]``, an array an MLA layer), the KDA layers over
    the slots' ``state`` (``{"s": [...], "conv": [...]}``, an array a KDA
    layer, slot-major).  Returns ``(logits, pools, moe, state)``; ``moe``
    as ``pangu_ultra_moe.paged_decode_step``'s.  An inactive slot
    (``lengths == 0``) writes its row to the null block and leaves its
    state alone."""
    positions = lengths[:, None].astype(jnp.int32)
    active = lengths > 0
    x = _embed(params, tokens[:, None], cfg)
    pool_of, state_of = iter(pools["ckv"]), iter(zip(state["s"], state["conv"]))
    new_rows, moes = [], []
    new_state = {"s": [], "conv": []}
    for i, layer in enumerate(params["layers"]):
        a = rms_norm(x, layer["ln_in"], cfg.rms_eps)
        if cfg.kda[i]:
            s, conv = next(state_of)
            attn, carried = kda_decode(
                layer, a, {"s": s, "conv": conv}, active, cfg
            )
            for part, value in carried.items():
                new_state[part].append(value)
        else:
            attn, pool = latent_decode(
                layer, a, positions, next(pool_of), tables, lengths, cfg, fused
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
    return logits, {"ckv": new_rows}, out, new_state
