"""The Olmo-Hybrid block (``model_type`` ``olmo_hybrid``): Gated DeltaNet
layers (a delta rule with ONE decay a head, keys narrower than values)
that carry a recurrent state a sequence, beside plain multi-head
full-attention layers that cache K and V a position; the OLMo family's
reordered norm (a sublayer's OUTPUT is normed, its input is not); a dense
gated-SiLU FFN in every layer; no experts, no rotary.

Served only, like the other configuration-file blocks: :func:`prefill`
and :func:`paged_decode_step` are the two walks over the layers.  A full
layer's decode goes through ``ops.paged_attention.paged_attention`` over
K and V pools as the dense block's does, its prefill through the flash
forward (``ops.pallas_attention``, the ``kvgrid`` variant, on a TPU) as
the latent blocks' does; a linear layer through ``ops.linear_attention``
(the chunked scan for a prompt, the one-token update for a round), with
the decay a head's: the rank of ``g`` says so.  What is new is the pair a
slot holds: a linear layer's state beside PLAIN ``k`` / ``v`` rows
(:func:`pool_layout`).

Both kinds of layer, on the residual stream ``x`` (reference:
``benchmarks/reference/olmo_hybrid_decoder.py``)::

    h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(FFN(h))
    FFN(h) = W_down (silu(W_gate h) * W_up h)

A linear layer (``H = gdn_heads`` heads, keys of ``gdn_dk``, values of
``gdn_dv``), on a token's ``x``:

- ``[q, k, v] = silu(conv(x W_qkv))``: a causal depthwise convolution over
  the last ``conv_taps`` positions of each of the ``H (2 d_k + d_v)``
  channels; ``q``, ``k`` L2-normalised a head, ``q`` times ``d_k ** -0.5``;
- log decay a HEAD ``g = -exp(A_log) softplus(x W_a + dt_bias)``; write
  strength ``beta = beta_scale sigmoid(x W_b)`` a head (``beta_scale`` 2
  where ``linear_allow_neg_eigval``: ``I - beta k k^T`` then has an
  eigenvalue in (-1, 1));
- in float32, ``S <- exp(g) S; u = beta (v - S^T k); S <- S + k u^T; o =
  S^T q``;
- ``W_o (rmsnorm_head(o) * silu(x W_g))``, the norm over a head's ``d_v``
  with one learned scale.

What a sequence carries through it: ``s`` (H, d_k, d_v) float32 and
``conv`` (conv_taps - 1, H (2 d_k + d_v)) in the compute dtype.

A full layer (``n_heads`` heads of ``head_dim`` over ``n_kv_heads`` K/V
heads): ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` (each norm over the
WHOLE projection, a learned scale as wide), ``v = x W_v``; no position
encoding; causal softmax of ``q k^T / sqrt(head_dim)``; ``W_o``.  It
caches ``k`` (after its norm) and ``v``.

Scopes (``jax.named_scope``, never one inside another): ``ft_embed``,
``ft_norm`` (``rms_norm``'s own: the two norms on the sublayers' outputs
and the final one), ``ft_gdn_proj`` (the projections, ``W_a``, ``W_b``, the
convolution, the output norm, gate and ``W_o``), ``ft_gdn_core`` (the
chunked scan, or the state's read, update, write and readout),
``ft_attn_full`` (projections, the q/k norms, attention, the K/V write),
``ft_mlp``, ``ft_head``.
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
from ..ops.paged_attention import (
    paged_attention,
    paged_attention_gather,
    put_rows,
    runs_kernel,
)
from ..ops.pallas_attention import flash_attention, kvgrid_tiles
from ..utils import backend
from .moe import gated_ffn
from .pangu_ultra_moe import (
    _embed,
    _in_row_blocks,
    blocked_causal_attention,
    head_logits,
    seeded_tree,
)
from .transformer import rms_norm

__all__ = [
    "OlmoHybridConfig",
    "config_from_dict",
    "init_params",
    "pool_layout",
    "kernel_layers",
    "state_kernel_layers",
    "gdn_prefill",
    "gdn_decode",
    "prefill",
    "paged_decode_step",
]

LINEAR, FULL = "linear_attention", "full_attention"
_L2_EPS = 1e-6
# A token's row is drawn at ONE a number.  The reordered norm makes every
# sublayer add a vector of about one a number to the stream whatever its
# input's size, so a row drawn at the input scale (0.016) is a hundredth of
# the second layer's input: every position's stream is then nearly the same
# running sums, a chunk's keys lie close together, and the delta rule turns
# bf16's rounding of such keys into a large error (PERF.md section 6, PR 34,
# where 0.25 did for sublayers that add a tenth)
_EMBED_STD = 1.0


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int
    d_model: int
    linear: tuple  # per layer: True for a linear layer, False for a full one
    # linear layers
    gdn_heads: int
    gdn_dk: int  # a head's key width
    gdn_dv: int  # a head's value width
    conv_taps: int
    beta_scale: float  # 2 where the write may flip a key's sign, else 1
    # full layers
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute, the cached rows, the conv tail
    param_dtype: Any = jnp.bfloat16  # how the weights are held
    gdn_chunk: int = 64
    gdn_segment: int = 4096  # tokens of a prompt the scan takes at a time
    q_block: int = 128
    kv_group: int = 1024
    ffn_rows: int = 4096

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads are no multiple of "
                f"{self.n_kv_heads} K/V heads"
            )

    @property
    def n_layers(self) -> int:
        return len(self.linear)

    @property
    def gdn_channels(self) -> int:
        """Channels the convolution runs over: q, k and v of every head."""
        return self.gdn_heads * (2 * self.gdn_dk + self.gdn_dv)

    @property
    def active_matmul_params(self) -> int:
        """Weights one decoded token multiplies with: ``serving.costs``
        prices a round from it."""
        d, hv = self.d_model, self.gdn_heads * self.gdn_dv
        gdn = d * self.gdn_channels + 2 * d * self.gdn_heads + 2 * d * hv
        hq, hkv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        full = 2 * d * hq + 2 * d * hkv
        n_lin = sum(self.linear)
        return (
            d * self.vocab_size + n_lin * gdn + (self.n_layers - n_lin) * full
            + self.n_layers * 3 * d * self.d_ff
        )


def pool_layout(cfg: OlmoHybridConfig) -> tuple:
    """A layer at a time: a full layer caches K and V, a row a K/V head,
    and holds nothing a slot; a linear layer caches NOTHING a position and
    holds a slot's state and its convolution's last inputs."""
    row = (cfg.n_kv_heads, cfg.head_dim)
    full = {"position": {"k": row, "v": row}, "slot": {}}
    lin = {"position": {}, "slot": {
        "s": ((cfg.gdn_heads, cfg.gdn_dk, cfg.gdn_dv), "float32"),
        "conv": ((cfg.conv_taps - 1, cfg.gdn_channels),
                 jnp.dtype(cfg.dtype).name),
    }}
    return tuple(lin if kind else full for kind in cfg.linear)


def kernel_layers(cfg: OlmoHybridConfig, pcfg) -> tuple:
    """``(attention layers of the fused decode program that read a paged
    pool, those of them that run the paged Mosaic kernel)``: the full
    layers, every one alike."""
    n_full = cfg.n_layers - sum(cfg.linear)
    q = jax.ShapeDtypeStruct((1, cfg.n_heads, cfg.head_dim), cfg.dtype)
    pool = jax.ShapeDtypeStruct(
        (pcfg.num_blocks, pcfg.block_size, cfg.n_kv_heads, cfg.head_dim),
        cfg.dtype,
    )
    return n_full, n_full * runs_kernel(q, pool)


def state_kernel_layers(cfg: OlmoHybridConfig) -> tuple:
    """``(layers of the decode program that hold a state a slot, those of
    them whose one-token update runs the Pallas kernel)``: the linear
    layers, every one alike."""
    n_lin = sum(cfg.linear)
    state = jax.ShapeDtypeStruct(
        (1, cfg.gdn_heads, cfg.gdn_dk, cfg.gdn_dv), jnp.float32
    )
    return n_lin, n_lin * runs_step_kernel(state)


def config_from_dict(c: dict) -> OlmoHybridConfig:
    """The configuration from the published keys: ``layer_types`` names
    each layer's kind, the ``linear_*`` keys are the Gated DeltaNet
    layer's arguments."""
    n = int(c["num_hidden_layers"])
    kinds = tuple(c["layer_types"])
    theta = (c.get("rope_parameters") or {}).get("rope_theta")
    heads = int(c["linear_num_value_heads"])
    refusals = (
        (len(kinds) != n, f"layer_types has {len(kinds)} entries for {n} "
         "layers"),
        (bool(set(kinds) - {LINEAR, FULL}),
         f"a layer type other than {LINEAR!r} and {FULL!r}"),
        (theta is not None, f"rotary full-attention layers (rope_theta "
         f"{theta}): the published config gives null and no rotary path is "
         "written for this block"),
        (int(c["linear_num_key_heads"]) != heads,
         "linear layers whose key heads and value heads differ in number"),
        (bool(c.get("attention_bias", False)), "attention_bias"),
        (c.get("hidden_act", "silu") != "silu", "an activation other than "
         "silu"),
        (bool(c.get("tie_word_embeddings", False)), "a tied head"),
    )
    for refused, what in refusals:
        if refused:
            raise ValueError(f"olmo_hybrid: {what} is not implemented")
    n_heads = int(c["num_attention_heads"])
    d = int(c["hidden_size"])
    return OlmoHybridConfig(
        vocab_size=int(c["vocab_size"]), d_model=d,
        linear=tuple(kind == LINEAR for kind in kinds),
        gdn_heads=heads, gdn_dk=int(c["linear_key_head_dim"]),
        gdn_dv=int(c["linear_value_head_dim"]),
        conv_taps=int(c["linear_conv_kernel_dim"]),
        beta_scale=2.0 if c.get("linear_allow_neg_eigval", False) else 1.0,
        n_heads=n_heads,
        n_kv_heads=int(c.get("num_key_value_heads") or n_heads),
        head_dim=int(c.get("head_dim") or d // n_heads),
        d_ff=int(c["intermediate_size"]),
        rms_eps=float(c.get("rms_norm_eps", 1e-6)),
        dtype=getattr(jnp, c.get("compute_dtype", "bfloat16")),
        param_dtype=getattr(jnp, c.get("param_dtype", "bfloat16")),
    )


# ------------------------------------------------------------- parameters


def _leaf_shapes(cfg: OlmoHybridConfig) -> dict:
    """``{path: (shape, std)}`` of every matrix, in a fixed order."""
    d, n = cfg.d_model, cfg.n_layers
    hv = cfg.gdn_heads * cfg.gdn_dv
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    inp = 1.0 / math.sqrt(d)
    res = lambda width: 1.0 / math.sqrt(width * 2 * n)  # noqa: E731
    leaves = {
        ("embed",): ((cfg.vocab_size, d), _EMBED_STD),
        ("head",): ((d, cfg.vocab_size), inp),
    }
    for i in range(n):
        at = ("layers", i)
        if cfg.linear[i]:
            leaves[at + ("wqkv",)] = ((d, cfg.gdn_channels), inp)
            leaves[at + ("conv",)] = (
                (cfg.conv_taps, cfg.gdn_channels), 1.0 / math.sqrt(cfg.conv_taps)
            )
            leaves[at + ("w_a",)] = ((d, cfg.gdn_heads), inp)
            leaves[at + ("w_b",)] = ((d, cfg.gdn_heads), inp)
            leaves[at + ("w_g",)] = ((d, hv), inp)
            leaves[at + ("wo",)] = ((hv, d), res(hv))
        else:
            leaves[at + ("wq",)] = ((d, hq), inp)
            leaves[at + ("wk",)] = ((d, hkv), inp)
            leaves[at + ("wv",)] = ((d, hkv), inp)
            leaves[at + ("wo",)] = ((hq, d), res(hq))
        leaves[at + ("mlp", "w_gate")] = ((d, cfg.d_ff), inp)
        leaves[at + ("mlp", "w_up")] = ((d, cfg.d_ff), inp)
        leaves[at + ("mlp", "w_down")] = ((cfg.d_ff, d), res(cfg.d_ff))
    return leaves


def init_params(key, cfg: OlmoHybridConfig) -> dict:
    """The parameter tree, made leaf by leaf in ``param_dtype``
    (``pangu_ultra_moe.seeded_tree``: no float32 copy of the tree exists at
    any moment; norm scales drawn near 1).  The decay's two parameters
    follow the published layer's initialiser, so that seeded decays lie
    where trained ones do: ``A_log = log U(0, 16)`` and ``dt_bias`` the
    inverse softplus of a step log-uniform in [1e-3, 1e-1], both a head
    and held in float32."""
    norms = [(("ln_f",), cfg.d_model)]
    for i in range(cfg.n_layers):
        inner = (
            [("ln_o", cfg.gdn_dv)] if cfg.linear[i] else
            [("ln_q", cfg.n_heads * cfg.head_dim),
             ("ln_k", cfg.n_kv_heads * cfg.head_dim)]
        )
        norms += [
            (("layers", i, name), width)
            for name, width in [
                ("ln_attn", cfg.d_model), ("ln_mlp", cfg.d_model), *inner
            ]
        ]
    params, put, spare = seeded_tree(
        key, _leaf_shapes(cfg), norms, cfg.n_layers, cfg.param_dtype,
        spare=2 * sum(cfg.linear),
    )
    decay_keys = iter(spare)
    for i in range(cfg.n_layers):
        if not cfg.linear[i]:
            continue
        put(("layers", i, "a_log"), jnp.log(jax.random.uniform(
            next(decay_keys), (cfg.gdn_heads,), jnp.float32, 0.0, 16.0)))
        step = jnp.exp(jax.random.uniform(
            next(decay_keys), (cfg.gdn_heads,), jnp.float32,
            math.log(1e-3), math.log(1e-1)))
        put(("layers", i, "dt_bias"), step + jnp.log(-jnp.expm1(-step)))
    return params


# ------------------------------------------------------------------ block


def _scaled_rms(x, scale, eps: float):
    """RMSNorm in float32 with NO scope of its own: the norms that sit
    inside a layer's scope (a scope never holds another)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * scale.astype(jnp.float32)


def _gdn_inputs(layer, x, tail, cfg: OlmoHybridConfig):
    """What the recurrence takes, from the stream ``x`` (B, T, d) and the
    convolution's inputs before it ``tail`` (B, taps - 1, channels; None:
    zeros): ``(q, k, v, g, beta, gate, tail)``, ``q``/``k`` (B, T, H, d_k),
    ``v`` and the output gate (B, T, H, d_v), ``g`` and ``beta`` (B, T, H),
    all float32, and the new ``tail``."""
    b, t, _ = x.shape
    h, dk, dv = cfg.gdn_heads, cfg.gdn_dk, cfg.gdn_dv
    f32 = jnp.float32
    mixed, tail = causal_conv(x @ layer["wqkv"], layer["conv"], tail)
    mixed = jax.nn.silu(mixed)
    q = mixed[..., : h * dk].reshape(b, t, h, dk)
    k = mixed[..., h * dk : 2 * h * dk].reshape(b, t, h, dk)
    v = mixed[..., 2 * h * dk :].reshape(b, t, h, dv)

    def unit(a):
        return a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + _L2_EPS)

    step = jnp.dot(x, layer["w_a"], preferred_element_type=f32)
    g = -jnp.exp(layer["a_log"]) * jax.nn.softplus(step + layer["dt_bias"])
    beta = cfg.beta_scale * jax.nn.sigmoid(
        jnp.dot(x, layer["w_b"], preferred_element_type=f32)
    )
    gate = jax.nn.silu(
        jnp.dot(x, layer["w_g"], preferred_element_type=f32)
    ).reshape(b, t, h, dv)
    return unit(q) * dk ** -0.5, unit(k), v, g, beta, gate, tail


def _gdn_output(layer, o, gate, cfg: OlmoHybridConfig):
    """``W_o (rmsnorm_head(o) * gate)`` for the recurrence's readout ``o``
    (B, T, H, d_v) float32."""
    b, t = o.shape[:2]
    normed = _scaled_rms(o, layer["ln_o"], cfg.rms_eps)
    return (normed * gate).astype(cfg.dtype).reshape(b, t, -1) @ layer["wo"]


def _gdn_pass(layer, x, state: dict, cfg: OlmoHybridConfig):
    """One linear layer over the tokens ``x`` (B, T, d) of a prompt, from
    what the tokens before them left (``state``: ``s`` and ``conv``) ->
    ``(mixed, state)``."""
    with jax.named_scope("ft_gdn_proj"):
        q, k, v, g, beta, gate, tail = _gdn_inputs(layer, x, state["conv"], cfg)
    with jax.named_scope("ft_gdn_core"):
        o, s = delta_rule_chunked(
            q, k, v, g, beta, state["s"], chunk=cfg.gdn_chunk,
            sub=math.gcd(cfg.gdn_chunk, 16),
        )
    with jax.named_scope("ft_gdn_proj"):
        return _gdn_output(layer, o, gate, cfg), {"s": s, "conv": tail}


def gdn_prefill(layer, x, cfg: OlmoHybridConfig):
    """One linear layer over a whole prompt from a zero state: the stream
    ``x`` (B, T, d) -> ``(mixed, state)``: the mixer's output (B, T, d),
    and what the sequence carries on, ``{"s": (B, H, d_k, d_v) float32,
    "conv": (B, taps - 1, channels)}``.  A prompt that ``cfg.gdn_segment``
    divides goes that many tokens at a time, each pass from what the last
    one left (its state, its convolution's last inputs): the same
    arithmetic, with the float32 arrays the recurrence takes and gives
    (ten times the stream's bytes) a segment's and not the prompt's."""
    b, t, d = x.shape
    seg = cfg.gdn_segment
    zero = {
        "s": jnp.zeros((b, cfg.gdn_heads, cfg.gdn_dk, cfg.gdn_dv), jnp.float32),
        "conv": jnp.zeros((b, cfg.conv_taps - 1, cfg.gdn_channels), x.dtype),
    }
    if t <= seg or t % seg:
        return _gdn_pass(layer, x, zero, cfg)

    def one(state, x_seg):
        mixed, state = _gdn_pass(layer, x_seg, state, cfg)
        return state, mixed

    state, mixed = jax.lax.scan(
        one, zero, jnp.moveaxis(x.reshape(b, t // seg, seg, d), 1, 0)
    )
    return jnp.moveaxis(mixed, 0, 1).reshape(b, t, d), state


def gdn_decode(layer, x, state: dict, active, cfg: OlmoHybridConfig):
    """One linear layer for one token a slot: the stream ``x`` (S, 1, d),
    the slots' ``state`` (``s`` (S, H, d_k, d_v), ``conv`` (S, taps - 1,
    channels)) -> ``(mixed, state)``.  A slot that is not ``active`` keeps
    its state bit for bit."""
    with jax.named_scope("ft_gdn_proj"):
        q, k, v, g, beta, gate, tail = _gdn_inputs(
            layer, x, state["conv"], cfg
        )
        tail = jnp.where(active[:, None, None], tail, state["conv"])
    with jax.named_scope("ft_gdn_core"):
        o, s = delta_rule_step(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state["s"], active
        )
    with jax.named_scope("ft_gdn_proj"):
        return _gdn_output(layer, o[:, None], gate, cfg), {"s": s, "conv": tail}


def _full_inputs(layer, x, cfg: OlmoHybridConfig):
    """``(q, k, v)`` of a full layer for the stream ``x`` (B, T, d): q (B,
    T, H, D) and k (B, T, Hkv, D) each normed over the whole projection, v
    as projected; nothing is rotated."""
    b, t, _ = x.shape
    q = _scaled_rms(x @ layer["wq"], layer["ln_q"], cfg.rms_eps)
    k = _scaled_rms(x @ layer["wk"], layer["ln_k"], cfg.rms_eps)
    heads = lambda a, n: a.astype(cfg.dtype).reshape(b, t, n, cfg.head_dim)  # noqa: E731
    return (
        heads(q, cfg.n_heads), heads(k, cfg.n_kv_heads),
        heads(x @ layer["wv"], cfg.n_kv_heads),
    )


def _causal_attention(q, k, v, cfg: OlmoHybridConfig):
    """A prompt's causal attention, (B, T, H, D) each.  Which
    implementation runs is decided from what can be observed, as
    ``pangu_ultra_moe._prefill_core`` decides: on a TPU the flash forward
    (no score leaves VMEM); elsewhere ``blocked_causal_attention``, whose
    rotary part is given no width."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if backend.kernel_platform() != "tpu":
        return blocked_causal_attention(
            q, q[..., :0], k, k[:, :, 0, :0], v, scale, cfg.q_block,
            cfg.kv_group,
        )
    return flash_attention(
        q, k, v, causal=True, scale=scale, variant="kvgrid",
        **kvgrid_tiles(cfg.head_dim, cfg.head_dim, v.dtype),
    )


def _after_mixer(layer, x, mixed, cfg: OlmoHybridConfig):
    """The stream after a layer, given its mixer's output: both sublayers'
    outputs are normed, their inputs are not.  A prompt's FFN goes
    ``cfg.ffn_rows`` rows at a time."""
    b, t, d = x.shape
    h = x + rms_norm(mixed, layer["ln_attn"], cfg.rms_eps)
    with jax.named_scope("ft_mlp"):
        y = _in_row_blocks(
            lambda r: gated_ffn(layer["mlp"], r), h.reshape(b * t, d),
            cfg.ffn_rows,
        )
    return h + rms_norm(y.reshape(b, t, d), layer["ln_mlp"], cfg.rms_eps)


# ------------------------------------------------------------------ walks


def prefill(params, tokens, cfg: OlmoHybridConfig, max_len: int):
    """Run the prompt ``tokens`` (B, T) through the model once.  Returns
    ``(last_logits, cache)``: (B, vocab) f32 logits of the LAST position,
    and what the sequence leaves behind: ``cache["k"]`` / ``cache["v"]``
    the full layers' rows (B, max_len, Hkv, D), zeros past the prompt, for
    ``kv_cache.write_prefill``; ``cache["state"]`` the linear layers' final
    state, ``{"s": [...], "conv": [...]}``, an array a linear layer, for
    ``kv_cache.write_state``."""
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(f"prompt length {t} exceeds max_len {max_len}")
    pad = ((0, 0), (0, max_len - t), (0, 0), (0, 0))
    groups = cfg.n_heads // cfg.n_kv_heads
    # the flash forward takes a K/V head a query head
    to_heads = lambda a: jnp.repeat(a, groups, axis=2) if groups > 1 else a  # noqa: E731
    x = _embed(params, tokens, cfg)
    ks, vs = [], []
    state = {"s": [], "conv": []}
    for i, layer in enumerate(params["layers"]):
        if cfg.linear[i]:
            mixed, carried = gdn_prefill(layer, x, cfg)
            for part, value in carried.items():
                state[part].append(value)
        else:
            with jax.named_scope("ft_attn_full"):
                q, k, v = _full_inputs(layer, x, cfg)
                attn = _causal_attention(q, to_heads(k), to_heads(v), cfg)
                mixed = attn.reshape(b, t, -1) @ layer["wo"]
                ks.append(jnp.pad(k, pad))
                vs.append(jnp.pad(v, pad))
        x = _after_mixer(layer, x, mixed, cfg)
    logits = head_logits(params, x[:, -1], cfg)
    cache = {
        "k": ks, "v": vs, "state": state,
        "length": jnp.full((b,), t, jnp.int32),
    }
    return logits, cache


def paged_decode_step(params, pools, tables, lengths, tokens,
                      cfg: OlmoHybridConfig, fused: bool = False, *, state):
    """One decode step for S slots: the full layers over the paged K and V
    pools (``pools["k"]`` / ``pools["v"]``, an array a full layer), the
    linear layers over the slots' ``state`` (``{"s": [...], "conv":
    [...]}``, an array a linear layer, slot-major).  Returns ``(logits,
    pools, state)``.  An inactive slot (``lengths == 0``) writes its row to
    the null block and leaves its state alone."""
    s = tokens.shape[0]
    bs = pools["k"][0].shape[1]
    blk = tables[jnp.arange(s), lengths // bs]  # (S,) current block a slot
    off = lengths % bs
    active = lengths > 0
    attend = paged_attention if fused else paged_attention_gather
    x = _embed(params, tokens[:, None], cfg)
    pool_of = iter(zip(pools["k"], pools["v"]))
    state_of = iter(zip(state["s"], state["conv"]))
    new_k, new_v = [], []
    new_state = {"s": [], "conv": []}
    for i, layer in enumerate(params["layers"]):
        if cfg.linear[i]:
            held, conv = next(state_of)
            mixed, carried = gdn_decode(
                layer, x, {"s": held, "conv": conv}, active, cfg
            )
            for part, value in carried.items():
                new_state[part].append(value)
        else:
            pk, pv = next(pool_of)
            with jax.named_scope("ft_attn_full"):
                q, k, v = _full_inputs(layer, x, cfg)
                attn = attend(
                    q[:, 0], k[:, 0], v[:, 0], pk, pv, tables, lengths
                )
                mixed = attn.reshape(s, 1, -1) @ layer["wo"]
                new_k.append(put_rows(pk, blk, off, k[:, 0]))
                new_v.append(put_rows(pv, blk, off, v[:, 0]))
        x = _after_mixer(layer, x, mixed, cfg)
    logits = head_logits(params, x[:, 0], cfg)
    return logits, {"k": new_k, "v": new_v}, new_state
