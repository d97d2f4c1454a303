"""The plain reference for ``model_type`` ``kimi_linear``: the whole
forward pass of one sequence in straightforward float32 ``jax.numpy``,
matmuls at ``"highest"``.  No cache, no state handed on, no chunks, no
absorbed product, no kernel, no sorting or grouping of tokens, and
nothing imported from ``flextree_tpu``: it reads the configuration's
published keys itself.

The equations (``config`` = the configuration file's keys; every norm is
RMSNorm with ``rms_norm_eps`` and a learned scale; pre-norm residuals: ``h
+= attn(ln_in(h))``; ``h += ffn(ln_mlp(h))``).  Layers are numbered from
1 in ``linear_attn_config``: those in ``kda_layers`` are KDA layers, those
in ``full_attn_layers`` MLA layers.

- *KDA layer*, ``H = num_heads`` heads of ``head_dim`` (keys and values
  alike), normed input ``x``: ``[q, k, v] = silu(conv(x W_qkv))``, the
  convolution causal and depthwise over ``short_conv_kernel_size``
  positions (zeros before position 0); ``q``, ``k`` L2-normalised a head
  (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times ``head_dim**-0.5``; log decay
  a CHANNEL ``g = -exp(A_log[head]) * softplus(x W_fa W_fb + dt_bias)``;
  ``beta = sigmoid(x W_beta)`` a head.  **The recurrence token by token**
  (``lax.scan``), a head's state ``S`` (head_dim, head_dim) from zeros:
  ``S <- Diag(exp(g)) S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q``.
  Output ``W_o (rmsnorm_head(o) * sigmoid(x W_ga W_gb + b_gb))``, the norm
  over a head's ``head_dim`` with one learned scale for all heads.
- *MLA layer*, expanded form, ``num_attention_heads`` heads: a head's
  query is ``x W_q`` to ``qk_nope_head_dim + qk_rope_head_dim``
  (``q_lora_rank`` null: no compression); ``[c, kr] = x W_kva``
  (``kv_lora_rank + qk_rope_head_dim``), ``c = ln_kv(c)``; a head's key
  ``[c W_kvb's first qk_nope_head_dim columns of that head, kr]``, its
  value the ``v_head_dim`` after them.  ``mla_use_nope``: NO rotary, on
  queries or on ``kr`` (a departure only where the key were false, which
  is refused).  Scores ``q . k / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)``, causal softmax, weighted values, ``W_o``.
- ``ffn``: ``W_down(silu(W_gate m) * W_up m)`` at ``intermediate_size`` in
  the first ``first_k_dense_replace`` layers; after them router scores
  ``sigmoid(m W_r)`` over ALL the experts (one expert group, so the
  grouped top-k is a plain one; the family's selection-only score bias is
  left out: zeros in a seeded tree), the ``num_experts_per_token`` largest,
  their scores over their sum (``moe_renormalize``) times
  ``routed_scaling_factor``, applied to the experts' OUTPUTS, plus the
  shared expert, ungated.
- ``ln_f``, untied head.

**The share**, **following the program's choices** and **memory**: as
``pangu_ultra_moe_decoder.py`` (``experts_held``; ``choices``; attention
one head at a time, experts one at a time, the head a slice of the
vocabulary at a time).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["forward", "routed_experts", "delta_rule", "kda_layer",
           "mla_layer"]

FFN_SLICES = 8  # slices a dense FFN's width is upcast in (where they divide it)
VOCAB_SLICES = 8  # slices the head is upcast in (where they divide it)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x / rms * _f32(scale)


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, one token after another.  ``q``, ``k``, ``g`` (T,
    H, d_k), ``v`` (T, H, d_v), ``beta`` (T, H); ``state`` (H, d_k, d_v),
    zeros when None.  Returns ``(o, state)``: (T, H, d_v) and the state
    after the last token."""
    if state is None:
        state = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    state, o = lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def kda_layer(layer, x, c: dict):
    """The KDA layer's output (T, d) for normed inputs ``x`` (T, d)."""
    lin = c["linear_attn_config"]
    heads, dim, taps = (
        int(lin["num_heads"]), int(lin["head_dim"]),
        int(lin["short_conv_kernel_size"]),
    )
    t = x.shape[0]
    proj = x @ _f32(layer["wqkv"])  # (T, 3 H dim)
    padded = jnp.pad(proj, ((taps - 1, 0), (0, 0)))
    conv = _f32(layer["conv"])
    mixed = jax.nn.silu(sum(padded[j : j + t] * conv[j] for j in range(taps)))
    q, k, v = (mixed[:, i * heads * dim : (i + 1) * heads * dim].reshape(
        t, heads, dim) for i in range(3))
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(q) * dim ** -0.5, unit(k)
    g = -jnp.exp(_f32(layer["a_log"]))[None, :, None] * jax.nn.softplus(
        (x @ _f32(layer["w_fa"]) @ _f32(layer["w_fb"])
         + _f32(layer["dt_bias"])).reshape(t, heads, dim)
    )
    beta = jax.nn.sigmoid(x @ _f32(layer["w_beta"]))
    o, _ = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid(
        x @ _f32(layer["w_ga"]) @ _f32(layer["w_gb"]) + _f32(layer["b_gb"])
    ).reshape(t, heads, dim)
    o = _rms_norm(o, layer["ln_o"], float(c["rms_norm_eps"])) * gate
    return o.reshape(t, heads * dim) @ _f32(layer["wo"])


def mla_layer(layer, x, c: dict):
    """The MLA layer's output (T, d) for normed inputs ``x`` (T, d), one
    head at a time, no rotary."""
    t = x.shape[0]
    heads = int(c["num_attention_heads"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    dv, rank = int(c["v_head_dim"]), int(c["kv_lora_rank"])
    ckr = x @ _f32(layer["wkv_a"])
    lat = _rms_norm(ckr[:, :rank], layer["ln_kv"], float(c["rms_norm_eps"]))
    kr = ckr[:, rank:]
    wq = layer["wq"].reshape(-1, heads, nope + rope)
    wkv = layer["wkv_b"].reshape(rank, heads, nope + dv)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]

    def one(h):
        q = x @ _f32(lax.dynamic_index_in_dim(wq, h, 1, keepdims=False))
        kv = lat @ _f32(lax.dynamic_index_in_dim(wkv, h, 1, keepdims=False))
        k = jnp.concatenate([kv[:, :nope], kr], axis=-1)
        s = (q @ k.T) / jnp.sqrt(jnp.float32(nope + rope))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ kv[:, nope:]

    out = lax.map(one, jnp.arange(heads))  # (H, T, dv)
    return out.transpose(1, 0, 2).reshape(t, heads * dv) @ _f32(layer["wo"])


def _gated(w, h, slices: int = 1):
    """``W_down(silu(W_gate h) * W_up h)``, a slice of the width at a
    time."""
    width = w["w_gate"].shape[-1]
    n = slices if width % slices == 0 else 1
    size = width // n

    def body(i, acc):
        cols = lambda m: _f32(lax.dynamic_slice_in_dim(m, i * size, size, 1))  # noqa: E731
        act = jax.nn.silu(h @ cols(w["w_gate"])) * (h @ cols(w["w_up"]))
        return acc + act @ _f32(
            lax.dynamic_slice_in_dim(w["w_down"], i * size, size, 0)
        )

    return lax.fori_loop(0, n, body, jnp.zeros_like(h))


def routed_experts(experts, h, choices, weights, held):
    """The held experts' part of the routed sum: every held expert on
    every token, its output weighted by the token's weight for it (zero
    where the token did not pick it), one expert at a time."""
    lo, hi = held
    ids = lo + jnp.arange(hi - lo)
    per_expert = jnp.einsum(
        "tk,tke->te", weights,
        (choices[:, :, None] == ids[None, None, :]).astype(jnp.float32),
    )  # (T, n_held): the token's weight for each held expert

    def body(i, acc):
        w = {k: lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
             for k, v in experts.items()}
        weight = lax.dynamic_slice_in_dim(per_expert, i, 1, axis=1)  # (T, 1)
        return acc + _gated(w, h) * weight

    return lax.fori_loop(0, hi - lo, body, jnp.zeros_like(h))


def _head(params, x):
    head = params["head"]
    vocab = head.shape[1]
    n = VOCAB_SLICES if vocab % VOCAB_SLICES == 0 else 1
    size = vocab // n
    parts = lax.map(
        lambda i: x @ _f32(lax.dynamic_slice_in_dim(head, i * size, size, 1)),
        jnp.arange(n),
    )  # (n, rows, size)
    return parts.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def forward(params, tokens, config: dict, experts_held=None, choices=None,
            logits_from: int = 0) -> dict:
    """The forward pass of ONE sequence ``tokens`` (T,) int32 on the
    program's parameter tree (any dtype; taken as float32).  Returns
    ``logits`` (T - logits_from, vocab) of positions ``logits_from..``,
    and for the sparse layers in order ``scores`` (L_s, T, E) and the
    reference's own top-k ``choices`` (L_s, T, k)."""
    c = config
    if not c.get("mla_use_nope", False):
        raise ValueError("only the NoPE latent attention is described here")
    lo, hi = experts_held if experts_held is not None else c.get(
        "experts_held", (0, int(c["num_experts"]))
    )
    eps = float(c["rms_norm_eps"])
    k_top = int(c["num_experts_per_token"])
    kda = set(c["linear_attn_config"]["kda_layers"])

    all_scores, all_choices = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for i, layer in enumerate(params["layers"]):
            a = _rms_norm(x, layer["ln_in"], eps)
            attend = kda_layer if i + 1 in kda else mla_layer
            x = x + attend(layer, a, c)
            m = _rms_norm(x, layer["ln_mlp"], eps)
            if i < int(c["first_k_dense_replace"]):
                y = _gated(layer["mlp"], m, FFN_SLICES)
            else:
                scores = jax.nn.sigmoid(m @ _f32(layer["router"]))
                _, own = lax.top_k(scores, k_top)
                used = own if choices is None else choices[len(all_choices)]
                w = jnp.take_along_axis(scores, used, axis=-1)
                if c.get("moe_renormalize", True):
                    w = w / w.sum(axis=-1, keepdims=True)
                w = w * float(c["routed_scaling_factor"])
                y = routed_experts(layer["experts"], m, used, w, (lo, hi))
                y = y + _gated(layer["shared"], m)
                all_scores.append(scores)
                all_choices.append(own)
            x = x + y
        x = _rms_norm(x[logits_from:], params["ln_f"], eps)
        logits = _head(params, x)
    return {
        "logits": logits,
        "scores": jnp.stack(all_scores),
        "choices": jnp.stack(all_choices).astype(jnp.int32),
    }
