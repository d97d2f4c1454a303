"""The plain reference for ``model_type`` ``olmo_hybrid``: the whole
forward pass of one sequence in straightforward float32 ``jax.numpy``,
matmuls at ``"highest"``.  No cache, no state handed on, no chunks, no
kernel, and nothing imported from ``flextree_tpu``: it reads the
configuration's published keys itself.

The equations (``config`` = the configuration file's keys; every norm is
RMSNorm with ``rms_norm_eps`` and a learned scale).  The OLMo family's
reordered norm, in both kinds of layer: a sublayer's OUTPUT is normed, its
input is the stream itself::

    h = x + ln_attn(Mixer(x));  y = h + ln_mlp(FFN(h))
    FFN(h) = W_down (silu(W_gate h) * W_up h)

``layer_types`` names each layer's mixer.

- *linear_attention*, a Gated DeltaNet layer (arXiv:2412.06464): ``H =
  linear_num_value_heads`` heads (``linear_num_key_heads`` the same), keys
  of ``linear_key_head_dim``, values of ``linear_value_head_dim``.  ``[q,
  k, v] = silu(conv(x W_qkv))``, the convolution causal and depthwise over
  ``linear_conv_kernel_dim`` positions (zeros before position 0), the
  columns of ``W_qkv`` all of q, then all of k, then all of v; ``q``, ``k``
  L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times
  ``key_dim ** -0.5``; log decay a HEAD ``g = -exp(A_log) * softplus(x W_a +
  dt_bias)``; ``beta = sigmoid(x W_b)`` a head, times 2 where
  ``linear_allow_neg_eigval``.  **The recurrence token by token**
  (``lax.scan``), a head's state ``S`` (key_dim, value_dim) from zeros:
  ``S <- exp(g) S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q``.
  Output ``W_o (rmsnorm_head(o) * silu(x W_g))``, the norm over a head's
  ``value_dim`` with one learned scale for all heads.
- *full_attention*, ``num_attention_heads`` heads of ``hidden_size /
  num_attention_heads`` over ``num_key_value_heads`` K/V heads: ``q =
  ln_q(x W_q)``, ``k = ln_k(x W_k)``, each norm over the WHOLE projection;
  ``v = x W_v``; NO position encoding (``rope_parameters.rope_theta`` is
  null in the published config; anything else is refused); scores ``q . k
  / sqrt(head_dim)``, causal softmax, weighted values, ``W_o``.
- ``ln_f``, untied head.

**Memory**: attention one head at a time, the FFN a slice of its width at a
time, the head a slice of the vocabulary at a time, so that 8,200 tokens at
the published widths fit beside the program they are compared with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["forward", "delta_rule", "linear_layer", "full_layer"]

FFN_SLICES = 8  # slices the FFN's width is upcast in (where they divide it)
VOCAB_SLICES = 8  # slices the head is upcast in (where they divide it)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x / rms * _f32(scale)


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, one token after another.  ``q``, ``k`` (T, H, d_k),
    ``v`` (T, H, d_v), ``g``, ``beta`` (T, H); ``state`` (H, d_k, d_v),
    zeros when None.  Returns ``(o, state)``: (T, H, d_v) and the state
    after the last token."""
    if state is None:
        state = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    state, o = lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def linear_layer(layer, x, c: dict):
    """The linear layer's mixer output (T, d) for the stream ``x`` (T, d)."""
    heads = int(c["linear_num_value_heads"])
    dk, dv = int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"])
    taps = int(c["linear_conv_kernel_dim"])
    t = x.shape[0]
    proj = x @ _f32(layer["wqkv"])  # (T, H (2 dk + dv))
    padded = jnp.pad(proj, ((taps - 1, 0), (0, 0)))
    conv = _f32(layer["conv"])
    mixed = jax.nn.silu(sum(padded[j : j + t] * conv[j] for j in range(taps)))
    q = mixed[:, : heads * dk].reshape(t, heads, dk)
    k = mixed[:, heads * dk : 2 * heads * dk].reshape(t, heads, dk)
    v = mixed[:, 2 * heads * dk :].reshape(t, heads, dv)
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(q) * dk ** -0.5, unit(k)
    g = -jnp.exp(_f32(layer["a_log"])) * jax.nn.softplus(
        x @ _f32(layer["w_a"]) + _f32(layer["dt_bias"])
    )
    beta = jax.nn.sigmoid(x @ _f32(layer["w_b"]))
    if c.get("linear_allow_neg_eigval", False):
        beta = 2.0 * beta
    o, _ = delta_rule(q, k, v, g, beta)
    gate = jax.nn.silu(x @ _f32(layer["w_g"])).reshape(t, heads, dv)
    o = _rms_norm(o, layer["ln_o"], float(c["rms_norm_eps"])) * gate
    return o.reshape(t, heads * dv) @ _f32(layer["wo"])


def full_layer(layer, x, c: dict):
    """The full layer's mixer output (T, d) for the stream ``x`` (T, d),
    one head at a time, no position encoding."""
    t, d = x.shape
    heads = int(c["num_attention_heads"])
    kv_heads = int(c.get("num_key_value_heads") or heads)
    dh = int(c.get("head_dim") or d // heads)
    eps = float(c["rms_norm_eps"])
    q = _rms_norm(x @ _f32(layer["wq"]), layer["ln_q"], eps)
    k = _rms_norm(x @ _f32(layer["wk"]), layer["ln_k"], eps)
    v = x @ _f32(layer["wv"])
    q = q.reshape(t, heads, dh).transpose(1, 0, 2)
    k = k.reshape(t, kv_heads, dh).transpose(1, 0, 2)
    v = v.reshape(t, kv_heads, dh).transpose(1, 0, 2)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]

    def one(h):
        kv = h // (heads // kv_heads)
        s = (q[h] @ k[kv].T) / jnp.sqrt(jnp.float32(dh))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ v[kv]

    out = lax.map(one, jnp.arange(heads))  # (H, T, dh)
    return out.transpose(1, 0, 2).reshape(t, heads * dh) @ _f32(layer["wo"])


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


def forward(params, tokens, config: dict, logits_from: int = 0) -> dict:
    """The forward pass of ONE sequence ``tokens`` (T,) int32 on the
    program's parameter tree (any dtype; taken as float32).  Returns
    ``logits`` (T - logits_from, vocab) of positions ``logits_from..``."""
    c = config
    theta = (c.get("rope_parameters") or {}).get("rope_theta")
    if theta is not None:
        raise ValueError("only full attention with no rotary is described here")
    eps = float(c["rms_norm_eps"])
    kinds = list(c["layer_types"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for kind, layer in zip(kinds, params["layers"]):
            mixer = linear_layer if kind == "linear_attention" else full_layer
            x = x + _rms_norm(mixer(layer, x, c), layer["ln_attn"], eps)
            y = _gated(layer["mlp"], x, FFN_SLICES)
            x = x + _rms_norm(y, layer["ln_mlp"], eps)
        x = _rms_norm(x[logits_from:], params["ln_f"], eps)
        logits = _head(params, x)
    return {"logits": logits}
