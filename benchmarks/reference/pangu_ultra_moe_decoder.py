"""The plain reference for ``model_type`` ``pangu_ultra_moe``: the whole
forward pass of one sequence in straightforward float32 ``jax.numpy``,
matmuls at ``"highest"``, in the EXPANDED form of latent attention only.
No cache, no absorbed product, no kernel, no sorting or grouping of
tokens, and nothing imported from ``flextree_tpu``: it reads the
configuration's published keys itself.

The equations (``config`` = the configuration file's keys; every norm is
RMSNorm with ``rms_norm_eps`` and a learned scale, no bias anywhere):

- ``a = ln_in(h)``.  ``cq = ln_q(a W_qa)`` (``q_lora_rank``); a head's
  query is ``cq W_qb`` to ``qk_nope_head_dim + qk_rope_head_dim``, the
  last ``qk_rope_head_dim`` rotated.  ``[c, kr] = a W_kva``
  (``kv_lora_rank + qk_rope_head_dim``); ``c = ln_kv(c)``; ``kr``
  rotated, one rotary key for all heads.  A head's key is ``[c W_kvb's
  first qk_nope_head_dim columns of that head, kr]``, its value the
  ``v_head_dim`` columns after them.  Rotary: the half-split layout,
  frequencies ``rope_theta**(-2i/qk_rope_head_dim)``, no scaling.
- scores ``q . k / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal
  softmax, weighted values, ``W_o``.
- ``sandwich_norm``: ``h += ln_post_attn(attn)``; ``m = ln_pre_mlp(h)``;
  ``h += ln_post_mlp(ffn(m))`` (false: the two post norms are left out).
- ``ffn``: ``W_down(silu(W_gate m) * W_up m)`` at ``intermediate_size``
  in the first ``first_k_dense_replace`` layers; after them router scores
  ``sigmoid(m W_r)`` (``scoring_func``, where a file has the key:
  ``sigmoid`` or ``softmax``) over ALL the experts, the ``num_experts_per_tok``
  largest, their scores over their sum (``norm_topk_prob``) times
  ``routed_scaling_factor``, applied to the experts' OUTPUTS (width
  ``moe_intermediate_size``), plus the shared expert (``n_shared_experts
  * moe_intermediate_size`` wide), ungated.
- ``ln_f``, untied head.

**The share.**  ``experts_held = (lo, hi)``: the parameter tree holds the
stacked weights of experts ``lo..hi-1`` only.  The router still scores all
of them; a pick of an absent expert keeps its normalised weight and adds
nothing, and that partial sum goes on to the next layer.

**Following the program's choices.**  ``choices`` (sparse layers, T, k):
the experts to use for each token in place of the reference's own top-k
(their weights still come from the reference's scores); see
``laguna_decoder.py`` for why the comparison holds the routing equal.

**Memory.**  Computed in blocks so that 8,200 tokens at the published
widths fit beside a resident engine: attention one head at a time (the
head's query, key and value are made from the compressed vectors inside
the step, so neither 128 x 192 queries nor any expanded key outlives it;
one head's (T, T) scores are 269 MB), a dense FFN a slice of its width at
a time, every held expert on every token one expert at a time, the head
one slice of the vocabulary at a time.  A block's weights are the only
ones upcast at once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["forward", "routed_experts"]

FFN_SLICES = 8  # slices a dense FFN's width is upcast in (where they divide it)
VOCAB_SLICES = 8  # slices the head is upcast in (where they divide it)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x / rms * _f32(scale)


def _rope(x, theta: float):
    """(T, D) rotated at positions 0..T-1, half-split layout."""
    dim = x.shape[-1]
    half = dim // 2
    freqs = jnp.asarray(
        [theta ** (-2.0 * i / dim) for i in range(half)], jnp.float32
    )
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1,
    )


def _attention(layer, a, c: dict):
    """The heads' outputs (T, H * v_head_dim) for normed inputs ``a``
    (T, d), one head at a time."""
    t = a.shape[0]
    heads = int(c["num_attention_heads"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    dv, rank = int(c["v_head_dim"]), int(c["kv_lora_rank"])
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    cq = _rms_norm(a @ _f32(layer["wq_a"]), layer["ln_q"], eps)
    ckr = a @ _f32(layer["wkv_a"])
    lat = _rms_norm(ckr[:, :rank], layer["ln_kv"], eps)
    kr = _rope(ckr[:, rank:], theta)
    wq = layer["wq_b"].reshape(-1, heads, nope + rope)
    wkv = layer["wkv_b"].reshape(rank, heads, nope + dv)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]

    def one(h):
        q = cq @ _f32(lax.dynamic_index_in_dim(wq, h, 1, keepdims=False))
        kv = lat @ _f32(lax.dynamic_index_in_dim(wkv, h, 1, keepdims=False))
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], theta)], axis=-1)
        k = jnp.concatenate([kv[:, :nope], kr], axis=-1)
        s = (q @ k.T) / jnp.sqrt(jnp.float32(nope + rope))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ kv[:, nope:]

    out = lax.map(one, jnp.arange(heads))  # (H, T, dv)
    return out.transpose(1, 0, 2).reshape(t, heads * dv)


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
    # (T, n_held): the token's weight for each held expert
    per_expert = jnp.einsum(
        "tk,tke->te", weights,
        (choices[:, :, None] == ids[None, None, :]).astype(jnp.float32),
    )

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
    held = int(c["n_routed_experts"])
    lo, hi = experts_held if experts_held is not None else c.get(
        "experts_held", (0, held)
    )
    eps = float(c["rms_norm_eps"])
    k_top = int(c["num_experts_per_tok"])
    score = {
        "sigmoid": jax.nn.sigmoid,
        "softmax": lambda z: jax.nn.softmax(z, axis=-1),
    }[c.get("scoring_func", "sigmoid")]

    def post(y, scale):  # a sandwich norm, where the block has them
        return _rms_norm(y, scale, eps) if c.get("sandwich_norm", True) else y

    all_scores, all_choices = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for i, layer in enumerate(params["layers"]):
            a = _rms_norm(x, layer["ln_in"], eps)
            attn = _attention(layer, a, c) @ _f32(layer["wo"])
            x = x + post(attn, layer["ln_post_attn"])
            m = _rms_norm(x, layer["ln_pre_mlp"], eps)
            if i < int(c["first_k_dense_replace"]):
                y = _gated(layer["mlp"], m, FFN_SLICES)
            else:
                scores = score(m @ _f32(layer["router"]))
                _, own = lax.top_k(scores, k_top)
                used = own if choices is None else choices[len(all_choices)]
                w = jnp.take_along_axis(scores, used, axis=-1)
                if c.get("norm_topk_prob", True):
                    w = w / w.sum(axis=-1, keepdims=True)
                w = w * float(c["routed_scaling_factor"])
                y = routed_experts(layer["experts"], m, used, w, (lo, hi))
                y = y + _gated(layer["shared"], m)
                all_scores.append(scores)
                all_choices.append(own)
            x = x + post(y, layer["ln_post_mlp"])
        x = _rms_norm(x[logits_from:], params["ln_f"], eps)
        logits = _head(params, x)
    return {
        "logits": logits,
        "scores": jnp.stack(all_scores),
        "choices": jnp.stack(all_choices).astype(jnp.int32),
    }
