"""The plain reference for ``model_type`` ``sdar_moe``: the whole forward
pass of one sequence in straightforward float32 ``jax.numpy``, matmuls at
``"highest"``, under the BLOCK-CAUSAL mask.  No cache, no kernel, no
batching, and nothing imported from ``flextree_tpu``: it reads the
configuration's published keys itself (the routed sum is
``laguna_decoder.routed_experts``, a reference too).

The equations (``config`` = the configuration file's keys):

- pre-norm RMSNorm (``rms_norm_eps``, scale only); ``num_attention_heads``
  query heads over ``num_key_value_heads`` K/V heads of ``head_dim``; no
  bias.  q and k each get an RMSNorm over a head's ``head_dim`` numbers
  (a learned scale of ``head_dim``), then rotary with ``rope_theta`` on the
  whole head in the half-split layout.
- block-causal grouped-query attention: with ``B = block_length``,
  position ``i`` sees position ``j`` iff ``j // B <= i // B`` (query head
  ``h`` reads K/V head ``h // group``).
- every layer an expert FFN: router softmax (f32) over ALL
  ``published.num_experts`` experts, the ``num_experts_per_tok`` largest,
  their scores divided by their sum (``norm_topk_prob``), applied to the
  experts' outputs ``W_down(silu(W_gate h) * W_up h)``; no shared expert,
  no dense layer.
- final norm, untied head.

**What the generation procedure adds is the caller's.**  A forward is over
a sequence as it stands: the prompt, the blocks committed so far, and the
open block with ``mask_token_id`` at its unrevealed places; under the
block-causal mask nothing after the open block changes its logits, so a
caller may pad the sequence to one length.  Departures from the published
description: none in the layer; the program tracks which places are masked
explicitly, where the published loop compares a place's id with the mask
id (a revealed token that IS the mask id would be masked again there).

**Following the program's choices.**  ``choices`` (L, T, k): the experts to
use for each token in place of the reference's own top-k (their weights
still come from the reference's scores), as in ``laguna_decoder``.

Returns beside the logits each layer's cached ``k`` (after norm and
rotary) and ``v``, the rows a commit pass keeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.laguna_decoder import routed_experts

__all__ = ["forward"]

VOCAB_SLICES = 8  # slices the head is upcast in (where they divide it)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x / rms * _f32(scale)


def _rope(x, theta: float):
    """(T, H, D) rotated at positions 0..T-1, half-split."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, block: int):
    """(T, H, D) queries over (T, Hkv, D) keys and values, block-causal,
    one K/V head (and its group of query heads) at a time."""
    t, h, d = q.shape
    hkv = k.shape[1]
    pos = jnp.arange(t) // block
    mask = pos[None, :] <= pos[:, None]

    def one(args):
        qg, kh, vh = args  # (G, T, D), (T, D), (T, D)
        s = jnp.einsum("gqd,kd->gqk", qg, kh) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vh)

    qg = q.reshape(t, hkv, h // hkv, d).transpose(1, 2, 0, 3)
    out = lax.map(one, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(t, h, d)


def _head(params, x):
    head = params["head"]
    vocab = head.shape[1]
    n = VOCAB_SLICES if vocab % VOCAB_SLICES == 0 else 1
    size = vocab // n
    parts = lax.map(
        lambda i: x @ _f32(lax.dynamic_slice_in_dim(head, i * size, size, 1)),
        jnp.arange(n),
    )
    return parts.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def forward(params, tokens, config: dict, choices=None,
            logits_from: int = 0) -> dict:
    """The forward pass of ONE sequence ``tokens`` (T,) int32 on the
    program's parameter tree (any dtype; taken as float32).  Returns
    ``logits`` (T - logits_from, vocab) of positions ``logits_from..``;
    for the layers in order ``scores`` (L, T, E), the reference's own
    top-k ``choices`` (L, T, k), and the cached ``k`` and ``v`` (L, T,
    Hkv, D)."""
    c = config
    held = int(c["num_experts"])
    lo, hi = c.get("experts_held", (0, held))
    dh, hkv = int(c["head_dim"]), int(c["num_key_value_heads"])
    eps = float(c.get("rms_norm_eps", 1e-6))
    theta = float(c["rope_theta"])
    block = int(c["block_length"])
    k_top = int(c["num_experts_per_tok"])
    t = tokens.shape[0]
    out = {"scores": [], "choices": [], "k": [], "v": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for layer in params["layers"]:
            h = _rms_norm(x, layer["ln1"], eps)
            q = (h @ _f32(layer["wq"])).reshape(t, -1, dh)
            k = (h @ _f32(layer["wk"])).reshape(t, hkv, dh)
            v = (h @ _f32(layer["wv"])).reshape(t, hkv, dh)
            q = _rope(_rms_norm(q, layer["q_norm"], eps), theta)
            k = _rope(_rms_norm(k, layer["k_norm"], eps), theta)
            a = _attention(q, k, v, block)
            x = x + a.reshape(t, -1) @ _f32(layer["wo"])
            h = _rms_norm(x, layer["ln2"], eps)
            scores = jax.nn.softmax(h @ _f32(layer["router"]), axis=-1)
            _, own = lax.top_k(scores, k_top)
            used = own if choices is None else choices[len(out["choices"])]
            w = jnp.take_along_axis(scores, used, axis=-1)
            if c.get("norm_topk_prob", True):
                w = w / w.sum(axis=-1, keepdims=True)
            x = x + routed_experts(layer["experts"], h, used, w, (lo, hi))
            for name, value in (("scores", scores), ("choices", own),
                                ("k", k), ("v", v)):
                out[name].append(value)
        x = _rms_norm(x[logits_from:], params["ln_f"], eps)
        logits = _head(params, x)
    stacked = {name: jnp.stack(vals) for name, vals in out.items()}
    stacked["choices"] = stacked["choices"].astype(jnp.int32)
    return {"logits": logits, **stacked}
