"""The plain reference for ``model_type`` ``laguna``: the whole forward
pass of one sequence in straightforward float32 ``jax.numpy``, matmuls at
``"highest"``.  No cache, no kernel, no sorting or grouping of tokens, and
nothing imported from ``flextree_tpu``: it reads the configuration's
published keys itself.

The equations (``config`` = the configuration file's keys):

- pre-norm RMSNorm (``rms_norm_eps``, scale only).  Layer ``l`` has
  ``num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` K/V heads of ``head_dim``; no bias.
- rotary by ``layer_types[l]`` from ``rope_parameters``: the first
  ``partial_rotary_factor * head_dim`` dimensions of each head rotate in
  the half-split layout.  ``default``: frequencies ``theta**(-2i/dim)``.
  ``yarn``: each frequency blends ``theta**(-2i/dim)`` with the same over
  ``factor``; the blend ramps from all-unscaled at the correction
  dimension of ``beta_fast`` rotations to all-scaled at that of
  ``beta_slow`` over ``original_max_position_embeddings`` positions; cos
  and sin are scaled by ``attention_factor``.
- causal grouped-query attention (query head ``j`` reads K/V head ``j //
  group``); a ``sliding_attention`` layer's query at ``p`` sees keys
  ``p - sliding_window + 1 .. p``.  Per-head output gate: ``sigmoid(h
  W_g)`` of the normed layer input times the head's output, before
  ``W_o``.
- FFN ``W_down(silu(W_gate h) * W_up h)``: ``dense`` at
  ``intermediate_size``; ``sparse`` = router softmax (f32) over ALL the
  experts, the ``num_experts_per_tok`` largest, their scores divided by
  their sum (``norm_topk_prob``) times ``moe_routed_scaling_factor``,
  applied to the experts' OUTPUTS, plus the shared expert, ungated.
- final norm, untied head.

**The share.**  ``experts_held = (lo, hi)``: the parameter tree holds the
stacked weights of experts ``lo..hi-1`` only.  The router still scores all
of them; a pick of an absent expert keeps its normalised weight and adds
nothing, and that partial sum goes on to the next layer.

**Following the program's choices.**  ``choices`` (sparse layers, T, k):
the experts to use for each token in place of the reference's own top-k
(their weights still come from the reference's scores).  With random
weights the k-th and (k+1)-th scores are often closer than bf16's rounding
of the layer input, so two correct computations can pick differently; the
comparison checks the choices apart (scores within a tolerance, any
difference only between experts that close to the cut) and the logits with
the routing held equal.

**Memory.**  Every expert runs on every token (no gather), a group of
experts at a time: a group's weights are the only ones upcast at once, so
the transient stays in the hundreds of MB at the published widths;
attention runs one K/V head at a time and the head one slice of the
vocabulary at a time, for the same reason.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["forward", "inv_frequencies", "routed_experts"]

EXPERT_GROUP = 8  # experts upcast together
VOCAB_SLICES = 8  # slices the head is upcast in (where they divide it)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x / rms * _f32(scale)


def inv_frequencies(rope: dict, head_dim: int):
    """``(frequencies (dim/2,), dim, attention factor)`` of one entry of
    ``rope_parameters`` (formulas in the module docstring)."""
    dim = int(round(head_dim * float(rope.get("partial_rotary_factor", 1))))
    theta = float(rope["rope_theta"])
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if rope.get("rope_type", "default") != "yarn":
        return jnp.asarray(plain, jnp.float32), dim, 1.0
    factor = float(rope["factor"])
    origin = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(origin / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        scaled_share = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * scaled_share + f * (1.0 - scaled_share))
    return jnp.asarray(out, jnp.float32), dim, float(rope["attention_factor"])


def _rope(x, rope: dict):
    """(T, H, D) rotated at positions 0..T-1."""
    freqs, dim, scale = inv_frequencies(rope, x.shape[-1])
    half = dim // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., dim:]], axis=-1
    )


def _attention(q, k, v, window):
    """(T, H, D) queries over (T, Hkv, D) keys and values, causal, one
    K/V head (and its group of query heads) at a time."""
    t, h, d = q.shape
    hkv = k.shape[1]
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window

    def one(args):
        qg, kh, vh = args  # (G, T, D), (T, D), (T, D)
        s = jnp.einsum("gqd,kd->gqk", qg, kh) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vh)

    qg = q.reshape(t, hkv, h // hkv, d).transpose(1, 2, 0, 3)
    out = lax.map(one, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(t, h, d)  # (T, H, D)


def _gated(w, h):
    g = h @ _f32(w["w_gate"])
    return (jax.nn.silu(g) * (h @ _f32(w["w_up"]))) @ _f32(w["w_down"])


def routed_experts(experts, h, choices, weights, held):
    """The held experts' part of the routed sum: every held expert on
    every token, its output weighted by the token's weight for it (zero
    where the token did not pick it), a group of experts at a time."""
    lo, hi = held
    n_held = hi - lo
    group = EXPERT_GROUP if n_held % EXPERT_GROUP == 0 else 1
    ids = lo + jnp.arange(n_held)
    # (T, n_held): the token's weight for each held expert
    per_expert = jnp.einsum(
        "tk,tke->te", weights,
        (choices[:, :, None] == ids[None, None, :]).astype(jnp.float32),
    )

    def body(i, acc):
        take = lambda a: _f32(lax.dynamic_slice_in_dim(a, i * group, group))  # noqa: E731
        g = jnp.einsum("td,edf->etf", h, take(experts["w_gate"]))
        u = jnp.einsum("td,edf->etf", h, take(experts["w_up"]))
        y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, take(experts["w_down"]))
        w = lax.dynamic_slice_in_dim(per_expert, i * group, group, axis=1)
        return acc + jnp.einsum("etd,te->td", y, w)

    return lax.fori_loop(0, n_held // group, body, jnp.zeros_like(h))


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
    held = int(c["num_experts"])
    lo, hi = experts_held if experts_held is not None else c.get(
        "experts_held", (0, held)
    )
    dh, hkv = int(c["head_dim"]), int(c["num_key_value_heads"])
    eps = float(c.get("rms_norm_eps", 1e-6))
    k_top = int(c["num_experts_per_tok"])
    t = tokens.shape[0]
    all_scores, all_choices = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for i, layer in enumerate(params["layers"]):
            kind = c["layer_types"][i]
            h = _rms_norm(x, layer["ln1"], eps)
            rope = c["rope_parameters"][kind]
            q = _rope((h @ _f32(layer["wq"])).reshape(t, -1, dh), rope)
            k = _rope((h @ _f32(layer["wk"])).reshape(t, hkv, dh), rope)
            v = (h @ _f32(layer["wv"])).reshape(t, hkv, dh)
            window = (
                int(c["sliding_window"]) if kind == "sliding_attention" else None
            )
            a = _attention(q, k, v, window)
            gate = jax.nn.sigmoid(h @ _f32(layer["wg"]))  # (T, H)
            x = x + (a * gate[:, :, None]).reshape(t, -1) @ _f32(layer["wo"])
            h = _rms_norm(x, layer["ln2"], eps)
            if c["mlp_layer_types"][i] == "dense":
                x = x + _gated(layer["mlp"], h)
                continue
            scores = jax.nn.softmax(h @ _f32(layer["router"]), axis=-1)
            _, own = lax.top_k(scores, k_top)
            used = own if choices is None else choices[len(all_choices)]
            w = jnp.take_along_axis(scores, used, axis=-1)
            if c.get("norm_topk_prob", True):
                w = w / w.sum(axis=-1, keepdims=True)
            w = w * float(c["moe_routed_scaling_factor"])
            x = x + routed_experts(layer["experts"], h, used, w, (lo, hi))
            x = x + _gated(layer["shared"], h)
            all_scores.append(scores)
            all_choices.append(own)
        x = _rms_norm(x[logits_from:], params["ln_f"], eps)
        logits = _head(params, x)
    return {
        "logits": logits,
        "scores": jnp.stack(all_scores),
        "choices": jnp.stack(all_choices).astype(jnp.int32),
    }
