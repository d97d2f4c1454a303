"""The plain reference: a dense decoder-only LM in straightforward float32
``jax.numpy``.  No kernel, no cache, no batching trick, and nothing
imported from ``flextree_tpu``.

It follows the PROGRAM's block at the published widths, not GPT-NeoX's
(see ``departures`` in ``benchmarks/configs/*.json``): pre-norm RMSNorm
(eps 1e-6, no bias), a sequential residual (attention, then the MLP on
its result), rotary embedding over the whole head dimension in the
half-split layout, bias-free linear layers, the tanh approximation of
GELU, and the embedding matrix reused as the output head.  The
``model_config`` PR that teaches the program the NeoX block changes this
file's equations with it.

Every matmul runs under ``jax.default_matmul_precision("highest")``: on
a TPU a float32 matmul otherwise runs in bf16 passes.  Callers jit these
functions; the precision context is entered inside, at trace time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["hidden_states", "logits_at", "forward_logits", "token_loss"]

RMS_EPS = 1e-6


def _rms_norm(x, scale):
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)
    return x / rms * scale


def _rope(x, theta):
    """Rotary embedding on (T, H, D): the first half of the head
    dimension paired with the second."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Full causal softmax attention on (T, H, D)."""
    t, _, d = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def hidden_states(params, tokens, n_heads: int, rope_theta: float = 10000.0):
    """Final hidden states (T, d), before the last norm, of ONE sequence
    ``tokens`` (T,) int32.  ``params`` is the program's parameter tree
    (``embed``, ``ln_f``, ``layers[i]`` with ``ln1 wq wk wv wo ln2 w1
    w2``), taken as float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        x = f32(params["embed"])[tokens]
        t = tokens.shape[0]
        for layer in params["layers"]:
            h = _rms_norm(x, f32(layer["ln1"]))
            q = (h @ f32(layer["wq"])).reshape(t, n_heads, -1)
            k = (h @ f32(layer["wk"])).reshape(t, n_heads, -1)
            v = (h @ f32(layer["wv"])).reshape(t, n_heads, -1)
            a = _attention(_rope(q, rope_theta), _rope(k, rope_theta), v)
            x = x + a.reshape(t, -1) @ f32(layer["wo"])
            h = _rms_norm(x, f32(layer["ln2"]))
            u = jax.nn.gelu(h @ f32(layer["w1"]), approximate=True)
            x = x + u @ f32(layer["w2"])
        return x


def logits_at(params, hidden):
    """Output-head logits for the given rows of hidden states."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(hidden, jnp.asarray(params["ln_f"], jnp.float32))
        return x @ jnp.asarray(params["embed"], jnp.float32).T


def forward_logits(params, tokens, n_heads: int, rope_theta: float = 10000.0):
    """(T, vocab) float32 logits of one sequence."""
    return logits_at(params, hidden_states(params, tokens, n_heads, rope_theta))


def token_loss(logits, targets):
    """Mean next-token cross entropy of (T, vocab) logits."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
