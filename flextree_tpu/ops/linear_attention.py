"""Delta-rule linear attention with a per-channel decay (the KDA layer's
core): the recurrence a sequence carries from token to token in ONE
state ``S`` of ``(d_k, d_v)`` a head, whatever its length.

For a token's ``q, k`` (d_k), ``v`` (d_v), log decay ``g <= 0`` (d_k: one
a CHANNEL of the key) and write strength ``beta``, in float32::

    S <- Diag(exp(g)) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

:func:`delta_rule_step` is that, one token a slot, for the decode round:
the state read twice and written once, no product on the MXU.

:func:`delta_rule_chunked` is the same recurrence over a whole prompt in
chunks of ``chunk`` tokens (the WY form): with ``G`` the running sum of
``g`` inside a chunk and ``S_0`` the state the chunk starts from,

- ``A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])`` for ``i < t``,
  ``B[t, i]`` the same with ``q_t`` for ``i <= t``;
- ``T = (I + Diag(beta) A)^-1`` (unit lower triangular: a product of
  ``log2(chunk)`` factors ``I + M^(2^j)``, ``M = -Diag(beta) A``, all of
  them matrix products);
- ``u = T (beta v) - T (beta k exp(G)) S_0``; ``o = (q exp(G)) S_0 + B u``;
  ``S_end = Diag(exp(G_end)) S_0 + (k exp(G_end - G))^T u``.

Everything but the dependence on ``S_0`` is computed for all chunks at
once; one ``lax.scan`` over the chunks carries the state.  Every product
is taken at ``HIGHEST`` precision: on a TPU a float32 product otherwise
rounds its operands to bfloat16, and ``T`` amplifies that where a chunk's
keys lie close together (seeded weights collapse deep layers' keys to a
cosine of 0.9: one layer's output then moved by 2%, ten layers' logits by
a tenth of the largest; PERF.md section 6, PR 34).  They are 3% of a
prompt's FLOPs.

**The decay is a channel's, so ``exp(G_t - G_i)`` does not factor safely.**
``(k_t exp(G_t)) . (k_i exp(-G_i))`` overflows float32 once a chunk's
decay passes ``exp(88)``: at ``g = -5`` a token after 18 tokens.  Here no
exponent is ever positive: inside a sub-chunk of ``sub`` tokens the
decays ``exp(G_t - G_i)`` are taken pair by pair (elementwise, ``i <=
t``); between sub-chunks both factors are taken against the END of the
sub-chunk before the row's (``exp(G_t - ref) <= 1`` for the row, ``exp(ref
- G_i) <= 1`` for a column of an earlier sub-chunk).  A strong decay
underflows to the zero it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["delta_rule_step", "delta_rule_chunked", "causal_conv"]

_HIGHEST = lax.Precision.HIGHEST


def delta_rule_step(q, k, v, g, beta, state, active=None):
    """One token a slot.  ``q``, ``k``, ``g`` (S, H, d_k), ``v`` (S, H,
    d_v), ``beta`` (S, H), ``state`` (S, H, d_k, d_v), all float32;
    ``active`` (S,) bool: a slot that is not keeps its state bit for bit.
    Returns ``(o, state)``: (S, H, d_v) and the updated state."""
    s1 = state * jnp.exp(g)[..., None]
    read = (s1 * k[..., None]).sum(axis=-2)
    seen = (s1 * q[..., None]).sum(axis=-2)
    u = (v - read) * beta[..., None]
    o = seen + (k * q).sum(axis=-1, keepdims=True) * u
    s2 = s1 + k[..., None] * u[..., None, :]
    if active is not None:
        s2 = jnp.where(active[:, None, None, None], s2, state)
    return o, s2


def _unit_lower_inverse(m):
    """``(I - m)^-1`` for strictly lower triangular ``m`` (..., C, C):
    ``m`` is nilpotent, so the Neumann series ends, and it is the product
    of ``I + m^(2^j)`` for ``2^j < C``."""
    c = m.shape[-1]
    eye = jnp.eye(c, dtype=m.dtype)
    inv, power = eye + m, m
    span = 2
    while span < c:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = inv + jnp.matmul(inv, power, precision=_HIGHEST)
        span *= 2
    return inv


@functools.partial(jax.jit, static_argnames=("chunk", "sub"))
def delta_rule_chunked(q, k, v, g, beta, state, *, chunk: int = 64,
                       sub: int = 16):
    """The recurrence over ``T`` tokens from ``state``.  ``q``, ``k``,
    ``g`` (B, T, H, d_k), ``v`` (B, T, H, d_v), ``beta`` (B, T, H),
    ``state`` (B, H, d_k, d_v) float32.  ``T`` need be no multiple of
    ``chunk`` (the tail is padded with tokens that neither decay nor
    write); ``sub`` divides ``chunk``.  Returns ``(o, state)``: (B, T, H,
    d_v) float32 and the state after the last token."""
    if chunk % sub:
        raise ValueError(f"sub-chunk {sub} does not divide chunk {chunk}")
    f32 = jnp.float32
    b, t, h, _ = k.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n, m = (t + pad) // chunk, chunk // sub

    def lay(x):  # (B, T, H, .) -> (B, H, n, C, .), padded with zeros
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, n, chunk, h, *x.shape[3:]), 3, 1)

    q, k, v, g, beta = lay(q), lay(k), lay(v), lay(g), lay(beta)
    run = jnp.cumsum(g, axis=-2)  # G: (B, H, n, C, dk), <= 0
    subs = lambda x: x.reshape(b, h, n, m, sub, x.shape[-1])  # noqa: E731
    run_s, k_s, q_s = subs(run), subs(k), subs(q)

    # inside a sub-chunk, pair by pair: exp(G_t - G_i), i <= t
    pair = jnp.exp(jnp.minimum(
        run_s[..., :, None, :] - run_s[..., None, :, :], 0.0
    ))  # (B, H, n, m, sub t, sub i, dk)
    k_pair = k_s[..., None, :, :] * pair
    a_diag = (k_s[..., :, None, :] * k_pair).sum(axis=-1)
    b_diag = (q_s[..., :, None, :] * k_pair).sum(axis=-1)

    # between sub-chunks, both sides against the end of the one before the
    # row's: ref[a] = G at the last token of sub-chunk a - 1 (0 for a = 0)
    ref = jnp.concatenate(
        [jnp.zeros_like(run_s[..., :1, -1, :]), run_s[..., :-1, -1, :]], axis=-2
    )  # (B, H, n, m, dk)
    rows = jnp.exp(run_s - ref[..., None, :])  # (B, H, n, m, sub, dk)
    cols = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., :, None, :] - run[..., None, :, :], 0.0
    ))  # (B, H, n, m a, C i, dk): column i as sub-chunk a's rows see it
    a_off = jnp.einsum("...sd,...id->...si", k_s * rows, cols, precision=_HIGHEST)
    b_off = jnp.einsum("...sd,...id->...si", q_s * rows, cols, precision=_HIGHEST)

    def whole(off, diag, low):
        """(…, C, C) from the blocks: ``off`` (…, m, sub, C) where the
        column's sub-chunk comes before the row's, ``diag`` (…, m, sub,
        sub) on the block diagonal where ``low`` (sub, sub) keeps it."""
        block = jnp.arange(chunk) // sub
        before = block[None, :] < block[:, None]
        out = jnp.where(before, off.reshape(*off.shape[:-3], chunk, chunk), 0.0)
        placed = (
            jnp.where(low, diag, 0.0)[..., :, :, None, :]
            * jnp.eye(m, dtype=f32)[:, None, :, None]
        )  # (…, m, sub, m, sub)
        return out + placed.reshape(out.shape)

    i = jnp.arange(sub)
    below, upto = i[None, :] < i[:, None], i[None, :] <= i[:, None]
    a = whole(a_off, a_diag, below)
    inv = _unit_lower_inverse(-beta[..., :, None] * a)  # T
    decay = jnp.exp(run)
    u_v = jnp.matmul(inv, beta[..., None] * v, precision=_HIGHEST)
    u_k = jnp.matmul(inv, beta[..., None] * k * decay, precision=_HIGHEST)
    reach = whole(b_off, b_diag, upto)  # B
    end = run[..., -1:, :]  # G at the chunk's last token
    k_end = k * jnp.exp(end - run)

    def step(s, x):
        u_v, u_k, q_in, reach, k_end, d_end = x
        u = u_v - jnp.matmul(u_k, s, precision=_HIGHEST)
        o = jnp.matmul(q_in, s, precision=_HIGHEST) + jnp.matmul(
            reach, u, precision=_HIGHEST
        )
        s = d_end[..., None] * s + jnp.einsum(
            "bhck,bhcv->bhkv", k_end, u, precision=_HIGHEST
        )
        return s, o

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    state, o = lax.scan(step, state.astype(f32), (
        by_chunk(u_v), by_chunk(u_k), by_chunk(q * decay), by_chunk(reach),
        by_chunk(k_end), by_chunk(jnp.exp(end[..., 0, :])),
    ))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], state


def causal_conv(x, w, tail=None):
    """A causal depthwise convolution over the last ``K`` positions:
    ``y_t[c] = sum_j w[j, c] x_(t - K + 1 + j)[c]``.  ``x`` (B, T, C),
    ``w`` (K, C), ``tail`` (B, K - 1, C) the inputs before position 0
    (zeros when None).  Returns ``(y, tail)``: (B, T, C) summed in float32,
    and the last ``K - 1`` inputs in ``x``'s dtype, what the next token's
    convolution needs."""
    taps = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), x.dtype)
    full = jnp.concatenate([tail, x], axis=1)
    t = x.shape[1]
    f32 = jnp.float32
    y = sum(full[:, j : j + t].astype(f32) * w[j].astype(f32) for j in range(taps))
    return y, full[:, t:]
