"""Delta-rule linear attention with a per-channel decay (the KDA layer's
core): the recurrence a sequence carries from token to token in ONE
state ``S`` of ``(d_k, d_v)`` a head, whatever its length.

For a token's ``q, k`` (d_k), ``v`` (d_v), log decay ``g <= 0`` (d_k: one
a CHANNEL of the key) and write strength ``beta``, in float32::

    S <- Diag(exp(g)) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

:func:`delta_rule_step` is that, one token a slot, for the decode round,
no product on the MXU.  ``u`` needs the whole reduction ``S^T k`` over
``d_k`` before any element of the state can change, so the least is one
read and one write of the state by a program that holds a head's tile on
chip meanwhile: the Pallas kernel ``kda_state_update``, which a TPU runs
at shapes its tiling admits (:func:`runs_step_kernel`), in place in the
donated state.  The ``jnp`` body (the CPU backend, narrower heads) is the
same arithmetic as XLA fuses it: on a TPU two fusions, the state read
twice and written once (PERF.md section 6, PR 35).

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

**A decay a HEAD** (the Gated DeltaNet layer's: ``g`` of ``(..., H)`` or
``(..., H, 1)`` where the channel form's is ``(..., H, d_k)``; the rank
says which, no flag) is the same recurrence with one number for all of a
key's channels, and keys and values of any two widths.  Then ``exp(G_t -
G_i)`` is ONE ``(chunk, chunk)`` matrix a chunk, taken whole with no
exponent positive, and ``A = (K K^T) * exp(G_t - G_i)`` one product: the
pair-by-pair tensor of ``(sub, sub, d_k)`` a sub-chunk, ``d_k`` times the
size, is never built (30 heads of 96 over 16,384 tokens: 0.13 GB where
the channel form takes 3.02).  The one-token update broadcasts the decay
over ``d_k``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import backend

__all__ = [
    "delta_rule_step", "delta_rule_chunked", "causal_conv",
    "step_kernel_admits", "runs_step_kernel",
]

_HIGHEST = lax.Precision.HIGHEST

# heads of one slot a grid step of the update's kernel: the most whose
# state block stays at or under this many bytes (in and out, each
# double-buffered: four such blocks in VMEM).  On the v5e, 32 heads of 128
# x 128: 8 a step 1.01 ms a layer (the 0.35 us a step shows), 16 and 32
# alike 0.92 (576 GB/s, what a DMA-only copy with the same blocks reads:
# PERF.md section 6, PR 35), so the smaller of the two
_STEP_BLOCK_BYTES = 1 << 20


def step_kernel_admits(state) -> bool:
    """Whether Mosaic takes the update's kernel at this state's shape
    (S, H, d_k, d_v): float32, a head's state whole 128-lane tiles both
    ways (``d_v`` lies on the lanes; the ``(heads, d_k)`` row blocks of
    ``q``, ``k`` and the decay are transposed in the kernel), and a head
    count whose groups are whole sublane tiles."""
    _, h, dk, dv = state.shape
    return (
        state.dtype == jnp.float32 and dk % 128 == 0 and dv % 128 == 0
        and h % 8 == 0
    )


def runs_step_kernel(state) -> bool:
    """Whether :func:`delta_rule_step` runs the Pallas kernel on
    ``state`` in this process: a TPU to lower for, and a shape the
    kernel's tiling admits."""
    return backend.kernel_platform() == "tpu" and step_kernel_admits(state)


def _heads_a_step(h: int, dk: int, dv: int) -> int:
    """Heads a grid step holds: the most (a multiple of 8 that divides
    ``h``) whose float32 block fits :data:`_STEP_BLOCK_BYTES`."""
    fits = [
        hb for hb in range(8, h + 1, 8)
        if h % hb == 0 and hb * dk * dv * 4 <= _STEP_BLOCK_BYTES
    ]
    return max(fits, default=8)


def _step_kernel(act_ref, q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref,
                 o_ref, s_out_ref):
    """Grid (slot, group of heads).  A step holds the group's state, ``hb``
    tiles of (d_k, d_v) with ``d_v`` on lanes, brought in once by the
    pipeline and written back once to where it came from.  Both
    reductions run over ``d_k``, the sublanes, so ``k``, ``q`` and the
    decay are needed as COLUMNS: the three (hb, d_k) row blocks are
    transposed here, once a step."""
    keep = act_ref[pl.program_id(0)] != 0
    q, k, v = q_ref[0], k_ref[0], v_ref[0]  # (hb, d_k) x2, (hb, d_v)
    beta = beta_ref[0]  # (hb, 1)
    q_cols, k_cols, decay = q.T, k.T, jnp.exp(g_ref[0]).T  # (d_k, hb)
    kq = (k * q).sum(axis=-1, keepdims=True)  # (hb, 1)
    for h in range(q.shape[0]):
        s = s_ref[0, h]  # (d_k, d_v)
        k_col = k_cols[:, h : h + 1]
        s1 = s * decay[:, h : h + 1]
        read = (s1 * k_col).sum(axis=0, keepdims=True)  # (1, d_v)
        seen = (s1 * q_cols[:, h : h + 1]).sum(axis=0, keepdims=True)
        u = (v[h : h + 1] - read) * beta[h : h + 1]
        o_ref[0, h : h + 1] = seen + kq[h : h + 1] * u
        s_out_ref[0, h] = jnp.where(keep, s1 + k_col * u, s)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _step_pallas(q, k, v, g, beta, state, active, *, heads=None,
                 interpret=False):
    s, h, dk, dv = state.shape
    hb = heads or _heads_a_step(h, dk, dv)
    q, k, g, v, beta = (
        x.astype(jnp.float32) for x in (q, k, g, v, beta[..., None])
    )
    row = lambda w: pl.BlockSpec((1, hb, w), lambda i, j, *_: (i, j, 0))  # noqa: E731
    tile = pl.BlockSpec((1, hb, dk, dv), lambda i, j, *_: (i, j, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((s, h, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, h // hb),
            in_specs=[row(dk), row(dk), row(dk), row(dv), row(1), tile],
            out_specs=(row(dv), tile),
        ),
        # the state is updated where it lies: operand 6 (the prefetched
        # ``active`` counts) is result 1
        input_output_aliases={6: 1},
        # four blocks in the pipeline, and room for the unrolled loop's
        # temporaries where one head's state outgrows the block limit
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=6 * hb * dk * dv * 4 + (8 << 20),
        ),
        name="kda_state_update",
        interpret=interpret,
    )(active.astype(jnp.int32), q, k, g, v, beta, state)


def _decay_with_its_axis(g, k):
    """The log decay ``g`` with a last axis: of 1 where it is one number a
    HEAD (its shape ``k``'s less the last axis, or with a last axis of 1),
    of ``d_k`` where it is one a channel of the key (``k``'s own shape)."""
    if g.ndim == k.ndim - 1:
        g = g[..., None]
    if g.ndim != k.ndim or g.shape[-1] not in (1, k.shape[-1]):
        raise ValueError(
            f"log decay of shape {g.shape} is neither a head's nor a "
            f"channel's for keys of shape {k.shape}"
        )
    return g


def _step_jnp(q, k, v, g, beta, state, active):
    s1 = state * jnp.exp(g)[..., None]
    read = (s1 * k[..., None]).sum(axis=-2)
    seen = (s1 * q[..., None]).sum(axis=-2)
    u = (v - read) * beta[..., None]
    o = seen + (k * q).sum(axis=-1, keepdims=True) * u
    s2 = s1 + k[..., None] * u[..., None, :]
    s2 = jnp.where(active[:, None, None, None], s2, state)
    return o, s2


def delta_rule_step(q, k, v, g, beta, state, active=None, *,
                    impl: str | None = None):
    """One token a slot.  ``q``, ``k``, ``g`` (S, H, d_k), ``v`` (S, H,
    d_v), ``beta`` (S, H), ``state`` (S, H, d_k, d_v), all float32;
    ``active`` (S,) bool: a slot that is not keeps its state bit for bit
    (None: every slot is).  A decay a head is ``g`` of (S, H) or (S, H,
    1): the same arithmetic with the decay broadcast over ``d_k``.
    Returns ``(o, state)``: (S, H, d_v) and the updated state.

    One algorithm, two lowerings, chosen from what can be observed
    (:func:`runs_step_kernel`): on a TPU at shapes its tiling admits the
    Pallas kernel ``kda_state_update`` (``_step_kernel``), which holds a
    group of heads' state in VMEM and so reads it once and writes it
    once, in place; elsewhere (the CPU backend, heads that are no whole
    lane tiles) the ``jnp`` body, which XLA:TPU makes two fusions of: the
    state read twice.  ``impl`` (``"pallas"`` / ``"jnp"``) forces one, for
    the tests."""
    if impl not in (None, "jnp", "pallas"):
        raise ValueError(f"unknown delta-rule step impl {impl!r}")
    if active is None:
        active = jnp.ones((state.shape[0],), bool)
    g = _decay_with_its_axis(g, k)
    if impl == "pallas" or (impl is None and runs_step_kernel(state)):
        # the kernel reads a channel's: a head's is broadcast
        g = jnp.broadcast_to(g, k.shape)
        return _step_pallas(
            q, k, v, g, beta, state, active,
            interpret=backend.pallas_interpret(),
        )
    return _step_jnp(q, k, v, g, beta, state, active)


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


def _unit_lower_inverse_by_blocks(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., C, C), by
    doubling: the inverse of a diagonal block of ``2 s`` from those of its
    two halves ``P``, ``Q`` and the corner ``E`` between them, ``[[P, 0],
    [-Q E P, Q]]``, for ``s = 1, 2, 4, ...``; every block of a size at
    once, two products a size.  Where :func:`_unit_lower_inverse` sums
    powers of ``a`` (entries that grow like ``beta^j`` times a binomial
    before they cancel: fine while ``beta <= 1`` and keys differ), this
    multiplies only inverses of blocks, whose entries stay the size of the
    answer's: what a write strength up to 2 over close keys needs."""
    c = a.shape[-1]
    idx = jnp.arange(c)
    row, col = idx[:, None], idx[None, :]

    def corner(s):  # the lower-left block of every diagonal block of 2 s
        return jnp.where(
            (row // (2 * s) == col // (2 * s))
            & ((row // s) % 2 == 1) & ((col // s) % 2 == 0), a, 0.0,
        )

    inv = jnp.eye(c, dtype=a.dtype) - corner(1)  # P = Q = 1
    s = 2
    while s < c:
        inv = inv - jnp.matmul(
            jnp.matmul(inv, corner(s), precision=_HIGHEST), inv,
            precision=_HIGHEST,
        )
        s *= 2
    return inv


def _pairs_a_channel(q, k, run, chunk: int, sub: int):
    """``(A, B)`` (..., C, C) of a chunk where the decay is a channel's:
    ``q``, ``k`` and the running log decay ``run`` (B, H, n, C, d_k)."""
    f32 = jnp.float32
    b, h, n = k.shape[:3]
    m = chunk // sub
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
    return whole(a_off, a_diag, below), whole(b_off, b_diag, upto)


def _pairs_a_head(q, k, run):
    """``(A, B)`` (..., C, C) of a chunk where the decay is a head's:
    ``run`` (B, H, n, C, 1), so ``exp(G_t - G_i)`` is one (C, C) matrix a
    chunk (``i <= t``: no exponent positive) and each of A and B one
    product times it."""
    pair = jnp.exp(jnp.minimum(run - jnp.swapaxes(run, -1, -2), 0.0))
    i = jnp.arange(run.shape[-2])
    below, upto = i[None, :] < i[:, None], i[None, :] <= i[:, None]
    kk = jnp.einsum("...td,...id->...ti", k, k, precision=_HIGHEST)
    qk = jnp.einsum("...td,...id->...ti", q, k, precision=_HIGHEST)
    return jnp.where(below, kk * pair, 0.0), jnp.where(upto, qk * pair, 0.0)


@functools.partial(jax.jit, static_argnames=("chunk", "sub"))
def delta_rule_chunked(q, k, v, g, beta, state, *, chunk: int = 64,
                       sub: int = 16):
    """The recurrence over ``T`` tokens from ``state``.  ``q``, ``k``,
    ``g`` (B, T, H, d_k), ``v`` (B, T, H, d_v), ``beta`` (B, T, H),
    ``state`` (B, H, d_k, d_v) float32.  ``T`` need be no multiple of
    ``chunk`` (the tail is padded with tokens that neither decay nor
    write); ``sub`` divides ``chunk``.  A decay a head is ``g`` of (B, T,
    H) or (B, T, H, 1) (``sub`` then plays no part).  Returns ``(o,
    state)``: (B, T, H, d_v) float32 and the state after the last token."""
    if chunk % sub:
        raise ValueError(f"sub-chunk {sub} does not divide chunk {chunk}")
    f32 = jnp.float32
    b, t, h, _ = k.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk
    g = _decay_with_its_axis(g, k)
    a_head = g.shape[-1] != k.shape[-1]

    def lay(x):  # (B, T, H, .) -> (B, H, n, C, .), padded with zeros
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, n, chunk, h, *x.shape[3:]), 3, 1)

    q, k, v, g, beta = lay(q), lay(k), lay(v), lay(g), lay(beta)
    run = jnp.cumsum(g, axis=-2)  # G: (B, H, n, C, dk or 1), <= 0
    a, reach = (
        _pairs_a_head(q, k, run) if a_head
        else _pairs_a_channel(q, k, run, chunk, sub)
    )  # A, B
    # T = (I + Diag(beta) A)^-1
    inv = (
        _unit_lower_inverse_by_blocks(beta[..., :, None] * a) if a_head
        else _unit_lower_inverse(-beta[..., :, None] * a)
    )
    decay = jnp.exp(run)
    u_v = jnp.matmul(inv, beta[..., None] * v, precision=_HIGHEST)
    u_k = jnp.matmul(inv, beta[..., None] * k * decay, precision=_HIGHEST)
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
