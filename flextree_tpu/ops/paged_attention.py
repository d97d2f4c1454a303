"""Fused paged-attention decode: stream K/V blocks, never gather the row.

The serving decode round (``serving/kv_cache.py``) historically ran
gather → ragged decode → scatter: every step materialized each slot's
block table into a contiguous ``(S, P·bs, H, Dh)`` K/V view (~5 MB of
copies per round at the bench config — named in docs/SERVING.md as the
single biggest paged overhead), spliced the new token's K/V into it, and
only then ran attention over the full padded width.  This module removes
the materialization: attention walks the block table directly with an
online-softmax accumulator (the same running max / normalizer scheme as
``ops/pallas_attention._kv_update`` and the ring-attention fold), reading
each K/V block from the pool exactly once and stopping at the batch's
causal frontier — blocks past ``max(lengths)`` are never touched, where
the gather path always paid for the full table width.

Two implementations, one contract:

- ``impl="jnp"`` — a pure-JAX block-streaming twin: a ``fori_loop`` whose
  trip count is the *runtime* block frontier walks ``block_chunk`` table
  columns per step, batched over all S slots.  This is the production
  path on the CPU backend.  ``block_chunk=1`` measured fastest there
  (1.5x over the gather round at the bench config's mid-run lengths —
  wider chunks gather more masked positions back in and lost the win);
  the knob exists because the trade flips on hardware where fewer,
  larger contractions beat tighter masking.
- ``impl="pallas"`` — a Pallas kernel, one grid step per slot, same
  accumulation order.  It runs under the Pallas interpreter on the CPU
  only, where it validates the kernel's numerics.  It CANNOT lower for a
  TPU as written (see :func:`require_runnable`), so on a TPU it is
  refused with one sentence; rewriting it is ROADMAP S6.

``paged_attention_gather`` is the retained gather-materialize oracle —
the exact computation the historical decode step ran, and the thing
proven **bitwise** against the contiguous-cache ``generate``.  The fused
paths change only floating-point summation order (online softmax folds
block by block; the oracle reduces the whole row at once), so they are
gated against the oracle within a pinned tolerance
(``FUSED_DECODE_ATOL`` — pinned in ``tests/test_paged_attention.py``),
not bitwise.

Masking mirrors ``models.generate.cached_attention``: pool positions at
or past a row's ``length`` are driven to ``-1e30`` *before* the running
max and their probabilities zeroed after it, so whatever an unwritten or
null-block position holds — including deliberately poisoned values —
contributes exactly ``0.0`` to the f32 accumulator.  The new token's K/V
(position ``length``, which the gather path spliced into the view) is
folded as a final always-visible online-softmax step instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..utils.backend import pallas_interpret

__all__ = [
    "FUSED_DECODE_ATOL",
    "paged_attention",
    "paged_attention_gather",
    "require_runnable",
]

_NEG_INF = -1e30

#: Pinned fused-vs-gather tolerance on the attention output (f32 compute):
#: the two paths differ only in summation order, and the observed gap on
#: the bench config is ~1e-7; the pin leaves two orders of headroom while
#: still catching any real masking/indexing defect (which shows up as
#: O(1) differences, not O(1e-5)).
FUSED_DECODE_ATOL = 2e-5


def _check_shapes(q, k_new, v_new, k_pool, v_pool, tables, lengths):
    if q.ndim != 3:
        raise ValueError(f"expected (S, H, D) queries, got {q.shape}")
    if k_pool.ndim != 4 or k_pool.shape != v_pool.shape:
        raise ValueError(
            f"expected matching (N, bs, Hkv, D) pools, got {k_pool.shape} "
            f"vs {v_pool.shape}"
        )
    new = (q.shape[0], *k_pool.shape[2:])
    if k_new.shape != new or v_new.shape != new:
        raise ValueError(
            f"new-token K/V must be shaped {new} like the pool's rows, got "
            f"{k_new.shape} / {v_new.shape}"
        )
    if k_pool.shape[3] != q.shape[2] or q.shape[1] % k_pool.shape[2]:
        raise ValueError(
            f"pool head/dim {k_pool.shape[2:]} does not group query "
            f"{q.shape[1:]}"
        )
    if tables.ndim != 2 or tables.shape[0] != q.shape[0]:
        raise ValueError(f"expected (S, P) tables, got {tables.shape}")
    if lengths.shape != (q.shape[0],):
        raise ValueError(f"expected (S,) lengths, got {lengths.shape}")


def paged_attention_gather(q, k_new, v_new, k_pool, v_pool, tables, lengths,
                           window: int | None = None):
    """The gather-materialize oracle: gather every table block into a
    contiguous ``(S, P·bs, H, D)`` view, splice the new token's K/V at
    each row's ``length``, and attend with the full-row softmax — exactly
    the historical decode-step computation (``cached_attention`` on the
    gathered view), kept as THE correctness reference: this path is the
    one proven bitwise against the contiguous-cache ``generate``.
    ``window`` is ``cached_attention``'s mask over the same whole view."""
    from ..models.generate import cached_attention

    _check_shapes(q, k_new, v_new, k_pool, v_pool, tables, lengths)
    s = q.shape[0]
    upd = jax.vmap(
        lambda c, u, p: lax.dynamic_update_slice_in_dim(c, u, p, axis=0)
    )
    kc = upd(k_pool[tables].reshape(s, -1, *k_pool.shape[2:]),
             k_new[:, None], lengths)
    vc = upd(v_pool[tables].reshape(s, -1, *v_pool.shape[2:]),
             v_new[:, None], lengths)
    positions = lengths[:, None].astype(jnp.int32)
    return cached_attention(q[:, None], kc, vc, positions, window=window)[:, 0]


# ------------------------------------------------------------ jnp streaming


def _stream_jnp(q, k_new, v_new, k_pool, v_pool, tables, lengths, scale,
                block_chunk, window=None):
    s, h, d = q.shape
    bs, hkv = k_pool.shape[1], k_pool.shape[2]
    p = tables.shape[1]
    cb = max(1, min(int(block_chunk), p))
    if hkv == h:
        def scores(kb):  # (S, B, H, D) keys -> (S, H, B)
            return jnp.einsum("shd,sbhd->shb", q, kb)

        def mix(pr, vb):  # (S, H, B) weights over (S, B, H, D) values
            return jnp.einsum("shb,sbhd->shd", pr, vb.astype(jnp.float32))

        k_mine, v_mine = k_new, v_new
    else:
        # grouped queries: query head j reads K/V head j // (H // Hkv)
        g = h // hkv
        qg = q.reshape(s, hkv, g, d)

        def scores(kb):  # f32 out of the product, as cached_attention's
            return jnp.einsum(
                "skgd,sbkd->skgb", qg, kb,
                preferred_element_type=jnp.float32,
            ).reshape(s, h, -1)

        def mix(pr, vb):
            return jnp.einsum(
                "skgb,sbkd->skgd", pr.reshape(s, hkv, g, -1),
                vb.astype(jnp.float32),
            ).reshape(s, h, d)

        k_mine = jnp.repeat(k_new, g, axis=1)
        v_mine = jnp.repeat(v_new, g, axis=1)
    if window is None:
        pos0 = None  # every row's walk starts at position 0
        reach = lengths
    else:
        # a window layer walks only the table columns that meet its
        # window: a per-row slice of the table from the block that holds
        # position length - window + 1, wide enough for any alignment
        first = jnp.maximum(lengths - (window - 1), 0) // bs  # (S,)
        p = min(p, (window - 1 + bs - 1) // bs + 1)
        cols = first[:, None] + jnp.arange(p)[None, :]
        tables = jnp.where(
            cols < tables.shape[1],
            jnp.take_along_axis(
                tables, jnp.minimum(cols, tables.shape[1] - 1), axis=1
            ),
            0,
        )
        pos0 = (first * bs)[:, None]  # (S, 1)
        reach = lengths - first * bs
    # pad the table width to a chunk multiple with null blocks: the pad
    # columns gather block 0, whose positions sit past every row's causal
    # bound and mask to exactly zero weight
    p_pad = -(-p // cb) * cb
    if p_pad != p:
        tables = jnp.pad(tables, ((0, 0), (0, p_pad - p)))
    # runtime frontier: blocks holding positions < max(lengths); the loop
    # never touches table columns past it (the gather oracle always pays
    # for all P — this bound is the streamed path's algorithmic win)
    frontier = (jnp.max(reach) + bs - 1) // bs
    n_steps = (frontier + cb - 1) // cb

    lengths_b = lengths[:, None]  # (S, 1)
    m0 = jnp.full((s, h), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((s, h), jnp.float32)
    acc0 = jnp.zeros((s, h, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        tb = lax.dynamic_slice_in_dim(tables, i * cb, cb, axis=1)  # (S, cb)
        kb = k_pool[tb].reshape(s, cb * bs, hkv, d)
        vb = v_pool[tb].reshape(s, cb * bs, hkv, d)
        # einsum in the compute dtype then f32, mirroring cached_attention
        sc = scores(kb).astype(jnp.float32) * scale
        kpos = i * cb * bs + jnp.arange(cb * bs)
        if pos0 is None:
            valid = kpos[None, :] < lengths_b  # (S, cb*bs)
        else:
            kpos = pos0 + kpos[None, :]
            valid = (kpos < lengths_b) & (kpos > lengths_b - window)
        sc = jnp.where(valid[:, None, :], sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        pr = jnp.exp(sc - m_new[..., None])
        # explicit zero: when a row's m is still the -1e30 sentinel (no
        # visible position yet) exp(0)=1 would leak masked content
        pr = jnp.where(valid[:, None, :], pr, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + pr.sum(axis=-1)
        acc = acc * corr[..., None] + mix(pr, vb)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(0, n_steps, body, (m0, l0, acc0))

    # the new token's K/V — position `length`, always visible to itself
    s_new = jnp.einsum(
        "shd,shd->sh", q, k_mine,
        preferred_element_type=None if hkv == h else jnp.float32,
    ).astype(jnp.float32) * scale
    m_fin = jnp.maximum(m, s_new)
    p_new = jnp.exp(s_new - m_fin)
    corr = jnp.exp(m - m_fin)
    l = l * corr + p_new
    acc = acc * corr[..., None] + p_new[..., None] * v_mine.astype(jnp.float32)
    return (acc / l[..., None]).astype(q.dtype)


# ------------------------------------------------------------ pallas kernel


def _paged_kernel(q_ref, kn_ref, vn_ref, tab_ref, len_ref, kp_ref, vp_ref,
                  o_ref, *, bs: int, scale: float):
    """One grid step = one slot: walk the row's block table with the
    online-softmax accumulator, then fold the new token's K/V.  Same
    accumulation order as ``_stream_jnp`` at ``block_chunk=1``."""
    q = q_ref[0]  # (H, D) native dtype — the score matmul stays native
    h, d = q.shape
    length = len_ref[0]
    nb = (length + bs - 1) // bs  # blocks holding positions < length

    m0 = jnp.full((h, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = jnp.zeros((h, d), jnp.float32)

    def body(p_i, carry):
        m, l, acc = carry
        blk = tab_ref[0, p_i]
        kb = kp_ref[blk]  # (bs, H, D)
        vb = vp_ref[blk]
        sc = jnp.einsum("hd,bhd->hb", q, kb).astype(jnp.float32) * scale
        kpos = p_i * bs + jnp.arange(bs)
        valid = (kpos < length)[None, :]  # (1, bs)
        sc = jnp.where(valid, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        pr = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + pr.sum(axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "hb,bhd->hd", pr, vb.astype(jnp.float32)
        )
        return m_new, l, acc

    m, l, acc = lax.fori_loop(0, nb, body, (m0, l0, acc0))

    kn = kn_ref[0]
    vn = vn_ref[0]
    s_new = jnp.einsum("hd,hd->h", q, kn)[:, None].astype(jnp.float32) * scale
    m_fin = jnp.maximum(m, s_new)
    p_new = jnp.exp(s_new - m_fin)
    corr = jnp.exp(m - m_fin)
    l = l * corr + p_new
    acc = acc * corr + p_new * vn.astype(jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def require_runnable(impl: str, interpret: bool | None = None) -> None:
    """Raise unless ``impl`` can run where this process runs.

    Mosaic refuses the ``"pallas"`` kernel at lowering, whatever the
    shape: the ``(1, P)`` block on the ``(S, P)`` int32 table breaks the
    (8, 128) tiling rule, the whole K/V pool is one VMEM block, and the
    table and lengths are read as scalars out of VMEM instead of SMEM.
    Saying so here, once, beats a lowering dump from inside the first
    decode round; nothing swaps in the ``jnp`` path on the caller's
    behalf.
    """
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    if impl == "pallas" and not pallas_interpret(interpret):
        raise NotImplementedError(
            "paged attention impl='pallas' cannot lower for TPU (its block "
            "shapes break Mosaic's (8, 128) rule and it holds the whole K/V "
            "pool in VMEM); use impl='jnp' on a TPU."
        )


def _stream_pallas(q, k_new, v_new, k_pool, v_pool, tables, lengths, scale,
                   interpret):
    s, h, d = q.shape
    n, bs = k_pool.shape[:2]
    p = tables.shape[1]
    return pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, scale=scale),
        out_shape=jax.ShapeDtypeStruct((s, h, d), q.dtype),
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),   # q row
            pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),   # new k
            pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),   # new v
            pl.BlockSpec((1, p), lambda i: (i, 0)),         # table row
            pl.BlockSpec((1,), lambda i: (i,)),             # length
            pl.BlockSpec((n, bs, h, d), lambda i: (0, 0, 0, 0)),  # k pool
            pl.BlockSpec((n, bs, h, d), lambda i: (0, 0, 0, 0)),  # v pool
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(q, k_new, v_new, tables.astype(jnp.int32), lengths.astype(jnp.int32),
      k_pool, v_pool)


def paged_attention(
    q,
    k_new,
    v_new,
    k_pool,
    v_pool,
    tables,
    lengths,
    *,
    scale: float | None = None,
    impl: str = "jnp",
    interpret: bool | None = None,
    block_chunk: int = 1,
    window: int | None = None,
):
    """Fused paged decode attention for one token per slot.

    ``q``: (S, H, D) — the decode step's query; ``k_new`` / ``v_new``:
    (S, Hkv, D) the new token's K/V, all already RoPE'd at each row's
    position.  ``H`` is a multiple of ``Hkv`` (grouped queries: query
    head ``j`` reads K/V head ``j // (H // Hkv)``; the dense model has
    ``Hkv == H``).  ``window``: each row sees only positions ``length -
    window + 1 .. length``, and walks only the table columns that hold
    them (``impl="jnp"``).
    ``k_pool`` / ``v_pool``: (N, bs, Hkv, D) per-layer pools; ``tables``:
    (S, P) int32 block ids; ``lengths``: (S,) int32 cache positions
    already written per row, each ``< P*bs`` (a row AT the table's
    capacity has no position left to decode into — the serving layer
    never reaches it, and the gather oracle's splice clamps there).
    Returns (S, H, D) in ``q``'s dtype —
    attention over pool positions ``< length`` plus the new token at
    position ``length``, equal to :func:`paged_attention_gather` within
    :data:`FUSED_DECODE_ATOL` (summation order is the only difference).

    ``impl="jnp"`` is the batched block-streaming path (``block_chunk``
    table columns per loop step); ``impl="pallas"`` runs the kernel under
    the interpreter on the CPU and is refused on a TPU
    (:func:`require_runnable`).
    """
    require_runnable(impl, interpret)
    _check_shapes(q, k_new, v_new, k_pool, v_pool, tables, lengths)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if impl == "jnp":
        return _stream_jnp(q, k_new, v_new, k_pool, v_pool, tables, lengths,
                           float(scale), block_chunk, window)
    if window is not None or k_pool.shape[2] != q.shape[1]:
        raise NotImplementedError(
            "paged attention impl='pallas' has neither grouped queries nor "
            "a window; use impl='jnp'"
        )
    return _stream_pallas(q, k_new, v_new, k_pool, v_pool, tables, lengths,
                          float(scale), pallas_interpret(interpret))
