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

Two paths, one contract, and :func:`paged_attention` picks between them
from what it can observe (the backend it compiles for, and the shapes):

- the Pallas kernel (``_decode_kernel``), on a TPU at shapes Mosaic's
  tiling admits (:func:`kernel_admits`).  The block tables and lengths
  are scalar-prefetched into SMEM, the pools stay in HBM, and every slot
  walks ITS OWN live blocks (for a window layer those that meet the
  window; none for an empty slot), each brought in once by its own DMA,
  three chunks in flight across slot boundaries.  It reads a pool as the
  device holds it, through a view that is a bitcast: ``(N, bs * Hkv,
  D)``, a block's rows (position, head), where the K/V heads fill a
  sublane tile (a multiple of 8, or 1, 2, 4); where they do not (30)
  XLA:TPU keeps the block-size axis next to the lanes
  (:func:`pool_relayouts`) and the kernel takes
  the pool TRANSPOSED, ``(N, Hkv * bs, D)``, a block's rows (head,
  position), a few whole heads of one block a compute step.  One kernel
  body serves both: only the two tables that say which query heads a row
  serves and where it stands know the order.  On the v5e it reads the
  dense cell's live K/V at 725 GB/s, the hybrid cell's 30-head pools at
  728, and the Laguna cell's at 370 to 470 (PERF.md §6, PR 31, PR 39;
  ROADMAP S1).
- the ``fori_loop`` (``_stream_jnp``), everywhere else: the CPU backend
  (tier 1, the chaos drivers, an RPC replica on the CPU) and shapes the
  kernel refuses.  Its trip count is the *runtime* block frontier of the
  LONGEST slot, ``block_chunk`` table columns a step, batched over all S
  slots.  ``block_chunk=1`` measured fastest on the CPU (1.5x over the
  gather round at the old CPU bench's mid-run lengths — wider chunks
  gather more masked positions back in); that is a statement about the
  CPU and not about the chip, where the sweep at the Laguna cell's
  shapes reads 1.45, 0.95, 0.73, 0.63 ms a layer at 1, 2, 4, 8 (and is
  flat at the dense cell's: 0.94, 0.95, 0.92, 0.96), so on a TPU the
  loop derives its chunk from the shapes.

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
contributes exactly ``0.0`` to the f32 accumulator (the kernel never
brings such a block in at all, and zeroes its buffers once, so that a
masked weight of 0.0 cannot meet a NaN left in VMEM).  The new token's K/V
(position ``length``, which the gather path spliced into the view) is
folded as a final always-visible online-softmax step instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import backend

__all__ = [
    "FUSED_DECODE_ATOL",
    "kernel_admits",
    "paged_attention",
    "paged_attention_gather",
    "paged_attention_latent",
    "paged_attention_latent_gather",
    "latent_kernel_admits",
    "heads_fill_a_tile",
    "runs_kernel",
    "runs_latent_kernel",
    "pool_relayouts",
    "take_blocks",
    "put_blocks",
    "put_rows",
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


def heads_fill_a_tile(hkv: int) -> bool:
    """Whether the v5e tiles a pool's (K/V head, head dim) rows as they
    are indexed: a head count that is whole 8-row sublane tiles, or one
    that a smaller tile the device takes for it holds whole (1, 2, 4: in
    the AOT compile for the v5e a (1025, 128, 4, 128) bf16 pool is
    ``{3,2,1,0:T(4,128)(2,1)}``, a 2-head one ``T(2,128)``, row-major, and
    a 1-head one's heads axis orders nothing; ``tests/test_tpu_aot.py``
    holds all three).  Any other count (6, 7, 30) pads to no tile of its
    own, and the device puts another axis next to the lanes
    (:func:`pool_relayouts`)."""
    return hkv % 8 == 0 or 8 % hkv == 0


def pool_relayouts(pool) -> bool:
    """Whether XLA:TPU holds this K/V pool (N, bs, Hkv, D) in another order
    than it is indexed in.  The device keeps an array with the axis that
    pads least to the sublane tile next to the lanes: for a head count
    whose rows fill no tile (:func:`heads_fill_a_tile`; 30 -> 32) that is
    the block-size or the blocks axis, not the heads', and a gather or a
    scatter over such a pool first copies it WHOLE into row-major order
    and back (AOT compile for the v5e at (529, 256, 30, 128): 4.4 GB of
    copies a round, 5.6 GB of temporaries; PERF.md section 6).
    ``dynamic_slice`` and ``dynamic_update_slice`` take the pool as it
    lies, and so does the decode kernel: at a block size that is a whole
    number of sublane tiles the device's order is ``transpose(pool, (0,
    2, 1, 3))`` row-major, so that view (and its ``(N, Hkv * bs, D)``
    reshape) is a bitcast where the row-major one would be the copy
    (``tests/test_tpu_aot.py`` holds both at the hybrid cell's shape).
    Four heads are held row-major in ``T(4,128)`` tiles: taken head-major
    they cost two whole-pool copies a call (277 MB of temporaries at
    (1057, 128, 4, 128)), taken as they lie one Mosaic call and none."""
    return (
        backend.kernel_platform() == "tpu" and pool.ndim == 4
        and not heads_fill_a_tile(pool.shape[2])
    )


def _blocks_copy_pool(pool) -> bool:
    """Whether a gather or a scatter of WHOLE blocks would first copy this
    pool on a TPU: for any K/V head count that is no multiple of 8.  A pool
    held head-major (:func:`pool_relayouts`) is copied into row-major order
    and back; one held row-major in a smaller tile (4 heads, ``T(4,128)``)
    is copied into ``T(8,128)`` tiles and back, as the block scatter wants
    them (24 whole-pool copies in the 4-head cell's prefill write, by the
    compile for the v5e).  A row scatter (:func:`put_rows`) takes such a
    row-major pool as it lies."""
    return (
        backend.kernel_platform() == "tpu" and pool.ndim == 4
        and pool.shape[2] % 8 != 0
    )


def take_blocks(pool, idx):
    """``pool[idx]`` for block ids ``idx`` of any (static) shape: one
    gather, or where that would copy the pool whole
    (:func:`_blocks_copy_pool`) one ``dynamic_slice`` a block."""
    if not _blocks_copy_pool(pool):
        return pool[idx]
    flat = idx.reshape(-1)
    blocks = jnp.stack([
        lax.dynamic_index_in_dim(pool, flat[i], 0, keepdims=False)
        for i in range(flat.shape[0])
    ])
    return blocks.reshape(*idx.shape, *pool.shape[1:])


def put_blocks(pool, idx, blocks):
    """``pool.at[idx].set(blocks)`` for (n,) block ids and (n, bs, Hkv, D)
    ``blocks``: one scatter, or where that would copy the pool whole
    (:func:`_blocks_copy_pool`) one ``dynamic_update_slice`` a block."""
    if not _blocks_copy_pool(pool):
        return pool.at[idx].set(blocks)
    for i in range(idx.shape[0]):
        pool = lax.dynamic_update_slice_in_dim(pool, blocks[i][None], idx[i], 0)
    return pool


def put_rows(pool, blk, off, rows):
    """``pool.at[blk, off].set(rows)``: each slot's new row (S, Hkv, D) at
    its block and offset; one scatter, or where that would copy the pool
    whole (a pool held head-major: :func:`pool_relayouts`) one
    ``dynamic_update_slice`` a slot (slots that share a place, the inactive
    ones' null block, keep the last)."""
    if not pool_relayouts(pool):
        return pool.at[blk, off].set(rows)
    zero = jnp.zeros((), blk.dtype)
    for i in range(rows.shape[0]):
        pool = lax.dynamic_update_slice(
            pool, rows[i][None, None], (blk[i], off[i].astype(blk.dtype), zero, zero)
        )
    return pool


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
        kb = take_blocks(k_pool, tb).reshape(s, cb * bs, hkv, d)
        vb = take_blocks(v_pool, tb).reshape(s, cb * bs, hkv, d)
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

#: Rows of K (or V) one compute step of the kernel holds: a chunk of
#: ``_CHUNK_ROWS // (bs * Hkv)`` blocks, 256 KB of bf16 at D = 128 in
#: the two older cells (2 dense blocks of 16 x 32 rows, 8 Laguna blocks of
#: 16 x 8); of a block longer than that, read head-major, the heads that
#: come nearest (``_chunk_heads``: 5 of the hybrid cell's 30 heads of 256
#: rows, 328 KB).  A step is a serial chain (product, softmax, product,
#: rescale), so it has to be long enough to hide under its own DMAs.
_CHUNK_ROWS = 1024
#: Chunks of K and of V in VMEM at once; all but the one being read are
#: in flight.  1.5 MB in flight covers the HBM's bandwidth x latency.
_CHUNKS_IN_VMEM = 4


def _kernel_chunk(bs: int, hkv: int, p: int) -> int:
    """Blocks a compute step of the kernel takes."""
    return max(1, min(_CHUNK_ROWS // (bs * hkv), p))


def _chunk_heads(bs: int, hkv: int) -> int:
    """K/V heads of ONE block a compute step of the kernel takes where it
    reads the block head-major, a head's ``bs`` rows together: the heads
    over the whole chunks a block holds, rounded up, so every step is at
    least a chunk and under two (30 heads of 256 rows hold 7 chunks: 5
    heads a step, six steps a block); all the heads of a block that holds
    under two."""
    return -(-hkv // max(1, bs * hkv // _CHUNK_ROWS))


def kernel_admits(q, k_pool) -> bool:
    """Whether Mosaic's tiling takes these shapes: a head dimension that
    fills the 128 lanes, and chunks of whole tiles whose scores fill the
    lanes too.  A pool the device holds as it is indexed (a K/V head count
    whose rows fill a tile: :func:`heads_fill_a_tile`, 4 among them) is
    read (position, head): blocks of whole tiles, a whole number of them a
    chunk, whole sublane tiles of query heads.  Any other head count is
    read (head, position), as the v5e holds such a pool: a head's rows of
    a block have to fill the lanes of the scores by themselves (a block
    size that is a multiple of 128) and fit a chunk; the query heads are
    padded to whole tiles.  Anything else (the CPU tests' toy head
    dimensions) walks the table in ``_stream_jnp``."""
    bs, hkv, d = k_pool.shape[1:]
    rows = bs * hkv
    if not heads_fill_a_tile(hkv):
        tiles = bs % 128 == 0 and bs <= _CHUNK_ROWS
    else:
        tiles = (
            bs % 8 == 0
            and rows % 128 == 0
            and _CHUNK_ROWS % rows == 0
            and q.shape[1] % 8 == 0
        )
    return (
        tiles
        and d % 128 == 0
        and k_pool.dtype in (jnp.bfloat16, jnp.float32)
        and q.dtype == k_pool.dtype
    )


def _weighted_values(pr, v):
    """``pr @ v`` with f32 weights over the pool's values, f32 out.  A
    bf16 pool goes through the MXU in its own type: the weights are split
    into three bf16 parts that sum to the f32 value exactly, stacked, and
    multiplied in one pass (the parts' products are exact in f32, so this
    is the f32 product, not an approximation of it)."""
    if v.dtype == jnp.float32:
        return jnp.dot(pr, v, preferred_element_type=jnp.float32,
                       precision=lax.Precision.HIGHEST)
    h = pr.shape[0]
    parts, rest = [], pr
    for _ in range(3):
        part = rest.astype(v.dtype).astype(jnp.float32)
        parts.append(part)
        rest = rest - part
    out = jnp.dot(jnp.concatenate(parts, axis=0).astype(v.dtype), v,
                  preferred_element_type=jnp.float32)
    return out[:h] + out[h:2 * h] + out[2 * h:]


def _decode_kernel(len_ref, tab_ref, q_ref, kn_ref, vn_ref, match_ref,
                   colpos_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, st,
                   *, bs, cb, sub, p, n_slots, window, scale, grouped):
    """One grid step = one slot.  The slot walks ITS OWN live blocks (for
    a window layer those that meet the window; none for an empty slot),
    each brought from the pool in HBM by its own DMAs, and folds them into
    the online softmax; then the new token.  A compute step takes ``cb``
    blocks, or one of the ``sub`` parts of ONE block (a block longer than
    a chunk: never both).

    The DMAs run ahead of the compute ACROSS slots: ``st`` (SMEM) holds
    the walk's issue cursor (slot, part of a table column, chunks issued)
    and the count of chunks consumed, so the first chunk of a slot is
    already in VMEM when its grid step starts.

    A chunk is rows of K and of V as the pool's view holds them: (position,
    K/V head) of whole blocks, or (K/V head, position) of some heads of
    one.  Every query head is multiplied with every row (one MXU product,
    ``(H, D) x (rows, D)``), and ``match`` keeps, for query head ``j``,
    the rows of K/V head ``j // (H // Hkv)``; the other products are
    masked like a position past ``length``, so a head outside the chunk
    passes through the step unchanged.  Only ``match`` and ``colpos`` (a
    row's position in the chunk) know the order.  The MXU is idle in a
    decode round: wasting its products costs less than moving the rows.
    """
    s = pl.program_id(0)
    nbuf = kbuf.shape[0]
    rows = k_hbm.shape[1]  # of one block
    piece = kbuf.shape[1] // cb  # rows one DMA brings
    f32 = jnp.float32

    def walk(slot):
        """First and end part of the slot's walk (a table column, where a
        block has one part)."""
        length = len_ref[slot]
        end = (length + (bs - 1)) // bs
        first = jnp.int32(0)
        if window is not None:
            first = jnp.maximum(length - (window - 1), 0) // bs
        return (first, end) if sub == 1 else (first * sub, end * sub)

    def place(u):
        """Table column and part of the block of the walk's part ``u``."""
        if sub == 1:
            return u, 0
        return lax.div(u, jnp.int32(sub)), lax.rem(u, jnp.int32(sub))

    def copies(blk, b, j, part):
        k_src, v_src = k_hbm.at[blk], v_hbm.at[blk]
        if sub > 1:
            # where the parts do not divide a block its last part starts
            # early: all DMAs are one size, and ``match`` drops the rows
            # the part before it has served
            src = pl.ds(jnp.minimum(part * piece, rows - piece), piece)
            k_src, v_src = k_src.at[src], v_src.at[src]
        dst = pl.ds(j * piece, piece)
        return (
            pltpu.make_async_copy(k_src, kbuf.at[b, dst], sems.at[0, b]),
            pltpu.make_async_copy(v_src, vbuf.at[b, dst], sems.at[1, b]),
        )

    def issue():
        """Start the DMAs of the walk's next chunk, if one is left."""
        last = n_slots - 1

        def exhausted(c):
            slot, u = c
            return (slot < n_slots) & (u >= walk(jnp.minimum(slot, last))[1])

        def next_slot(c):
            slot = c[0] + 1
            return slot, walk(jnp.minimum(slot, last))[0]

        slot, u = lax.while_loop(exhausted, next_slot, (st[0], st[1]))
        st[0] = slot
        st[1] = u

        @pl.when(slot < n_slots)
        def _():
            end = walk(slot)[1]
            b = lax.rem(st[2], jnp.int32(nbuf))
            for j in range(cb):
                @pl.when(u + j < end)
                def _():
                    col, part = place(u + j)
                    for c in copies(tab_ref[slot * p + col], b, j, part):
                        c.start()
            st[1] = u + cb
            st[2] = st[2] + 1

    @pl.when(s == 0)
    def _():
        # a chunk's tail past the slot's last block is never written by
        # a DMA: it holds an earlier chunk's rows, or these zeros, so a
        # masked weight of 0.0 never meets a NaN
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        st[0] = 0
        st[1] = walk(0)[0]
        st[2] = 0
        st[3] = 0
        for _ in range(nbuf - 1):
            issue()

    length = len_ref[s]
    first, end = walk(s)
    n_steps = (end - first + (cb - 1)) // cb
    done = st[3]
    q = q_ref[0]  # (H, D)
    h, d = q.shape
    # (sub * H, chunk rows): a part of a block has its own heads
    match = match_ref[...] != 0 if sub == 1 else None
    colpos = colpos_ref[...]  # (1, chunk rows): position inside the chunk
    precision = lax.Precision.HIGHEST if q.dtype == f32 else None

    def step(i, carry):
        m, l, acc = carry
        # into the buffer the step before this one read
        issue()
        b = lax.rem(done + i, jnp.int32(nbuf))
        u0 = first + i * cb
        for j in range(cb):
            @pl.when(u0 + j < end)
            def _():
                for c in copies(0, b, j, 0):
                    c.wait()
        sc = lax.dot_general(
            q, kbuf[b], (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=precision,
        )  # (H, chunk rows)
        if not grouped:
            # the dense model rounds its scores to the compute type, as
            # cached_attention does
            sc = sc.astype(q.dtype).astype(f32)
        col0, part = place(u0)
        kpos = col0 * bs + colpos
        seen = kpos < length
        if window is not None:
            seen = seen & (kpos > length - window)
        mine = match
        if sub > 1:
            mine = match_ref[pl.ds(pl.multiple_of(part * h, 8), h), :] != 0
        valid = mine & seen
        sc = jnp.where(valid, sc * scale, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        pr = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + pr.sum(axis=-1, keepdims=True)
        acc = acc * corr + _weighted_values(pr, vbuf[b])
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        jnp.int32(0), n_steps, step,
        (jnp.full((h, 1), _NEG_INF, f32), jnp.zeros((h, 1), f32),
         jnp.zeros((h, d), f32)),
    )
    st[3] = done + n_steps

    # the new token's K/V, already one row a query head
    s_new = (q.astype(f32) * kn_ref[0].astype(f32)).sum(axis=-1, keepdims=True)
    if not grouped:
        s_new = s_new.astype(q.dtype).astype(f32)
    s_new = s_new * scale
    m_fin = jnp.maximum(m, s_new)
    p_new = jnp.exp(s_new - m_fin)
    corr = jnp.exp(m - m_fin)
    l = l * corr + p_new
    acc = acc * corr + p_new * vn_ref[0].astype(f32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


# jitted so that the layers of a decode program that share their shapes
# share ONE traced kernel and one Mosaic lowering (a function the program
# calls eight times), not eight: lowering is paid by every process before
# the persistent cache is asked, 0.6 s a kernel in the dense cell's set-up
@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "cb", "heads", "nbuf", "interpret"),
)
def _stream_kernel(q, k_new, v_new, k_pool, v_pool, tables, lengths, *,
                   scale, window, cb, heads, nbuf, interpret):
    """``cb`` blocks a compute step, ``nbuf`` chunks of K and of V in
    VMEM.  ``heads`` is the order the pools are read in, which the entry
    takes from :func:`pool_relayouts`: ``None``, a block's rows (position,
    K/V head) as a row-major pool holds them; a number, its rows (K/V
    head, position) as the v5e holds a pool whose heads fill no sublane
    tile, that many heads of ONE block a step (``cb`` is 1 where they are
    not all of them).  Both views are bitcasts of what the device holds."""
    s, h, d = q.shape
    n, bs, hkv = k_pool.shape[:3]
    p = tables.shape[1]
    g = h // hkv
    rows = bs * hkv
    if g > 1:
        k_new = jnp.repeat(k_new, g, axis=1)
        v_new = jnp.repeat(v_new, g, axis=1)
    hp = -(-h // 8) * 8  # whole sublane tiles of query heads
    if hp != h:
        # rows of zeros that no K/V row matches: they see the new token's
        # row alone, which is zeros too
        q, k_new, v_new = (
            jnp.pad(x, ((0, 0), (0, hp - h), (0, 0))) for x in (q, k_new, v_new)
        )
    head_major = heads is not None
    if head_major:
        k_pool, v_pool = (
            jnp.transpose(x, (0, 2, 1, 3)) for x in (k_pool, v_pool)
        )
    else:
        heads = hkv
    sub = -(-hkv // heads)  # compute steps a block
    if sub > 1 and cb > 1:
        raise ValueError(f"{cb} blocks a step, each in {sub} parts")
    piece = heads * bs  # rows one DMA brings: a block, or a part of one
    # which query heads a row of the chunk serves, and its position in the
    # chunk, from the order: the row's place in its DMA (``at``), its block
    # in the chunk, and the part of the block the DMA brought
    row = np.arange(cb * piece)
    at = row % piece
    head, pos = (at // bs, at % bs) if head_major else (at % hkv, at // hkv)
    colpos = row // piece * bs + pos
    # where the parts do not divide the heads the last one starts early,
    # and serves only the heads the part before it has not
    part = np.arange(sub)[:, None]
    head = np.minimum(part * heads, hkv - heads) + head  # (sub, chunk rows)
    match = (head >= part * heads)[:, None, :] & (
        head[:, None, :] == (np.arange(hp) // g)[None, :, None]
    )
    slot_block = pl.BlockSpec((1, hp, d), lambda i, *_: (i, 0, 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))  # noqa: E731
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, bs=bs, cb=cb, sub=sub, p=p, n_slots=s,
            window=window, scale=scale, grouped=g > 1,
        ),
        out_shape=jax.ShapeDtypeStruct((s, hp, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[
                slot_block, slot_block, slot_block,
                whole(sub * hp, cb * piece), whole(1, cb * piece),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=slot_block,
            scratch_shapes=[
                pltpu.VMEM((nbuf, cb * piece, d), k_pool.dtype),
                pltpu.VMEM((nbuf, cb * piece, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, nbuf)),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="paged_decode_attention",
        interpret=interpret,
    )(
        lengths, tables.reshape(-1), q, k_new, v_new,
        jnp.asarray(match.reshape(sub * hp, -1), jnp.int32),
        jnp.asarray(colpos[None, :], jnp.int32),
        k_pool.reshape(n, rows, d), v_pool.reshape(n, rows, d),
    )
    return out if hp == h else out[:, :h]


# ------------------------------------------------------------------- entry


def runs_kernel(q, k_pool) -> bool:
    """Whether :func:`paged_attention` takes the kernel for these shapes
    where this process compiles for: on a TPU, at shapes the kernel's
    tiling admits.  ``q`` / ``k_pool`` need a shape and a dtype only."""
    return backend.kernel_platform() == "tpu" and kernel_admits(q, k_pool)


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
    impl: str | None = None,
    interpret: bool | None = None,
    block_chunk: int | None = None,
    window: int | None = None,
):
    """Fused paged decode attention for one token per slot.

    ``q``: (S, H, D) — the decode step's query; ``k_new`` / ``v_new``:
    (S, Hkv, D) the new token's K/V, all already RoPE'd at each row's
    position.  ``H`` is a multiple of ``Hkv`` (grouped queries: query
    head ``j`` reads K/V head ``j // (H // Hkv)``; the dense model has
    ``Hkv == H``).  ``window``: each row sees only positions ``length -
    window + 1 .. length``, and walks only the table columns that hold
    them.
    ``k_pool`` / ``v_pool``: (N, bs, Hkv, D) per-layer pools; ``tables``:
    (S, P) int32 block ids; ``lengths``: (S,) int32 cache positions
    already written per row, each ``< P*bs`` (a row AT the table's
    capacity has no position left to decode into — the serving layer
    never reaches it, and the gather oracle's splice clamps there).
    Returns (S, H, D) in ``q``'s dtype —
    attention over pool positions ``< length`` plus the new token at
    position ``length``, equal to :func:`paged_attention_gather` within
    :data:`FUSED_DECODE_ATOL` (summation order is the only difference).

    Which path runs is this function's to decide, from what it can
    observe (:func:`runs_kernel`): the kernel on a TPU at shapes its
    tiling admits, the ``fori_loop`` everywhere else.  ``impl`` is the
    tests' way to force one (``"pallas"``: the kernel, under the
    interpreter on the CPU; ``"jnp"``: the loop, ``block_chunk`` table
    columns a step), not a choice of speed.  ``block_chunk=None`` is
    derived: 1 on the CPU, and on a TPU, where the loop is left with the
    shapes the kernel refuses, the kernel's own chunk (the sweep's winner
    at the Laguna cell's shapes, 8; flat at the dense cell's).
    """
    if impl not in (None, "jnp", "pallas"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    _check_shapes(q, k_new, v_new, k_pool, v_pool, tables, lengths)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if impl == "pallas" or (impl is None and runs_kernel(q, k_pool)):
        bs, hkv = k_pool.shape[1:3]
        return _stream_kernel(
            q, k_new, v_new, k_pool, v_pool, tables, lengths,
            scale=float(scale), window=window,
            cb=_kernel_chunk(bs, hkv, tables.shape[1]),
            heads=_chunk_heads(bs, hkv) if pool_relayouts(k_pool) else None,
            nbuf=_CHUNKS_IN_VMEM,
            interpret=backend.pallas_interpret(interpret),
        )
    if block_chunk is None:
        block_chunk = 1
        if backend.kernel_platform() == "tpu":
            block_chunk = _kernel_chunk(*k_pool.shape[1:3], tables.shape[1])
    return _stream_jnp(q, k_new, v_new, k_pool, v_pool, tables, lengths,
                       float(scale), block_chunk, window)


# ------------------------------------------------------------------ latent

def _check_latent(q, row_new, pool, tables, lengths, value_dim):
    if q.ndim != 3 or pool.ndim != 3 or pool.shape[2] != q.shape[2]:
        raise ValueError(
            f"expected (S, H, R) queries over an (N, bs, R) pool of rows, "
            f"got {q.shape} over {pool.shape}"
        )
    if row_new.shape != (q.shape[0], pool.shape[2]):
        raise ValueError(
            f"the new token's row must be shaped {(q.shape[0], pool.shape[2])}"
            f", got {row_new.shape}"
        )
    if not 0 < value_dim <= pool.shape[2]:
        raise ValueError(
            f"value_dim {value_dim} is no prefix of a {pool.shape[2]}-wide row"
        )
    if tables.ndim != 2 or tables.shape[0] != q.shape[0]:
        raise ValueError(f"expected (S, P) tables, got {tables.shape}")
    if lengths.shape != (q.shape[0],):
        raise ValueError(f"expected (S,) lengths, got {lengths.shape}")


def paged_attention_latent_gather(q, row_new, pool, tables, lengths, *,
                                  value_dim: int, scale: float):
    """The latent walk's oracle: gather every table block into a
    contiguous (S, P*bs, R) view, splice the new token's row in at each
    slot's ``length``, and attend with the full-row softmax.  Arguments
    and result as :func:`paged_attention_latent`."""
    _check_latent(q, row_new, pool, tables, lengths, value_dim)
    s = q.shape[0]
    rows = jax.vmap(
        lambda c, u, p: lax.dynamic_update_slice_in_dim(c, u, p, axis=0)
    )(pool[tables].reshape(s, -1, pool.shape[2]), row_new[:, None], lengths)
    sc = jnp.einsum(
        "shr,skr->shk", q, rows, preferred_element_type=jnp.float32
    ) * scale
    seen = jnp.arange(rows.shape[1])[None, :] <= lengths[:, None]
    pr = jax.nn.softmax(jnp.where(seen[:, None, :], sc, _NEG_INF), axis=-1)
    return jnp.einsum(
        "shk,skv->shv", pr, rows[..., :value_dim].astype(jnp.float32)
    ).astype(q.dtype)


def _stream_latent_jnp(q, row_new, pool, tables, lengths, value_dim, scale,
                       block_chunk):
    s, h, r = q.shape
    bs, p = pool.shape[1], tables.shape[1]
    cb = max(1, min(int(block_chunk), p))
    p_pad = -(-p // cb) * cb
    if p_pad != p:  # null blocks: past every slot's causal bound
        tables = jnp.pad(tables, ((0, 0), (0, p_pad - p)))
    n_steps = ((jnp.max(lengths) + bs - 1) // bs + cb - 1) // cb
    f32 = jnp.float32

    def fold(carry, rows, valid):
        """One online-softmax step over ``rows`` (S, K, R)."""
        m, l, acc = carry
        sc = jnp.einsum("shr,skr->shk", q, rows, preferred_element_type=f32)
        sc = jnp.where(valid[:, None, :], sc * scale, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        pr = jnp.where(valid[:, None, :], jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + pr.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "shk,skv->shv", pr.astype(rows.dtype), rows[..., :value_dim],
            preferred_element_type=f32,
        )
        return m_new, l, acc

    def body(i, carry):
        tb = lax.dynamic_slice_in_dim(tables, i * cb, cb, axis=1)  # (S, cb)
        kpos = i * cb * bs + jnp.arange(cb * bs)
        return fold(
            carry, pool[tb].reshape(s, cb * bs, r),
            kpos[None, :] < lengths[:, None],
        )

    carry = lax.fori_loop(0, n_steps, body, (
        jnp.full((s, h), _NEG_INF, f32), jnp.zeros((s, h), f32),
        jnp.zeros((s, h, value_dim), f32),
    ))
    _, l, acc = fold(carry, row_new[:, None], jnp.ones((s, 1), bool))
    return (acc / l[..., None]).astype(q.dtype)


def latent_kernel_admits(q, pool, value_dim: int) -> bool:
    """Whether Mosaic takes the latent kernel at these shapes: whole
    sublane tiles of positions a block and of heads, values that fill
    whole lane tiles (the rest of the row, the rotary key, may be a part
    of one: the block's last axis is the array's own)."""
    bs, r = pool.shape[1:]
    return (
        bs % 16 == 0
        and q.shape[1] % 8 == 0
        and value_dim % 128 == 0 and 0 < value_dim <= r
        and r % 8 == 0
        and pool.dtype in (jnp.bfloat16, jnp.float32)
        and q.dtype == pool.dtype
    )


def runs_latent_kernel(q, pool, value_dim: int) -> bool:
    """:func:`runs_kernel` for :func:`paged_attention_latent`."""
    return backend.kernel_platform() == "tpu" and latent_kernel_admits(
        q, pool, value_dim
    )


def _latent_kernel(len_ref, tab_ref, q_ref, new_ref, pool_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs, p, scale, value_dim):
    """Grid (slot, table column).  A step holds ONE block of the slot,
    brought in by the pipeline: the block's index comes from the table in
    SMEM, and a column past the slot's last live block names that last
    block again, which costs no DMA (the index did not change) and whose
    compute is skipped.  A 576-wide row cannot be sliced out of a VMEM
    buffer by hand (Mosaic wants slices of whole 128-lane tiles), so the
    manual DMAs of ``_decode_kernel`` are not to be had here; a block whose
    last axis is the array's own is.

    Every query head is multiplied with every row (``(H, R) x (bs, R)``:
    the MXU's own shape, no masked head pairs), the values are the first
    ``value_dim`` numbers of the very rows the scores were taken with, and
    the last column folds the new token's row in."""
    s, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    length = len_ref[s]
    q = q_ref[0]  # (H, R)
    h = q.shape[0]
    precision = lax.Precision.HIGHEST if q.dtype == f32 else None

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(j * bs < length)
    def _():
        rows = pool_ref[0]  # (bs, R)
        sc = lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=precision,
        )  # (H, bs)
        kpos = j * bs + lax.broadcasted_iota(jnp.int32, (h, bs), 1)
        valid = kpos < length
        sc = jnp.where(valid, sc * scale, _NEG_INF)
        m = m_ref[:, 0:1]
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        pr = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, 0:1] * corr + pr.sum(axis=-1, keepdims=True), l_ref.shape
        )
        # weights drop to the rows' type for the MXU in ONE pass (flash
        # practice; exact for an f32 pool): the three-part product of
        # ``_weighted_values`` made the step MXU-bound at 128 heads
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            pr.astype(rows.dtype), rows[:, :value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=f32,
            precision=precision,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == p - 1)
    def _():
        new = new_ref[0]  # (1, R): position `length`, always visible
        s_new = (q.astype(f32) * new.astype(f32)).sum(
            axis=-1, keepdims=True
        ) * scale
        m = m_ref[:, 0:1]
        m_fin = jnp.maximum(m, s_new)
        p_new = jnp.exp(s_new - m_fin)
        corr = jnp.exp(m - m_fin)
        l = l_ref[:, 0:1] * corr + p_new
        acc = acc_ref[...] * corr + p_new * new[:, :value_dim].astype(f32)
        o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "value_dim", "interpret")
)
def _stream_latent_kernel(q, row_new, pool, tables, lengths, *, scale,
                          value_dim, interpret):
    s, h, r = q.shape
    bs, p = pool.shape[1], tables.shape[1]

    def block_of(i, j, len_ref, tab_ref):
        # the slot's j-th block, or its last live one again past that
        last = jnp.maximum((len_ref[i] + bs - 1) // bs - 1, 0)
        return tab_ref[i * p + jnp.minimum(j, last)], 0, 0

    return pl.pallas_call(
        functools.partial(
            _latent_kernel, bs=bs, p=p, scale=scale, value_dim=value_dim
        ),
        out_shape=jax.ShapeDtypeStruct((s, h, value_dim), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, p),
            in_specs=[
                pl.BlockSpec((1, h, r), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((1, 1, r), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((1, bs, r), block_of),
            ],
            out_specs=pl.BlockSpec(
                (1, h, value_dim), lambda i, j, *_: (i, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),  # m, lane-replicated
                pltpu.VMEM((h, 128), jnp.float32),  # l
                pltpu.VMEM((h, value_dim), jnp.float32),  # acc
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="paged_latent_attention",
        interpret=interpret,
    )(lengths, tables.reshape(-1), q, row_new[:, None, :], pool)


def paged_attention_latent(q, row_new, pool, tables, lengths, *,
                           value_dim: int, scale: float,
                           impl: str | None = None,
                           interpret: bool | None = None,
                           block_chunk: int = 1):
    """Fused paged decode attention over a LATENT pool, one token a slot:
    the counterpart of :func:`paged_attention` where a cached position is
    one row that is key and value at once.

    ``q``: (S, H, R) the absorbed queries, every head against the same
    rows; ``row_new``: (S, R) the new token's row (position ``length``,
    always visible to itself); ``pool``: (N, bs, R), ONE array a layer;
    ``tables`` (S, P), ``lengths`` (S,) as for :func:`paged_attention`.
    Scores are ``q . row * scale`` over all R numbers, values the rows'
    first ``value_dim``.  Returns (S, H, value_dim) in ``q``'s dtype: what
    :func:`paged_attention_latent_gather` returns, in another summation
    order.

    Two paths, chosen as :func:`paged_attention` chooses
    (:func:`runs_latent_kernel`): on a TPU at shapes its tiling admits, a
    Pallas kernel that walks every slot's own live blocks
    (``_latent_kernel``); elsewhere (the CPU, a block that is no whole
    number of sublane tiles) the ``fori_loop`` (``_stream_latent_jnp``):
    all S slots a step to the frontier of the LONGEST one, ``block_chunk``
    table columns a step.  On the v5e the loop also pays copies of the
    whole pool a layer a round (PERF.md section 6, PR 32): it is no path
    to serve from there.  ``impl`` forces a path, for the tests.
    """
    if impl not in (None, "jnp", "pallas"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    _check_latent(q, row_new, pool, tables, lengths, value_dim)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if impl == "pallas" or (
        impl is None and runs_latent_kernel(q, pool, value_dim)
    ):
        return _stream_latent_kernel(
            q, row_new, pool, tables, lengths, scale=float(scale),
            value_dim=int(value_dim),
            interpret=backend.pallas_interpret(interpret),
        )
    return _stream_latent_jnp(
        q, row_new, pool, tables, lengths, value_dim, float(scale),
        block_chunk,
    )
