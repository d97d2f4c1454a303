"""Pallas TPU kernel: fused causal flash attention (forward).

The attention analog of ``pallas_reduce``: where that kernel pins the
allreduce's local-reduce layout, this one fuses the model layer's hot op —
the (Tq x Tk) score/softmax/value contraction — into a single VMEM-resident
pass, so the T x T score matrix never touches HBM.  One grid step owns one
(batch*head, q-block) tile; an inner ``fori_loop`` walks k/v blocks with
the online-softmax running max / normalizer (the same accumulation scheme
as ``flextree_tpu.parallel.ring_attention.local_attention_block``, but per
128-row tile on the MXU instead of per ring hop).

Causality is positional (``q_offset``/``k_offset`` give the blocks' global
coordinates), so the kernel drops straight into the Ulysses path — after
its all-to-all the full sequence is local — and into plain single-device
attention; the causal upper bound also *shortens the k loop* per q tile,
halving the work vs a masked dense matmul.

Differentiable via ``jax.custom_vjp`` with a **blockwise flash backward**:
the forward additionally emits the per-row logsumexp, and two backward
kernels recompute probabilities tile-by-tile from (q, k, v, lse) — one
gridded over q tiles producing dq, one over k tiles producing dk/dv — so
the backward, like the forward, never materializes the (Tq, Tk) score
matrix.  Total residual memory is O(T) beyond the inputs (out + lse +
delta rows).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.backend import pallas_interpret

__all__ = ["flash_attention", "attention_with_offsets", "kvgrid_tiles"]

_NEG_INF = -1e30
_LANE = 128  # lse is lane-replicated to satisfy Mosaic's (8, 128) block rule
_LOG2E = 1.4426950408889634

# forward k-loop unroll factor (env-overridable for tuning experiments);
# the default stays 1 — the knob exists for other chips/shapes
import os as _os

_FWD_UNROLL = int(_os.environ.get("FLEXTREE_FLASH_UNROLL", "1"))

# Default forward k-walk schedule.  "loop" is the variant the benchmark's
# train cells run; "pipelined"/"kvgrid" are CPU-parity-pinned and not
# measured on the chip (ROADMAP D2 times all three and keeps one).
# Env-overridable so a sweep needs no edit of call sites.
DEFAULT_FWD_VARIANT = _os.environ.get("FLEXTREE_FLASH_VARIANT", "loop")

# Mosaic's default scoped-VMEM budget on v5e: 16 MiB unless a kernel raises
# vmem_limit_bytes, which these do not.
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def _check_vmem(kernel: str, blocks, q) -> None:
    """Refuse at trace time what Mosaic refuses at compile time.

    ``blocks``: the (block_shape, dtype) of every BlockSpec'd operand and
    result of one ``pallas_call``.  The pipeline double-buffers each, and
    ``2 * sum(bytes)`` reproduced Mosaic's own "scoped allocation" figure
    to the byte at every shape it refused (AOT compiles for v5e, B4 H16
    D128, T 2048..16384, bf16 and f32, default block sizes, jax 0.9.0 /
    libtpu 0.0.34).  It is a floor, not the whole need: Mosaic adds its
    own scratch for the score tiles, which grows with block_q x block_k
    (the pipelined forward at block_q=1024 passed this rule and was
    refused by Mosaic on the chip), so a shape this passes can still be
    refused by the compiler.  With fewer than 8 batch*head rows Mosaic
    single-buffers blocks that never move and accepts more than this rule
    would; the compiler decides there.
    """
    b, _, h, _ = q.shape
    if b * h < 8:
        return
    need = 2 * sum(
        math.prod(shape) * jnp.dtype(dtype).itemsize for shape, dtype in blocks
    )
    if need > _SCOPED_VMEM_BYTES:
        raise ValueError(
            f"flash attention {kernel} kernel does not fit VMEM for "
            f"q{tuple(q.shape)} {q.dtype.name}: its double-buffered blocks "
            f"need {need} bytes ({need / 2**20:.2f} MiB) against the "
            f"{_SCOPED_VMEM_BYTES}-byte (16 MiB) scoped-VMEM limit. The "
            f"kernel keeps whole-sequence operands resident, so the need "
            f"grows with T and the element size: shard the sequence, use "
            f"bfloat16, or use attn_impl='reference'."
        )


def attention_with_offsets(
    q, k, v, *, causal: bool, scale: float, q_offset=0, k_offset=0
):
    """Pure-jnp oracle on (BH, Tq, D)/(BH, Tk, D): full score matrix with
    positional causal masking — the A/B reference and the VJP recompute."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None], s, _NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if causal:
        p = jnp.where(mask[None], p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    out = jnp.where(l > 0, out / jnp.where(l > 0, l, 1.0), 0.0)
    return out.astype(q.dtype)


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *maybe_lse_ref,
    block_q: int,
    block_k: int,
    t_kv: int,
    t_kv_valid: int,
    causal: bool,
    scale: float,
    q_offset: int,
    k_offset: int,
    unroll: int = 1,
    pipeline: bool = False,
):  # variant="loop"/"pipelined" kernel; the "kvgrid" variant is below
    i = pl.program_id(1)
    # fold scale*log2(e) into q once (bq x D) instead of scaling each
    # (bq x bk) score tile, and run the online softmax in the exp2 domain —
    # softmax is base-invariant when max/normalizer use the same base.
    # (The speed-up of this and the full/masked loop split below is not
    # measured on today's code.)
    q = q_ref[0] * (scale * _LOG2E)  # native dtype — bf16 q/k feed the MXU
    d = q.shape[-1]
    n_kb = t_kv // block_k

    if causal:
        # highest visible k position for this q tile (exclusive)
        hi = q_offset + (i + 1) * block_q - k_offset
        kb_hi = jnp.clip((hi + block_k - 1) // block_k, 0, n_kb)
        # tiles fully visible to every row of this q tile need no mask:
        # the first row (qpos = q_offset + i*block_q) sees `lo_vis` leading
        # k positions, so tiles strictly inside that prefix skip the
        # iota/compare/select entirely
        lo_vis = q_offset + i * block_q - k_offset + 1
        kb_full = jnp.clip(lo_vis // block_k, 0, n_kb)
    else:
        kb_hi = n_kb
        kb_full = n_kb
    if t_kv_valid < t_kv:  # static: only tiles before the pad are mask-free
        kb_full = jnp.minimum(kb_full, t_kv_valid // block_k)

    def tile(j):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk) f32 log2-domain scores from native-dtype operands
        return s, vb

    def update(carry, s, vb, valid=None):
        return _kv_update(*carry, s, vb, valid)

    def step_full(j, carry):
        s, vb = tile(j)
        return update(carry, s, vb)

    def step_masked(j, carry):
        s, vb = tile(j)
        kpos = (
            k_offset
            + j * block_k
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        )
        valid = kpos - k_offset < t_kv_valid
        if causal:
            qpos = (
                q_offset
                + i * block_q
                + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            )
            valid = valid & (qpos >= kpos)
        return update(carry, s, vb, valid=valid)

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    if pipeline:
        # Software-pipelined full loop: iteration j's body computes tile
        # j's scores (MXU, independent of the softmax carry) *and* folds
        # tile j-1's already-computed scores into the online softmax (VPU +
        # the p@v MXU op).  Inside one loop body the two are explicitly
        # independent, so Mosaic can overlap them — the cross-iteration
        # scheduling a carry-serialized ``fori_loop`` body denies it.
        s0, vb0 = tile(0)  # safe: t_kv >= block_k always (padded geometry)

        def step_pipe(j, carry):
            m, l, acc, s_prev, vb_prev = carry
            s_next, vb_next = tile(j)
            m, l, acc = update((m, l, acc), s_prev, vb_prev)
            return m, l, acc, s_next, vb_next

        m, l, acc, s_last, vb_last = lax.fori_loop(
            1, kb_full, step_pipe, (m0, l0, acc0, s0, vb0)
        )
        # epilogue: tile kb_full-1's scores are computed but unconsumed;
        # fold them in — unless the full loop was empty (kb_full == 0),
        # where the prefetched tile 0 must be discarded
        fed = update((m, l, acc), s_last, vb_last)
        m, l, acc = jax.tree.map(
            lambda a, b: jnp.where(kb_full > 0, a, b), fed, (m, l, acc)
        )
        carry = (m, l, acc)
    else:
        try:
            carry = lax.fori_loop(
                0, kb_full, step_full, (m0, l0, acc0), unroll=unroll
            )
        except ValueError:
            # JAX rejects unroll with the dynamic (causal) bound;
            # unroll is a tuning knob, never a semantics change — fall back
            carry = lax.fori_loop(0, kb_full, step_full, (m0, l0, acc0))
    m, l, acc = lax.fori_loop(kb_full, kb_hi, step_masked, carry)
    out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)
    if maybe_lse_ref:  # only the differentiated path pays for the lse store
        # lse is stored in NATURAL-log units (m is log2-domain: divide the
        # whole thing by log2(e)); fully-masked rows get a +inf-like
        # sentinel so the backward's exp(s - lse) is exactly zero for them;
        # the value is replicated across the 128-lane minor dim (Mosaic
        # block constraint)
        lse = jnp.where(
            l > 0,
            (m + jnp.log2(jnp.maximum(l, 1e-38))) * (1.0 / _LOG2E),
            -_NEG_INF,
        )
        maybe_lse_ref[0][0] = jnp.broadcast_to(lse, (block_q, _LANE))


def _flash_kernel_kvgrid(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *rest_refs,
    block_q: int,
    block_k: int,
    block_k_major: int,
    t_kv: int,
    t_kv_valid: int,
    causal: bool,
    scale: float,
    q_offset: int,
    k_offset: int,
):
    """The "kvgrid" forward: k/v-major tiles are a GRID dimension, not a
    ``fori_loop``.

    The softmax carry (m, l, acc) lives in VMEM scratch across the
    ``arbitrary``-semantics kv axis, each grid step's inner walk over
    ``block_k`` minor tiles is a *statically unrolled* Python loop, and
    k/v blocks arrive by BlockSpec DMA — so Mosaic sees straight-line code
    per step, double-buffers the k/v fetches across steps, and can overlap
    tile t+1's DMA/matmul with tile t's softmax.  This is the structure
    the stock Pallas TPU flash kernel uses; the ``loop`` variant's dynamic
    trip count denies Mosaic all of it.
    Causally-invisible (i, j) grid steps skip compute under ``pl.when``
    (their k/v DMA still happens — same total traffic as the loop
    variant's whole-k/v residency).
    """
    has_lse = len(rest_refs) == 4
    if has_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest_refs
    else:
        acc_ref, m_ref, l_ref = rest_refs
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_j = t_kv // block_k_major

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    if causal:
        # exclusive bound of visible local k positions for this q tile
        hi = q_offset + (i + 1) * block_q - k_offset
        run = (j * block_k_major) < hi
        # last kv-major tile with any visible position — where the output
        # is finalized (0 when nothing is visible: zero acc, l=0 path)
        j_last = jnp.clip(-(-hi // block_k_major) - 1, 0, n_j - 1)
        # fully-visible prefix (min over the tile's rows), for mask skipping
        lo_vis = q_offset + i * block_q - k_offset + 1
    else:
        run = True
        j_last = n_j - 1
        lo_vis = t_kv

    def _body():
        q = q_ref[0] * (scale * _LOG2E)
        m = m_ref[:, 0:1]
        l = l_ref[:, 0:1]
        acc = acc_ref[...]
        for jj in range(block_k_major // block_k):
            base = j * block_k_major + jj * block_k  # local k index (traced)
            kb = k_ref[0, jj * block_k:(jj + 1) * block_k, :]
            vb = v_ref[0, jj * block_k:(jj + 1) * block_k, :]
            # the score matmul lives INSIDE the branches so a skipped minor
            # tile (fully invisible: beyond the causal bound or entirely in
            # the pad) costs neither MXU nor VPU work — with
            # block_k_major > block_k the last visible major tile otherwise
            # computes up to (bkM - bk) columns of zeros per q tile
            visible = base < t_kv_valid
            if causal:
                visible = visible & (base < hi)
            needs_mask = base + block_k > t_kv_valid
            if causal:
                needs_mask = needs_mask | (base + block_k > lo_vis)

            def scores(q):
                return jax.lax.dot_general(
                    q, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

            def masked(op):
                m, l, acc, q = op
                s = scores(q)
                kpos = base + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                valid = kpos < t_kv_valid
                if causal:
                    qpos = (
                        q_offset - k_offset + i * block_q
                        + lax.broadcasted_iota(
                            jnp.int32, (block_q, block_k), 0
                        )
                    )
                    valid = valid & (qpos >= kpos)
                return _kv_update(m, l, acc, s, vb, valid)

            def unmasked(op):
                m, l, acc, q = op
                return _kv_update(m, l, acc, scores(q), vb, None)

            def folded(op):
                return lax.cond(needs_mask, masked, unmasked, op)

            m, l, acc = lax.cond(
                visible, folded, lambda op: op[:3], (m, l, acc, q)
            )
        m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l, l_ref.shape)
        acc_ref[...] = acc

    if causal:
        pl.when(run)(_body)
    else:
        _body()

    @pl.when(j == j_last)
    def _finalize():
        m = m_ref[:, 0:1]
        l = l_ref[:, 0:1]
        acc = acc_ref[...]
        out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = out.astype(o_ref.dtype)
        if has_lse:
            lse = jnp.where(
                l > 0,
                (m + jnp.log2(jnp.maximum(l, 1e-38))) * (1.0 / _LOG2E),
                -_NEG_INF,
            )
            lse_ref[0] = jnp.broadcast_to(lse, (block_q, _LANE))


def _kv_update(m, l, acc, s, vb, valid):
    """One online-softmax fold — THE implementation, shared by every
    forward variant (``_flash_kernel`` wraps it as ``update``); a numerics
    change here changes all three schedules identically.  Probabilities
    drop to v's dtype for the MXU (standard flash practice; exact when v
    is f32, ~1e-2 abs err in bf16)."""
    if valid is not None:
        s = jnp.where(valid, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp2(s - m_new)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp2(m - m_new)
    l_new = l * corr + p.sum(axis=-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _blocks(q, k, block_q, block_k):
    """Resolved (bq, bk, tq_pad, tk_pad, interpret-independent) geometry.

    Clamped block sizes are rounded up to a multiple of 8 (Mosaic's
    second-minor tiling unit for f32): tq=100 must yield bq=104, not 100 —
    a non-multiple-of-8 block would tile poorly or be rejected on real TPU.
    The sequence padding below already absorbs the overshoot.
    """
    tq, tk = q.shape[1], k.shape[1]
    bq = -(-min(block_q, max(tq, 8)) // 8) * 8
    bk = -(-min(block_k, max(tk, 8)) // 8) * 8
    return bq, bk, -(-tq // bq) * bq, -(-tk // bk) * bk


def _kvgrid_vmem_need(bq, bk, bkM, d, dv, dtype) -> int:
    """An estimate of a kvgrid forward step's VMEM: the double-buffered q,
    k, v and out blocks with their last axis padded to whole lane tiles,
    the f32 carry, and three (bq, bk) f32 score tiles (scores,
    probabilities, a mask)."""
    lanes = lambda w: -(-w // _LANE) * _LANE  # noqa: E731
    item = jnp.dtype(dtype).itemsize
    blocks = 2 * item * (
        bq * lanes(d) + bkM * lanes(d) + bkM * lanes(dv) + bq * lanes(dv)
    )
    carry = 4 * bq * (lanes(dv) + 2 * _LANE)
    return blocks + carry + 3 * 4 * bq * bk


def _kvgrid_vmem_limit(bq, bk, bkM, d, dv, dtype):
    """``vmem_limit_bytes`` for the kvgrid forward: None (Mosaic's scoped
    default, what the train cells' tiles have always compiled under) while
    :func:`_kvgrid_vmem_need` stays well inside it, else twice the
    estimate, capped under the v5e's 128 MiB."""
    need = _kvgrid_vmem_need(bq, bk, bkM, d, dv, dtype)
    if need <= (_SCOPED_VMEM_BYTES * 3) // 4:
        return None
    return min(2 * need, 96 * 1024 * 1024)


def kvgrid_tiles(d: int, dv: int, dtype) -> dict:
    """``block_q`` and ``block_k`` for a forward-only ``kvgrid`` call over
    a long sequence at many heads (a prefill), from the head widths and
    the type: the largest tiles, keys twice the queries and at most the
    kernel's 2,048-row DMA granule, whose :func:`_kvgrid_vmem_need` stays
    inside a quarter of the v5e's 128 MiB.  At 192-wide bf16 keys over
    128-wide values that is 1,024 x 2,048, the best of eleven sizes tried
    there at 2,048 to 8,192 tokens (PERF.md section 6, PR 32); a shorter
    sequence clamps them (:func:`_blocks`)."""
    bk = 2048
    while bk > 256 and _kvgrid_vmem_need(
        bk // 2, bk, bk, d, dv, dtype
    ) > 32 * 1024 * 1024:
        bk //= 2
    return {"block_q": bk // 2, "block_k": bk}


def _to_bhd(x, t_pad):
    """(B, T, H, D) -> (B*H, T_pad, D)."""
    b, t, h, d = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    return x


def _from_bhd(x, b, h, t):
    return x[:, :t].reshape(b, h, t, x.shape[-1]).transpose(0, 2, 1, 3)


def _flash_fwd_impl(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
    emit_lse: bool = False,
    variant: str | None = None,
):
    """(B, Tq, H, D) x (B, Tk, H, D)^2 -> fused attention out, plus the
    per-row logsumexp (B*H, Tq_pad) when ``emit_lse`` (else None) — the
    primal/inference path skips that extra HBM store entirely.

    ``variant``: "loop" (carry-serialized fori_loop), "pipelined"
    (software-pipelined fori_loop), or "kvgrid" (k/v walk as a grid axis
    with VMEM scratch carry — see ``_flash_kernel_kvgrid``).
    """
    if variant is None:
        variant = DEFAULT_FWD_VARIANT
    if variant not in ("loop", "pipelined", "kvgrid"):
        raise ValueError(f"unknown flash variant {variant!r}")
    b, tq, h, d = q.shape
    tk = k.shape[1]
    # values may be narrower or wider than queries and keys (latent
    # attention's expanded form: 192-wide q and k over 128-wide v); only
    # the kvgrid forward, whose kernel never names a width, takes that
    dv = v.shape[-1]
    if dv != d and variant != "kvgrid":
        raise ValueError(
            f"values {dv} wide under {d}-wide keys need variant='kvgrid' "
            f"(forward only), got {variant!r}"
        )
    interpret = pallas_interpret(interpret)
    bq, bk, tq_pad, tk_pad = _blocks(q, k, block_q, block_k)
    q3, k3, v3 = _to_bhd(q, tq_pad), _to_bhd(k, tk_pad), _to_bhd(v, tk_pad)

    out_shape = [jax.ShapeDtypeStruct((b * h, tq_pad, dv), q.dtype)]
    if variant == "kvgrid":
        out_specs = [pl.BlockSpec((1, bq, dv), lambda bh, i, j: (bh, i, 0))]
        if emit_lse:
            out_shape.append(
                jax.ShapeDtypeStruct((b * h, tq_pad, _LANE), jnp.float32)
            )
            out_specs.append(
                pl.BlockSpec((1, bq, _LANE), lambda bh, i, j: (bh, i, 0))
            )
        # k/v-major DMA granule: up to 4 minor tiles (<= 2048 rows) per
        # grid step, statically unrolled in the kernel — bigger transfers
        # for the pipeline to double-buffer, with per-minor-tile compute
        # skip keeping the causal diagonal cheap
        n_minor = tk_pad // bk
        # default 1: the 2048 cap bounds the UPSIZING only — a single
        # larger-than-2048 minor tile (big block_k) still runs unchanged
        u = next(
            (u for u in (4, 2, 1) if n_minor % u == 0 and bk * u <= 2048), 1
        )
        bkM = bk * u
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_kvgrid_vmem_limit(bq, bk, bkM, d, dv, q.dtype),
        )
        res = pl.pallas_call(
            functools.partial(
                _flash_kernel_kvgrid,
                block_q=bq,
                block_k=bk,
                block_k_major=bkM,
                t_kv=tk_pad,
                t_kv_valid=tk,
                causal=causal,
                scale=scale,
                q_offset=q_offset,
                k_offset=k_offset,
            ),
            out_shape=tuple(out_shape),
            grid=(b * h, tq_pad // bq, tk_pad // bkM),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bkM, d), lambda bh, i, j: (bh, j, 0)),
                pl.BlockSpec((1, bkM, dv), lambda bh, i, j: (bh, j, 0)),
            ],
            out_specs=tuple(out_specs),
            scratch_shapes=[
                pltpu.VMEM((bq, dv), jnp.float32),     # acc
                pltpu.VMEM((bq, _LANE), jnp.float32),  # m
                pltpu.VMEM((bq, _LANE), jnp.float32),  # l
            ],
            compiler_params=compiler_params,
            interpret=interpret,
        )(q3, k3, v3)
    else:
        out_specs = [pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0))]
        if emit_lse:
            out_shape.append(
                jax.ShapeDtypeStruct((b * h, tq_pad, _LANE), jnp.float32)
            )
            out_specs.append(
                pl.BlockSpec((1, bq, _LANE), lambda bh, i: (bh, i, 0))
            )
        if not interpret:
            # k and v are resident whole: the need grows with Tk
            _check_vmem(
                f"forward ({variant})",
                [((bq, d), q.dtype), ((tk_pad, d), k.dtype),
                 ((tk_pad, d), v.dtype), ((bq, d), q.dtype)]
                + ([((bq, _LANE), jnp.float32)] if emit_lse else []),
                q,
            )
        res = pl.pallas_call(
            functools.partial(
                _flash_kernel,
                block_q=bq,
                block_k=bk,
                t_kv=tk_pad,
                t_kv_valid=tk,
                causal=causal,
                scale=scale,
                q_offset=q_offset,
                k_offset=k_offset,
                unroll=_FWD_UNROLL,
                pipeline=variant == "pipelined",
            ),
            out_shape=tuple(out_shape),
            grid=(b * h, tq_pad // bq),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, tk_pad, d), lambda bh, i: (bh, 0, 0)),
                pl.BlockSpec((1, tk_pad, d), lambda bh, i: (bh, 0, 0)),
            ],
            out_specs=tuple(out_specs),
            interpret=interpret,
        )(q3, k3, v3)
    if emit_lse:
        out, lse = res
        # store only one lane's row as the residual (128x smaller); the
        # backward re-broadcasts to the block layout on entry
        return _from_bhd(out, b, h, tq), lse[..., 0]
    return _from_bhd(res[0], b, h, tq), None


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
    has_glse, block_q, block_k, t_kv, t_kv_valid, causal, scale,
    q_offset, k_offset,
):
    dq_ref = rest[-1]
    i = pl.program_id(1)
    # prescale q into the log2 domain (see _flash_kernel); the raw k tile
    # still feeds the final ds @ k matmul, so dq's chain-rule `* scale`
    # at the end is unchanged
    qs = q_ref[0] * (scale * _LOG2E)
    do = do_ref[0].astype(jnp.float32)
    # residual lse is natural-log; shift it into the log2 domain once
    lse2 = lse_ref[0][:, 0:1] * _LOG2E  # (bq, 1) — lane-replicated storage
    # cotangent of the lse output; operand only exists when it was consumed
    glse = rest[0][0][:, 0:1] if has_glse else 0.0
    # delta_i = dout_i . out_i (the softmax-normalizer term)
    delta = jnp.sum(do * o_ref[0].astype(jnp.float32), axis=-1, keepdims=True)
    d = qs.shape[-1]
    n_kb = t_kv // block_k
    if causal:
        hi = q_offset + (i + 1) * block_q - k_offset
        kb_hi = jnp.clip((hi + block_k - 1) // block_k, 0, n_kb)
        lo_vis = q_offset + i * block_q - k_offset + 1
        kb_full = jnp.clip(lo_vis // block_k, 0, n_kb)
    else:
        kb_hi = n_kb
        kb_full = n_kb
    if t_kv_valid < t_kv:
        kb_full = jnp.minimum(kb_full, t_kv_valid // block_k)

    def tile_dq(j, dq, p, kb, vb):
        dp = jax.lax.dot_general(
            do, vb.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # d(lse_i)/d(s_ij) = p_ij, so the lse cotangent adds glse_i * p_ij
        ds = p * (dp - delta + glse)
        return dq + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def loads(j):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            qs, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # log2-domain scores
        return s, kb, vb

    def body_full(j, dq):
        s, kb, vb = loads(j)
        return tile_dq(j, dq, jnp.exp2(s - lse2), kb, vb)

    def body_masked(j, dq):
        s, kb, vb = loads(j)
        kpos = (
            k_offset + j * block_k
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        )
        valid = kpos - k_offset < t_kv_valid
        if causal:
            qpos = (
                q_offset + i * block_q
                + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            )
            valid = valid & (qpos >= kpos)
        p = jnp.where(valid, jnp.exp2(s - lse2), 0.0)
        return tile_dq(j, dq, p, kb, vb)

    dq = lax.fori_loop(0, kb_full, body_full, jnp.zeros((block_q, d), jnp.float32))
    dq = lax.fori_loop(kb_full, kb_hi, body_masked, dq)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
    has_glse, block_q, block_k, t_q, t_kv, t_kv_valid, causal, scale,
    q_offset, k_offset,
):
    glse_ref = rest[0] if has_glse else None
    dk_ref, dv_ref = rest[-2], rest[-1]
    j = pl.program_id(1)
    kb = k_ref[0]
    # log2-domain prescale lives on the k tile here (q appears raw in the
    # final ds^T @ q matmul, so prescaling q would corrupt dk); one
    # (bk x D) multiply per grid step replaces a (bq x bk) score scale per
    # q tile
    kbs = kb * (scale * _LOG2E)
    vb = v_ref[0]
    d = kb.shape[-1]
    n_qb = t_q // block_q
    if causal:
        # first q tile whose last row can see this k tile
        lo = (k_offset + j * block_k - q_offset) // block_q
        qb_lo = jnp.clip(lo, 0, n_qb)
        # first q tile whose FIRST row sees the whole k tile — from there
        # on no causal mask is needed
        full_lo = -(-(k_offset + (j + 1) * block_k - 1 - q_offset) // block_q)
        qb_full_lo = jnp.clip(full_lo, 0, n_qb)
    else:
        qb_lo = 0
        qb_full_lo = 0

    kpos = (
        k_offset + j * block_k
        + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    )
    k_valid = kpos - k_offset < t_kv_valid

    def tiles(i):
        qb = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        ob = o_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse2 = lse_ref[0, pl.ds(i * block_q, block_q), 0:1] * _LOG2E
        glse = (
            glse_ref[0, pl.ds(i * block_q, block_q), 0:1] if has_glse else 0.0
        )
        delta = jnp.sum(do * ob, axis=-1, keepdims=True)
        s = jax.lax.dot_general(
            qb, kbs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # log2-domain scores
        return qb, do, lse2, glse, delta, s

    def accumulate(carry, qb, do, glse, delta, p):
        dk, dv = carry
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vb.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta + glse)
        dk = dk + jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    def body_masked(i, carry):
        qb, do, lse2, glse, delta, s = tiles(i)
        valid = k_valid
        if causal:
            qpos = (
                q_offset + i * block_q
                + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            )
            valid = valid & (qpos >= kpos)
        p = jnp.where(valid, jnp.exp2(s - lse2), 0.0)
        return accumulate(carry, qb, do, glse, delta, p)

    def body_full(i, carry):
        qb, do, lse2, glse, delta, s = tiles(i)
        return accumulate(carry, qb, do, glse, delta, jnp.exp2(s - lse2))

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    if t_kv_valid < t_kv:
        # k padding present: every q tile needs the k-validity mask
        dk, dv = lax.fori_loop(qb_lo, n_qb, body_masked, (dk0, dv0))
    else:
        carry = lax.fori_loop(qb_lo, qb_full_lo, body_masked, (dk0, dv0))
        dk, dv = lax.fori_loop(qb_full_lo, n_qb, body_full, carry)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_impl(
    q, k, v, out, lse, g, g_lse, causal, scale, q_offset, k_offset,
    block_q, block_k, interpret,
):
    """``g``: cotangent of the attention output; ``g_lse``: cotangent of
    the lse output ((B*H, Tq_pad) or None when lse was not consumed)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    interpret = pallas_interpret(interpret)
    bq, bk, tq_pad, tk_pad = _blocks(q, k, block_q, block_k)
    if not interpret:
        n_lse = 2 if g_lse is not None else 1
        # dq walks whole k/v per q tile; dk/dv keeps whole-T q, do, o and
        # the lane-replicated f32 lse (and its cotangent) resident per k
        # tile — the operand set that outgrows VMEM first
        _check_vmem(
            "backward dq",
            [((bq, d), q.dtype)] * 4 + [((tk_pad, d), k.dtype)] * 2
            + [((bq, _LANE), jnp.float32)] * n_lse,
            q,
        )
        _check_vmem(
            "backward dk/dv",
            [((tq_pad, d), q.dtype)] * 3 + [((bk, d), k.dtype)] * 4
            + [((tq_pad, _LANE), jnp.float32)] * n_lse,
            q,
        )
    q3, k3, v3 = _to_bhd(q, tq_pad), _to_bhd(k, tk_pad), _to_bhd(v, tk_pad)
    do3 = _to_bhd(g, tq_pad)
    o3 = _to_bhd(out, tq_pad)
    # residual lse is one row per query; rebuild the lane-replicated block
    # layout the kernels read ([:, 0:1])
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANE))
    has_glse = g_lse is not None
    dq_inputs = [q3, k3, v3, do3, o3, lse]
    if has_glse:
        g_lse = jnp.broadcast_to(
            g_lse.astype(jnp.float32)[..., None], lse.shape
        )
        dq_inputs.append(g_lse)

    common = dict(
        has_glse=has_glse, block_q=bq, block_k=bk, causal=causal,
        scale=scale, q_offset=q_offset, k_offset=k_offset,
    )
    dq_tile_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
        pl.BlockSpec((1, tk_pad, d), lambda bh, i: (bh, 0, 0)),
        pl.BlockSpec((1, tk_pad, d), lambda bh, i: (bh, 0, 0)),
        pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
        pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
        pl.BlockSpec((1, bq, _LANE), lambda bh, i: (bh, i, 0)),
    ]
    if has_glse:
        dq_tile_specs.append(pl.BlockSpec((1, bq, _LANE), lambda bh, i: (bh, i, 0)))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, t_kv=tk_pad, t_kv_valid=tk, **common
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_pad, d), q.dtype),
        grid=(b * h, tq_pad // bq),
        in_specs=dq_tile_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
        interpret=interpret,
    )(*dq_inputs)

    dkv_specs = [
        pl.BlockSpec((1, tq_pad, d), lambda bh, j: (bh, 0, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
        pl.BlockSpec((1, tq_pad, d), lambda bh, j: (bh, 0, 0)),
        pl.BlockSpec((1, tq_pad, d), lambda bh, j: (bh, 0, 0)),
        pl.BlockSpec((1, tq_pad, _LANE), lambda bh, j: (bh, 0, 0)),
    ]
    if has_glse:
        dkv_specs.append(
            pl.BlockSpec((1, tq_pad, _LANE), lambda bh, j: (bh, 0, 0))
        )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, t_q=tq_pad, t_kv=tk_pad, t_kv_valid=tk,
            **common,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, tk_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk_pad, d), v.dtype),
        ),
        grid=(b * h, tk_pad // bk),
        in_specs=dkv_specs,
        out_specs=(
            pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0)),
        ),
        interpret=interpret,
    )(*dq_inputs)

    return (
        _from_bhd(dq, b, h, tq),
        _from_bhd(dk, b, h, tk),
        _from_bhd(dv, b, h, tk),
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash_attention_core(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
    variant,
):
    out, _ = _flash_fwd_impl(
        q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
        variant=variant,
    )
    return out


def _core_fwd(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
    variant,
):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
        emit_lse=True, variant=variant,
    )
    return out, (q, k, v, out, lse)


def _core_bwd(
    causal, scale, q_offset, k_offset, block_q, block_k, interpret, variant,
    res, g,
):
    q, k, v, out, lse = res
    return _flash_bwd_impl(
        q, k, v, out, lse, g, None, causal, scale, q_offset, k_offset,
        block_q, block_k, interpret,
    )


_flash_attention_core.defvjp(_core_fwd, _core_bwd)


# -- variant exposing a differentiable logsumexp output (ring-merge input) --


def _lse_to_btH(lse, b, h, t):
    """(B*H, Tq_pad) row layout -> (B, Tq, H), sentinel -> -inf-like."""
    out = lse[:, :t].reshape(b, h, t).transpose(0, 2, 1)
    # in-kernel sentinel for fully-masked rows is +1e30 (so the backward's
    # exp(s - lse) vanishes); the public meaning is "no mass" = -inf-like
    return jnp.where(out >= -_NEG_INF, _NEG_INF, out)


def _lse_from_btH(g_lse, tq_pad):
    """(B, Tq, H) cotangent -> (B*H, Tq_pad) row layout."""
    b, t, h = g_lse.shape
    g = g_lse.transpose(0, 2, 1).reshape(b * h, t)
    if tq_pad != t:
        g = jnp.pad(g, ((0, 0), (0, tq_pad - t)))
    return g


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash_attention_lse_core(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
    variant,
):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
        emit_lse=True, variant=variant,
    )
    b, tq, h, _ = q.shape
    return out, _lse_to_btH(lse, b, h, tq)


def _lse_core_fwd(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
    variant,
):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
        emit_lse=True, variant=variant,
    )
    b, tq, h, _ = q.shape
    return (out, _lse_to_btH(lse, b, h, tq)), (q, k, v, out, lse)


def _lse_core_bwd(
    causal, scale, q_offset, k_offset, block_q, block_k, interpret, variant,
    res, g,
):
    q, k, v, out, lse = res
    g_out, g_lse = g
    tq_pad = lse.shape[1]
    return _flash_bwd_impl(
        q, k, v, out, lse, g_out, _lse_from_btH(g_lse, tq_pad),
        causal, scale, q_offset, k_offset, block_q, block_k, interpret,
    )


_flash_attention_lse_core.defvjp(_lse_core_fwd, _lse_core_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    k_offset: int = 0,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
    return_lse: bool = False,
    variant: str | None = None,
):
    """Fused attention on (B, Tq, H, D) queries / (B, Tk, H, D) keys-values
    (values may have a width of their own under ``variant="kvgrid"``,
    forward only: the output then has theirs).

    Same contract as ``attention_reference`` (output for the local queries
    in ``q``'s dtype) plus global ``q_offset``/``k_offset`` positions for
    causal masking of shifted blocks.  ``interpret=None`` runs the Mosaic
    kernel on a TPU and the Pallas interpreter on the CPU (so tests run
    there); any other backend is an error
    (``utils.backend.pallas_interpret``).

    VMEM limits, found by AOT compile for v5e at B4 H16 D128 and refused
    here with a ``ValueError`` at trace time (``_check_vmem``) instead of
    Mosaic's stack dump from inside a train step: the ``loop``/``pipelined``
    forward keeps whole k and v resident and stops fitting at f32 T >= 8192
    (bf16 T >= 16384); the dK/dV backward keeps whole-T q, do, o and the
    lane-replicated lse resident and stops fitting at bf16 T >= 8192 and
    f32 T >= 4096.  ``kvgrid`` tiles k/v and has no such forward limit.
    T is the LOCAL length: under ring/zigzag sequence parallelism each hop
    sees T/sp.

    With ``return_lse=True`` also returns the per-row logsumexp of the
    masked scores, shape (B, Tq, H) float32 (fully-masked rows: -1e30) —
    differentiable, which is what lets blockwise consumers (the flash ring
    attention) merge partial attentions exactly.

    ``variant`` selects the forward k-walk structure — identical numerics:
    "loop" (carry-serialized fori_loop; the default via
    ``DEFAULT_FWD_VARIANT`` until a chip measurement picks one),
    "pipelined" (software-pipelined fori_loop: tile j's MXU score matmul
    issued alongside tile j-1's VPU softmax), "kvgrid" (k/v tiles as a
    grid axis with VMEM scratch carry and BlockSpec-DMA'd k/v — Mosaic
    pipelines grid steps).  The backward kernels are shared.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected (B, T, H, D) inputs, got {q.shape}")
    if k.shape[:-1] != v.shape[:-1]:
        raise ValueError(f"k/v shapes differ: {k.shape} vs {v.shape}")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    core = _flash_attention_lse_core if return_lse else _flash_attention_core
    return core(
        q, k, v, causal, float(scale), int(q_offset), int(k_offset),
        int(block_q), int(block_k), interpret,
        str(DEFAULT_FWD_VARIANT if variant is None else variant),
    )
