"""Gradient bucketing/fusion for the FlexTree gradient sync.

The reference's whole value proposition is amortizing per-message latency
across the fabric (``cost_model/CostModel.h``), and a transformer gradient
tree hands the sync a tail of tiny bias/layernorm leaves — every leaf
synced alone pays the full per-stage launch+latency term (measured ~3.6 ms
per extra dispatch on the bench host, WINS.md).  The standard fix is
message fusion: pack leaves into a flat bucket and run ONE scheduled
collective per bucket — k small leaves pay ``k * (launch + latency)``
per-leaf, one fused bucket pays it once.

Fusion is not free, and on the TPU it is dear: a bucket is a flat copy of
its leaves, and there a flatten is a copy.  A 2-D f32 array lives in
(8, 128) tiles and its 1-D view in another tiling, so ``reshape(-1)``,
``concatenate`` and the slices and reshapes that take a bucket apart again
are each a pass through HBM (PERF.md §6, PR 29: 29 ms of a 305 ms step in
the four-chip cell, and the optimizer reading its gradients out of slices
of a flat buffer besides).  So packing pays only for leaves under the size
at which it still lowers the cost the planner prices
(``planner.choose_in_place_bytes``: where a leaf's own byte time reaches
twice a collective's fixed cost — 566 KB on the default ICI constants).  A
leaf of that size or more is a bucket of its own: it is handed to
``allreduce`` as it is, which keeps it in its own shape wherever its
leading dimension divides by the axis size
(``allreduce._tree_keeps_shape``).  In a model of this repo's widths that is
every matrix; what is packed is the norm scales.  The α-β decomposition
behind both sizes is the time-cost model of arXiv:2409.04202; they come
from the calibrated planner (``planner.choose_bucket_bytes`` for the packed
buckets' cap), not from magic constants.

Grouping: leaves fuse only when they agree on **(replication-axis-set,
dtype)** — the axis set because each bucket runs exactly one allreduce
sequence (a leaf synced over ``(dp, sp)`` cannot share a buffer with one
synced over ``(dp, sp, tp)``), the dtype because the flat buffer has one.
:func:`replication_key` is the shared helper both this module and
``train.global_grad_norm``'s axis-set grouping use.

**Bitwise identity** with the per-leaf sync is a hard design constraint
(the per-leaf path stays as the A/B oracle): it holds because, per mesh
axis, every element keeps the exact cross-rank reduction association it
had per-leaf:

- *tree/flat stages* (``psum_scatter``/``all_gather``) reduce elementwise
  across a rank group — the association is position-independent, so
  packing leaves into one buffer cannot change any element's value;
- *tails*: each leaf's <N-element remainder is fused into ONE dense
  ``psum`` per bucket (vs one per leaf) — ``psum`` is elementwise, so
  fusing tails is also value-preserving, and which elements are tail
  elements is decided per leaf exactly as ``_split_main_tail`` does;
- *ring*: the ring's accumulation order for an element depends on its
  block index ``b = pos // (size // N)``, so naive concatenation WOULD
  change values.  Ring buckets therefore pack **block-interleaved** —
  fused block ``b`` is the concatenation of every leaf's block ``b`` —
  which preserves each element's block index and hence its association.

Lonely (``m+l``) topologies interleave a positional buddy fold with the
ppermute-ring stage machinery and are not position-independent in any
packing; buckets fall back to per-leaf sync there (lonely shapes exist for
awkward world sizes, not for throughput — WINS.md).  The bucketed path is
sum-only, which is all a gradient sync needs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..schedule.stages import LonelyTopology, Topology
from ..utils.profiling import comm_span
from .allreduce import _NATIVE_PSUM, allreduce, ring_allreduce, tree_allreduce

__all__ = [
    "spec_axes",
    "replication_key",
    "Bucket",
    "plan_buckets",
    "plan_counts",
    "bucketed_sync_grads",
    "DEFAULT_MAX_BUCKET_BYTES",
    "CPU_MAX_BUCKET_BYTES",
]

#: Memory cap on a fused flat buffer when the planner-derived size is used —
#: a bucket materializes one packed copy of its leaves, so an unbounded
#: bucket would double peak gradient memory for the largest group.  It
#: bounds the PACKED buckets only: under the derived plan a leaf large
#: enough to matter here goes alone and is never copied.
DEFAULT_MAX_BUCKET_BYTES = 64 << 20

#: Planner-derived cap on CPU backends.  The alpha-beta chooser only prices
#: dispatch + bytes, and on a one-core CPU host it lands on one giant
#: bucket, which ran slower inside the train step than per-leaf sync
#: while 64-128 KiB buckets ran faster: in-step, the fused pack ->
#: collective -> unpack -> AdamW chain must stay cache-hot, a locality
#: term the dispatch model cannot see.  (A CPU observation; the chip's cap
#: is ``DEFAULT_MAX_BUCKET_BYTES``.)
CPU_MAX_BUCKET_BYTES = 128 << 10


def _default_max_bucket_bytes() -> int:
    """Backend-resolved cap for the planner-derived bucket size (the same
    per-backend-constants pattern as ``planner.calibrate.default_params``)."""
    try:
        backend = jax.default_backend()
    except Exception:  # no backend initialized (e.g. pure planning tests)
        backend = "cpu"
    return CPU_MAX_BUCKET_BYTES if backend == "cpu" else DEFAULT_MAX_BUCKET_BYTES


def spec_axes(spec) -> tuple[str, ...]:
    """Mesh axes a ``PartitionSpec`` *names* (sorted) — the axes the leaf is
    sharded over.  ``None`` (fully replicated) names no axes."""
    names: set[str] = set()
    for entry in tuple(spec) if spec is not None else ():
        if entry is None:
            continue
        names.update(entry if isinstance(entry, (tuple, list)) else (entry,))
    return tuple(sorted(names))


def replication_key(spec, mesh_axes) -> tuple[str, ...]:
    """Mesh axes a parameter with PartitionSpec ``spec`` is *replicated* on,
    in ``mesh_axes`` order — the axes its gradient must be allreduced over,
    and the grouping key for bucketing.  Complement of :func:`spec_axes`
    within ``mesh_axes``."""
    used = set(spec_axes(spec))
    return tuple(a for a in mesh_axes if a not in used)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fused sync unit: ``indices`` into the flattened gradient leaves
    (flat-tree order), all sharing ``axes`` (replication axes to reduce
    over, mesh order) and ``dtype``."""

    axes: tuple[str, ...]
    dtype: str
    indices: tuple[int, ...]
    nbytes: int

    @property
    def packed(self) -> bool:
        """False for a leaf that goes alone: it is handed to ``allreduce``
        in its own shape and no flat buffer is built around it."""
        return len(self.indices) > 1


def plan_buckets(
    leaves: Sequence[Any],
    specs: Sequence[Any],
    mesh_axes,
    topos: Mapping[str, Any] | None = None,
    axis_sizes: Mapping[str, int] | None = None,
    bucket_bytes: int | None = None,
    params=None,
    max_bucket_bytes: int | None = None,
    codec=None,
    sharded: bool = False,
) -> tuple[Bucket, ...]:
    """Partition flattened gradient leaves into sync buckets.

    ``leaves`` only need ``.size``/``.dtype`` (abstract values work, so HLO
    tests can plan without materializing).  Leaves group by
    ``(replication_key, dtype)`` preserving flat order.  Groups with an
    empty axis set (leaves sharded over every mesh axis) are skipped — they
    need no sync.

    ``bucket_bytes=None`` derives the plan per group from the calibrated
    cost model.  A leaf of at least ``planner.choose_in_place_bytes`` (the
    size at which packing stops lowering the predicted cost, from the
    group's own topologies) is a bucket of its own and keeps its shape;
    the leaves under it pack greedily, in flat order and across the large
    leaves lying between them (the sums are elementwise, so which small
    leaves share a buffer changes no value), up to
    ``planner.choose_bucket_bytes`` of their own total, capped at
    ``max_bucket_bytes`` — backend-resolved when None: in-step cache
    locality caps CPU hosts at ``CPU_MAX_BUCKET_BYTES``, see the constants
    above.  The sharded (ZeRO) and lossy-codec plans own their bucket's
    layout and keep packing every leaf.

    An explicit ``bucket_bytes`` is a plain cap: consecutive leaves of a
    group pack greedily until the bucket reaches it.
    """
    from ..planner.choose import choose_in_place_bytes

    if max_bucket_bytes is None:
        max_bucket_bytes = _default_max_bucket_bytes()
    groups: dict[tuple[tuple[str, ...], str], list[int]] = {}
    for i, (g, spec) in enumerate(zip(leaves, specs)):
        axes = replication_key(spec, mesh_axes)
        if axes and axis_sizes is not None:
            axes = tuple(a for a in axes if axis_sizes.get(a, 1) > 1)
        if not axes:
            continue
        groups.setdefault((axes, jnp.dtype(g.dtype).name), []).append(i)

    buckets: list[Bucket] = []
    for (axes, dtype), idxs in groups.items():
        itemsize = jnp.dtype(dtype).itemsize
        sizes = {i: leaves[i].size * itemsize for i in idxs}
        group: list[Bucket] = []
        cap = bucket_bytes
        if cap is None:
            cost_topos = _cost_topos(axes, topos or {}, axis_sizes or {})
            if cost_topos and codec is None and not sharded:
                alone = choose_in_place_bytes(cost_topos, params=params)
                group += [
                    Bucket(axes, dtype, (i,), sizes[i])
                    for i in idxs if sizes[i] >= alone
                ]
                idxs = [i for i in idxs if sizes[i] < alone]
            cap = _derived_bucket_bytes(
                sum(sizes[i] for i in idxs), len(idxs), cost_topos, params,
                max_bucket_bytes, codec, sharded=sharded,
            )
        cap = max(int(cap), 1)
        cur: list[int] = []
        cur_bytes = 0
        for i in idxs:
            if cur and cur_bytes + sizes[i] > cap:
                group.append(Bucket(axes, dtype, tuple(cur), cur_bytes))
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += sizes[i]
        if cur:
            group.append(Bucket(axes, dtype, tuple(cur), cur_bytes))
        # a group's buckets in the flat-tree order of their first leaves
        buckets += sorted(group, key=lambda b: b.indices[0])
    return tuple(buckets)


def plan_counts(buckets: Sequence[Bucket]) -> dict[str, int]:
    """How often the plan keeps a leaf in its own shape: leaves and bytes
    that go alone (``in_place_*``) against those packed into a shared flat
    buffer (``packed_*``)."""
    alone = [b for b in buckets if not b.packed]
    packed = [b for b in buckets if b.packed]
    return {
        "in_place_leaves": len(alone),
        "packed_leaves": sum(len(b.indices) for b in packed),
        "in_place_bytes": sum(b.nbytes for b in alone),
        "packed_bytes": sum(b.nbytes for b in packed),
    }


def _cost_topos(axes, topos, axis_sizes) -> list:
    """The resolved topologies the planner prices a group's sync with, one
    per replication axis; the ``"psum"`` sentinel (None) is priced as the
    flat tree of its axis, and an axis of unknown size is left out."""
    cost_topos = []
    for ax in axes:
        n = int(axis_sizes.get(ax, 0)) or None
        topo = topos.get(ax)
        if topo is None:  # the "psum" sentinel: one fused native collective
            if n is None:
                continue
            topo = Topology.flat(n)
        cost_topos.append(Topology.resolve(n or topo.num_nodes, topo))
    return cost_topos


def _derived_bucket_bytes(
    total_bytes, n_leaves, cost_topos, params, max_bucket_bytes,
    codec=None, sharded: bool = False,
):
    """Planner-derived bucket size for one (axes, dtype) group: the sync
    runs one allreduce per axis per bucket, so the launch term the chooser
    amortizes is the sum of the per-axis fixed costs.  ``codec`` makes the
    chooser's byte terms wire-accurate for compressed syncs (fewer wire
    bytes per bucket -> the argmin shifts toward fewer, larger buckets).
    ``sharded`` prices the ZeRO split schedule instead (grad
    reduce-scatter + param all-gather on the first axis, shard-sized
    allreduce on the rest — ``planner.choose_bucket_bytes``)."""
    from ..planner.choose import choose_bucket_bytes

    if not cost_topos:
        return max_bucket_bytes
    derived = choose_bucket_bytes(
        total_bytes, cost_topos, n_leaves=n_leaves, params=params, codec=codec,
        sharded=sharded,
    )
    return min(derived, max_bucket_bytes)


def _unpack(fused, segments):
    """Split a fused flat buffer back into per-leaf pieces of ``segments``
    element counts."""
    out, off = [], 0
    for s in segments:
        out.append(lax.slice_in_dim(fused, off, off + s, axis=0))
        off += s
    return out


def _fused_native_psum(leaves, axis_name):
    """Fuse the ``"psum"``-sentinel axis: one native all-reduce per bucket.
    ``psum`` is elementwise across ranks, so fusion is value-preserving."""
    if len(leaves) == 1:  # a leaf that goes alone keeps its shape
        return [_NATIVE_PSUM(leaves[0], axis_name)]
    flats = [g.reshape(-1) for g in leaves]
    red = _NATIVE_PSUM(jnp.concatenate(flats), axis_name)
    return [
        p.reshape(g.shape) for p, g in zip(_unpack(red, [f.size for f in flats]), leaves)
    ]


def _fused_axis_allreduce(leaves, axis_name, topo, chunks: int = 1):
    """One FlexTree allreduce over ``axis_name`` for a whole bucket.

    Packs the leaves' divisible heads into one scheduled collective and
    their <N-element remainders into ONE dense tail collective (vs one per
    leaf via ``_split_main_tail``), preserving each element's per-leaf
    reduction association — see the module docstring for why each packing
    is bitwise-safe.
    """
    n = lax.axis_size(axis_name)
    if n <= 1:
        return list(leaves)
    topo = Topology.resolve(n, topo)
    if isinstance(topo, LonelyTopology):
        # positional buddy fold: not packing-invariant — per-leaf fallback
        return [allreduce(g, axis_name, topo=topo, op="sum") for g in leaves]
    if len(leaves) == 1:
        return [allreduce(leaves[0], axis_name, topo=topo, op="sum", chunks=chunks)]

    flats = [g.reshape(-1) for g in leaves]
    mains = [(v.size // n) * n for v in flats]
    head_ids = [i for i, m in enumerate(mains) if m]
    tail_ids = [i for i, (v, m) in enumerate(zip(flats, mains)) if v.size > m]
    heads_out: dict[int, jax.Array] = {}
    tails_out: dict[int, jax.Array] = {}

    if head_ids:
        if topo.is_ring:
            # block-interleaved packing: fused block b = [leaf block b ...],
            # so each element keeps its ring block index (= association)
            cols = [flats[i][: mains[i]].reshape(n, -1) for i in head_ids]
            widths = [c.shape[1] for c in cols]
            fused = jnp.concatenate(cols, axis=1).reshape(-1)
            red = ring_allreduce(fused, axis_name, op="sum").reshape(n, -1)
            off = 0
            for i, w in zip(head_ids, widths):
                heads_out[i] = red[:, off : off + w].reshape(-1)
                off += w
        else:
            segs = [flats[i][: mains[i]] for i in head_ids]
            fused = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
            red = tree_allreduce(fused, axis_name, topo=topo, op="sum", chunks=chunks)
            for i, piece in zip(head_ids, _unpack(red, [s.size for s in segs])):
                heads_out[i] = piece
    if tail_ids:
        segs = [flats[i][mains[i] :] for i in tail_ids]
        fused = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
        red = _NATIVE_PSUM(fused, axis_name)
        for i, piece in zip(tail_ids, _unpack(red, [s.size for s in segs])):
            tails_out[i] = piece

    out = []
    for i, g in enumerate(leaves):
        h, t = heads_out.get(i), tails_out.get(i)
        if h is None and t is None:
            out.append(g)  # zero-size leaf
        elif t is None:
            out.append(h.reshape(g.shape))
        elif h is None:
            out.append(t.reshape(g.shape))
        else:
            out.append(jnp.concatenate([h, t]).reshape(g.shape))
    return out


def _unpack_to(leaves, fused):
    """Reshape a fused flat f32 buffer back into the leaves' shapes/dtypes."""
    return [
        p.reshape(g.shape).astype(g.dtype)
        for p, g in zip(_unpack(fused, [g.size for g in leaves]), leaves)
    ]


def _fused_compressed_bucket(leaves, axes, topos, codec, chunks, step, bi, nbytes):
    """Lossy-codec bucket sync: pack the leaves into one flat buffer, run
    one ``compressed_allreduce`` per axis, unpack.  No bitwise contract
    (that belongs to the identity codec), so no block-interleaving or
    split-tail choreography is needed — the compressed collective handles
    its own sub-N tail in exact f32.  Returns (synced leaves, per-leaf
    input-quantization residuals): wire-exact when the FIRST axis is
    compressed (only that axis sees this rank's local data — a residual
    taken after an exact psum axis would be re-injected once per rank of
    that axis next step), else the canonical ``x - C(x)``.  Same rule as
    the per-leaf path in ``train.sync_grads``."""
    from .compressed import compressed_allreduce, local_residual

    from ..obs import bucket_provenance

    flats = [g.reshape(-1).astype(jnp.float32) for g in leaves]
    fused = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    res = None
    for k, ax in enumerate(axes):
        name = f"ftq_bucket{bi}_{ax}_{len(leaves)}leaves_{nbytes}B"
        prov = bucket_provenance(
            (ax,), topos, nbytes, n_leaves=len(leaves), codec=codec,
            chunks=chunks,
        )
        with comm_span(name, provenance=prov):
            if topos[ax] is None:
                fused = _NATIVE_PSUM(fused, ax)  # sentinel stays exact f32
            elif res is None and k == 0:
                fused, res = compressed_allreduce(
                    fused, ax, topo=topos[ax], codec=codec, chunks=chunks,
                    step=step, return_residual=True,
                )
            else:
                fused = compressed_allreduce(
                    fused, ax, topo=topos[ax], codec=codec, chunks=chunks,
                    step=step,
                )
    if res is None:
        # first axis was exact (psum sentinel) or no axis at all: canonical
        # residual of the packed input
        src = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        res = local_residual(src, codec, step)
    return _unpack_to(leaves, fused), _unpack_to(leaves, res)


def bucketed_sync_grads(
    grads,
    pspecs,
    mesh_axes,
    topos: Mapping[str, Any],
    bucket_bytes: int | None = None,
    chunks: int = 1,
    params=None,
    codec="f32",
    step=0,
    return_residual: bool = False,
):
    """Bucketed/fused FlexTree gradient sync — the fused twin of
    ``train.sync_grads`` (collective-context function; call inside
    ``shard_map``).

    Semantics are identical (sum each leaf over its replication axes, per
    axis in ``mesh_axes`` order) and the result is bitwise-identical to the
    per-leaf sync; the collective count drops from leaves x stages to
    buckets x stages (+ one fused tail per bucket per axis).
    ``bucket_bytes=None`` derives the plan from the calibrated planner (a
    leaf large enough is a bucket of its own and keeps its shape, the
    small ones are packed: :func:`plan_buckets`);
    ``chunks > 1`` runs each bucket's tree collectives chunk-pipelined.
    Per-bucket ``comm_span`` scopes (``ft_bucket*``) mark each bucket's
    collectives in profiler traces so comm time is attributable per bucket.

    A lossy ``codec`` routes each bucket through ``compressed_allreduce``
    (wire-compressed per hop; the bitwise contract applies to the identity
    codec only); ``return_residual=True`` then also returns the per-leaf
    error-feedback residuals.
    """
    from ..obs import bucket_provenance, record_event
    from ..ops.quantize import get_codec

    codec = get_codec(codec)
    flat_g, treedef = jax.tree.flatten(grads)
    flat_s = treedef.flatten_up_to(pspecs)
    axis_sizes = {ax: lax.axis_size(ax) for ax in mesh_axes}
    buckets = plan_buckets(
        flat_g, flat_s, mesh_axes, topos=topos, axis_sizes=axis_sizes,
        bucket_bytes=bucket_bytes, params=params,
        codec=codec if codec.lossy else None,
    )
    # trace time, and a no-op without a flight recorder
    record_event("bucket_plan", n_buckets=len(buckets), **plan_counts(buckets))
    out = list(flat_g)
    residuals = [jnp.zeros_like(g) for g in flat_g] if return_residual else None
    for bi, b in enumerate(buckets):
        leaves = [out[i] for i in b.indices]
        if codec.lossy:
            leaves, res = _fused_compressed_bucket(
                leaves, b.axes, topos, codec, chunks, step, bi, b.nbytes
            )
            if return_residual:
                for i, r in zip(b.indices, res):
                    residuals[i] = r
        else:
            for ax in b.axes:
                name = f"ft_bucket{bi}_{ax}_{len(b.indices)}leaves_{b.nbytes}B"
                prov = bucket_provenance(
                    (ax,), topos, b.nbytes, n_leaves=len(b.indices),
                    dtype=b.dtype, chunks=chunks, packed=b.packed,
                )
                with comm_span(name, provenance=prov):
                    if topos[ax] is None:
                        leaves = _fused_native_psum(leaves, ax)
                    else:
                        leaves = _fused_axis_allreduce(
                            leaves, ax, topos[ax], chunks
                        )
        for i, g in zip(b.indices, leaves):
            out[i] = g
    out_tree = treedef.unflatten(out)
    if return_residual:
        return out_tree, treedef.unflatten(residuals)
    return out_tree
