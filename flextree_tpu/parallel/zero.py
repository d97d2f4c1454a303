"""ZeRO-1 sharded-optimizer training: FlexTree split collectives on the
gradient-sync seam.

The replicated train path keeps a full copy of the optimizer moments on
every data-parallel rank and syncs gradients with a full allreduce — but
FlexTree's phase 1 already *is* a grouped reduce-scatter and phase 2 an
allgather (``parallel/allreduce.py``).  This module splits the step at
that seam (the ROADMAP's "Sharded training workload" item):

1. **reduce-scatter** each gradient bucket over the leaf's FIRST
   replication axis (wire-compressed per hop when a codec is set — the
   regime EQuARX targets, where the quantized payload also shrinks with
   world size);
2. secondary replication axes allreduce only the 1/N **shard**;
3. the AdamW update runs on the owned shard only, against sharded
   moments (``mu``/``nu`` memory drops by the shard-axis size);
4. **all-gather** the updated parameter shards (per bucket, so XLA can
   overlap one bucket's gather with another's optimizer math).

Wire accounting per synced byte ``B`` on the shard axis: the replicated
path moves ``2B(N-1)/N`` (reduce-scatter + allgather of *gradients*); the
sharded path moves ``B(N-1)/N`` of gradients down and ``B(N-1)/N`` of
*parameters* up — identical for f32, but the codec now applies to BOTH
phases (grads down, params up), so the quantized sharded step moves
``~2·r·B(N-1)/N`` bytes (``r`` = wire ratio, ~0.25 for int8) against the
replicated fused f32 baseline's ``2B(N-1)/N`` — the floor
``tests/test_sharded.py::TestWireBytes`` holds on the static count
(``hlo_lint.collective_wire_bytes``).  Parameter quantization is safe because
the authoritative **master copy is sharded f32** (``master_*`` state
entries, lossy codecs only): every rank's working params are
``decode(encode(master))`` of identical bytes, so replicas cannot drift
and the quantization error never accumulates (unlike gradients, which
carry an EF residual for exactly that reason).

Shard layout (the contract ``docs/SHARDED.md`` documents): per LOCAL
leaf (the per-device shard a model-parallel axis may already have
carved), the divisible head splits into ``N`` blocks and the rank at
shard-axis position ``r`` owns block ``schedule.blocks.owned_block(topo,
r)``; the ``< N``-element tail is reduced by one dense collective and
updated REPLICATED on every rank (tails are bias/norm scraps — sharding
them would cost a broadcast to save bytes).  Buckets pack leaf heads
**block-interleaved** (fused block ``b`` = every leaf's block ``b``) so
one fused collective per bucket still yields per-leaf shards — and so
the ring walk keeps each element's per-leaf block association, which is
what makes the sharded step **bitwise equal** to the replicated step for
the identity codec across flat/tree/ring shard topologies
(property-tested in ``tests/test_sharded.py``).  Lonely shard topologies
fall back to the flat tree for the sharded collectives (lonely ranks own
no block; lonely shapes exist for awkward world sizes, not for ZeRO).

Checkpoints of sharded runs are CONSOLIDATED (``make_consolidate_fn`` —
each survivor all-gathers every leaf back to the replicated layout on
device, through the same ``all_gather`` collective the step uses), so a
checkpoint is world-size-independent and the elastic runtime's
shrink-to-survivors re-shards it into any survivor world
(``make_reshard_fn``) — the ``fit`` loop's ``state_pack``/
``state_unpack`` hooks wire this through.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..schedule.blocks import shard_layout
from ..schedule.stages import LonelyTopology, Topology
from ..utils.profiling import comm_span
from .allreduce import _NATIVE_PSUM, all_gather, allreduce, reduce_scatter
from .bucketing import plan_buckets, replication_key, spec_axes

__all__ = [
    "ZeroShard",
    "ZeroLeafPlan",
    "ZeroLayout",
    "build_zero_layout",
    "init_zero_entries",
    "zero_state_specs",
    "zero_reduce_scatter_grads",
    "zero_apply_and_gather",
    "zero_sync_and_update",
    "sharded_grad_norm",
    "maybe_clip_shards",
    "make_consolidate_fn",
    "make_reshard_fn",
]


class ZeroShard:
    """One leaf's sharded gradient: the owned head block plus the
    replicated tail.  Deliberately NOT a registered pytree — tree
    utilities must treat it as a leaf so the overlap engine can carry
    shard trees through its per-segment machinery unchanged."""

    __slots__ = ("tile", "tail")

    def __init__(self, tile, tail):
        self.tile = tile
        self.tail = tail


@dataclasses.dataclass(frozen=True)
class ZeroLeafPlan:
    """Static sharding plan for one gradient/parameter leaf.  All sizes
    are LOCAL (per-device): a model-parallel axis in the leaf's own
    PartitionSpec has already carved the leaf before the optimizer
    sharding sees it."""

    index: int
    axes: tuple[str, ...]  # replication axes (size > 1), mesh order
    model_axes: tuple[str, ...]  # axes in the leaf's own spec, mesh order
    shard_ax: str | None  # axes[0], or None for unsynced leaves
    n: int  # shard-axis size (1 when unsharded)
    size: int  # local element count
    head: int  # (size // n) * n
    tile: int  # head // n — owned elements
    tail: int  # size - head — replicated elements

    @property
    def sharded(self) -> bool:
        return self.shard_ax is not None


@dataclasses.dataclass(frozen=True)
class ZeroLayout:
    """The whole tree's sharding plan (host-level, static).

    Built once at step-build time from parameter shapes, specs and axis
    sizes — deliberately independent of the wire topology, so the state
    SHAPES survive an autotune re-pick; only the block→rank permutation
    (``perm_for``) reads the live topology.
    """

    mesh_axes: tuple[str, ...]
    axis_sizes: Mapping[str, int]
    leaves: tuple[ZeroLeafPlan, ...]

    @property
    def n_sharded(self) -> int:
        return sum(1 for l in self.leaves if l.sharded)

    def perm_for(self, topos: Mapping[str, Any], ax: str) -> tuple[int, ...]:
        """Block owned per shard-axis position on ``ax`` under the
        resolved ``topos``."""
        n = int(self.axis_sizes[ax])
        return shard_layout(_shard_topo(topos.get(ax), n))


def _shard_topo(topo, n: int):
    """The topology the sharded collectives actually run on an axis: the
    configured shape, except ``None`` (the "psum" sentinel) and lonely
    shapes resolve to the flat tree (one grouped XLA collective per
    phase; lonely ranks own no block, so the seam is not shardable)."""
    if topo is None:
        return Topology.flat(n)
    topo = Topology.resolve(n, topo)
    if isinstance(topo, LonelyTopology):
        return Topology.flat(n)
    return topo


def _local_size(shape, spec, axis_sizes: Mapping[str, int]) -> int:
    """Per-device element count of a leaf whose GLOBAL shape is ``shape``
    under PartitionSpec ``spec``."""
    total = 1
    for d in shape:
        total *= int(d)
    denom = 1
    for a in spec_axes(spec):
        denom *= int(axis_sizes.get(a, 1))
    return total // denom


def build_zero_layout(
    params_shapes,
    pspecs,
    mesh_axes,
    axis_sizes: Mapping[str, int],
    local: bool = False,
) -> ZeroLayout:
    """Sharding plan for a parameter tree: each leaf shards over the
    FIRST mesh axis (mesh order) it is replicated on; leaves replicated
    nowhere (covered by model-parallel axes) stay unsharded.

    ``local=False`` (host side) treats ``params_shapes`` as GLOBAL shapes
    and divides by the leaf's own spec axes; ``local=True`` (inside
    ``shard_map``, where tracers already carry per-device shapes) uses
    the sizes as given.
    """
    flat_p, treedef = jax.tree.flatten(params_shapes)
    flat_s = treedef.flatten_up_to(pspecs)
    leaves = []
    for i, (p, spec) in enumerate(zip(flat_p, flat_s)):
        axes = tuple(
            a
            for a in replication_key(spec, mesh_axes)
            if int(axis_sizes.get(a, 1)) > 1
        )
        model_axes = tuple(a for a in mesh_axes if a in set(spec_axes(spec)))
        size = (
            int(p.size) if local else _local_size(p.shape, spec, axis_sizes)
        )
        if axes:
            shard_ax = axes[0]
            n = int(axis_sizes[shard_ax])
        else:
            shard_ax, n = None, 1
        tile = size // n
        leaves.append(
            ZeroLeafPlan(
                i, axes, model_axes, shard_ax, n, size,
                tile * n, tile, size - tile * n,
            )
        )
    return ZeroLayout(tuple(mesh_axes), dict(axis_sizes), tuple(leaves))


# ------------------------------------------------------------ state layout


def _global_len(plan: ZeroLeafPlan, per_device: int, axis_sizes, with_shard_ax):
    mult = 1
    if with_shard_ax and plan.shard_ax is not None:
        mult *= int(axis_sizes[plan.shard_ax])
    for a in plan.model_axes:
        mult *= int(axis_sizes.get(a, 1))
    return per_device * mult


def init_zero_entries(params, layout: ZeroLayout, lossy: bool) -> dict:
    """Sharded-optimizer state entries around a HOST-GLOBAL params tree.

    Moment layout per leaf: ``*_shard`` holds the owned head block (a
    per-device ``(tile,)`` buffer, sharded over ``(shard_ax, *model
    axes)``), ``*_tail`` the replicated <N tail (sharded over the model
    axes only), ``*_rep`` the full leaf for unsynced leaves.  A lossy
    wire codec adds the sharded f32 ``master_*`` parameter copy; it
    initializes to ZEROS and the first step bootstraps it from the (still
    exact) working params — which block a rank owns depends on the wire
    topology, something the step knows and host init deliberately
    doesn't.  Empty slots are zero-size arrays so every entry shares the
    params treedef.
    """
    flat_p, treedef = jax.tree.flatten(params)
    sizes = layout.axis_sizes

    def build(part):
        out = []
        for plan, p in zip(layout.leaves, flat_p):
            if part == "rep":
                out.append(
                    jnp.zeros_like(p)
                    if not plan.sharded
                    else jnp.zeros((0,), jnp.float32)
                )
            elif part == "shard":
                n = (
                    _global_len(plan, plan.tile, sizes, with_shard_ax=True)
                    if plan.sharded
                    else 0
                )
                out.append(jnp.zeros((n,), jnp.float32))
            else:  # tail
                n = (
                    _global_len(plan, plan.tail, sizes, with_shard_ax=False)
                    if plan.sharded
                    else 0
                )
                out.append(jnp.zeros((n,), jnp.float32))
        return treedef.unflatten(out)

    entries = {
        "mu_shard": build("shard"),
        "mu_tail": build("tail"),
        "mu_rep": build("rep"),
        "nu_shard": build("shard"),
        "nu_tail": build("tail"),
        "nu_rep": build("rep"),
    }
    if lossy:
        entries["master_shard"] = build("shard")
        entries["master_tail"] = build("tail")
    return entries


def zero_state_specs(pspecs, layout: ZeroLayout, lossy: bool) -> dict:
    """PartitionSpecs for :func:`init_zero_entries`' trees: owned blocks
    are 1-D buffers sharded over the compound ``(shard_ax, *model
    axes)``; tails over the model axes alone; ``*_rep`` keeps the leaf's
    own spec."""
    flat_s, treedef = jax.tree.flatten(
        pspecs, is_leaf=lambda x: isinstance(x, P) or x is None
    )

    def build(part):
        out = []
        for plan, spec in zip(layout.leaves, flat_s):
            if part == "rep":
                out.append(spec if not plan.sharded else P())
            elif not plan.sharded:
                out.append(P())
            elif part == "shard":
                out.append(P((plan.shard_ax,) + plan.model_axes))
            else:
                out.append(P(plan.model_axes) if plan.model_axes else P())
        return treedef.unflatten(out)

    specs = {
        "mu_shard": build("shard"),
        "mu_tail": build("tail"),
        "mu_rep": build("rep"),
        "nu_shard": build("shard"),
        "nu_tail": build("tail"),
        "nu_rep": build("rep"),
    }
    if lossy:
        specs["master_shard"] = build("shard")
        specs["master_tail"] = build("tail")
    return specs


# -------------------------------------------------------- collective layer


def _interleave_pack(heads: Sequence[jax.Array], n: int) -> jax.Array:
    """Block-interleaved bucket packing: fused block ``b`` is the
    concatenation of every leaf's block ``b``, so one fused collective
    yields per-leaf shards AND each element keeps its per-leaf block
    index (the ring association rule — same packing as the replicated
    fused ring path, which is what keeps the sharded sync bitwise)."""
    cols = [h.reshape(n, -1) for h in heads]
    fused = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return fused.reshape(-1)


def _uninterleave(flat: jax.Array, n: int, widths: Sequence[int]) -> list[jax.Array]:
    """Inverse of :func:`_interleave_pack` for a full (n-block) buffer."""
    rows = flat.reshape(n, -1)
    out, off = [], 0
    for w in widths:
        out.append(lax.slice_in_dim(rows, off, off + w, axis=1).reshape(-1))
        off += w
    return out


def _split_tile(tile: jax.Array, widths: Sequence[int]) -> list[jax.Array]:
    out, off = [], 0
    for w in widths:
        out.append(lax.slice_in_dim(tile, off, off + w, axis=0))
        off += w
    return out


def _rs_wire(fused, ax, topo, codec, step):
    """Phase-1 wire for one packed bucket: returns (owned block, local
    input-quantization residual or None).  Delegates to the split
    collectives — ONE wire implementation, so a codec/salt/residual fix
    there cannot silently diverge from the sharded step (the packed
    bucket is always block-divisible, so the tail path never engages)."""
    if not codec.lossy:
        return reduce_scatter(fused, ax, topo=topo), None
    from .compressed import compressed_reduce_scatter

    return compressed_reduce_scatter(
        fused, ax, topo=topo, codec=codec, step=step, return_residual=True
    )


def _ag_wire(tile, ax, topo, codec, step):
    """Phase-2 wire for one packed bucket of updated param blocks —
    delegates like :func:`_rs_wire`."""
    if not codec.lossy:
        return all_gather(tile, ax, topo=topo)
    from .compressed import compressed_all_gather

    return compressed_all_gather(tile, ax, topo=topo, codec=codec, step=step)


def zero_reduce_scatter_grads(
    grads,
    pspecs,
    mesh_axes,
    topos: Mapping[str, Any],
    *,
    layout: ZeroLayout | None = None,
    bucket_bytes: int | None = None,
    codec="f32",
    step=0,
    return_residual: bool = False,
):
    """Sharded gradient sync, phase 1: one fused reduce-scatter per bucket
    over the shard axis (wire-compressed under a lossy ``codec``), one
    dense collective per bucket for the <N tails, and an allreduce of the
    *shard* over each secondary replication axis — exactly the replicated
    fused sync's per-element reductions, minus the gradient allgather.

    Returns a tree of :class:`ZeroShard` per synced leaf (unsynced leaves
    pass through as plain arrays); with ``return_residual=True`` also the
    per-leaf error-feedback residual tree (the wire's actual first-hop
    encode for the shard axis).  Collective-context function.
    """
    from ..ops.quantize import get_codec

    codec = get_codec(codec)
    flat_g, treedef = jax.tree.flatten(grads)
    flat_s = treedef.flatten_up_to(pspecs)
    axis_sizes = {ax: lax.axis_size(ax) for ax in mesh_axes}
    if layout is None:
        layout = build_zero_layout(
            flat_g, flat_s, mesh_axes, axis_sizes, local=True
        )
    buckets = plan_buckets(
        flat_g, flat_s, mesh_axes, topos=topos, axis_sizes=axis_sizes,
        bucket_bytes=bucket_bytes, codec=codec if codec.lossy else None,
        sharded=True,
    )
    out: list[Any] = list(flat_g)
    residuals = [jnp.zeros_like(g) for g in flat_g] if return_residual else None

    for bi, b in enumerate(buckets):
        plans = [layout.leaves[i] for i in b.indices]
        shard_ax = b.axes[0]
        n = int(axis_sizes[shard_ax])
        topo = _shard_topo(topos.get(shard_ax), n)
        leaves = [flat_g[i].reshape(-1).astype(jnp.float32) for i in b.indices]
        heads = [g[: p.head] for g, p in zip(leaves, plans) if p.tile]
        head_plans = [p for p in plans if p.tile]
        tails = [g[p.head :] for g, p in zip(leaves, plans) if p.tail]
        tail_plans = [p for p in plans if p.tail]
        name = f"ftz_rs_bucket{bi}_{shard_ax}_{len(b.indices)}leaves_{b.nbytes}B"

        tile = None
        with comm_span(name):
            if heads:
                fused = _interleave_pack(heads, n)
                tile, res = _rs_wire(fused, shard_ax, topo, codec, step)
                if return_residual and res is not None:
                    widths = [p.tile for p in head_plans]
                    for p, r in zip(head_plans, _uninterleave(res, n, widths)):
                        flat_res = jnp.zeros((p.size,), jnp.float32)
                        flat_res = flat_res.at[: p.head].set(r)
                        residuals[p.index] = flat_res.reshape(
                            flat_g[p.index].shape
                        ).astype(flat_g[p.index].dtype)
            red_tail = None
            if tails:
                fused_t = tails[0] if len(tails) == 1 else jnp.concatenate(tails)
                red_tail = _NATIVE_PSUM(fused_t, shard_ax)
            # secondary replication axes: sync only the shard (1/N bytes)
            for ax in b.axes[1:]:
                if topos.get(ax) is None:
                    if tile is not None:
                        tile = _NATIVE_PSUM(tile, ax)
                    if red_tail is not None:
                        red_tail = _NATIVE_PSUM(red_tail, ax)
                    continue
                t2 = Topology.resolve(int(axis_sizes[ax]), topos[ax])
                if tile is not None:
                    if codec.lossy:
                        from .compressed import compressed_allreduce

                        tile = compressed_allreduce(
                            tile, ax, topo=t2, codec=codec, step=step
                        )
                    else:
                        tile = allreduce(tile, ax, topo=t2, op="sum")
                if red_tail is not None:
                    red_tail = _NATIVE_PSUM(red_tail, ax)

        tile_parts = (
            _split_tile(tile, [p.tile for p in head_plans])
            if tile is not None
            else []
        )
        tile_by_idx = {p.index: t for p, t in zip(head_plans, tile_parts)}
        tail_parts = (
            _split_tile(red_tail, [p.tail for p in tail_plans]) if tails else []
        )
        tail_by_idx = {p.index: t for p, t in zip(tail_plans, tail_parts)}
        for i in b.indices:
            out[i] = ZeroShard(
                tile_by_idx.get(i, jnp.zeros((0,), jnp.float32)),
                tail_by_idx.get(i, jnp.zeros((0,), jnp.float32)),
            )
    if return_residual:
        return treedef.unflatten(out), treedef.unflatten(residuals)
    return treedef.unflatten(out)


# ----------------------------------------------------------- update + AG


def sharded_grad_norm(shard_tree, pspecs, layout: ZeroLayout):
    """True global L2 norm of a sharded gradient tree: owned head blocks
    partition each leaf's head over the shard axis (psum restores the
    total exactly once); tails are replicated over the shard axis, so
    their square-sum joins WITHOUT that psum; leaf-spec (model-parallel)
    axes psum once per axis-set group, exactly as
    ``train.global_grad_norm``."""
    flat_g, treedef = jax.tree.flatten(
        shard_tree, is_leaf=lambda x: isinstance(x, ZeroShard)
    )
    flat_s = treedef.flatten_up_to(pspecs)
    by_key: dict[tuple, Any] = {}

    def add(key, val):
        by_key[key] = by_key.get(key, jnp.float32(0.0)) + val

    for plan, g, spec in zip(layout.leaves, flat_g, flat_s):
        leaf_axes = spec_axes(spec)
        if isinstance(g, ZeroShard):
            if plan.tile:
                add(
                    (plan.shard_ax,) + leaf_axes,
                    jnp.sum(jnp.square(g.tile.astype(jnp.float32))),
                )
            if plan.tail:
                add(leaf_axes, jnp.sum(jnp.square(g.tail.astype(jnp.float32))))
        else:
            add(leaf_axes, jnp.sum(jnp.square(g.astype(jnp.float32))))
    total = jnp.float32(0.0)
    for axes, sq in by_key.items():
        for ax in axes:
            sq = lax.psum(sq, ax)
        total = total + sq
    return jnp.sqrt(total)


def maybe_clip_shards(
    shard_tree, pspecs, train_cfg, layout: ZeroLayout, metrics: dict | None
):
    """Sharded twin of ``train.maybe_clip_grads``: the true global norm
    from owned shards, recorded and applied.  Values match the replicated
    path to float tolerance (different summation order), so the bitwise
    sharded==replicated contract holds only with clipping off —
    documented in docs/SHARDED.md."""
    if not train_cfg.grad_clip_norm:
        return shard_tree
    if train_cfg.grad_clip_norm < 0:
        raise ValueError(
            f"grad_clip_norm must be positive, got {train_cfg.grad_clip_norm}"
        )
    norm = sharded_grad_norm(shard_tree, pspecs, layout)
    if metrics is not None:
        metrics["grad_norm"] = norm
    scale = jnp.minimum(1.0, train_cfg.grad_clip_norm / jnp.maximum(norm, 1e-12))

    def scl(g):
        if isinstance(g, ZeroShard):
            return ZeroShard(g.tile * scale, g.tail * scale)
        return g * scale.astype(g.dtype)

    return jax.tree.map(scl, shard_tree, is_leaf=lambda x: isinstance(x, ZeroShard))


def zero_apply_and_gather(
    state,
    shard_tree,
    pspecs,
    mesh_axes,
    topos: Mapping[str, Any],
    train_cfg,
    layout: ZeroLayout,
    ok=None,
):
    """Phase 2 of the sharded step: AdamW on the owned shards, then one
    fused parameter all-gather per bucket (wire-compressed under the
    step's codec; every rank decodes identical bytes, and lossy codecs
    update the sharded f32 master copy so the error never accumulates —
    the master bootstraps from the working params at step 0, when they
    are still exact).  Returns the new state dict (params fully
    materialized).  Collective-context function.

    ``ok``: the step's verdict (``train.step_verdict``).  Where it is
    false ``train.adamw_elem`` leaves every owned shard (moments, and the
    master copy of a lossy codec) as it was, so an exact gather returns
    the parameters as they were; a lossy codec's gather, whose rounding
    is keyed by the step, is put back leaf for leaf.
    """
    from ..ops.quantize import get_codec
    from .train import adamw_elem, keep_if_refused, schedule_lr

    codec = get_codec(train_cfg.codec)
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    lr = schedule_lr(train_cfg, step)
    lossy = codec.lossy
    bootstrap = state["step"] == 0  # master_* holds zeros before step 1

    params = state["params"]
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(shard_tree)
    flat_s = treedef.flatten_up_to(pspecs)
    axis_sizes = {ax: lax.axis_size(ax) for ax in mesh_axes}

    def flt(key):
        return treedef.flatten_up_to(state[key])

    mu_sh, mu_tl, mu_rp = flt("mu_shard"), flt("mu_tail"), flt("mu_rep")
    nu_sh, nu_tl, nu_rp = flt("nu_shard"), flt("nu_tail"), flt("nu_rep")
    ma_sh = flt("master_shard") if lossy else [None] * len(flat_p)
    ma_tl = flt("master_tail") if lossy else [None] * len(flat_p)

    new_p = list(flat_p)
    new = {
        k: [None] * len(flat_p)
        for k in (
            "mu_shard", "mu_tail", "mu_rep", "nu_shard", "nu_tail", "nu_rep"
        )
    }
    if lossy:
        new["master_shard"] = [None] * len(flat_p)
        new["master_tail"] = [None] * len(flat_p)

    # per-bucket parameter all-gather: group synced leaves exactly like
    # the gradient reduce-scatter did, so gathers stay fused
    buckets = plan_buckets(
        flat_p, flat_s, mesh_axes, topos=topos, axis_sizes=axis_sizes,
        bucket_bytes=train_cfg.bucket_bytes,
        codec=codec if codec.lossy else None, sharded=True,
    )
    bucketed = {i for b in buckets for i in b.indices}

    # --- unsynced (model-parallel-only) leaves: plain replicated AdamW
    for i, plan in enumerate(layout.leaves):
        if i in bucketed:
            continue
        g = flat_g[i]
        new_p[i], mu, nu = adamw_elem(
            flat_p[i], g.astype(flat_p[i].dtype), mu_rp[i], nu_rp[i], t, lr,
            train_cfg, ok,
        )
        new["mu_rep"][i], new["nu_rep"][i] = mu, nu
        new["mu_shard"][i], new["nu_shard"][i] = mu_sh[i], nu_sh[i]
        new["mu_tail"][i], new["nu_tail"][i] = mu_tl[i], nu_tl[i]
        if lossy:
            new["master_shard"][i] = ma_sh[i]
            new["master_tail"][i] = ma_tl[i]

    for bi, b in enumerate(buckets):
        shard_ax = b.axes[0]
        n = int(axis_sizes[shard_ax])
        topo = _shard_topo(topos.get(shard_ax), n)
        perm = jnp.asarray(layout.perm_for(topos, shard_ax), jnp.int32)
        own_b = perm[lax.axis_index(shard_ax)]

        upd_tiles: list[jax.Array] = []
        head_plans: list[ZeroLeafPlan] = []
        for i in b.indices:
            plan = layout.leaves[i]
            g = flat_g[i]
            p_flat = flat_p[i].reshape(-1).astype(jnp.float32)
            if plan.tile:
                own_block = lax.dynamic_slice_in_dim(
                    p_flat[: plan.head], own_b * plan.tile, plan.tile, axis=0
                )
                p_tile = (
                    jnp.where(bootstrap, own_block, ma_sh[i]) if lossy else own_block
                )
                new_tile, mu, nu = adamw_elem(
                    p_tile, g.tile, mu_sh[i], nu_sh[i], t, lr, train_cfg, ok
                )
                new["mu_shard"][i], new["nu_shard"][i] = mu, nu
                if lossy:
                    new["master_shard"][i] = new_tile
                upd_tiles.append(new_tile)
                head_plans.append(plan)
            else:
                new["mu_shard"][i], new["nu_shard"][i] = mu_sh[i], nu_sh[i]
                if lossy:
                    new["master_shard"][i] = ma_sh[i]
            if plan.tail:
                p_tail = p_flat[plan.head :]
                if lossy:
                    p_tail = jnp.where(bootstrap, p_tail, ma_tl[i])
                new_tail, mu, nu = adamw_elem(
                    p_tail, g.tail, mu_tl[i], nu_tl[i], t, lr, train_cfg, ok
                )
                new["mu_tail"][i], new["nu_tail"][i] = mu, nu
                if lossy:
                    new["master_tail"][i] = new_tail
            else:
                new_tail = jnp.zeros((0,), jnp.float32)
                new["mu_tail"][i], new["nu_tail"][i] = mu_tl[i], nu_tl[i]
                if lossy:
                    new["master_tail"][i] = ma_tl[i]
            new["mu_rep"][i], new["nu_rep"][i] = mu_rp[i], nu_rp[i]
            new_p[i] = ("pending", new_tail)  # filled after the gather

        name = f"ftz_ag_bucket{bi}_{shard_ax}_{len(b.indices)}leaves_{b.nbytes}B"
        full_by_idx: dict[int, jax.Array] = {}
        if upd_tiles:
            packed = (
                upd_tiles[0] if len(upd_tiles) == 1 else jnp.concatenate(upd_tiles)
            )
            with comm_span(name):
                full = _ag_wire(packed, shard_ax, topo, codec, step)
            widths = [p.tile for p in head_plans]
            for p, h in zip(head_plans, _uninterleave(full, n, widths)):
                full_by_idx[p.index] = h
        for i in b.indices:
            plan = layout.leaves[i]
            _, new_tail = new_p[i]
            parts = []
            if plan.tile:
                parts.append(full_by_idx[i])
            if plan.tail:
                # lossy codecs roundtrip the tail through the codec too:
                # the tail never hits the wire, but replicas must hold
                # the SAME deterministic view of the master — the exact
                # f32 tail is that view (every rank computed it
                # identically), so it needs no quantization
                parts.append(new_tail)
            flat_new = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            new_p[i] = flat_new.reshape(flat_p[i].shape).astype(flat_p[i].dtype)

    out = {"params": treedef.unflatten(new_p), "step": step}
    if lossy and ok is not None:
        out["params"] = keep_if_refused(ok, out["params"], params)
    for k, vals in new.items():
        out[k] = treedef.unflatten(vals)
    return out


def zero_clip_apply_and_gather(
    state, shard_tree, new_ef, pspecs, mesh_axes, topos, train_cfg,
    layout: ZeroLayout, metrics: dict,
):
    """The sharded step's tail from the reduce-scattered gradients on:
    (optional) global-norm clipping from shards, the step's verdict on
    ``metrics`` (``train.step_verdict``), then the sharded AdamW, the
    parameter all-gather and the error-feedback residual under it."""
    from .train import keep_if_refused, step_verdict

    shard_tree = maybe_clip_shards(shard_tree, pspecs, train_cfg, layout, metrics)
    ok = step_verdict(metrics)
    new_state = zero_apply_and_gather(
        state, shard_tree, pspecs, mesh_axes, topos, train_cfg, layout, ok
    )
    if new_ef is not None:
        new_state["ef"] = keep_if_refused(ok, new_ef, state["ef"])
    return new_state


def zero_sync_and_update(
    state, grads, pspecs, mesh_axes, topos, train_cfg, layout: ZeroLayout,
    metrics: dict,
):
    """The whole sharded optimizer step: EF merge, per-bucket quantized
    reduce-scatter, (optional) global-norm clipping from shards, sharded
    AdamW, per-bucket parameter all-gather.  Returns the new state.
    The step-family twin of ``sync_with_feedback`` + ``maybe_clip_grads``
    + ``adamw_apply`` — bitwise-equal results for the identity codec.
    """
    from .train import _sync_codec

    codec = _sync_codec(train_cfg)
    new_ef = None
    if codec.lossy:
        v = jax.tree.map(lambda g, e: g + e.astype(g.dtype), grads, state["ef"])
        shard_tree, new_ef = zero_reduce_scatter_grads(
            v, pspecs, mesh_axes, topos, layout=layout,
            bucket_bytes=train_cfg.bucket_bytes, codec=codec,
            step=state["step"], return_residual=True,
        )
    else:
        shard_tree = zero_reduce_scatter_grads(
            grads, pspecs, mesh_axes, topos, layout=layout,
            bucket_bytes=train_cfg.bucket_bytes,
        )
    return zero_clip_apply_and_gather(
        state, shard_tree, new_ef, pspecs, mesh_axes, topos, train_cfg,
        layout, metrics,
    )


# -------------------------------------------------- host-side re-sharding


def make_consolidate_fn(mesh, pspecs, layout: ZeroLayout, grad_topo, lossy: bool):
    """Jitted ``sharded state -> replicated checkpoint state`` converter.

    Every survivor all-gathers each leaf's moment (and master) shards
    back into the replicated layout — on device, through the same
    ``all_gather`` collective the step runs, so the consolidated
    checkpoint is world-size-independent (``{"params", "mu", "nu",
    "step"[, "ef"]}``, restorable by the replicated path too).  With a
    lossy codec the consolidated ``params`` are the f32 MASTER values
    (the authoritative copy) — except at step 0, before the first update
    populated the master, when the working params (still exact) stand in.
    """
    from .train import resolve_axis_topos

    mesh_axes = layout.mesh_axes
    topos = resolve_axis_topos(mesh, mesh_axes, grad_topo)
    in_specs = {"params": pspecs, "step": P()}
    in_specs.update(zero_state_specs(pspecs, layout, lossy))
    out_specs = {"params": pspecs, "mu": pspecs, "nu": pspecs, "step": P()}

    def device_fn(state):
        flat_p, treedef = jax.tree.flatten(state["params"])

        def gather(shard_key, tail_key, rep_key):
            sh = treedef.flatten_up_to(state[shard_key])
            tl = treedef.flatten_up_to(state[tail_key])
            rp = treedef.flatten_up_to(state[rep_key])
            out = []
            for plan, base in zip(layout.leaves, flat_p):
                if not plan.sharded:
                    out.append(rp[plan.index].astype(base.dtype))
                    continue
                topo = _shard_topo(topos.get(plan.shard_ax), plan.n)
                shard = jnp.concatenate([sh[plan.index], tl[plan.index]])
                full = all_gather(
                    shard, plan.shard_ax, topo=topo, out_shape=base.shape
                )
                out.append(full.astype(base.dtype))
            return treedef.unflatten(out)

        out = {
            "mu": gather("mu_shard", "mu_tail", "mu_rep"),
            "nu": gather("nu_shard", "nu_tail", "nu_rep"),
            "step": state["step"],
        }
        if lossy:
            # unsynced leaves have no master — their working params are
            # authoritative, so "params" is the rep source; at step 0 the
            # master is still the zeros placeholder and the (still exact)
            # working params stand in
            gathered = gather("master_shard", "master_tail", "params")
            out["params"] = jax.tree.map(
                lambda m, p: jnp.where(state["step"] == 0, p, m), gathered,
                state["params"],
            )
            out["ef"] = state["ef"]
        else:
            out["params"] = state["params"]
        return out

    if lossy:
        in_specs["ef"] = pspecs
        out_specs["ef"] = pspecs

    return jax.jit(
        jax.shard_map(
            device_fn, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
            check_vma=False,
        )
    )


def make_reshard_fn(mesh, pspecs, layout: ZeroLayout, grad_topo, lossy: bool):
    """Jitted ``replicated checkpoint state -> sharded state`` converter
    for ``layout``'s (possibly different) world — the live
    shrink-to-survivors re-shard: every survivor re-partitions the full
    CRC-verified checkpoint into its newly owned blocks."""
    from .train import resolve_axis_topos

    mesh_axes = layout.mesh_axes
    topos = resolve_axis_topos(mesh, mesh_axes, grad_topo)
    in_specs = {"params": pspecs, "mu": pspecs, "nu": pspecs, "step": P()}
    out_specs = {"params": pspecs, "step": P()}
    out_specs.update(zero_state_specs(pspecs, layout, lossy))
    if lossy:
        in_specs["ef"] = pspecs
        out_specs["ef"] = pspecs

    def device_fn(state):
        flat_p, treedef = jax.tree.flatten(state["params"])

        def split(tree):
            flat = treedef.flatten_up_to(tree)
            shards, tails, reps = [], [], []
            for plan, v in zip(layout.leaves, flat):
                if not plan.sharded:
                    shards.append(jnp.zeros((0,), jnp.float32))
                    tails.append(jnp.zeros((0,), jnp.float32))
                    reps.append(v)
                    continue
                perm = jnp.asarray(
                    layout.perm_for(topos, plan.shard_ax), jnp.int32
                )
                own_b = perm[lax.axis_index(plan.shard_ax)]
                fv = v.reshape(-1).astype(jnp.float32)
                shards.append(
                    lax.dynamic_slice_in_dim(
                        fv[: plan.head], own_b * plan.tile, plan.tile, axis=0
                    )
                    if plan.tile
                    else jnp.zeros((0,), jnp.float32)
                )
                tails.append(fv[plan.head :])
                reps.append(jnp.zeros((0,), jnp.float32))
            return (
                treedef.unflatten(shards),
                treedef.unflatten(tails),
                treedef.unflatten(reps),
            )

        mu_s, mu_t, mu_r = split(state["mu"])
        nu_s, nu_t, nu_r = split(state["nu"])
        out = {
            "params": state["params"],
            "step": state["step"],
            "mu_shard": mu_s, "mu_tail": mu_t, "mu_rep": mu_r,
            "nu_shard": nu_s, "nu_tail": nu_t, "nu_rep": nu_r,
        }
        if lossy:
            ma_s, ma_t, _ = split(state["params"])
            out["master_shard"], out["master_tail"] = ma_s, ma_t
            out["ef"] = state["ef"]
        return out

    return jax.jit(
        jax.shard_map(
            device_fn, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
            check_vma=False,
        )
    )
