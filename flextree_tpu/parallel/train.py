"""Sharded training step: dp x sp x tp over one mesh, FlexTree grad sync.

This is the framework's end-to-end composition — the role the reference
plays inside a host framework when its allreduce interposes on the data-
parallel gradient sync (``mpi_mod.hpp:1167-1171``): here the gradient
allreduce *is* our topology-parameterized collective, and it also provides
the TP partial-sum combine inside the model forward.

Parallelism layout (one ``shard_map`` over a 3-axis mesh):

- ``dp``  — batch dimension; no collective in the forward, gradients are
  summed across it explicitly (the classic gradient allreduce).
- ``sp``  — sequence dimension; ring attention moves K/V around the ring
  in the forward, and its transpose carries the cross-shard gradient
  contributions back automatically.
- ``tp``  — heads / hidden units; column/row-parallel matmuls with the
  row-parallel partials combined by ``flextree_tpu.parallel.allreduce``.

Gradient-sync rule: automatic differentiation of the per-device loss gives,
on every device, the gradient of the *sum of all devices' losses* with
respect to that device's local parameter copy (collective transposes carry
the cross-device terms).  The true gradient of a logically-shared parameter
is the sum over its distinct copies — so each gradient leaf is explicitly
allreduced over exactly the axes its parameter is *replicated* on: tp-
sharded weights sync over (dp, sp); replicated ones over (dp, sp, tp).  The
per-device loss is normalized by the global token count *including* the
tp-fold redundancy, which makes the total differentiated quantity the true
global mean loss.

Optimizer is an inline AdamW (decoupled weight decay); its moments shard
exactly like the parameters, so optimizer memory scales down with TP.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import (
    TransformerConfig,
    cross_entropy_loss,
    forward,
    init_params,
    param_specs,
)
from ..schedule.stages import Topology, TopologyError
from .allreduce import allreduce
from .bucketing import bucketed_sync_grads, replication_key, spec_axes

__all__ = [
    "TrainConfig",
    "init_train_state",
    "state_specs",
    "make_train_step",
    "make_mesh_3d",
    "factor_devices",
    "resolve_axis_topos",
    "sync_grads",
    "sync_with_feedback",
    "maybe_autotune_grad_topo",
    "adamw_apply",
    "copy_state",
    "keeping_state",
    "schedule_lr",
    "global_grad_norm",
    "clip_by_global_norm",
    "maybe_clip_grads",
    "metric_specs",
]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    # topology spec for the gradient-sync allreduce (None -> FT_TOPO/flat).
    # Either one spec — used on every mesh axis whose size matches its
    # product, flat elsewhere — or a dict {axis_name: spec}.  The sentinel
    # "psum" selects the native XLA all-reduce instead of FlexTree — the
    # A/B oracle (and escape hatch) inside the production train step.
    grad_topo: Any = None
    # global-norm gradient clipping (0 = off).  The norm is the TRUE global
    # norm: tp-sharded leaves psum their shard's square-sum over the tp
    # axis before the total (see global_grad_norm).
    grad_clip_norm: float = 0.0
    # learning-rate schedule: "constant", or "warmup_cosine" (linear ramp
    # over warmup_steps, cosine decay to min_lr_frac*lr at total_steps —
    # total_steps required then)
    schedule: str = "constant"
    warmup_steps: int = 0
    total_steps: int = 0
    min_lr_frac: float = 0.1
    # gradient bucketing/fusion (parallel/bucketing.py): the sync packs
    # gradient leaves grouped by (replication-axis-set, dtype) into fused
    # flat buckets and runs ONE FlexTree allreduce per bucket — bitwise-
    # identical to per-leaf, but buckets x stages collectives instead of
    # leaves x stages.  None (default) -> the plan derived from the
    # calibrated planner: a leaf of planner.choose_in_place_bytes or more
    # goes alone, in its own shape (on the TPU a flatten is a copy), the
    # smaller ones pack up to planner.choose_bucket_bytes; 0 -> per-leaf
    # sync (the A/B oracle / escape hatch); > 0 -> explicit bucket-size
    # cap in bytes.
    bucket_bytes: int | None = None
    # chunk-pipelined allreduce: > 1 splits each bucket's tree collective
    # into C chunks with phase-2/phase-1 interleaving (allreduce chunks=C);
    # bitwise-identical for the sum sync, 1 = off.
    grad_chunks: int = 1
    # wire codec for the gradient sync (ops/quantize.py): "f32" (identity,
    # the default — bitwise-identical to the historical sync), "bf16", or
    # "int8" (block-scaled, deterministic stochastic rounding keyed off
    # the step counter).  Lossy codecs carry an EF21-style error-feedback
    # residual in the train state ("ef", zeros at init — see
    # init_train_state / docs/QUANTIZED_COLLECTIVES.md), so the long-run
    # synced gradient converges to exact.
    codec: str = "f32"
    # measured plan autotuner (planner/autotune.py): when True and
    # grad_topo is None, the step builders resolve the sync topology per
    # mesh axis by timing the analytic top-K candidates on the live
    # backend (cached under FLEXTREE_PLAN_CACHE — the second build is a
    # pure cache hit) instead of trusting the cost-model argmin.
    autotune: bool = False
    # readiness-ordered backward/comm overlap (parallel/overlap.py): the
    # dense/MoE steps decompose the backward per layer and fire each
    # gradient bucket's collective as soon as its grads exist (reverse
    # layer order), with bucket boundaries chosen by the planner to
    # equalize per-bucket comm time against the remaining backward
    # compute (planner.choose.choose_overlap_boundaries); the pipeline
    # step schedules its bucket collectives into the post-backward bubble
    # (the scan transpose is a dataflow barrier — docs/OVERLAP.md).
    # Bitwise-identical to the serialized sync for the identity codec;
    # EF/codec semantics carried through unchanged.  False (default) is
    # the historical serialized path, byte-for-byte.
    overlap: bool = False
    # ZeRO-1 sharded-optimizer path (parallel/zero.py, docs/SHARDED.md):
    # optimizer state (and, for lossy codecs, the f32 master param copy)
    # shards over each leaf's FIRST replication axis; the step
    # reduce-scatters gradients (wire-compressed under ``codec``), applies
    # AdamW on the owned shard only, and all-gathers updated parameters
    # per bucket.  Per-rank mu/nu memory drops by the shard-axis size;
    # the quantized sharded step moves ~wire_ratio x the bytes of the
    # replicated fused f32 sync (BOTH phases ride the codec).  For the
    # identity codec the step is BITWISE-equal to the replicated step
    # across flat/tree/ring shard topologies (lonely shapes fall back to
    # the flat tree for the sharded collectives).  Composes with
    # ``overlap`` (per-bucket reduce-scatter fires at grad readiness; the
    # parameter all-gathers overlap the remaining per-bucket optimizer
    # work).  State init/specs need the mesh (init_train_state(mesh=...)).
    shard_optimizer: bool = False


def prime_factors(n: int) -> list[int]:
    """Prime factors of ``n`` by trial division (ascending, with
    multiplicity) — the planner-side twin is
    ``flextree_tpu.planner.factorize``."""
    factors = []
    m, p = n, 2
    while m > 1:
        while m % p == 0:
            factors.append(p)
            m //= p
        p += 1
    return factors


def spread_factors(n: int, n_dims: int, order: list[int] | None = None) -> tuple:
    """Split ``n`` into ``n_dims`` near-balanced dims: largest prime factors
    first, assigned round-robin over ``order`` (default 0..n_dims-1)."""
    if order is None:
        order = list(range(n_dims))
    dims = [1] * n_dims
    for i, f in enumerate(sorted(prime_factors(n), reverse=True)):
        dims[order[i % n_dims]] *= f
    return tuple(dims)


def make_mesh_nd(n_devices: int | None, shape, axis_names) -> Mesh:
    """A mesh of ``shape`` x ``axis_names`` over the first n local devices."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, only {len(devs)} visible")
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {shape} does not cover {n} devices")
    return jax.make_mesh(shape, axis_names, devices=devs[:n])


def factor_devices(n: int) -> tuple[int, int, int]:
    """Split ``n`` devices into a (dp, sp, tp) shape, most-square-first.

    Greedy largest-prime-first assignment cycling dp -> sp -> tp, so 8 ->
    (2, 2, 2), 4 -> (2, 2, 1), 12 -> (3, 2, 2), 1 -> (1, 1, 1).
    """
    return spread_factors(n, 3)


def make_mesh_3d(
    n_devices: int | None = None,
    shape: tuple[int, int, int] | None = None,
    axis_names: tuple[str, str, str] = ("dp", "sp", "tp"),
) -> Mesh:
    """A (dp, sp, tp) mesh over the first ``n_devices`` local devices."""
    if shape is None:
        shape = factor_devices(
            len(jax.devices()) if n_devices is None else n_devices
        )
    return make_mesh_nd(n_devices, shape, axis_names)


def make_train_state(
    params, train_cfg: "TrainConfig | None" = None, *, layout=None
) -> dict:
    """Fresh AdamW state around a parameter pytree (any layout).

    A lossy gradient-sync codec (``train_cfg.codec``) adds the
    error-feedback residual tree ``"ef"`` (zeros, param-shaped): each step
    syncs ``grad + ef`` and stores what the wire's input quantization lost
    back into ``ef``, so no gradient mass is ever dropped — only delayed.

    ``train_cfg.shard_optimizer`` replaces the full ``mu``/``nu`` trees
    with the sharded layout of ``parallel.zero`` (owned head block +
    replicated tail per leaf, plus the f32 master shards for lossy
    codecs) — pass the :class:`~flextree_tpu.parallel.zero.ZeroLayout`
    built for the mesh (``zero_layout_for`` / ``init_train_state(mesh=)``).
    """
    sharded = train_cfg is not None and train_cfg.shard_optimizer
    if sharded:
        from .zero import init_zero_entries

        if layout is None:
            raise ValueError(
                "shard_optimizer=True needs the mesh's ZeroLayout — call "
                "init_train_state(..., mesh=mesh) or pass layout="
            )
        state = {"params": params, "step": jnp.zeros((), jnp.int32)}
        state.update(
            init_zero_entries(params, layout, _sync_codec(train_cfg).lossy)
        )
    else:
        state = {
            "params": params,
            "mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "step": jnp.zeros((), jnp.int32),
        }
    if train_cfg is not None and _sync_codec(train_cfg).lossy:
        state["ef"] = jax.tree.map(jnp.zeros_like, params)
    return state


def _sync_codec(train_cfg: "TrainConfig"):
    from ..ops.quantize import get_codec

    return get_codec(train_cfg.codec)


def validate_tp(model_cfg: TransformerConfig, tp_size: int) -> None:
    """Shared precondition check for every train-step builder."""
    if model_cfg.d_model % model_cfg.n_heads or model_cfg.n_heads % tp_size:
        raise ValueError(
            f"n_heads={model_cfg.n_heads} must divide d_model="
            f"{model_cfg.d_model} and be divisible by tp={tp_size}"
        )
    if model_cfg.d_ff % tp_size:
        raise ValueError(
            f"d_ff={model_cfg.d_ff} must be divisible by tp={tp_size}"
        )


def zero_layout_for(mesh: Mesh, params_shapes, pspecs, axis_names):
    """The mesh's :class:`~flextree_tpu.parallel.zero.ZeroLayout` for a
    parameter tree — shared by state init, spec building and the step
    builders so the three can never disagree on who owns which block."""
    from .zero import build_zero_layout

    axis_sizes = {ax: int(mesh.shape[ax]) for ax in axis_names}
    return build_zero_layout(params_shapes, pspecs, tuple(axis_names), axis_sizes)


def init_train_state(
    key,
    cfg: TransformerConfig,
    train_cfg: "TrainConfig | None" = None,
    mesh: Mesh | None = None,
    axis_names: tuple[str, str, str] = ("dp", "sp", "tp"),
) -> dict:
    params = init_params(key, cfg)
    layout = None
    if train_cfg is not None and train_cfg.shard_optimizer:
        if mesh is None:
            raise ValueError("shard_optimizer=True: init_train_state needs mesh=")
        layout = zero_layout_for(
            mesh, params, param_specs(cfg, axis_names[-1]), axis_names
        )
    return make_train_state(params, train_cfg, layout=layout)


def make_state_specs(
    pspecs, train_cfg: "TrainConfig | None" = None, *, layout=None
) -> dict:
    """Optimizer-state specs around parameter specs (moments shard alike;
    the error-feedback residual of a lossy sync codec shards alike too).
    Under ``shard_optimizer`` the moment specs come from the
    ``ZeroLayout`` instead (owned blocks ``P(shard_ax)``, tails ``P()``)."""
    if train_cfg is not None and train_cfg.shard_optimizer:
        from .zero import zero_state_specs

        if layout is None:
            raise ValueError("shard_optimizer=True needs layout= for specs")
        specs = {"params": pspecs, "step": P()}
        specs.update(
            zero_state_specs(pspecs, layout, _sync_codec(train_cfg).lossy)
        )
    else:
        specs = {"params": pspecs, "mu": pspecs, "nu": pspecs, "step": P()}
    if train_cfg is not None and _sync_codec(train_cfg).lossy:
        specs["ef"] = pspecs
    return specs


def state_specs(
    cfg: TransformerConfig,
    tp_axis: str | None = "tp",
    train_cfg: "TrainConfig | None" = None,
    mesh: Mesh | None = None,
    axis_names: tuple[str, str, str] = ("dp", "sp", "tp"),
) -> dict:
    pspecs = param_specs(cfg, tp_axis)
    layout = None
    if train_cfg is not None and train_cfg.shard_optimizer:
        if mesh is None:
            raise ValueError("shard_optimizer=True: state_specs needs mesh=")
        shapes = jax.eval_shape(
            lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
        )
        layout = zero_layout_for(mesh, shapes, pspecs, axis_names)
    return make_state_specs(pspecs, train_cfg, layout=layout)


def resolve_axis_topos(mesh: Mesh, mesh_axes, grad_topo) -> dict:
    """Per-axis FlexTree topology for the gradient sync.

    ``grad_topo``: a single spec (used on each axis whose size its product
    matches, flat elsewhere) or a dict ``{axis_name: spec}``.
    """

    def axis_topo(ax):
        spec = grad_topo
        if isinstance(spec, dict):
            spec = spec.get(ax)
        if spec == "psum":
            return None  # sentinel: native XLA all-reduce on this axis
        from ..schedule.ir import is_ir_family_spec

        if is_ir_family_spec(spec):
            # the train sync seam (bucketing, cost model, zero layout)
            # prices and executes legacy topologies only — refusing loudly
            # beats the flat fallback silently discarding a measured plan
            # (IR families on this seam are the named ROADMAP follow-up)
            raise TopologyError(
                f"grad_topo {spec!r} on axis {ax!r}: IR families "
                f"(swing/generalized) are not supported on the train sync "
                f"seam yet — use a widths-vector spec or 'psum'"
            )
        try:
            return Topology.resolve(mesh.shape[ax], spec)
        except TopologyError:
            return Topology.flat(mesh.shape[ax])

    return {ax: axis_topo(ax) for ax in mesh_axes}


@jax.named_scope("ft_grad_sync")
def sync_grads(
    grads,
    pspecs,
    mesh_axes,
    topos: dict,
    bucket_bytes: int | None = 0,
    chunks: int = 1,
    codec="f32",
    step=0,
    return_residual: bool = False,
):
    """FlexTree gradient sync: sum each leaf over its replication axes.

    An axis whose topology is ``None`` (the ``"psum"`` sentinel) uses the
    native all-reduce — the in-step analog of the benchmark's
    ``--comm-type xla`` baseline.

    ``bucket_bytes`` selects the execution strategy: ``0`` (default, the
    historical behavior) syncs per leaf — one allreduce sequence per
    gradient leaf; any other value routes through the bucketed/fused sync
    (``parallel.bucketing.bucketed_sync_grads`` — ``None`` derives the
    plan from the calibrated planner: large leaves alone and in their own
    shape, small ones packed; ``> 0`` is an explicit cap), which is
    bitwise-identical but runs one collective per *bucket*.
    The train-step builders pass their ``TrainConfig.bucket_bytes`` through,
    so the bucketed path is the production default.  ``chunks > 1`` runs
    tree collectives chunk-pipelined (both paths).

    ``codec`` selects the wire format (``ops/quantize.py``): the identity
    keeps both paths exactly as before (bitwise contract intact); a lossy
    codec routes FlexTree axes through ``compressed_allreduce`` with
    ``step`` keying the deterministic stochastic rounding.  ``"psum"``
    sentinel axes stay native f32 — compression is a FlexTree property.
    ``return_residual=True`` additionally returns the per-leaf input-
    quantization residual for error feedback: the wire-exact residual of
    the first compressed axis (the one that sees this rank's local data),
    or the canonical ``x - C(x)`` when the first synced axis is exact.
    """
    from ..ops.quantize import get_codec
    from .allreduce import _NATIVE_PSUM
    from .compressed import compressed_allreduce, local_residual

    codec = get_codec(codec)
    if bucket_bytes != 0:
        return bucketed_sync_grads(
            grads, pspecs, mesh_axes, topos,
            bucket_bytes=bucket_bytes, chunks=chunks,
            codec=codec, step=step, return_residual=return_residual,
        )

    def sync(g, spec):
        res = None
        for k, ax in enumerate(replication_key(spec, mesh_axes)):
            topo = topos[ax]
            if topo is None:
                g = _NATIVE_PSUM(g, ax)
            elif not codec.lossy:
                g = allreduce(g, ax, topo=topo, op="sum", chunks=chunks)
            elif k == 0:
                # only the FIRST axis sees this rank's local data, so only
                # its wire residual has per-rank EF semantics: a residual
                # taken after an exact psum axis would be replicated over
                # that axis and re-injected once PER RANK next step,
                # over-counting by the axis size.  Later-axis (and
                # post-psum) losses fall back to the canonical residual —
                # same rule as the bucketed path.
                g, res = compressed_allreduce(
                    g, ax, topo=topo, codec=codec, chunks=chunks, step=step,
                    return_residual=True,
                )
            else:
                g = compressed_allreduce(
                    g, ax, topo=topo, codec=codec, chunks=chunks, step=step
                )
        return g, res

    flat_g, treedef = jax.tree.flatten(grads)
    flat_s = treedef.flatten_up_to(pspecs)
    synced, residuals = [], []
    for g, spec in zip(flat_g, flat_s):
        out, res = sync(g, spec)
        synced.append(out)
        if return_residual:
            residuals.append(
                res if res is not None else local_residual(g, codec, step)
            )
    out_tree = treedef.unflatten(synced)
    if return_residual:
        return out_tree, treedef.unflatten(residuals)
    return out_tree


def sync_with_feedback(state, grads, pspecs, mesh_axes, topos, train_cfg):
    """The train-step gradient sync under ``train_cfg``: identity codec ->
    the plain (bitwise) sync and ``None``; lossy codec -> error-feedback
    sync — add the carried residual, sync ``grad + ef`` compressed, return
    the new residual (what the wire's input quantization lost) for the
    caller to store back into ``state['ef']``.  Shared by the dense,
    pipeline and MoE steps so their EF accounting cannot diverge."""
    codec = _sync_codec(train_cfg)
    if not codec.lossy:
        return (
            sync_grads(
                grads, pspecs, mesh_axes, topos,
                bucket_bytes=train_cfg.bucket_bytes,
                chunks=train_cfg.grad_chunks,
            ),
            None,
        )
    v = jax.tree.map(lambda g, e: g + e.astype(g.dtype), grads, state["ef"])
    return sync_grads(
        v, pspecs, mesh_axes, topos,
        bucket_bytes=train_cfg.bucket_bytes, chunks=train_cfg.grad_chunks,
        codec=codec, step=state["step"], return_residual=True,
    )


def maybe_autotune_grad_topo(
    mesh: Mesh, model_cfg, train_cfg: "TrainConfig", axis_names,
    init_fn=None,
) -> "TrainConfig":
    """Resolve the gradient-sync topology by *measurement* when
    ``train_cfg.autotune`` is set and no explicit ``grad_topo`` was given.

    Host-level (runs once at step-build time, never inside the trace):
    for each mesh axis with size > 1, time the analytic top-K candidates
    for the model's total parameter bytes under the configured codec
    (``planner.autotune.autotune_plan``) and pin the measured winner into
    ``grad_topo``.  Results persist in the ``FLEXTREE_PLAN_CACHE`` plan
    cache, so rebuilding the step (or re-running the trainer) is a pure
    cache hit; axes with equal size share one cache entry by construction.
    """
    if not train_cfg.autotune or train_cfg.grad_topo is not None:
        return train_cfg
    from ..planner.autotune import autotune_plan

    if init_fn is None:
        init_fn = init_params  # dense; pipeline/MoE builders pass theirs
    shapes = jax.eval_shape(
        lambda k: init_fn(k, model_cfg), jax.random.PRNGKey(0)
    )
    nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(shapes))
    spec: dict = {}
    for ax in axis_names:
        n = int(mesh.shape[ax])
        if n <= 1:
            continue
        plan = autotune_plan(
            n, nbytes, dtype="float32", codecs=(train_cfg.codec,), top_k=3,
            repeat=3, overlap=train_cfg.overlap,
            sharded=train_cfg.shard_optimizer,
            # the train sync seam executes legacy topologies only (see
            # resolve_axis_topos): never offer the measured search a
            # winner the step builder would have to refuse
            ir_families=(),
        )
        spec[ax] = plan.to_ft_topo()
    return dataclasses.replace(train_cfg, grad_topo=spec, autotune=False)


def schedule_lr(train_cfg: "TrainConfig", step):
    """Learning rate at (1-based) ``step`` under the config's schedule.

    "constant": ``lr``.  "warmup_cosine": linear 0 -> lr over
    ``warmup_steps``, then cosine from lr down to ``min_lr_frac * lr`` at
    ``total_steps`` (flat at the floor beyond).  Pure jnp on a traced
    step, so it lives inside the jitted train step.
    """
    if train_cfg.schedule == "constant":
        return jnp.float32(train_cfg.lr)
    if train_cfg.schedule != "warmup_cosine":
        raise ValueError(f"unknown schedule {train_cfg.schedule!r}")
    if train_cfg.total_steps <= 0:
        raise ValueError("schedule='warmup_cosine' needs total_steps > 0")
    t = step.astype(jnp.float32) if hasattr(step, "astype") else jnp.float32(step)
    warm = jnp.float32(train_cfg.warmup_steps)
    ramp = jnp.minimum(t / jnp.maximum(warm, 1.0), 1.0)
    span = jnp.float32(max(train_cfg.total_steps - train_cfg.warmup_steps, 1))
    frac = jnp.clip((t - warm) / span, 0.0, 1.0)
    floor = jnp.float32(train_cfg.min_lr_frac)
    cos = floor + (1.0 - floor) * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.float32(train_cfg.lr) * ramp * jnp.where(t <= warm, 1.0, cos)


def global_grad_norm(grads, pspecs):
    """True global L2 norm of a sharded gradient tree.

    A leaf holds only this device's shard along every mesh axis its
    PartitionSpec names — its square-sum psums over exactly those axes
    before joining the total; axes NOT in the spec see the leaf
    replicated, where a psum would overcount by the axis size.  (After
    ``sync_grads``, gradients are replicated across data axes, which
    never appear in param specs — so the rule is uniform across the
    dense, pipeline, and MoE steps: tp-column shards, pp stage stacks,
    and ep expert shards all sum once each.)  Leaves are grouped by
    their axis-set and each group's local total psums ONCE per set
    (psum is linear) — 2-3 scalar collectives per step, not one per leaf.
    """
    flat_g, treedef = jax.tree.flatten(grads)
    flat_s = treedef.flatten_up_to(pspecs)
    by_axes: dict[tuple, Any] = {}
    for g, spec in zip(flat_g, flat_s):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        key = spec_axes(spec)
        by_axes[key] = by_axes.get(key, jnp.float32(0.0)) + sq
    total = jnp.float32(0.0)
    for axes, sq in by_axes.items():
        for axis in axes:
            sq = lax.psum(sq, axis)
        total = total + sq
    return jnp.sqrt(total)


def clip_by_global_norm(grads, norm, clip: float):
    """Scale the tree so its global norm is at most ``clip`` (> 0)."""
    if clip <= 0:
        raise ValueError(f"clip must be positive, got {clip}")
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)


@jax.named_scope("ft_grad_clip")
def maybe_clip_grads(grads, pspecs, train_cfg: "TrainConfig", metrics: dict):
    """Shared clip-and-record step for every train-step builder: when
    ``grad_clip_norm`` is set (must be positive), clips ``grads`` to it
    and records the pre-clip norm in ``metrics['grad_norm']``."""
    if not train_cfg.grad_clip_norm:
        return grads
    if train_cfg.grad_clip_norm < 0:
        raise ValueError(
            f"grad_clip_norm must be positive, got {train_cfg.grad_clip_norm}"
        )
    norm = global_grad_norm(grads, pspecs)
    metrics["grad_norm"] = norm
    return clip_by_global_norm(grads, norm, train_cfg.grad_clip_norm)


def metric_specs(train_cfg: "TrainConfig", base: dict) -> dict:
    """Out-specs for a step's metrics dict: ``base`` plus the clip norm
    when clipping is on — must mirror :func:`maybe_clip_grads` — and the
    guard's verdict (:func:`step_verdict`)."""
    out = dict(base)
    if train_cfg.grad_clip_norm:
        out["grad_norm"] = P()
    out["applied"] = P()
    return out


def step_verdict(metrics: dict):
    """The NaN guard's verdict, made inside the step: true where the
    step's loss (and its gradient norm, when clipping computed one) is
    finite — the two numbers the host's guard reads for a step that gives
    no verdict.  Recorded as ``metrics["applied"]``, which ``loop.fit``
    reads one step late; the update takes it (:func:`adamw_elem`) and
    leaves the state as it was where it is false."""
    ok = jnp.isfinite(metrics["loss"])
    if "grad_norm" in metrics:
        ok = ok & jnp.isfinite(metrics["grad_norm"])
    metrics["applied"] = ok
    return ok


def keep_if_refused(ok, new, old):
    """``new`` where the verdict holds, else ``old``, leaf for leaf: for
    what the step writes outside the AdamW arithmetic (the error-feedback
    residual; parameters gathered through a lossy codec)."""
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


def adamw_elem(p, g, mu, nu, t, lr, train_cfg: "TrainConfig", ok=None):
    """One leaf's AdamW arithmetic, ``(new_p, new_mu, new_nu)``: the one
    expression tree of the replicated update and the sharded one
    (``parallel.zero``), so that the two cannot drift (bitwise for f32:
    same inputs, same tree).

    ``ok`` is the step's verdict (:func:`step_verdict`).  Where it is
    false the update is refused INSIDE its own arithmetic: the gradient
    reads as zero, the moments' decay as 1, their gain and the learning
    rate as 0, so every result is its argument (``1 * mu + 0 * 0``,
    ``p - 0 * delta``; a ``-0.0`` may come back ``+0.0``).  Scalars, not
    a ``where`` on the results: the tree stays the unguarded update's, so
    with the verdict true the bits are the unguarded update's on every
    backend (XLA:CPU contracts ``a * b + c * d`` differently once a
    select follows the sum), and no pass over the state is added: the one
    select is on the gradient, where it is produced.  ``lax.cond`` would
    pull the update out of the fusions that write it.
    """
    c1 = 1.0 - train_cfg.b1 ** t
    c2 = 1.0 - train_cfg.b2 ** t
    b1, gain1 = train_cfg.b1, 1.0 - train_cfg.b1
    b2, gain2 = train_cfg.b2, 1.0 - train_cfg.b2
    if ok is not None:

        def scalar(value, idle):
            return jnp.where(ok, value, idle).astype(mu.dtype)

        b1, gain1 = scalar(b1, 1.0), scalar(gain1, 0.0)
        b2, gain2 = scalar(b2, 1.0), scalar(gain2, 0.0)
        lr = jnp.where(ok, lr, 0.0)
        g = jnp.where(ok, g, jnp.zeros((), g.dtype))
    mu = b1 * mu + gain1 * g
    nu = b2 * nu + gain2 * (g * g)
    delta = (mu / c1) / (jnp.sqrt(nu / c2) + train_cfg.eps)
    if train_cfg.weight_decay:
        delta = delta + train_cfg.weight_decay * p
    return p - lr * delta, mu, nu


def jit_step(sharded, mesh: Mesh, sspecs: dict):
    """The jitted form of a built step.  The state (argument 0) is
    DONATED: the step updates it in place, and the caller's handle to the
    state it passed in is dead once the call returns.  The state's
    shardings are stated (``sspecs`` over ``mesh``): a state that arrives
    unplaced (``init_train_state`` builds it on one device) is placed by
    the call, so that every result leaf can take its argument's buffer
    ("Some donated buffers were not usable" otherwise)."""
    placed = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), sspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.jit(
        sharded, in_shardings=(placed, None, None), donate_argnums=0
    )


def copy_leaf(x):
    """A device array with a buffer of its own (same sharding)."""
    if x.size == 0:  # jax 0.9 cannot copy an empty sharded array
        return jax.device_put(jnp.zeros(x.shape, x.dtype), x.sharding)
    return jnp.copy(x)


def copy_state(state):
    """``state`` with buffers of its own: what to hand a built step, which
    donates its argument, when the caller goes on using the state."""
    return jax.tree.map(copy_leaf, state)


def keeping_state(step):
    """``step`` as a function that leaves its argument alive: each call
    hands the built step a copy.  For harnesses that call many steps, many
    times, on ONE state (the same cost in every variant they compare)."""
    return lambda state, tokens, targets: step(
        copy_state(state), tokens, targets
    )


@jax.named_scope("ft_optimizer")
def adamw_apply(state: dict, grads, train_cfg: "TrainConfig", ok=None) -> dict:
    """One AdamW update on (sharded) state; moments shard like the params.
    ``ok``: the step's verdict (:func:`adamw_elem`); ``step`` advances
    either way, so a refused update is a skipped batch."""
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    lr = schedule_lr(train_cfg, step)

    flat_p, treedef = jax.tree.flatten(state["params"])
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(state["mu"])
    flat_nu = treedef.flatten_up_to(state["nu"])
    out = [
        adamw_elem(p, g, m, v, t, lr, train_cfg, ok)
        for p, g, m, v in zip(flat_p, flat_g, flat_mu, flat_nu)
    ]
    return {
        "params": treedef.unflatten([o[0] for o in out]),
        "mu": treedef.unflatten([o[1] for o in out]),
        "nu": treedef.unflatten([o[2] for o in out]),
        "step": step,
    }


def guarded_adamw(state: dict, grads, new_ef, train_cfg, metrics: dict) -> dict:
    """The replicated step's tail, shared by its three builders: the
    verdict on ``metrics``, then the AdamW update and the error-feedback
    residual under it."""
    ok = step_verdict(metrics)
    new_state = adamw_apply(state, grads, train_cfg, ok)
    if new_ef is not None:
        new_state["ef"] = keep_if_refused(ok, new_ef, state["ef"])
    return new_state


def make_train_step(
    mesh: Mesh,
    model_cfg: TransformerConfig,
    train_cfg: TrainConfig = TrainConfig(),
    axis_names: tuple[str, str, str] = ("dp", "sp", "tp"),
    serialize_overlap: bool = False,
):
    """Build the jitted full train step ``(state, tokens, targets) ->
    (state, metrics)``.

    ``tokens``/``targets``: (B, T) int32, batch sharded over dp, sequence
    over sp.  ``metrics``: {'loss': global mean token loss}.

    ``serialize_overlap`` (with ``train_cfg.overlap``) builds the
    serialized TWIN of the overlapped step: the identical program with a
    full-backward ``optimization_barrier`` before the first sync
    collective — the bench/verifier comparator (equal collective counts,
    bitwise-equal results) and the ``overlap-serialization`` mutant.
    """
    dp, sp, tp = axis_names
    for a in axis_names:
        if a not in mesh.shape:
            raise ValueError(f"mesh is missing axis {a!r}; has {mesh.axis_names}")
    validate_tp(model_cfg, mesh.shape[tp])
    train_cfg = maybe_autotune_grad_topo(
        mesh, model_cfg, train_cfg, axis_names
    )

    sspecs = state_specs(
        model_cfg, tp, train_cfg, mesh=mesh, axis_names=axis_names
    )
    data_spec = P(dp, sp)
    mesh_axes = axis_names
    zero_layout = None
    if train_cfg.shard_optimizer:
        shapes = jax.eval_shape(
            lambda k: init_params(k, model_cfg), jax.random.PRNGKey(0)
        )
        zero_layout = zero_layout_for(
            mesh, shapes, sspecs["params"], axis_names
        )

    def device_step(state, tokens, targets):
        n_total_tokens = (
            tokens.size
            * lax.axis_size(dp)
            * lax.axis_size(sp)
            * lax.axis_size(tp)  # tp-fold redundancy, see module docstring
        )

        topos = resolve_axis_topos(mesh, mesh_axes, train_cfg.grad_topo)
        if train_cfg.overlap:
            from .overlap import dense_overlap_step_grads

            loss, grads, new_ef = dense_overlap_step_grads(
                state, tokens, targets, model_cfg, train_cfg,
                sspecs["params"], mesh_axes, topos, n_total_tokens,
                tp_axis=tp, sp_axis=sp, serialize=serialize_overlap,
                zero_layout=zero_layout,
            )
        else:

            def local_loss(params):
                logits = forward(
                    params, tokens, model_cfg, tp_axis=tp, sp_axis=sp
                )
                loss_sum, _ = cross_entropy_loss(logits, targets)
                return loss_sum / n_total_tokens

            loss, grads = jax.value_and_grad(local_loss)(state["params"])
            if not train_cfg.shard_optimizer:
                grads, new_ef = sync_with_feedback(
                    state, grads, sspecs["params"], mesh_axes, topos, train_cfg
                )
            else:
                new_ef = None  # the zero path carries EF itself
        global_loss = lax.psum(lax.psum(lax.psum(loss, dp), sp), tp)

        metrics = {"loss": global_loss}
        if train_cfg.shard_optimizer:
            from .zero import (
                zero_clip_apply_and_gather,
                zero_sync_and_update,
            )

            if train_cfg.overlap:
                # the engine already reduce-scattered per fired bucket;
                # grads is a tree of ZeroShard (and new_ef the residuals)
                new_state = zero_clip_apply_and_gather(
                    state, grads, new_ef, sspecs["params"], mesh_axes,
                    topos, train_cfg, zero_layout, metrics,
                )
            else:
                new_state = zero_sync_and_update(
                    state, grads, sspecs["params"], mesh_axes, topos,
                    train_cfg, zero_layout, metrics,
                )
        else:
            grads = maybe_clip_grads(grads, sspecs["params"], train_cfg, metrics)
            new_state = guarded_adamw(state, grads, new_ef, train_cfg, metrics)
        return new_state, metrics

    mspec = metric_specs(train_cfg, {"loss": P()})
    sharded = jax.shard_map(
        device_step,
        mesh=mesh,
        in_specs=(sspecs, data_spec, data_spec),
        out_specs=(sspecs, mspec),
        check_vma=False,
    )
    return jit_step(sharded, mesh, sspecs)
