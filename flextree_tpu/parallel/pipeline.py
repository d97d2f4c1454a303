"""Pipeline parallelism: stage-sharded layers, microbatched GPipe schedule.

The fourth parallelism axis of the framework (after dp/sp/tp): the
transformer's layer stack is split into ``pp`` contiguous stages, each
device on the ``pp`` mesh axis holds ``n_layers/pp`` layers (the per-layer
parameter pytree is *stacked* on a leading layer axis and sharded over
``pp``), and activations flow stage-to-stage with ``lax.ppermute`` — the
same ICI neighbor-exchange primitive as the ring allreduce
(``flextree_tpu.parallel.allreduce.ring_allreduce``; the reference's ring
block walk, ``allreduce_over_mpi/mpi_mod.hpp:1119-1147``, repurposed to
carry activations instead of gradient blocks).

Schedule: GPipe.  The local batch splits into ``M`` microbatches; the loop
runs ``M + pp - 1`` ticks.  Each tick every stage processes one microbatch
(or a bubble), then the activation rotates one hop right.  Stage 0 injects
embeddings; the last stage computes loss.  Bubbles compute garbage that is
never read — their cotangent is zero, so gradients are exact (the moral
analog of the reference's empty trailing blocks that are skipped rather
than special-cased, ``mpi_mod.hpp:679-696``).  The loop is a ``lax.scan``,
so the compiled program is O(1) in ``M``.

SPMD note: every stage runs the *same* program every tick (uniform compute,
one collective permute) — no data-dependent control flow crosses a
collective, which is what keeps the schedule compilable under ``jit`` with
static shapes.  The final-norm + vocab matmul and the loss are computed on
every stage and masked, rather than branched, for the same reason.

Gradient sync composes with the other axes exactly as in
``flextree_tpu.parallel.train``: stacked layer parameters are *sharded*
over ``pp`` (no sync on that axis), embeddings/final-norm are replicated
over ``pp`` and synced with the FlexTree allreduce alongside dp/sp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer import (
    TransformerConfig,
    cross_entropy_loss,
    final_logits,
    global_positions,
    init_params,
    layer_forward,
    param_specs,
)
from .train import (
    TrainConfig,
    guarded_adamw,
    jit_step,
    maybe_clip_grads,
    metric_specs,
    make_mesh_nd,
    make_state_specs,
    make_train_state,
    maybe_autotune_grad_topo,
    resolve_axis_topos,
    spread_factors,
    sync_with_feedback,
    validate_tp,
    zero_layout_for,
)

__all__ = [
    "stack_layer_params",
    "unstack_layer_params",
    "pipeline_param_specs",
    "pipeline_state_specs",
    "init_pipeline_train_state",
    "make_pipeline_train_step",
    "make_mesh_4d",
    "factor_devices_4d",
]


# ------------------------------------------------------------ param layout


def stack_layer_params(params: dict) -> dict:
    """List-of-layer-dicts -> one dict of (L, ...) stacked leaves.

    The stacked leading axis is the pipeline shard axis; ``lax.scan`` over
    it applies the stage's local layers in order.
    """
    layers = params["layers"]
    stacked = {
        k: jnp.stack([layer[k] for layer in layers]) for k in layers[0]
    }
    return {"embed": params["embed"], "ln_f": params["ln_f"], "layers": stacked}


def unstack_layer_params(params: dict) -> dict:
    """Inverse of :func:`stack_layer_params` (host-side, for checkpoints)."""
    stacked = params["layers"]
    n_layers = next(iter(stacked.values())).shape[0]
    layers = [
        {k: v[i] for k, v in stacked.items()} for i in range(n_layers)
    ]
    return {"embed": params["embed"], "ln_f": params["ln_f"], "layers": layers}


def pipeline_param_specs(
    cfg: TransformerConfig, pp_axis: str | None = "pp", tp_axis: str | None = "tp"
) -> dict:
    """PartitionSpecs for the stacked layout: leading layer axis over
    ``pp_axis``, per-layer dims tp-sharded as in ``param_specs``."""
    per_layer = param_specs(cfg, tp_axis)["layers"][0]
    stacked = {k: P(pp_axis, *spec) for k, spec in per_layer.items()}
    return {"embed": P(None, None), "ln_f": P(None), "layers": stacked}


def init_pipeline_train_state(
    key, cfg: TransformerConfig, train_cfg=None, mesh=None,
    axis_names: tuple[str, str, str, str] = ("dp", "pp", "sp", "tp"),
) -> dict:
    params = stack_layer_params(init_params(key, cfg))
    layout = None
    if train_cfg is not None and train_cfg.shard_optimizer:
        if mesh is None:
            raise ValueError(
                "shard_optimizer=True: init_pipeline_train_state needs mesh="
            )
        layout = zero_layout_for(
            mesh, params,
            pipeline_param_specs(cfg, axis_names[1], axis_names[3]),
            axis_names,
        )
    return make_train_state(params, train_cfg, layout=layout)


def pipeline_state_specs(
    cfg: TransformerConfig, pp_axis: str | None = "pp", tp_axis: str | None = "tp",
    train_cfg=None, mesh=None,
    axis_names: tuple[str, str, str, str] = ("dp", "pp", "sp", "tp"),
) -> dict:
    pspecs = pipeline_param_specs(cfg, pp_axis, tp_axis)
    layout = None
    if train_cfg is not None and train_cfg.shard_optimizer:
        if mesh is None:
            raise ValueError(
                "shard_optimizer=True: pipeline_state_specs needs mesh="
            )
        shapes = jax.eval_shape(
            lambda k: stack_layer_params(init_params(k, cfg)),
            jax.random.PRNGKey(0),
        )
        layout = zero_layout_for(mesh, shapes, pspecs, axis_names)
    return make_state_specs(pspecs, train_cfg, layout=layout)


# ------------------------------------------------------------- mesh helper


def factor_devices_4d(n: int) -> tuple[int, int, int, int]:
    """Split ``n`` devices into (dp, pp, sp, tp), pp/sp/tp-first.

    Largest prime factors land on pp, then sp, then tp, then dp — the
    axes that exercise distinct machinery get covered before plain data
    parallelism (8 -> (1, 2, 2, 2), 16 -> (2, 2, 2, 2)).
    """
    return spread_factors(n, 4, order=[1, 2, 3, 0])


def make_mesh_4d(
    n_devices: int | None = None,
    shape: tuple[int, int, int, int] | None = None,
    axis_names: tuple[str, str, str, str] = ("dp", "pp", "sp", "tp"),
) -> Mesh:
    if shape is None:
        shape = factor_devices_4d(
            len(jax.devices()) if n_devices is None else n_devices
        )
    return make_mesh_nd(n_devices, shape, axis_names)


# ---------------------------------------------------------------- schedule


def _pipeline_loss_sum(
    params,
    toks,
    tgts,
    cfg: TransformerConfig,
    *,
    pp_axis: str,
    tp_axis: str | None,
    sp_axis: str | None,
):
    """Sum of token losses over all local microbatches, on the last stage.

    ``toks``/``tgts``: (M, mb, T_local) int32.  Returns a scalar that is
    the full loss sum on the last pipeline stage and 0 elsewhere (so a
    plain ``psum`` over the mesh gives the global sum exactly once).
    """
    n = lax.axis_size(pp_axis)
    idx = lax.axis_index(pp_axis)
    m_count, mb, t_local = toks.shape
    positions = global_positions(t_local, sp_axis)
    right = [(j, (j + 1) % n) for j in range(n)]

    def stage_apply(x):
        def body(h, layer):
            return (
                layer_forward(
                    layer, h, positions, cfg, tp_axis=tp_axis, sp_axis=sp_axis
                ),
                None,
            )

        x, _ = lax.scan(body, x, params["layers"])
        return x

    def final_loss(y, tgt_mb):
        logits = final_logits(params["embed"], params["ln_f"], y)
        loss_sum, _ = cross_entropy_loss(logits, tgt_mb)
        return loss_sum

    def tick(carry, t):
        state, loss_acc = carry
        tok_mb = lax.dynamic_index_in_dim(
            toks, jnp.clip(t, 0, m_count - 1), keepdims=False
        )
        inj = params["embed"][tok_mb].astype(cfg.dtype)
        x = jnp.where(idx == 0, inj, state)
        y = stage_apply(x)
        mb_i = t - (n - 1)
        tgt_mb = lax.dynamic_index_in_dim(
            tgts, jnp.clip(mb_i, 0, m_count - 1), keepdims=False
        )
        l = final_loss(y, tgt_mb)
        valid = (idx == n - 1) & (mb_i >= 0)
        loss_acc = loss_acc + jnp.where(valid, l, 0.0)
        state = lax.ppermute(y, pp_axis, right)
        return (state, loss_acc), None

    state0 = jnp.zeros((mb, t_local, cfg.d_model), cfg.dtype)
    # inherit q-style varying axes from the embed of the first microbatch so
    # the scan carry has a consistent vma type under tp/sp sharding
    state0 = state0 + 0 * params["embed"][toks[0]].astype(cfg.dtype)
    (state, loss_sum), _ = lax.scan(
        tick, (state0, jnp.zeros((), jnp.float32)), jnp.arange(m_count + n - 1)
    )
    return loss_sum


def make_pipeline_train_step(
    mesh: Mesh,
    model_cfg: TransformerConfig,
    train_cfg: TrainConfig = TrainConfig(),
    n_microbatches: int = 2,
    axis_names: tuple[str, str, str, str] = ("dp", "pp", "sp", "tp"),
    serialize_overlap: bool = False,
):
    """Jitted 4-axis train step ``(state, tokens, targets) -> (state,
    metrics)`` with GPipe pipeline parallelism over ``axis_names[1]``.

    ``state`` uses the stacked layout (``init_pipeline_train_state``);
    ``tokens``/``targets`` are (B, T) int32, batch over dp, sequence over
    sp; the per-device batch must be divisible by ``n_microbatches``.

    ``train_cfg.overlap``: the backward of the GPipe tick loop is a
    ``lax.scan`` transpose — one fused op emitting every gradient at
    once, a dataflow barrier readiness ordering cannot reach inside (that
    would take MPMD per-stage programs).  The overlap path therefore
    schedules the sync collectives into the post-backward bubble: fired
    per readiness bucket (head / layer stack / embed), each
    data-dependent only on its own leaves, overlappable with the loss
    psum, metrics and optimizer tail (``overlap.overlap_sync_with_
    feedback``; docs/OVERLAP.md states the honest limit).
    ``serialize_overlap`` builds its barrier twin.
    """
    dp, pp, sp, tp = axis_names
    for a in axis_names:
        if a not in mesh.shape:
            raise ValueError(f"mesh is missing axis {a!r}; has {mesh.axis_names}")
    pp_size = mesh.shape[pp]
    if model_cfg.n_layers % pp_size:
        raise ValueError(
            f"n_layers={model_cfg.n_layers} must be divisible by pp={pp_size}"
        )
    validate_tp(model_cfg, mesh.shape[tp])
    train_cfg = maybe_autotune_grad_topo(
        mesh, model_cfg, train_cfg, axis_names,
        init_fn=lambda k, cfg: stack_layer_params(init_params(k, cfg)),
    )

    sspecs = pipeline_state_specs(
        model_cfg, pp, tp, train_cfg, mesh=mesh, axis_names=axis_names
    )
    data_spec = P(dp, sp)
    mesh_axes = axis_names
    zero_layout = None
    if train_cfg.shard_optimizer:
        shapes = jax.eval_shape(
            lambda k: stack_layer_params(init_params(k, model_cfg)),
            jax.random.PRNGKey(0),
        )
        zero_layout = zero_layout_for(mesh, shapes, sspecs["params"], axis_names)

    def device_step(state, tokens, targets):
        b_local, t_local = tokens.shape
        if b_local % n_microbatches:
            raise ValueError(
                f"local batch {b_local} not divisible by "
                f"n_microbatches={n_microbatches}"
            )
        mb = b_local // n_microbatches
        toks = tokens.reshape(n_microbatches, mb, t_local)
        tgts = targets.reshape(n_microbatches, mb, t_local)
        # loss exists once per (dp, sp, tp) replica set (on the last pp
        # stage), so normalize by the global token count including the
        # tp-fold redundancy — same rule as train.make_train_step
        n_total_tokens = (
            tokens.size
            * lax.axis_size(dp)
            * lax.axis_size(sp)
            * lax.axis_size(tp)
        )

        def local_loss(params):
            loss_sum = _pipeline_loss_sum(
                params, toks, tgts, model_cfg,
                pp_axis=pp, tp_axis=tp, sp_axis=sp,
            )
            return loss_sum / n_total_tokens

        loss, grads = jax.value_and_grad(local_loss)(state["params"])

        topos = resolve_axis_topos(mesh, mesh_axes, train_cfg.grad_topo)
        if train_cfg.shard_optimizer:
            # ZeRO path: the scan transpose already emits every gradient
            # at once (the GPipe dataflow barrier — docs/OVERLAP.md), and
            # the sharded sync fires per bucket with each bucket
            # data-dependent only on its own leaves, so the post-backward
            # bubble scheduling the overlap path buys is structural here;
            # the overlap/serialize flags are no-ops for the sharded
            # pipeline step.
            from .zero import zero_sync_and_update

            global_loss = loss
            for ax in mesh_axes:
                global_loss = lax.psum(global_loss, ax)
            metrics = {"loss": global_loss}
            new_state = zero_sync_and_update(
                state, grads, sspecs["params"], mesh_axes, topos, train_cfg,
                zero_layout, metrics,
            )
            return new_state, metrics

        if train_cfg.overlap:
            from .overlap import overlap_sync_with_feedback

            grads, new_ef = overlap_sync_with_feedback(
                state, grads, sspecs["params"], mesh_axes, topos, train_cfg,
                serialize=serialize_overlap,
            )
        else:
            grads, new_ef = sync_with_feedback(
                state, grads, sspecs["params"], mesh_axes, topos, train_cfg
            )
        global_loss = loss
        for ax in mesh_axes:
            global_loss = lax.psum(global_loss, ax)

        metrics = {"loss": global_loss}
        grads = maybe_clip_grads(grads, sspecs["params"], train_cfg, metrics)
        return guarded_adamw(state, grads, new_ef, train_cfg, metrics), metrics

    mspec = metric_specs(train_cfg, {"loss": P()})
    sharded = jax.shard_map(
        device_step,
        mesh=mesh,
        in_specs=(sspecs, data_spec, data_spec),
        out_specs=(sspecs, mspec),
        check_vma=False,
    )
    return jit_step(sharded, mesh, sspecs)
