"""Training step for the MoE model: dp x ep x sp x tp in one shard_map.

Composition rules (extending ``flextree_tpu.parallel.train``):

- ``ep`` is a *data* axis outside the MoE layers (the batch shards over
  dp x ep jointly) and the *expert* axis inside them (tokens all-to-all to
  their experts' owners) — the standard "expert parallelism reuses data
  parallelism's devices" layout.
- Expert weights shard over ep (leading expert axis) and tp (hidden dim),
  so they sync only over the axes they're replicated on (dp, sp) — the
  same replication-axes rule, driven by the MoE param specs.
- The loss adds the router load-balance term: ``ce_mean +
  router_aux_weight * aux_mean``, with the aux averaged over all devices.
"""

from __future__ import annotations

import jax

from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.moe import MoEConfig, init_moe_params, moe_forward, moe_param_specs
from ..models.transformer import cross_entropy_loss
from .pipeline import factor_devices_4d, make_mesh_4d
from .train import (
    TrainConfig,
    guarded_adamw,
    jit_step,
    maybe_clip_grads,
    metric_specs,
    make_state_specs,
    make_train_state,
    maybe_autotune_grad_topo,
    resolve_axis_topos,
    sync_with_feedback,
    validate_tp,
    zero_layout_for,
)

__all__ = [
    "init_moe_train_state",
    "moe_state_specs",
    "make_moe_train_step",
    "make_mesh_moe",
    "factor_devices_moe",
]


def init_moe_train_state(
    key, cfg: MoEConfig, train_cfg=None, mesh=None,
    axis_names: tuple[str, str, str, str] = ("dp", "ep", "sp", "tp"),
) -> dict:
    params = init_moe_params(key, cfg)
    layout = None
    if train_cfg is not None and train_cfg.shard_optimizer:
        if mesh is None:
            raise ValueError(
                "shard_optimizer=True: init_moe_train_state needs mesh="
            )
        layout = zero_layout_for(
            mesh, params,
            moe_param_specs(cfg, axis_names[3], axis_names[1]), axis_names,
        )
    return make_train_state(params, train_cfg, layout=layout)


def moe_state_specs(
    cfg: MoEConfig, tp_axis: str | None = "tp", ep_axis: str | None = "ep",
    train_cfg=None, mesh=None,
    axis_names: tuple[str, str, str, str] = ("dp", "ep", "sp", "tp"),
) -> dict:
    pspecs = moe_param_specs(cfg, tp_axis, ep_axis)
    layout = None
    if train_cfg is not None and train_cfg.shard_optimizer:
        if mesh is None:
            raise ValueError("shard_optimizer=True: moe_state_specs needs mesh=")
        shapes = jax.eval_shape(
            lambda k: init_moe_params(k, cfg), jax.random.PRNGKey(0)
        )
        layout = zero_layout_for(mesh, shapes, pspecs, axis_names)
    return make_state_specs(pspecs, train_cfg, layout=layout)


def factor_devices_moe(n: int) -> tuple[int, int, int, int]:
    """(dp, ep, sp, tp) with ep covered first (8 -> (1, 2, 2, 2)) — the
    same specialty-axis-first policy as the pipeline's 4-axis split."""
    return factor_devices_4d(n)


def make_mesh_moe(
    n_devices: int | None = None,
    shape: tuple[int, int, int, int] | None = None,
    axis_names: tuple[str, str, str, str] = ("dp", "ep", "sp", "tp"),
) -> Mesh:
    return make_mesh_4d(n_devices, shape, axis_names)


def make_moe_train_step(
    mesh: Mesh,
    model_cfg: MoEConfig,
    train_cfg: TrainConfig = TrainConfig(),
    axis_names: tuple[str, str, str, str] = ("dp", "ep", "sp", "tp"),
    serialize_overlap: bool = False,
):
    """Jitted ``(state, tokens, targets) -> (state, metrics)``.

    ``tokens``/``targets``: (B, T) int32, batch sharded over (dp, ep),
    sequence over sp.  ``metrics``: global mean ``loss`` (cross entropy),
    ``aux`` (router balance), and ``total`` (what is optimized).

    ``train_cfg.overlap`` routes the backward through the readiness-
    ordered segmented engine (``parallel.overlap``) — per-layer grads
    fire their sync buckets as they are produced; ``serialize_overlap``
    builds its barrier twin (see ``train.make_train_step``).
    """
    dp, ep, sp, tp = axis_names
    for a in axis_names:
        if a not in mesh.shape:
            raise ValueError(f"mesh is missing axis {a!r}; has {mesh.axis_names}")
    ep_size, tp_size = mesh.shape[ep], mesh.shape[tp]
    if model_cfg.n_experts % ep_size:
        raise ValueError(
            f"n_experts={model_cfg.n_experts} must be divisible by ep={ep_size}"
        )
    if model_cfg.top_k > model_cfg.n_experts:
        raise ValueError("top_k cannot exceed n_experts")
    validate_tp(model_cfg, tp_size)
    train_cfg = maybe_autotune_grad_topo(
        mesh, model_cfg, train_cfg, axis_names, init_fn=init_moe_params
    )

    sspecs = moe_state_specs(
        model_cfg, tp, ep, train_cfg, mesh=mesh, axis_names=axis_names
    )
    data_spec = P((dp, ep), sp)
    mesh_axes = axis_names
    n_devices = 1
    for a in mesh_axes:
        n_devices *= mesh.shape[a]
    zero_layout = None
    if train_cfg.shard_optimizer:
        shapes = jax.eval_shape(
            lambda k: init_moe_params(k, model_cfg), jax.random.PRNGKey(0)
        )
        zero_layout = zero_layout_for(mesh, shapes, sspecs["params"], axis_names)

    def device_step(state, tokens, targets):
        # tp-fold redundancy only: dp/ep/sp partition the data
        n_total_tokens = (
            tokens.size
            * lax.axis_size(dp)
            * lax.axis_size(ep)
            * lax.axis_size(sp)
            * lax.axis_size(tp)
        )

        topos = resolve_axis_topos(mesh, mesh_axes, train_cfg.grad_topo)
        if train_cfg.overlap:
            from .overlap import moe_overlap_step_grads

            ce, aux, grads, new_ef = moe_overlap_step_grads(
                state, tokens, targets, model_cfg, train_cfg,
                sspecs["params"], mesh_axes, topos, n_total_tokens,
                n_devices, tp_axis=tp, sp_axis=sp, ep_axis=ep,
                serialize=serialize_overlap, zero_layout=zero_layout,
            )
        else:

            def local_loss(params):
                logits, aux = moe_forward(
                    params, tokens, model_cfg,
                    tp_axis=tp, sp_axis=sp, ep_axis=ep,
                )
                loss_sum, _ = cross_entropy_loss(logits, targets)
                ce = loss_sum / n_total_tokens
                # aux is a per-device mean; average it over every device
                # (tp copies are redundant but identical, so the global
                # mean is exact under the same 1/n_devices weighting)
                aux_term = model_cfg.router_aux_weight * aux / n_devices
                return ce + aux_term, (ce, aux)

            (_, (ce, aux)), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(state["params"])
            if not train_cfg.shard_optimizer:
                grads, new_ef = sync_with_feedback(
                    state, grads, sspecs["params"], mesh_axes, topos, train_cfg
                )
            else:
                new_ef = None  # the zero path carries EF itself

        global_ce = ce
        global_aux = aux / n_devices
        for ax in mesh_axes:
            global_ce = lax.psum(global_ce, ax)
            global_aux = lax.psum(global_aux, ax)

        metrics = {
            "loss": global_ce,
            "aux": global_aux,
            "total": global_ce + model_cfg.router_aux_weight * global_aux,
        }
        if train_cfg.shard_optimizer:
            from .zero import (
                zero_clip_apply_and_gather,
                zero_sync_and_update,
            )

            if train_cfg.overlap:
                new_state = zero_clip_apply_and_gather(
                    state, grads, new_ef, sspecs["params"], mesh_axes,
                    topos, train_cfg, zero_layout, metrics,
                )
            else:
                new_state = zero_sync_and_update(
                    state, grads, sspecs["params"], mesh_axes, topos,
                    train_cfg, zero_layout, metrics,
                )
            return new_state, metrics
        grads = maybe_clip_grads(grads, sspecs["params"], train_cfg, metrics)
        return guarded_adamw(state, grads, new_ef, train_cfg, metrics), metrics

    mspec = metric_specs(train_cfg, {"loss": P(), "aux": P(), "total": P()})
    sharded = jax.shard_map(
        device_step,
        mesh=mesh,
        in_specs=(sspecs, data_spec, data_spec),
        out_specs=(sspecs, mspec),
        check_vma=False,
    )
    return jit_step(sharded, mesh, sspecs)
