"""End-to-end training entrypoint: ``python -m flextree_tpu.trainer``.

Ties the whole framework together from the command line: pick a model
family (dense / MoE) and parallelism layout, train on a synthetic corpus
with the FlexTree gradient sync, checkpoint and resume.  Examples::

    # dense LM, 8 virtual CPU devices, (2, 2, 2) dp/sp/tp mesh
    python -m flextree_tpu.trainer --cpu 8 --steps 50

    # pipeline-parallel over (1, 2, 2, 2) dp/pp/sp/tp
    python -m flextree_tpu.trainer --cpu 8 --model pipeline --mesh 1,2,2,2

    # mixture-of-experts over (1, 2, 2, 2) dp/ep/sp/tp with a 2-stage
    # hierarchical gradient-sync topology
    python -m flextree_tpu.trainer --cpu 8 --model moe --mesh 1,2,2,2 --grad-topo 2,2

    # the flagship width on the chip(s) JAX finds (no --cpu: landing on the
    # CPU unasked is an error)
    python -m flextree_tpu.trainer --d-model 2048 --n-heads 16 --n-layers 4 \
        --d-ff 8192 --vocab 32768 --dtype bfloat16 --attn-impl flash \
        --batch 4 --seq-len 2048 --steps 6
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import shutil
import tempfile
from typing import Any


@dataclasses.dataclass
class TrainRun:
    """What :func:`train` hands back: the fit result plus the objects it
    ran with, so a caller (``chip_smoke.py``) can inspect the compiled
    step without rebuilding it."""

    result: Any  # parallel.loop.FitResult
    step_fn: Any  # the jitted step as built (a feedback replan may swap it)
    mesh: Any
    dataset: Any


def build(args, init_state=True):
    """(state, step_fn, mesh, restore_specs, state_pack, state_unpack)
    for the chosen model family.  ``restore_specs`` describes the
    CHECKPOINT layout (for sharded runs that is the consolidated
    replicated layout; ``state_pack``/``state_unpack`` convert — None for
    replicated runs).  ``init_state=False`` skips materializing the
    train state (returns None in its slot) — the feedback replan rebuild
    only needs the step fn, and initializing a second full model +
    optimizer state beside the live one doubles peak memory at exactly
    the replan moment."""
    import jax

    import jax.numpy as jnp

    from .models.transformer import TransformerConfig
    from .parallel.train import TrainConfig

    tc = TrainConfig(
        lr=args.lr,
        grad_topo=args.grad_topo,
        grad_clip_norm=args.grad_clip,
        schedule=args.schedule,
        warmup_steps=args.warmup_steps,
        total_steps=args.steps if args.schedule == "warmup_cosine" else 0,
        min_lr_frac=args.min_lr_frac,
        codec=args.codec,
        autotune=args.autotune,
        overlap=args.overlap,
        shard_optimizer=args.shard_optimizer,
    )
    key = jax.random.PRNGKey(args.seed)
    mesh_shape = (
        tuple(int(x) for x in args.mesh.split(",")) if args.mesh else None
    )

    common = dict(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_layers=args.n_layers,
        d_ff=args.d_ff,
        sp_impl=args.sp_impl,
        attn_impl=args.attn_impl,
        dtype=getattr(jnp, args.dtype),
    )

    def sharded_hooks(mesh, pspecs, params_shapes, axis_names, sspecs, tc):
        """(state_specs_for_restore, pack, unpack) for the run: sharded
        runs checkpoint CONSOLIDATED (world-size-independent), so the
        restore specs are the replicated layout and pack/unpack are the
        on-device converters (docs/SHARDED.md).  ``tc`` must be the
        RESOLVED config (autotune already pinned into ``grad_topo``) —
        the converters' shard-block permutation has to match the step's.
        """
        if not tc.shard_optimizer:
            return sspecs, None, None
        import dataclasses as _dc

        from .parallel.train import _sync_codec, make_state_specs, zero_layout_for
        from .parallel.zero import make_consolidate_fn, make_reshard_fn

        layout = zero_layout_for(mesh, params_shapes, pspecs, axis_names)
        lossy = _sync_codec(tc).lossy
        packed_specs = make_state_specs(
            pspecs, _dc.replace(tc, shard_optimizer=False)
        )
        pack = make_consolidate_fn(mesh, pspecs, layout, tc.grad_topo, lossy)
        unpack = make_reshard_fn(mesh, pspecs, layout, tc.grad_topo, lossy)
        return packed_specs, pack, unpack

    def announce_sync_plan(mesh, pspecs, params_shapes, axis_names, tc):
        """The start-up line: which constants price the gradient sync's
        buckets, and how the plan they give treats the leaves (the plan
        ``bucketed_sync_grads`` will trace: it plans what a device holds,
        so from the abstract shapes of the parameters' shards)."""
        from jax.sharding import NamedSharding

        from .parallel.bucketing import plan_buckets, plan_counts
        from .parallel.train import resolve_axis_topos

        line = "planner constants: " + (
            os.environ.get("FLEXTREE_CALIBRATION")
            or "built-in defaults (not calibrated on this fabric)"
        )
        if tc.bucket_bytes != 0 and not (tc.overlap or tc.shard_optimizer):
            from .ops.quantize import get_codec

            codec = get_codec(tc.codec)
            flat, treedef = jax.tree.flatten(params_shapes)
            specs = treedef.flatten_up_to(pspecs)
            shards = [
                jax.ShapeDtypeStruct(
                    NamedSharding(mesh, s).shard_shape(g.shape), g.dtype
                )
                for g, s in zip(flat, specs)
            ]
            counts = plan_counts(plan_buckets(
                shards, specs, axis_names,
                topos=resolve_axis_topos(mesh, axis_names, tc.grad_topo),
                axis_sizes={ax: int(mesh.shape[ax]) for ax in axis_names},
                bucket_bytes=tc.bucket_bytes,
                codec=codec if codec.lossy else None,
            ))
            line += (
                "; gradient sync: {in_place_leaves} leaves / {in_place_bytes} "
                "bytes in place, {packed_leaves} leaves / {packed_bytes} "
                "bytes packed".format(**counts)
            )
        print(line, flush=True)

    if args.model == "dense":
        from .models.transformer import init_params, param_specs
        from .parallel.train import (
            init_train_state,
            make_mesh_3d,
            make_train_step,
            maybe_autotune_grad_topo,
            state_specs,
        )

        cfg = TransformerConfig(**common)
        mesh = make_mesh_3d(args.devices, mesh_shape)
        axis_names = ("dp", "sp", "tp")
        # resolve autotune NOW so the checkpoint converters below see the
        # same grad_topo the step will run (make_train_step re-resolves —
        # a no-op after this: autotune=False and the plan cache hits)
        tc = maybe_autotune_grad_topo(mesh, cfg, tc, axis_names)
        sspecs = state_specs(cfg, train_cfg=tc, mesh=mesh)
        params_shapes = jax.eval_shape(
            lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
        )
        announce_sync_plan(
            mesh, param_specs(cfg, "tp"), params_shapes, axis_names, tc
        )
        restore_specs, pack, unpack = sharded_hooks(
            mesh, param_specs(cfg, "tp"), params_shapes, axis_names, sspecs, tc
        )
        return (
            init_train_state(key, cfg, tc, mesh=mesh) if init_state else None,
            make_train_step(mesh, cfg, tc),
            mesh,
            restore_specs,
            pack,
            unpack,
        )
    if args.model == "pipeline":
        from .models.transformer import init_params
        from .parallel.pipeline import (
            init_pipeline_train_state,
            make_mesh_4d,
            make_pipeline_train_step,
            pipeline_param_specs,
            pipeline_state_specs,
            stack_layer_params,
        )

        cfg = TransformerConfig(**common)
        mesh = make_mesh_4d(args.devices, mesh_shape)
        axis_names = ("dp", "pp", "sp", "tp")
        from .parallel.train import maybe_autotune_grad_topo

        tc = maybe_autotune_grad_topo(
            mesh, cfg, tc, axis_names,
            init_fn=lambda k, c: stack_layer_params(init_params(k, c)),
        )
        sspecs = pipeline_state_specs(cfg, train_cfg=tc, mesh=mesh)
        params_shapes = jax.eval_shape(
            lambda k: stack_layer_params(init_params(k, cfg)),
            jax.random.PRNGKey(0),
        )
        announce_sync_plan(
            mesh, pipeline_param_specs(cfg), params_shapes, axis_names, tc
        )
        restore_specs, pack, unpack = sharded_hooks(
            mesh, pipeline_param_specs(cfg), params_shapes, axis_names, sspecs,
            tc,
        )
        return (
            init_pipeline_train_state(key, cfg, tc, mesh=mesh)
            if init_state else None,
            make_pipeline_train_step(
                mesh, cfg, tc, n_microbatches=args.microbatches
            ),
            mesh,
            restore_specs,
            pack,
            unpack,
        )
    if args.model == "moe":
        from .models.moe import MoEConfig, init_moe_params, moe_param_specs
        from .parallel.moe_train import (
            init_moe_train_state,
            make_mesh_moe,
            make_moe_train_step,
            moe_state_specs,
        )

        cfg = MoEConfig(
            **common,
            n_experts=args.n_experts,
            top_k=args.top_k,
            capacity_factor=args.capacity_factor,
        )
        mesh = make_mesh_moe(args.devices, mesh_shape)
        axis_names = ("dp", "ep", "sp", "tp")
        from .parallel.train import maybe_autotune_grad_topo

        tc = maybe_autotune_grad_topo(
            mesh, cfg, tc, axis_names, init_fn=init_moe_params
        )
        sspecs = moe_state_specs(cfg, train_cfg=tc, mesh=mesh)
        params_shapes = jax.eval_shape(
            lambda k: init_moe_params(k, cfg), jax.random.PRNGKey(0)
        )
        announce_sync_plan(
            mesh, moe_param_specs(cfg), params_shapes, axis_names, tc
        )
        restore_specs, pack, unpack = sharded_hooks(
            mesh, moe_param_specs(cfg), params_shapes, axis_names, sspecs, tc
        )
        return (
            init_moe_train_state(key, cfg, tc, mesh=mesh)
            if init_state else None,
            make_moe_train_step(mesh, cfg, tc),
            mesh,
            restore_specs,
            pack,
            unpack,
        )
    raise ValueError(f"unknown model {args.model!r}")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="flextree_tpu.trainer")
    ap.add_argument("--model", choices=["dense", "pipeline", "moe"],
                    default="dense")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=128)
    ap.add_argument("--n-experts", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--capacity-factor", type=float, default=2.0)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument(
        "--sp-impl", choices=["ring", "zigzag", "ulysses"], default="ring"
    )
    ap.add_argument("--attn-impl", choices=["reference", "flash"],
                    default="reference")
    ap.add_argument(
        "--dtype", choices=["float32", "bfloat16"], default="float32",
        help="compute dtype (TransformerConfig.dtype); parameters, "
        "optimizer state, softmax and the loss stay float32",
    )
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument(
        "--grad-clip", type=float, default=0.0,
        help="global-norm gradient clipping (0 = off); the norm psums "
        "tp-sharded leaves so it is the TRUE global norm",
    )
    ap.add_argument(
        "--schedule", choices=["constant", "warmup_cosine"],
        default="constant",
        help="warmup_cosine ramps over --warmup-steps then decays to "
        "min_lr_frac*lr at --steps",
    )
    ap.add_argument("--warmup-steps", type=int, default=0)
    ap.add_argument(
        "--min-lr-frac", type=float, default=0.1,
        help="cosine floor as a fraction of --lr (warmup_cosine only)",
    )
    ap.add_argument("--grad-topo", type=str, default=None,
                    help="FT_TOPO-style widths for the gradient allreduce")
    ap.add_argument(
        "--codec", choices=["f32", "bf16", "int8"], default="f32",
        help="gradient-sync wire codec (docs/QUANTIZED_COLLECTIVES.md): "
        "f32 = identity (bitwise-identical sync), bf16/int8 compress the "
        "collective payload per hop with an error-feedback residual "
        "carried in the train state",
    )
    ap.add_argument(
        "--autotune", action="store_true",
        help="pick the gradient-sync topology by measuring the analytic "
        "top-K candidates on this backend (planner/autotune.py) instead "
        "of trusting the cost-model argmin; cached under "
        "FLEXTREE_PLAN_CACHE so the next run is a pure cache hit "
        "(overlapped and serialized plans never share a cache entry)",
    )
    ap.add_argument(
        "--shard-optimizer", action="store_true",
        help="ZeRO-1 sharded-optimizer path (docs/SHARDED.md): shard "
        "optimizer state (and the f32 master copy for lossy codecs) over "
        "each leaf's first replication axis; the step reduce-scatters "
        "grads (wire-compressed under --codec), updates the owned shard "
        "only, and all-gathers updated params per bucket. Per-rank mu/nu "
        "memory drops by the shard-axis size; bitwise-identical to the "
        "replicated step for the f32 codec. Checkpoints are written "
        "CONSOLIDATED (world-size-independent), so elastic shrink "
        "re-shards them onto the survivors",
    )
    ap.add_argument(
        "--overlap", action=argparse.BooleanOptionalAction, default=False,
        help="readiness-ordered backward/comm overlap (docs/OVERLAP.md): "
        "fire each gradient bucket's collective as soon as its grads are "
        "produced (reverse layer order), boundaries planner-equalized "
        "against remaining backward compute; bitwise-identical to the "
        "serialized sync for the f32 codec. --no-overlap (default) keeps "
        "the historical serialized sync",
    )
    ap.add_argument("--mesh", type=str, default=None,
                    help="comma mesh shape, e.g. 2,2,2 (dense) or 1,2,2,2")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--cpu", type=int, default=None, metavar="N",
                    help="run on N virtual CPU devices")
    ap.add_argument("--corpus-tokens", type=int, default=200_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-resume", action="store_true")
    # runtime supervision (flextree_tpu.runtime; docs/FAILURE_MODEL.md)
    ap.add_argument(
        "--step-timeout", type=float, default=None, metavar="S",
        help="per-step watchdog deadline in seconds (env FT_STEP_TIMEOUT); "
        "a hung step raises a typed FT_STEP_TIMEOUT instead of blocking",
    )
    ap.add_argument(
        "--heartbeat-dir", type=str, default=None,
        help="shared heartbeat directory: this process beats its lease + "
        "step progress there and watches peers (straggler/dead "
        "classification feeds run_report.json)",
    )
    ap.add_argument("--heartbeat-rank", type=int, default=0,
                    help="this process's rank in the heartbeat group")
    ap.add_argument("--heartbeat-world", type=int, default=None,
                    help="configured group size for membership accounting")
    ap.add_argument(
        "--no-preempt-checkpoint", action="store_true",
        help="disable the SIGTERM 'checkpoint now' fast path (on by "
        "default whenever --ckpt-dir is set)",
    )
    # closed-loop planner feedback (planner/feedback.py; docs/FEEDBACK.md)
    ap.add_argument(
        "--feedback-every", type=int, default=0, metavar="K",
        help="arm the closed-loop planner feedback: every K steps (with "
        "the flight recorder on — pair with --obs-dir/--flight-recorder) "
        "probe the live wire, compare measured comm time against the "
        "calibrated prediction, and past the drift band refit the cost "
        "constants, invalidate stale plan-cache entries and swap in a "
        "replanned step in-run. 0 (default) = off; with the recorder off "
        "the armed hook costs one None check per step",
    )
    ap.add_argument(
        "--feedback-band", type=float, default=0.5, metavar="R",
        help="relative-residual drift band for --feedback-every: a replan "
        "triggers when the median |predicted-measured|/measured over the "
        "sliding window exceeds R",
    )
    ap.add_argument(
        "--feedback-calibration", type=str, default=None, metavar="PATH",
        help="write feedback refits back to this CALIBRATION.json "
        "(source=\"feedback\" provenance stamp); defaults to a run-local "
        "CALIBRATION.feedback.json under --obs-dir, seeded as a copy of "
        "$FLEXTREE_CALIBRATION when that is set — the user's measured "
        "file is never overwritten by an in-run fit (the replan rebuild "
        "reads the refit from this file)",
    )
    # telemetry (flextree_tpu.obs; docs/OBSERVABILITY.md)
    ap.add_argument(
        "--obs-dir", type=str, default=None, metavar="DIR",
        help="write this rank's flight-recorder events "
        "(flight_{rank}.jsonl), failure dumps and metrics snapshot under "
        "DIR; merge a run's ranks with `python -m flextree_tpu.obs merge "
        "DIR` into one Perfetto-loadable timeline",
    )
    ap.add_argument(
        "--flight-recorder", action="store_true",
        help="enable the flight recorder with a default directory "
        "({--ckpt-dir}/obs, or ./ft_obs without a checkpoint dir); "
        "equivalent to --obs-dir with that path",
    )
    return ap.parse_args(argv)


def train(args: argparse.Namespace) -> TrainRun:
    """Everything ``main`` does short of printing the summary line."""
    import jax

    from .utils.backend import announce_devices, enable_compile_cache

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    enable_compile_cache()
    announce_devices("flextree_tpu.trainer")
    from .data import LMDataset, synthetic_tokens
    from .parallel.loop import FitConfig, Supervision, fit

    # runtime supervision wiring: any flag arms the layer; SIGTERM
    # preemption checkpointing is on by default when checkpointing is
    supervision = None
    want_preempt = args.ckpt_dir and not args.no_preempt_checkpoint
    if args.step_timeout or args.heartbeat_dir or want_preempt:
        from .runtime import (
            MembershipView,
            PreemptionGuard,
            Supervisor,
            SupervisorConfig,
        )

        supervisor = membership = None
        if args.heartbeat_dir:
            cfg_hb = SupervisorConfig.from_env(
                rank=args.heartbeat_rank, dir=args.heartbeat_dir
            )
            supervisor = Supervisor(cfg_hb)
            membership = MembershipView.for_config(
                cfg_hb, configured=args.heartbeat_world
            )
        supervision = Supervision(
            supervisor=supervisor,
            membership=membership,
            configured_world=args.heartbeat_world,
            step_timeout_s=args.step_timeout,
            preemption=PreemptionGuard().install() if want_preempt else None,
        )

    # flight recorder: installed BEFORE build so compile-time events
    # (bucket plans with provenance) land in the record too
    import contextlib

    obs_ctx = contextlib.nullcontext()
    obs_dir = None
    if args.obs_dir or args.flight_recorder:
        from .obs import flight_recorder, install_signal_dump

        obs_dir = args.obs_dir or (
            os.path.join(args.ckpt_dir, "obs") if args.ckpt_dir else "ft_obs"
        )
        obs_ctx = flight_recorder(obs_dir, rank=args.heartbeat_rank)

    with obs_ctx as obs_rec:
        if obs_rec is not None and (
            supervision is None or supervision.preemption is None
        ):
            # no PreemptionGuard routing SIGTERM through fit's dump path:
            # chain a flush+dump onto the default handler so even a bare
            # terminate leaves the forensic record
            install_signal_dump(obs_rec)
        state, step_fn, mesh, sspecs, state_pack, state_unpack = build(args)
        if args.feedback_every > 0:
            # closed-loop planner feedback (docs/FEEDBACK.md): probes ride
            # the largest mesh axis (the dominant sync wire); a drift-
            # triggered replan rebuilds the step so the refreshed
            # calibration re-derives bucket sizes/topology at trace time
            from .planner.feedback import FeedbackConfig, FeedbackController

            param_bytes = sum(
                l.size * l.dtype.itemsize
                for l in jax.tree.leaves(state["params"])
            )
            n_fb = max((int(s) for s in mesh.shape.values()), default=1)
            # the refit must land somewhere build() can SEE: the rebuild
            # below re-derives bucket sizes/topology through the planner,
            # which resolves constants from $FLEXTREE_CALIBRATION — so
            # default the write-back path to a run-local file rather than
            # leaving the loop open (refit written nowhere the rebuilt
            # step reads)
            fb_prev_cal = os.environ.get("FLEXTREE_CALIBRATION")
            fb_cal = args.feedback_calibration
            if not fb_cal:
                # the same derived record dir the flight recorder uses
                # (the controller only ever ticks with the recorder on,
                # so a recorder-less run writes nothing anywhere —
                # don't allocate a throwaway dir for it).  NEVER default
                # to $FLEXTREE_CALIBRATION itself: a drift refit calls
                # save_calibration, which replaces the backend's section
                # in place — a noisy in-run fit must not destroy the
                # host's measured tools/calibrate_host.py artifact.
                # Seeding the run-local file from it keeps the other
                # backends' sections and the measured provenance intact.
                # no obs dir: a PER-RUN private dir, never a fixed name
                # in the world-shared tempdir (a foreign-owned or
                # pre-planted file at a fixed /tmp path would abort the
                # copy below or redirect it through a symlink)
                fb_cal = os.path.join(
                    obs_dir
                    if obs_dir is not None
                    else tempfile.mkdtemp(prefix="ft-feedback-"),
                    "CALIBRATION.feedback.json",
                )
                if fb_prev_cal and os.path.exists(fb_prev_cal):
                    shutil.copyfile(fb_prev_cal, fb_cal)

            def _feedback_rebuild(plan, params):
                # rebuild with the refitted constants: point the planner
                # at the calibration the controller just wrote back (the
                # live state stays — only the fn/mesh/specs swap, so the
                # rebuild skips materializing a second train state).
                # The env var must STAY pointed at the refit for the rest
                # of the run: build() only constructs the jitted fn — the
                # swapped step first TRACES on the next fit iteration,
                # where plan_buckets resolves $FLEXTREE_CALIBRATION to
                # derive bucket sizes.  Restoring here would hand that
                # trace the stale constants and silently re-open the
                # loop's bucket half (the fit-end finally below restores
                # the original value for in-process callers).
                os.environ["FLEXTREE_CALIBRATION"] = fb_cal
                _none, f2, m2, sp2, pk2, up2 = build(args, init_state=False)
                return (f2, m2, sp2, pk2, up2)

            controller = FeedbackController(
                n_fb,
                param_bytes,
                FeedbackConfig(
                    every_k=args.feedback_every,
                    band=args.feedback_band,
                    calibration_path=fb_cal,
                    on_replan=_feedback_rebuild,
                ),
            )
            if supervision is None:
                supervision = Supervision()
            supervision.feedback = controller
        dataset = LMDataset(
            synthetic_tokens(args.corpus_tokens, args.vocab, seed=args.seed),
            batch=args.batch,
            seq_len=args.seq_len,
            seed=args.seed,
        )
        # the built step donates its state, so fit gets the ONLY
        # reference to it (popped in the call itself): a name kept here
        # would hold the initial state's buffers until the first step
        # consumed them, and a deleted array after it
        hand_over = [state]
        del state
        try:
            result = fit(
                hand_over.pop(),
                step_fn,
                dataset,
                FitConfig(
                    num_steps=args.steps,
                    ckpt_dir=args.ckpt_dir,
                    ckpt_every=args.ckpt_every,
                    log_every=args.log_every,
                    resume=not args.no_resume,
                ),
                mesh=mesh,
                state_specs=sspecs,
                supervision=supervision,
                state_pack=state_pack,
                state_unpack=state_unpack,
            )
        finally:
            if supervision is not None and supervision.preemption is not None:
                supervision.preemption.uninstall()  # in-process callers (tests)
            if args.feedback_every > 0:
                # a replan rebuild repoints $FLEXTREE_CALIBRATION at the
                # refit file for the rest of the run (the swapped step
                # traces lazily); restore the pre-run value so in-process
                # callers (tests) aren't left with a run-local path
                if fb_prev_cal is None:
                    os.environ.pop("FLEXTREE_CALIBRATION", None)
                else:
                    os.environ["FLEXTREE_CALIBRATION"] = fb_prev_cal
    return TrainRun(result, step_fn, mesh, dataset)


def main(argv=None) -> int:
    args = parse_args(argv)
    run = train(args)
    result, mesh = run.result, run.mesh
    first = result.losses[0][1] if result.losses else float("nan")
    last = result.losses[-1][1] if result.losses else float("nan")
    print(
        f"{args.model}: {result.steps_run} steps on mesh "
        f"{dict(mesh.shape)}; loss {first:.4f} -> {last:.4f}"
        + (f" (resumed from {result.resumed_from})" if result.resumed_from else "")
        + (
            f" (preempted at step {result.report.preempted_at}, checkpointed)"
            if result.report.preempted_at is not None
            else ""
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
