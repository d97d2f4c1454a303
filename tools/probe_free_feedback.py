#!/usr/bin/env python
"""Executed proof of probe-free per-step cost attribution (ISSUE 15).

The closed loop PR 12 proved (FEEDBACK.json) timed DEDICATED probe
collectives every K steps.  This driver proves the same mis-calibrated
start recovers with ZERO dedicated wire collectives: every recorded
training step is itself the measurement (host-timed against its
compile-time plan, ``obs/stepclock.py``), drift is detected from the
per-step spans, and the refit solves per-phase scale factors across a
bucket-size ROTATION — bitwise-invariant plan variants of the same
training run, so the calibration sample is free production traffic
(the arXiv:1912.03413 microbenchmark dissection without the
microbenchmarks).

Scenario, all on the live 8-virtual-device CPU backend:

1. **Oracle calibration** (measured fit) and a **deliberately skewed**
   CALIBRATION whose argmin is provably different (tiny buckets), as in
   ``tools/feedback_convergence.py``.
2. **Compute floor**: the sync-free twin (``make_nosync_train_step``) is
   timed for a few steps — it runs ZERO collectives (asserted via a span
   ledger), so the floor measurement keeps the scenario probe-free on
   the wire.
3. **The probe-free run**: ``fit(supervision=Supervision(feedback=...))``
   with ``probe_free=True`` and a probe timer that RAISES if ever
   called.  Per-step spans detect the drift, the controller rotates the
   step through bucket-size variants, fits per-phase scales, refits the
   calibration (``source="feedback"``, ``fit.mode="probe-free"``),
   invalidates the plan cache, and swaps in the replanned step in-run.
4. **Fleet pooling**: three mini probe-based runs each record a
   deliberately THIN residual set (one topology at two sizes — alone,
   each refuses to fit); ``python -m flextree_tpu.obs fleet`` pools them
   per backend fingerprint and the pooled fit must be strictly
   better-conditioned than every constituent.
5. **Machine checks** (non-zero exit on violation):
   - zero dedicated probe collectives in the probe-free run (counted
     from the flight record: no ``ftfb`` probe events, no probing
     ticks) and a probe-free refit actually fired with per-phase scales
     in its calibration provenance;
   - paired recovery >= 0.9 x the probe-based FEEDBACK.json recovery
     (the committed artifact is the baseline this rung must hold);
   - per-step span overhead <= 2% of a step: the span clock's host path
     (events + apportionment + detector feed + spill, full plan,
     recorder on) timed directly per call — the enforceable number; the
     ``ours_fused_recorded``-style paired step A/B is recorded beside
     it as context (on this timeshared host its contention spikes are
     bimodal and swing the paired ratio past the budget between runs of
     identical code — the same reason FEEDBACK.json enforces the
     directly-measured hook, not the whole-fit A/B);
   - fleet-pooled fit strictly better-conditioned than every
     constituent run;
   - the merged timeline is schema-valid and renders measured-vs-
     predicted span pairs carrying per-phase breakdowns.

``--smoke`` shrinks every measured phase and waives the TIMING floors
(recovery fraction, mis-calibration gap, span overhead — a CI
container's timeshared minute cannot hold them honestly) while keeping
every correctness floor.  The committed OBS_ATTRIBUTION.json is always
a full run.

Usage: python tools/probe_free_feedback.py [--out OBS_ATTRIBUTION.json]
       [--smoke]
"""

from __future__ import annotations

import argparse
import contextlib
import datetime
import io
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: recovery must hold this fraction of the PROBE-BASED artifact's
#: recovery (FEEDBACK.json timing.recovery_frac) — probe-free may cost a
#: little fidelity, not a regime
RECOVERY_VS_PROBE_FLOOR = 0.90
MISCAL_GAP_FLOOR = 1.05
SPAN_BUDGET_FRAC = 0.02  # per-step span-clock cost, the PR-10 2% budget


@contextlib.contextmanager
def _calibration_env(path: str):
    prev = os.environ.get("FLEXTREE_CALIBRATION")
    prev_b = os.environ.get("FLEXTREE_CALIBRATION_BACKEND")
    os.environ["FLEXTREE_CALIBRATION"] = path
    os.environ["FLEXTREE_CALIBRATION_BACKEND"] = "cpu"
    try:
        yield
    finally:
        for key, val in (
            ("FLEXTREE_CALIBRATION", prev),
            ("FLEXTREE_CALIBRATION_BACKEND", prev_b),
        ):
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


def make_nosync_train_step(mesh, model_cfg, train_cfg, axis_names=("dp", "sp", "tp")):
    """The sync-free twin of ``make_train_step``: identical forward,
    backward and AdamW, gradient sync elided — NOT a training step (the
    replicas would diverge) but the compute floor: it runs zero
    collectives on the gradients, so timing it keeps the scenario
    probe-free on the wire."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from flextree_tpu.models.transformer import cross_entropy_loss, forward
    from flextree_tpu.parallel.train import (
        guarded_adamw,
        maybe_clip_grads,
        metric_specs,
        state_specs,
        validate_tp,
    )

    dp, sp, tp = axis_names
    validate_tp(model_cfg, mesh.shape[tp])
    sspecs = state_specs(model_cfg, tp, train_cfg)
    data_spec = P(dp, sp)

    def device_step(state, tokens, targets):
        n_total_tokens = (
            tokens.size
            * lax.axis_size(dp)
            * lax.axis_size(sp)
            * lax.axis_size(tp)
        )

        def local_loss(params):
            logits = forward(params, tokens, model_cfg, tp_axis=tp, sp_axis=sp)
            loss_sum, _ = cross_entropy_loss(logits, targets)
            return loss_sum / n_total_tokens

        loss, grads = jax.value_and_grad(local_loss)(state["params"])
        global_loss = lax.psum(lax.psum(lax.psum(loss, dp), sp), tp)
        metrics = {"loss": global_loss}
        # clip compute stays (compute parity with the real step — only
        # the SYNC is elided), and it also keeps the metrics pytree
        # matching metric_specs when clipping is configured
        grads = maybe_clip_grads(grads, sspecs["params"], train_cfg, metrics)
        new_state = guarded_adamw(state, grads, None, train_cfg, metrics)
        return new_state, metrics

    mspec = metric_specs(train_cfg, {"loss": P()})
    return jax.jit(
        jax.shard_map(
            device_step, mesh=mesh, in_specs=(sspecs, data_spec, data_spec),
            out_specs=(sspecs, mspec), check_vma=False,
        )
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(REPO, "OBS_ATTRIBUTION.json"))
    ap.add_argument(
        "--smoke", action="store_true",
        help="shrink measured phases; waive timing floors, keep "
        "correctness floors",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    import statistics
    import tempfile

    import numpy as np  # noqa: F401 (assertions below)

    from flextree_tpu.bench import measure_points
    from flextree_tpu.data import LMDataset, synthetic_tokens
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.obs import flight_recorder
    from flextree_tpu.obs.__main__ import main as obs_cli
    from flextree_tpu.obs.timeline import (
        merge_dir,
        read_dir,
        residual_pairs,
        residual_table,
        validate_trace,
    )
    from flextree_tpu.parallel.loop import FitConfig, Supervision, fit
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        state_specs,
    )
    from flextree_tpu.parallel.train import keeping_state
    from flextree_tpu.parallel.train import make_train_step as _built_step

    def make_train_step(*a, **kw):
        # a built step donates its state; this harness reuses ONE state
        return keeping_state(_built_step(*a, **kw))

    from flextree_tpu.planner import (
        LinkParams,
        TpuCostParams,
        autotune_plan,
        choose_topology,
        fit_cost_params,
        save_calibration,
    )
    from flextree_tpu.planner.choose import choose_bucket_bytes
    from flextree_tpu.planner.feedback import (
        FeedbackConfig,
        FeedbackController,
        ProbePoint,
    )
    from flextree_tpu.schedule.stages import Topology
    from flextree_tpu.utils.buildstamp import artifact_meta
    from flextree_tpu.utils.profiling import span_ledger
    from flextree_tpu.utils.timing import time_interleaved

    smoke = args.smoke
    n = 8
    every_k = 5
    rotation_cycles = 2 if smoke else 3
    # detection tick + (2 variants + base revisit) x cycles swaps + fit
    # tick + recovered tail, with room for a SECOND full rotation
    # attempt when a noisy first window refuses the fit
    num_steps = every_k * (3 * rotation_cycles * 2 + (4 if smoke else 8))
    time_repeat = 6 if smoke else 16
    floor_steps = 3 if smoke else 6
    violations: list[str] = []
    result: dict = {
        "smoke": smoke,
        "build": artifact_meta(),
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "protocol": {
            "devices": n,
            "every_k": every_k,
            "num_steps": num_steps,
            "time_repeat": time_repeat,
            "floors": {
                "recovery_vs_probe": RECOVERY_VS_PROBE_FLOOR,
                "miscal_gap": MISCAL_GAP_FLOOR,
                "span_overhead": SPAN_BUDGET_FRAC,
                "timing_floors_enforced": not smoke,
            },
        },
    }

    mesh = make_mesh_nd(n, (n, 1, 1), ("dp", "sp", "tp"))
    model_cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4,
        n_layers=3 if smoke else 6, d_ff=128,
    )
    tcfg = TrainConfig()
    state = init_train_state(jax.random.PRNGKey(args.seed), model_cfg)
    sspecs = state_specs(
        model_cfg, "tp", tcfg, mesh=mesh, axis_names=("dp", "sp", "tp")
    )
    param_leaves = jax.tree.leaves(state["params"])
    param_bytes = sum(l.size * l.dtype.itemsize for l in param_leaves)
    n_leaves = len(param_leaves)
    dataset = LMDataset(
        synthetic_tokens(120_000, 256, seed=args.seed),
        batch=8, seq_len=64, seed=args.seed,
    )
    toks, tgts = dataset.batch_at(0)
    result["model"] = {"param_bytes": param_bytes, "n_leaves": n_leaves}

    with tempfile.TemporaryDirectory() as td:
        # ---- 1. oracle + skewed calibrations ---------------------------
        print("== phase 1: oracle calibration + deliberate skew")
        points = measure_points(
            ["8", "4,2", "2,2,2", "1"],
            [1 << 14, 1 << 17, 1 << 20] if not smoke else [1 << 14, 1 << 18],
            repeat=3 if smoke else 7,
            devices=n,
        )
        oracle_params = fit_cost_params(points)
        oracle_path = os.path.join(td, "CALIBRATION_oracle.json")
        save_calibration(
            oracle_path, oracle_params, backend="cpu", source="measured",
            meta={"protocol": "probe_free_feedback oracle fit"},
        )
        skew_params = TpuCostParams(
            ici=LinkParams(bandwidth_GBps=0.01, latency_us=0.001),
            dcn=LinkParams(bandwidth_GBps=0.01, latency_us=0.001),
            reduce_bw_GBps=0.05,
            control_us_per_width=0.0,
            launch_us=0.001,
        )
        skew_path = os.path.join(td, "CALIBRATION_live.json")
        skew_frozen_path = os.path.join(td, "CALIBRATION_skew_frozen.json")
        for p in (skew_path, skew_frozen_path):
            save_calibration(
                p, skew_params, backend="cpu", source="measured",
                meta={"protocol": "DELIBERATELY SKEWED (probe_free_feedback)"},
            )
        topo = Topology.flat(n)
        oracle_bucket = choose_bucket_bytes(
            param_bytes, [topo], n_leaves=n_leaves, params=oracle_params
        )
        skew_bucket = choose_bucket_bytes(
            param_bytes, [topo], n_leaves=n_leaves, params=skew_params
        )
        result["plans"] = {
            "oracle": {"bucket_bytes": oracle_bucket,
                       "topo": choose_topology(
                           n, param_bytes, params=oracle_params).to_ft_topo()},
            "miscalibrated": {"bucket_bytes": skew_bucket,
                              "topo": choose_topology(
                                  n, param_bytes, params=skew_params
                              ).to_ft_topo()},
        }
        print(f"   oracle bucket {oracle_bucket}B vs skewed {skew_bucket}B")
        if skew_bucket >= oracle_bucket:
            violations.append(
                f"scenario invalid: skewed bucket argmin {skew_bucket}B not "
                f"smaller than oracle's {oracle_bucket}B"
            )

        def build_step(calib_path, bucket_bytes=None):
            cfg = (
                tcfg if bucket_bytes is None
                else TrainConfig(bucket_bytes=int(bucket_bytes))
            )
            with _calibration_env(calib_path):
                fn = make_train_step(mesh, model_cfg, cfg)
                jax.block_until_ready(fn(state, toks, tgts))
            return fn

        print("== phase 2: build the oracle step")
        step_oracle = build_step(oracle_path)
        # the feedback run's step is deliberately UNCOMPILED: its first
        # call must trace inside the run so the plan capture sees the
        # compile-time bucket plan (the production pattern — a fresh run
        # always compiles its step under the recorder)
        with _calibration_env(skew_path):
            step_live = make_train_step(mesh, model_cfg, tcfg)

        # ---- 2. the compute floor: sync-free twin, zero collectives ----
        print("== phase 3: compute floor from the sync-free twin")
        with _calibration_env(skew_path):
            nosync = make_nosync_train_step(mesh, model_cfg, tcfg)
        with span_ledger() as led:
            jax.block_until_ready(nosync(state, toks, tgts))  # compile
        nosync_spans = len(led.names)
        floor_times = []
        for _ in range(floor_steps):
            t0 = time.perf_counter()
            jax.block_until_ready(nosync(state, toks, tgts))
            floor_times.append(time.perf_counter() - t0)
        compute_floor_us = min(floor_times) * 1e6
        result["compute_floor"] = {
            "floor_us": round(compute_floor_us, 1),
            "nosync_comm_spans": nosync_spans,
            "steps": floor_steps,
        }
        if nosync_spans != 0:
            violations.append(
                f"sync-free twin traced {nosync_spans} comm span(s) — the "
                "floor measurement is not collective-free"
            )

        # ---- 3. the probe-free feedback run ----------------------------
        print("== phase 4: probe-free feedback run (skewed start)")
        cache_path = os.path.join(td, "plan_cache.json")
        with _calibration_env(skew_path):
            seed_plan = autotune_plan(
                n, param_bytes, codecs=("f32",), top_k=2, repeat=2,
                cache_path=cache_path,
            )
        cache_sources = [seed_plan.source]
        obs_dir = os.path.join(td, "obs")
        rebuild_log: list = []
        rotate_log: list = []

        def on_replan(plan, params):
            fn = make_train_step(mesh, model_cfg, tcfg)
            rebuild_log.append(plan.to_ft_topo())
            return (fn, mesh, sspecs)

        def on_rotate(bucket_bytes):
            rotate_log.append(int(bucket_bytes))
            with _calibration_env(skew_path):
                fn = make_train_step(
                    mesh, model_cfg, TrainConfig(bucket_bytes=int(bucket_bytes))
                )
            return (fn, mesh, sspecs)

        def forbidden_timer(probes, nn):
            raise AssertionError(
                "dedicated probe timer ran in the probe-free scenario"
            )

        controller = FeedbackController(
            n, param_bytes,
            FeedbackConfig(
                every_k=every_k,
                band=0.5,
                probe_free=True,
                compute_floor_us=compute_floor_us,
                rotation_cycles=rotation_cycles,
                # rotate DOWNWARD: many tiny buckets make the per-bucket
                # fixed cost move the step time well past the host's
                # noise, and small sizes stay inside the regime the α-β
                # model is valid in (past the backend cap a BIGGER bucket
                # gets slower from cache pressure — the model's documented
                # blind spot; the controller clamps there regardless)
                rotation_factors=(0.0625, 0.25),
                calibration_path=skew_path,
                plan_cache_path=cache_path,
                on_replan=on_replan,
                on_rotate=on_rotate,
                run_id="probe_free_feedback",
            ),
            params=skew_params,
            timer=forbidden_timer,
        )
        with _calibration_env(skew_path):
            with flight_recorder(obs_dir, 0):
                fb_result = fit(
                    state, step_live, dataset,
                    FitConfig(num_steps=num_steps, log_every=0, prefetch=0),
                    mesh=mesh, state_specs=sspecs,
                    supervision=Supervision(feedback=controller),
                )
        print("== phase 5: build recovered + mis-calibrated timing steps")
        step_recovered = build_step(skew_path)
        step_miscal = build_step(skew_frozen_path)

        report = fb_result.report
        result["feedback_run"] = {
            "steps": fb_result.steps_run,
            "refits": report.feedback_refits,
            "replans": report.feedback_replans,
            "refusals": report.feedback_refusals,
            "rotations": controller.rotations,
            "rotation_bucket_bytes": rotate_log,
            "rebuilds": rebuild_log,
            "ticks": controller.ticks,
            "step_samples": len(controller.step_clock.samples),
        }
        if report.feedback_replans < 1:
            violations.append(
                f"no probe-free replan fired within {num_steps} steps "
                f"(refits={report.feedback_refits}, "
                f"refusals={report.feedback_refusals}, "
                f"rotations={controller.rotations})"
            )

        # refit provenance: source=feedback, mode=probe-free, phase scales
        with open(skew_path) as f:
            live_doc = json.load(f)
        sec = live_doc.get("cpu", {})
        fit_meta = sec.get("meta", {}).get("fit", {})
        result["refit_calibration"] = {
            "source": sec.get("source"),
            "schema": sec.get("schema"),
            "mode": fit_meta.get("mode"),
            "phase_scales": fit_meta.get("phase_scales"),
            "drifted_phase": fit_meta.get("drifted_phase"),
            "plans": fit_meta.get("plans"),
            "floor_us": fit_meta.get("floor_us"),
        }
        if sec.get("source") != "feedback":
            violations.append(
                f"refit calibration source is {sec.get('source')!r}, "
                "expected 'feedback'"
            )
        if fit_meta.get("mode") != "probe-free":
            violations.append(
                f"refit fit mode is {fit_meta.get('mode')!r}, expected "
                "'probe-free'"
            )
        if not fit_meta.get("phase_scales"):
            violations.append(
                "refit calibration carries no per-phase scales"
            )
        refit_bucket = choose_bucket_bytes(
            param_bytes, [topo], n_leaves=n_leaves, params=controller.params
        )
        result["plans"]["recovered"] = {
            "bucket_bytes": refit_bucket,
            "topo": choose_topology(
                n, param_bytes, params=controller.params
            ).to_ft_topo(),
        }

        # plan-cache invalidation trail (same contract as FEEDBACK.json)
        with _calibration_env(skew_path):
            replan_tune = autotune_plan(
                n, param_bytes, codecs=("f32",), top_k=2, repeat=2,
                cache_path=cache_path,
            )
            cache_sources.append(replan_tune.source)
        result["plan_cache"] = {"sources": cache_sources}
        if cache_sources != ["measured", "measured"]:
            violations.append(
                "drift-invalidated plan-cache entry was not re-measured: "
                f"{cache_sources}"
            )

        # ---- 4. zero dedicated probes, counted from the record ---------
        events, _dumps = read_dir(obs_dir)
        probe_events = [
            ev for ev in events
            if ev.get("kind") == "bucket_measured"
            and (ev.get("axis") == "ftfb"
                 or str(ev.get("name", "")).startswith("ftfb_probe"))
        ]
        probing_ticks = [
            ev for ev in events
            if ev.get("kind") == "feedback_tick" and ev.get("probes", 0)
        ]
        per_step_events = [
            ev for ev in events
            if ev.get("kind") == "bucket_measured" and ev.get("per_step")
        ]
        step_measured = [
            ev for ev in events if ev.get("kind") == "step_measured"
        ]
        result["probe_audit"] = {
            "dedicated_probe_events": len(probe_events),
            "probing_ticks": len(probing_ticks),
            "per_step_bucket_measured": len(per_step_events),
            "step_measured": len(step_measured),
        }
        if probe_events or probing_ticks:
            violations.append(
                f"probe-free run executed dedicated probes: "
                f"{len(probe_events)} probe event(s), "
                f"{len(probing_ticks)} probing tick(s)"
            )
        if not per_step_events:
            violations.append("no per-step bucket_measured events recorded")

        # residual extraction: per-step samples must pair with breakdowns
        samples, skipped = residual_pairs(events)
        step_samples = [s for s in samples if s.source == "step"]
        with_phases = [s for s in step_samples if s.phases is not None]
        result["residuals"] = {
            "samples": len(samples),
            "per_step": len(step_samples),
            "with_breakdowns": len(with_phases),
            "skipped": skipped,
            "table": residual_table(samples, skipped).splitlines(),
        }
        if not with_phases:
            violations.append(
                "per-step residual samples carry no per-phase breakdowns"
            )

        # merged timeline: measured-vs-predicted pairs per phase
        doc = merge_dir(obs_dir)
        bad = validate_trace(doc)
        plan_names = {
            ev.get("name") for ev in doc["traceEvents"]
            if ev.get("cat") == "comm-plan"
        }
        measured_spans = [
            ev for ev in doc["traceEvents"]
            if ev.get("cat") == "comm-measured"
        ]
        paired_spans = [
            ev for ev in measured_spans
            if ev.get("name") in plan_names
            and isinstance(ev.get("args", {}).get("predicted"), dict)
        ]
        result["timeline"] = {
            "events": len(doc["traceEvents"]),
            "schema_violations": bad,
            "comm_measured_spans": len(measured_spans),
            "paired_phase_spans": len(paired_spans),
            "step_measured_spans": sum(
                1 for ev in doc["traceEvents"]
                if ev.get("cat") == "step-measured"
            ),
        }
        if bad:
            violations.append(f"merged timeline schema-invalid: {bad[:3]}")
        if not paired_spans:
            violations.append(
                "timeline renders no measured spans paired to comm-plan "
                "spans with per-phase breakdowns"
            )

        # ---- 5. fleet pooling: thin runs alone refuse, pooled fits -----
        print("== phase 6: fleet pooling across thin single-shape runs")
        fleet_dirs = []
        for i, spec in enumerate(["8", "4,2", "ring"]):
            fdir = os.path.join(td, f"fleet_{i}")
            probes = (
                ProbePoint(spec, 1 << 20),
                ProbePoint(spec, 1 << 16),
            )
            mini = FeedbackController(
                n, param_bytes,
                FeedbackConfig(probes=probes, band=1e9, every_k=1, repeat=2),
                params=oracle_params,
            )
            with flight_recorder(fdir, 0):
                mini.tick(1)
            fleet_dirs.append(fdir)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            fleet_rc = obs_cli(["fleet", *fleet_dirs, "--json"])
        fleet_doc = json.loads(buf.getvalue())
        result["fleet"] = {"rc": fleet_rc, **fleet_doc}
        pooled_entries = [
            e for e in fleet_doc["pooled"].values()
            if e["condition"] is not None
        ]
        if not pooled_entries:
            violations.append("fleet pooled fit refused on every fingerprint")
        else:
            pooled_cond = min(e["condition"] for e in pooled_entries)
            single_conds = [
                r["condition"] if r["condition"] is not None else float("inf")
                for r in fleet_doc["runs"]
            ]
            result["fleet"]["pooled_condition"] = pooled_cond
            result["fleet"]["single_conditions"] = [
                (c if c != float("inf") else "refused") for c in single_conds
            ]
            if not all(pooled_cond < c for c in single_conds):
                violations.append(
                    f"fleet-pooled condition {pooled_cond:.3g} is not "
                    f"strictly better than every constituent "
                    f"({single_conds})"
                )

        # ---- 6. paired timing: oracle / miscal / recovered -------------
        print("== phase 7: paired step timing (oracle / miscal / recovered)")
        rows = time_interleaved(
            {
                "oracle": (step_oracle, (state, toks, tgts)),
                "miscal": (step_miscal, (state, toks, tgts)),
                "recovered": (step_recovered, (state, toks, tgts)),
            },
            time_repeat,
        )
        o_ts = rows["oracle"]["times_ms"]
        m_ts = rows["miscal"]["times_ms"]
        r_ts = rows["recovered"]["times_ms"]
        recovery_frac = statistics.median(
            o / max(r, 1e-9) for o, r in zip(o_ts, r_ts)
        )
        miscal_gap = statistics.median(
            m / max(o, 1e-9) for m, o in zip(m_ts, o_ts)
        )
        probe_based = None
        feedback_json = os.path.join(REPO, "FEEDBACK.json")
        if os.path.exists(feedback_json):
            with open(feedback_json) as f:
                probe_based = (
                    json.load(f).get("timing", {}).get("recovery_frac")
                )
        recovery_floor = (
            RECOVERY_VS_PROBE_FLOOR * probe_based
            if probe_based is not None
            else RECOVERY_VS_PROBE_FLOOR
        )
        result["timing"] = {
            "rows": rows,
            "recovery_frac": round(recovery_frac, 4),
            "miscal_gap": round(miscal_gap, 4),
            "probe_based_recovery": probe_based,
            "recovery_floor": round(recovery_floor, 4),
            "protocol": "median of per-round paired ratios "
            "(shuffled-interleaved rounds)",
        }
        print(
            f"   paired recovery {recovery_frac:.3f} (floor "
            f"{recovery_floor:.3f} = {RECOVERY_VS_PROBE_FLOOR} x "
            f"probe-based {probe_based}), miscal gap {miscal_gap:.3f}"
        )
        if not smoke:
            if recovery_frac < recovery_floor:
                violations.append(
                    f"probe-free recovery {recovery_frac:.3f} < floor "
                    f"{recovery_floor:.3f} ({RECOVERY_VS_PROBE_FLOOR} x the "
                    f"probe-based FEEDBACK.json recovery {probe_based})"
                )
            if miscal_gap < MISCAL_GAP_FLOOR:
                violations.append(
                    f"mis-calibrated gap {miscal_gap:.3f} < "
                    f"{MISCAL_GAP_FLOOR} — scenario not probative"
                )

        # ---- 7. per-step span overhead (paired, recorder on both sides)
        print("== phase 8: per-step span-clock overhead (paired)")
        from flextree_tpu.utils.profiling import plan_capture

        span_ctl = FeedbackController(
            n, param_bytes,
            FeedbackConfig(probe_free=True,
                           compute_floor_us=compute_floor_us),
            params=controller.params,
            timer=forbidden_timer,
        )
        ov_dir = os.path.join(td, "obs_overhead")
        with flight_recorder(ov_dir, 0):
            with plan_capture() as cap:
                fn_ov = build_step(skew_path)  # fresh trace under capture
            span_ctl.set_step_plan(cap)

            ov_step = {"i": 0}

            # ONE compiled program for both variants: the paired delta is
            # exactly the span clock's host path, nothing else
            def plain_step(st, tk, tg):
                return jax.block_until_ready(fn_ov(st, tk, tg))

            def clocked_step(st, tk, tg):
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn_ov(st, tk, tg))
                ov_step["i"] += 1
                span_ctl.observe_step(ov_step["i"], time.perf_counter() - t0)
                return out

            ov_rows = time_interleaved(
                {
                    "plain": (plain_step, (state, toks, tgts)),
                    "spanclock": (clocked_step, (state, toks, tgts)),
                },
                time_repeat,
            )
            # (a) the ENFORCED number: the span clock's per-step host
            # path timed directly — observe_step with the full plan, the
            # recorder on, events + apportionment + detector feed + spill
            # amortized over many calls.  The paired whole-step A/B below
            # is recorded for context, but on this timeshared host its
            # noise is bimodal (18→64 ms spikes hit single rounds on one
            # side) and swings far past the 2% budget between runs of
            # IDENTICAL code — the same reason FEEDBACK.json enforces the
            # directly-measured hook, not the whole-fit A/B.
            direct_calls = 200
            t0 = time.perf_counter()
            for i in range(direct_calls):
                span_ctl.observe_step(
                    10_000 + i, ov_rows["plain"]["min_ms"] * 1e-3
                )
            span_us_per_step = (
                (time.perf_counter() - t0) / direct_calls * 1e6
            )
        span_frac = span_us_per_step / max(
            ov_rows["plain"]["min_ms"] * 1e3, 1e-9
        )
        ab_ratio = ov_rows["spanclock"]["min_ms"] / max(
            ov_rows["plain"]["min_ms"], 1e-9
        )
        result["span_overhead"] = {
            "clock_us_per_step": round(span_us_per_step, 2),
            "frac_of_step": round(span_frac, 6),
            "budget_frac": SPAN_BUDGET_FRAC,
            "step_ab_ratio_informational": round(ab_ratio, 4),
            "step_ab_note": (
                "paired whole-step A/B on this timeshared host is "
                "bimodal (contention spikes hit single rounds) and "
                "swings past the budget between runs of identical code "
                "— context only; the enforced number is the "
                "directly-measured per-step span-clock cost above"
            ),
            "rows": ov_rows,
            "buckets_in_plan": len(span_ctl.step_clock.plan.buckets),
        }
        print(
            f"   span clock {span_us_per_step:.1f}us/step = "
            f"{span_frac:.4f} of a step (budget "
            f"{SPAN_BUDGET_FRAC}); step A/B ratio "
            f"{ab_ratio:.4f} (informational)"
        )
        if not smoke and span_frac > SPAN_BUDGET_FRAC:
            violations.append(
                f"per-step span clock costs {span_frac:.4f} of a step "
                f"> budget {SPAN_BUDGET_FRAC}"
            )

    result["violations"] = violations
    result["ok"] = not violations
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")
    if violations:
        for v in violations:
            print(f"VIOLATION: {v}", file=sys.stderr)
        return 1
    print("all probe-free attribution checks passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
