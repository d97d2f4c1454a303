#!/usr/bin/env python
"""Executed proof of the closed planner-feedback loop (ISSUE 12).

Scenario, all on the live 8-virtual-device CPU backend — real collectives,
real jitted train steps, real flight records:

1. **Oracle calibration**: fit the cost constants from fresh measured
   (topology, size) points (``calibrate.measure_points`` +
   ``fit_cost_params`` — the calibrate_host protocol), and build the
   oracle train step from them.
2. **Deliberate mis-calibration**: write a CALIBRATION whose α-β skew
   (near-zero launch/latency, starved bandwidth) drives
   ``choose_bucket_bytes`` to a provably different argmin — tiny
   per-leaf-scale buckets instead of the oracle's fused ones — and build the
   mis-calibrated step from it.  The tool REFUSES the scenario if the two
   plans coincide (nothing would be proven).
3. **The feedback run**: ``fit(supervision=Supervision(feedback=...))``
   starting from the skewed constants, flight recorder ON.  Every K
   steps the controller probes the wire; the drift band breaches, the
   constants refit from the recorded residuals
   (``save_calibration(source="feedback")``), the seeded autotune
   plan-cache entry is invalidated, and the replan hook rebuilds the
   step — which re-derives its bucket plan from the refreshed
   calibration at trace time.
4. **Machine checks** (non-zero exit on violation):
   - a feedback replan fired within the step budget;
   - the refit calibration carries ``source="feedback"`` + sample count;
   - the drift-invalidated plan-cache entry is RE-MEASURED on the next
     autotune call (``source="measured"``, not ``"cache"``), then cached;
   - the recovered step's measured time is ≥ 90% of the oracle step's
     (shuffled-interleaved rounds; the enforced number is the median of
     per-round PAIRED oracle/recovered ratios — two variants'
     independent min-of-reps draws swing far more on a timeshared host
     than any within-round ratio does) — the convergence floor;
   - the mis-calibrated step is genuinely slower than the oracle step
     (scenario validity — without a gap, "recovery" is vacuous);
   - recorder-off overhead: with NO recorder installed the armed hook
     (a) never ticks a probe and (b) costs a machine-measured fraction
     of one step far under the budget — the hook is one None check, and
     that is measured directly (a paired whole-fit A/B is recorded as
     informational context: on a timeshared host its run-to-run wander
     is orders of magnitude larger than the hook itself, so it cannot
     be an enforceable floor — the direct measurement can);
   - the run's flight record yields paired residual samples and a
     schema-valid merged timeline.

``--smoke`` shrinks every measured phase and waives the three TIMING
floors (recovery fraction, mis-calibration gap, overhead ratio — a CI
container's timeshared minute cannot hold them honestly) while keeping
every correctness floor.  The committed FEEDBACK.json is always a full
run.

Usage: python tools/feedback_convergence.py [--out FEEDBACK.json] [--smoke]
"""

from __future__ import annotations

import argparse
import contextlib
import datetime
import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RECOVERY_FLOOR = 0.90  # recovered >= 90% of the oracle step time
MISCAL_GAP_FLOOR = 1.05  # the wrong plan must be measurably wrong
#: recorder-off budget: the armed hook's directly-measured per-step cost
#: as a fraction of the measured step time (one None check ~ tens of ns
#: against a tens-of-ms step; 0.5% leaves 3 orders of magnitude slack)
OVERHEAD_FRAC_BUDGET = 0.005


@contextlib.contextmanager
def _calibration_env(path: str):
    """Point FLEXTREE_CALIBRATION at ``path`` for a build+warm window —
    bucket sizes are derived from it at trace time."""
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(REPO, "FEEDBACK.json"))
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
    import numpy as np

    import tempfile

    from flextree_tpu.bench import measure_points
    from flextree_tpu.data import LMDataset, synthetic_tokens
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.obs import flight_recorder
    from flextree_tpu.obs.timeline import (
        merge_dir,
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
        extract_residuals,
    )
    from flextree_tpu.schedule.stages import Topology
    from flextree_tpu.utils.buildstamp import artifact_meta
    from flextree_tpu.utils.timing import time_interleaved

    smoke = args.smoke
    n = 8
    every_k = 3 if smoke else 5
    num_steps = every_k * (3 if smoke else 6)
    time_repeat = 6 if smoke else 16
    overhead_reps = 4 if smoke else 12
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
                "recovery_frac": RECOVERY_FLOOR,
                "miscal_gap": MISCAL_GAP_FLOOR,
                "overhead_frac": OVERHEAD_FRAC_BUDGET,
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
    result["model"] = {
        "param_bytes": param_bytes,
        "n_leaves": n_leaves,
        "n_layers": model_cfg.n_layers,
    }

    with tempfile.TemporaryDirectory() as td:
        # ---- 1. oracle calibration: fresh measured fit -----------------
        print("== phase 1: oracle calibration (measured fit)")
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
            meta={"protocol": "feedback_convergence oracle fit"},
        )

        # ---- 2. deliberate mis-calibration -----------------------------
        # near-zero fixed costs + starved bandwidth: the byte term
        # dominates every fixed term, so choose_bucket_bytes' argmin runs
        # to k_max — per-leaf-scale buckets
        skew_params = TpuCostParams(
            ici=LinkParams(bandwidth_GBps=0.01, latency_us=0.001),
            dcn=LinkParams(bandwidth_GBps=0.01, latency_us=0.001),
            reduce_bw_GBps=0.05,
            control_us_per_width=0.0,
            launch_us=0.001,
        )
        skew_path = os.path.join(td, "CALIBRATION_live.json")
        save_calibration(
            skew_path, skew_params, backend="cpu", source="measured",
            meta={"protocol": "DELIBERATELY SKEWED (feedback_convergence)"},
        )

        topo = Topology.flat(n)
        oracle_bucket = choose_bucket_bytes(
            param_bytes, [topo], n_leaves=n_leaves, params=oracle_params
        )
        skew_bucket = choose_bucket_bytes(
            param_bytes, [topo], n_leaves=n_leaves, params=skew_params
        )
        result["plans"] = {
            "oracle": {
                "bucket_bytes": oracle_bucket,
                "topo": choose_topology(
                    n, param_bytes, params=oracle_params
                ).to_ft_topo(),
            },
            "miscalibrated": {
                "bucket_bytes": skew_bucket,
                "topo": choose_topology(
                    n, param_bytes, params=skew_params
                ).to_ft_topo(),
            },
        }
        print(f"   oracle bucket {oracle_bucket}B vs skewed {skew_bucket}B")
        if skew_bucket >= oracle_bucket:
            violations.append(
                f"scenario invalid: skewed bucket argmin {skew_bucket}B is "
                f"not smaller than the oracle's {oracle_bucket}B — the "
                "mis-calibration proves nothing"
            )

        # ---- build + warm the oracle and mis-calibrated steps ----------
        def build_step(calib_path):
            with _calibration_env(calib_path):
                fn = make_train_step(mesh, model_cfg, tcfg)
                jax.block_until_ready(fn(state, toks, tgts))  # trace here
            return fn

        print("== phase 2: build oracle + mis-calibrated steps")
        step_oracle = build_step(oracle_path)
        step_miscal = build_step(skew_path)

        # ---- 3. the feedback run ---------------------------------------
        print("== phase 3: feedback run from the mis-calibrated start")
        cache_path = os.path.join(td, "plan_cache.json")
        with _calibration_env(skew_path):
            seed_plan = autotune_plan(
                n, param_bytes, codecs=("f32",), top_k=2, repeat=2,
                cache_path=cache_path,
            )
        cache_sources = [seed_plan.source]

        obs_dir = os.path.join(td, "obs")
        rebuild_log: list = []

        def on_replan(plan, params):
            fn = make_train_step(mesh, model_cfg, tcfg)
            rebuild_log.append(plan.to_ft_topo())
            return (fn, mesh, sspecs)

        controller = FeedbackController(
            n, param_bytes,
            FeedbackConfig(
                every_k=every_k,
                band=0.5,
                calibration_path=skew_path,  # refits overwrite the live file
                plan_cache_path=cache_path,
                on_replan=on_replan,
                run_id="feedback_convergence",
            ),
            params=skew_params,
        )
        with _calibration_env(skew_path):
            with flight_recorder(obs_dir, 0):
                fb_result = fit(
                    state, step_miscal, dataset,
                    FitConfig(num_steps=num_steps, log_every=0, prefetch=0),
                    mesh=mesh, state_specs=sspecs,
                    supervision=Supervision(feedback=controller),
                )
            # the recovered step: trace against the REFIT calibration
            print("== phase 4: build recovered step from the refit")
            step_recovered = build_step(skew_path)

        report = fb_result.report
        result["feedback_run"] = {
            "steps": fb_result.steps_run,
            "refits": report.feedback_refits,
            "replans": report.feedback_replans,
            "refusals": report.feedback_refusals,
            "rebuilds": rebuild_log,
            "probe_ticks": controller.ticks,
        }
        if report.feedback_replans < 1:
            violations.append(
                f"no feedback replan fired within {num_steps} steps "
                f"(refits={report.feedback_refits}, "
                f"refusals={report.feedback_refusals})"
            )

        # refit provenance stamp
        with open(skew_path) as f:
            live_doc = json.load(f)
        sec = live_doc.get("cpu", {})
        result["refit_calibration"] = {
            "source": sec.get("source"),
            "schema": sec.get("schema"),
            "samples": sec.get("meta", {}).get("samples"),
            "run_id": sec.get("meta", {}).get("run_id"),
        }
        if sec.get("source") != "feedback":
            violations.append(
                f"refit calibration source is {sec.get('source')!r}, "
                "expected 'feedback'"
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

        # drift-invalidated cache entry re-measured, then a pure hit
        with _calibration_env(skew_path):
            replan_tune = autotune_plan(
                n, param_bytes, codecs=("f32",), top_k=2, repeat=2,
                cache_path=cache_path,
            )
            cache_sources.append(replan_tune.source)
            hit_tune = autotune_plan(
                n, param_bytes, codecs=("f32",), top_k=2, repeat=2,
                cache_path=cache_path,
            )
            cache_sources.append(hit_tune.source)
        result["plan_cache"] = {"sources": cache_sources}
        if cache_sources != ["measured", "measured", "cache"]:
            violations.append(
                "plan-cache trail should be seeded-measured -> "
                "re-measured-after-invalidation -> cache-hit; got "
                f"{cache_sources}"
            )

        # residual extraction + merged timeline from the run's record
        samples, skipped = extract_residuals(obs_dir)
        result["residuals"] = {
            "samples": len(samples),
            "paired": sum(1 for s in samples if s.source == "paired"),
            "skipped": skipped,
            "table": residual_table(samples, skipped).splitlines(),
        }
        if not samples:
            violations.append("flight record yielded no residual samples")
        doc = merge_dir(obs_dir)
        bad = validate_trace(doc)
        measured_spans = sum(
            1 for ev in doc["traceEvents"]
            if ev.get("cat") == "comm-measured"
        )
        result["timeline"] = {
            "events": len(doc["traceEvents"]),
            "schema_violations": bad,
            "comm_measured_spans": measured_spans,
        }
        if bad:
            violations.append(f"merged timeline schema-invalid: {bad[:3]}")
        if measured_spans == 0:
            violations.append("merged timeline has no comm-measured spans")

        # ---- 5. paired timing: oracle vs miscal vs recovered -----------
        print("== phase 5: paired step timing (oracle / miscal / recovered)")
        rows = time_interleaved(
            {
                "oracle": (step_oracle, (state, toks, tgts)),
                "miscal": (step_miscal, (state, toks, tgts)),
                "recovered": (step_recovered, (state, toks, tgts)),
            },
            time_repeat,
        )
        oracle_ms = rows["oracle"]["min_ms"]
        miscal_ms = rows["miscal"]["min_ms"]
        recovered_ms = rows["recovered"]["min_ms"]
        # PAIRED statistic: round i of all three variants ran inside the
        # same shuffled round, so per-round ratios cancel round-level
        # contention.  The median of those ratios is the enforced number —
        # on this oversubscribed host (8 virtual devices on 2 cores) the
        # min-of-reps of two variants' INDEPENDENT draws was measured
        # swinging 0.67..1.02 between runs of the identical plan pair,
        # while the paired median moves a few percent.
        import statistics

        o_ts = rows["oracle"]["times_ms"]
        m_ts = rows["miscal"]["times_ms"]
        r_ts = rows["recovered"]["times_ms"]
        recovery_frac = statistics.median(
            o / max(r, 1e-9) for o, r in zip(o_ts, r_ts)
        )
        miscal_gap = statistics.median(
            m / max(o, 1e-9) for m, o in zip(m_ts, o_ts)
        )
        result["timing"] = {
            "rows": rows,
            "oracle_min_ms": oracle_ms,
            "miscal_min_ms": miscal_ms,
            "recovered_min_ms": recovered_ms,
            "recovery_frac": round(recovery_frac, 4),
            "miscal_gap": round(miscal_gap, 4),
            "protocol": "median of per-round paired ratios "
            "(shuffled-interleaved rounds)",
        }
        print(
            f"   oracle {oracle_ms:.2f}ms, miscal {miscal_ms:.2f}ms, "
            f"recovered {recovered_ms:.2f}ms (min-of-reps, context) -> "
            f"paired recovery {recovery_frac:.3f}, "
            f"miscal gap {miscal_gap:.3f}"
        )
        if not smoke:
            if recovery_frac < RECOVERY_FLOOR:
                violations.append(
                    f"recovered step holds only {recovery_frac:.3f} of the "
                    f"oracle step time < floor {RECOVERY_FLOOR}"
                )
            if miscal_gap < MISCAL_GAP_FLOOR:
                violations.append(
                    f"mis-calibrated step gap {miscal_gap:.3f} < "
                    f"{MISCAL_GAP_FLOOR} — scenario not probative on this "
                    "host"
                )

        # ---- 6. recorder-off overhead ----------------------------------
        print("== phase 6: recorder-off overhead of the armed hook")
        armed = FeedbackController(
            n, param_bytes, FeedbackConfig(every_k=every_k),
            params=controller.params,
            timer=lambda probes, nn: (_ for _ in ()).throw(
                AssertionError("probe timer ran with the recorder off")
            ),
        )
        # (a) the DIRECT measurement: the hook is called once per step;
        # with no recorder installed it must short-circuit on the same
        # None check record_event makes.  Time it alone — this is the
        # enforceable number (a whole-fit A/B below is recorded for
        # context, but its run-to-run wander on a timeshared host is
        # orders of magnitude larger than the hook itself).
        calls = 100_000
        t0 = time.perf_counter()
        for i in range(calls):
            armed.maybe_tick(i)
        hook_us = (time.perf_counter() - t0) / calls * 1e6
        overhead_frac = hook_us / max(oracle_ms * 1e3, 1e-9)  # vs step in us
        if armed.ticks != 0:
            violations.append(
                "feedback controller ticked with no recorder installed"
            )
        # (b) informational paired whole-fit A/B: armed-no-recorder vs
        # unarmed, shuffled-interleaved, min-of-reps
        warm_step = step_recovered  # compiled; both variants share it
        import jax.numpy as jnp

        base_state = dict(fb_result.state)
        base_state["step"] = jnp.zeros_like(base_state["step"])
        overhead_steps = 6

        def timed_fit(supervision):
            t0 = time.perf_counter()
            fit(
                base_state, warm_step, dataset,
                FitConfig(num_steps=overhead_steps, log_every=0, prefetch=0),
                supervision=supervision,
            )
            return time.perf_counter() - t0

        lap: dict[str, list[float]] = {"armed": [], "off": []}
        order = ["armed", "off"]
        shuffler = random.Random(0)
        for _ in range(overhead_reps):
            shuffler.shuffle(order)
            for name in order:
                sup = (
                    Supervision(feedback=armed)
                    if name == "armed"
                    else Supervision()
                )
                lap[name].append(timed_fit(sup))
        ab_ratio = min(lap["armed"]) / max(min(lap["off"]), 1e-9)
        result["overhead"] = {
            "hook_us_per_step": round(hook_us, 4),
            "overhead_frac_of_step": round(overhead_frac, 7),
            "frac_budget": OVERHEAD_FRAC_BUDGET,
            "fit_ab_ratio_informational": round(ab_ratio, 4),
            "fit_ab_note": (
                "whole-fit A/B on a timeshared host wanders several "
                "percent run-to-run — context only; the enforced number "
                "is the directly-measured hook cost above"
            ),
            "reps": overhead_reps,
            "steps_per_fit": overhead_steps,
        }
        print(
            f"   hook {hook_us:.3f}us/step = {overhead_frac:.2e} of a "
            f"step (budget {OVERHEAD_FRAC_BUDGET}); fit A/B ratio "
            f"{ab_ratio:.4f} (informational)"
        )
        if not smoke and overhead_frac > OVERHEAD_FRAC_BUDGET:
            violations.append(
                f"recorder-off hook costs {overhead_frac:.2e} of a step "
                f"> budget {OVERHEAD_FRAC_BUDGET}"
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
    print("all feedback-convergence checks passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
