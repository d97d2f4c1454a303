#!/usr/bin/env python
"""Executed observability proof: a real 2-process SIGKILL chaos run with
the flight recorder on, merged into one cross-rank timeline.

What ``tools/chaos_runtime.py`` proves about *recovery*, this driver
proves about *evidence*: when a peer dies mid-run, the question "what did
each rank do in the moments before?" must be answerable from the files
the run left behind — not from a debugger that was never attached.

Scenario (one host, two real OS processes sharing a heartbeat dir and an
obs dir):

- **rank 0** runs a REAL jitted dense train step (bucketed FlexTree
  gradient sync over a dp-2 virtual-CPU mesh) under
  ``fit(supervision=...)`` with its flight recorder on — so the record
  contains provenance-annotated ``bucket_planned`` comm events (widths /
  codec / predicted CostBreakdown) next to measured ``step`` spans;
- **rank 1** is a heartbeating peer with its own flight recorder,
  SIGKILL'd mid-run.  A SIGKILL'd process runs no handlers — its record
  IS its spill file, written through per-step flushes;
- rank 0's membership view confirms the death, ``fit`` shrinks 2 → 1,
  and the shrink path records the epoch AND writes the guaranteed
  failure dump (``flight_00000.dump.json``).

The driver then merges both ranks' files with the production merger
(``flextree_tpu.obs``), schema-validates the result, and machine-checks
the floors (non-zero exit on any violation):

1. the killed rank's per-step-flushed record exists and carries its
   final events (last recorded step within the flush lag of the kill);
2. the survivor's dump exists with the shrink context;
3. the merged timeline is loadable Chrome-trace JSON containing the
   killed rank's track, the survivor's shrink marker, and
   provenance-annotated bucket spans.

Artifacts: ``OBS_CHAOS.json`` (checks + floors) and ``OBS_TIMELINE.json``
(the merged timeline itself — open it at https://ui.perfetto.dev).

Usage: python tools/obs_chaos.py [--out OBS_CHAOS.json]
       [--timeline-out OBS_TIMELINE.json] [--no-artifact]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# supervision budgets (seconds) — mirrors tools/chaos_runtime.py so the
# lease math below is "within budget" by construction
HB_INTERVAL = 0.2
STRAGGLER_S = 0.8
LEASE_S = 2.0
STEP_SLEEP = 0.1


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------


def child_train() -> int:
    """Rank 0: jitted bucketed train step, supervised fit, recorder on."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    import numpy as np

    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.obs import flight_recorder
    from flextree_tpu.parallel.loop import FitConfig, Supervision, fit
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_train_step,
    )
    from flextree_tpu.runtime import (
        MembershipView,
        PreemptionGuard,
        Supervisor,
        SupervisorConfig,
    )

    hb_dir = os.environ["FT_HB_DIR"]
    obs_dir = os.environ["FT_OBS_DIR"]
    world = int(os.environ["FT_WORLD"])
    steps = int(os.environ["FT_STEPS"])

    model_cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    mesh = make_mesh_nd(2, (2, 1, 1), ("dp", "sp", "tp"))
    jit_step = make_train_step(mesh, model_cfg, TrainConfig())

    def step_fn(state, tokens, targets):
        time.sleep(STEP_SLEEP)  # give the supervision layer wall-time
        return jit_step(state, tokens, targets)

    class _LMData:
        def batch_at(self, step):
            tok = (np.arange(4 * 16, dtype=np.int32).reshape(4, 16) + step) % 64
            return tok, tok

    cfg_hb = SupervisorConfig(
        rank=0, dir=hb_dir, interval_s=HB_INTERVAL,
        straggler_s=STRAGGLER_S, lease_s=LEASE_S,
    )
    supervisor = Supervisor(cfg_hb)
    supervisor.beat_now()
    barrier_view = MembershipView.for_config(cfg_hb, configured=world)
    deadline = time.time() + 60.0
    while time.time() < deadline:
        if all(s.step >= 0 for s in barrier_view.poll().values()):
            break
        time.sleep(0.05)
    else:
        print("FAIL: peers never assembled for supervision", flush=True)
        return 1

    supervision = Supervision(
        supervisor=supervisor,
        membership=MembershipView.for_config(cfg_hb, configured=world),
        configured_world=world,
        step_timeout_s=60.0,
        on_shrink=lambda n, plan: None,  # dp mesh is virtual: keep the step
        nbytes_hint=1 << 16,
        preemption=PreemptionGuard().install(),
    )

    # recorder installed BEFORE the first step so compile-time bucket
    # provenance lands in the record
    with flight_recorder(obs_dir, rank=0) as rec:
        state = init_train_state(jax.random.PRNGKey(0), model_cfg, mesh=mesh)
        result = fit(
            state, step_fn, _LMData(),
            FitConfig(num_steps=steps, log_every=10, prefetch=0),
            supervision=supervision,
        )
        payload = {
            "final_step": int(np.asarray(jax.device_get(result.state["step"]))),
            "report": result.report.to_payload(),
            "dump_path": rec.dump_path,
            "recorded": rec.recorded,
            "dumps": rec.dumps,
            "losses": [float(l) for _, l in result.losses],
        }
    print("OBS_JSON: " + json.dumps(payload), flush=True)
    return 0


def child_peer() -> int:
    """Rank 1: heartbeating peer with its own recorder — the victim."""
    from flextree_tpu.obs import flight_recorder, record_event
    from flextree_tpu.runtime import Supervisor, SupervisorConfig

    rank = int(os.environ["FT_RANK"])
    seconds = float(os.environ.get("FT_PEER_SECONDS", "60"))
    with flight_recorder(
        os.environ["FT_OBS_DIR"], rank=rank, source="peer"
    ):
        sup = Supervisor(
            SupervisorConfig(
                rank=rank, dir=os.environ["FT_HB_DIR"],
                interval_s=HB_INTERVAL, straggler_s=STRAGGLER_S,
                lease_s=LEASE_S,
            )
        ).start()
        t0 = time.time()
        step = 0
        while time.time() - t0 < seconds:
            record_event("step_start", step=step)
            time.sleep(STEP_SLEEP)
            record_event("step_end", step=step)  # flush kind: per-step spill
            step += 1
            sup.record_step(step, STEP_SLEEP)
        sup.stop()
    return 0


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------


def _spawn(role: str, env: dict):
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env={**os.environ, "FT_CHAOS_ROLE": role, **env},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _wait_for_step(hb_dir, rank, step, timeout=120.0) -> int:
    from flextree_tpu.runtime import read_control_json

    path = os.path.join(hb_dir, f"hb_{rank:05d}.json")
    deadline = time.time() + timeout
    while time.time() < deadline:
        beat = read_control_json(path)  # beats are CRC-trailered now
        if beat is not None and beat.get("step", -1) >= step:
            return beat["step"]
        time.sleep(0.05)
    raise TimeoutError(f"rank {rank} never reached step {step}")


def _payload(log: str) -> dict:
    for line in log.splitlines():
        if line.startswith("OBS_JSON: "):
            return json.loads(line[len("OBS_JSON: "):])
    return {}


def run_kill_scenario(workdir: str) -> dict:
    """SIGKILL the recorded peer mid-run; harvest + merge the evidence."""
    from flextree_tpu.obs import merge_events, read_dir, validate_trace
    from flextree_tpu.obs.recorder import DUMP_FILE_FMT, EVENT_FILE_FMT

    hb = os.path.join(workdir, "hb")
    obs = os.path.join(workdir, "obs")
    os.makedirs(hb, exist_ok=True)
    os.makedirs(obs, exist_ok=True)
    steps = 40
    env = {"FT_HB_DIR": hb, "FT_OBS_DIR": obs, "FT_WORLD": "2",
           "FT_STEPS": str(steps)}
    trainer = _spawn("train", env)
    peer = _spawn("peer", {**env, "FT_RANK": "1", "FT_PEER_SECONDS": "90"})
    checks: dict = {}
    try:
        kill_at = _wait_for_step(hb, 0, 8)
        peer_step_at_kill = _wait_for_step(hb, 1, 0)
        os.kill(peer.pid, signal.SIGKILL)
        kill_wall = time.time()
        checks["killed_at_trainer_step"] = kill_at
        checks["peer_step_at_kill"] = peer_step_at_kill
        log, rc = "", None
        try:
            log, _ = trainer.communicate(timeout=300)
            rc = trainer.returncode
        except subprocess.TimeoutExpired:
            trainer.kill()
            log, _ = trainer.communicate()
            log += "\n[parent] TIMEOUT"
    finally:
        for p in (trainer, peer):
            if p.poll() is None:
                p.kill()
                p.communicate()

    payload = _payload(log)
    report = payload.get("report", {})
    epochs = report.get("membership_epochs", [])

    # ---- the evidence floors ----------------------------------------------
    killed_file = os.path.join(obs, EVENT_FILE_FMT.format(rank=1))
    survivor_dump = os.path.join(obs, DUMP_FILE_FMT.format(rank=0))
    events, dumps = read_dir(obs)
    killed_events = [e for e in events if e.get("rank") == 1]
    survivor_events = [e for e in events if e.get("rank") == 0]
    bucket_events = [
        e for e in survivor_events
        if e["kind"] == "bucket_planned" and "predicted_us" in e
        and e.get("topo")
    ]
    shrink_events = [e for e in survivor_events if e["kind"] == "shrink"]
    last_killed_ts = max((e["ts"] for e in killed_events), default=0.0)

    doc = merge_events(events, dumps)
    violations = validate_trace(doc)
    names = {ev.get("name", "") for ev in doc["traceEvents"]}
    pids = {ev.get("pid") for ev in doc["traceEvents"] if ev.get("ph") != "M"}

    floors = {
        # 1. the killed rank left a per-step-flushed record with its
        # final events (within 2 steps + a flush of the kill moment)
        "killed_rank_file_exists": os.path.exists(killed_file),
        "killed_rank_has_events": len(killed_events) > 0,
        "killed_rank_final_events_fresh": (
            bool(killed_events) and kill_wall - last_killed_ts < 3 * STEP_SLEEP + 1.0
        ),
        # 2. the survivor's guaranteed dump fired on the shrink path
        "survivor_dump_exists": os.path.exists(survivor_dump),
        "survivor_dump_reason_shrink": (
            dumps.get(0, {}).get("reason") == "peer_shrink"
        ),
        "survivor_recorded_shrink": len(shrink_events) > 0,
        # 3. the merged timeline is schema-valid and complete
        "merge_schema_valid": not violations,
        "timeline_has_killed_track": 1 in pids,
        "timeline_has_shrink": "shrink" in names,
        "timeline_has_bucket_spans": len(bucket_events) > 0,
        # recovery itself (chaos_runtime owns the deep recovery checks;
        # here it gates that the evidence run was a REAL recovery run)
        "run_recovered": (
            rc == 0 and payload.get("final_step") == steps
            and len(epochs) == 2 and epochs[-1]["alive"] == 1
        ),
    }
    ok = all(floors.values())
    return {
        "scenario": "sigkill_recorded",
        "injection": "SIGKILL of recorder-on peer rank 1 mid-run",
        "ok": ok,
        "floors": floors,
        "checks": {
            **checks,
            "trainer_rc": rc,
            "epochs": epochs,
            "killed_rank_events": len(killed_events),
            "survivor_events": len(survivor_events),
            "bucket_events": len(bucket_events),
            "bucket_provenance_example": (
                {k: bucket_events[0][k] for k in
                 ("name", "topo", "codec", "nbytes", "predicted_us")
                 if k in bucket_events[0]}
                if bucket_events else None
            ),
            "kill_to_last_killed_event_s": (
                round(kill_wall - last_killed_ts, 3) if killed_events else None
            ),
            "schema_violations": violations[:10],
        },
        "timeline": doc,
        "log_tail": log.splitlines()[-30:],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--out", default=os.path.join(REPO, "OBS_CHAOS.json"))
    ap.add_argument(
        "--timeline-out", default=os.path.join(REPO, "OBS_TIMELINE.json")
    )
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        role = os.environ.get("FT_CHAOS_ROLE", "train")
        return child_train() if role == "train" else child_peer()

    print("=== scenario sigkill_recorded ===", flush=True)
    with tempfile.TemporaryDirectory(prefix="ft_obs_chaos_") as wd:
        try:
            scenario = run_kill_scenario(wd)
        except Exception as e:  # a crashed driver is a failed floor
            scenario = {
                "scenario": "sigkill_recorded", "ok": False,
                "error": f"{type(e).__name__}: {e}", "floors": {},
            }
    print(
        f"scenario sigkill_recorded: {'OK' if scenario['ok'] else 'FAILED'} "
        + json.dumps(scenario.get("floors", {})),
        flush=True,
    )

    timeline = scenario.pop("timeline", None)
    ok = scenario["ok"]
    if not args.no_artifact:
        from flextree_tpu.obs import write_trace
        from flextree_tpu.utils.buildstamp import artifact_meta
        from flextree_tpu.utils.logging import write_result_file

        if timeline is not None:
            write_trace(timeline, args.timeline_out)
            print(f"wrote {args.timeline_out} "
                  f"({len(timeline['traceEvents'])} trace events)")
        write_result_file(
            args.out,
            {
                "description": "Executed observability chaos on one host: a "
                               "recorder-on 2-process SIGKILL run whose "
                               "per-rank flight records merge into one "
                               "schema-valid Chrome-trace timeline (killed "
                               "rank's final events, survivor's shrink + "
                               "guaranteed dump, provenance-annotated bucket "
                               "spans) — see docs/OBSERVABILITY.md",
                "build": artifact_meta(),
                "ok": ok,
                "budgets": {
                    "heartbeat_interval_s": HB_INTERVAL,
                    "straggler_s": STRAGGLER_S,
                    "lease_s": LEASE_S,
                    "step_sleep_s": STEP_SLEEP,
                },
                "scenario": scenario,
                "timeline_artifact": os.path.basename(args.timeline_out),
            },
        )
        print(f"wrote {args.out} (ok={ok})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
