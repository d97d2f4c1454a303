#!/usr/bin/env python
"""Runtime chaos: SIGKILL / SIGSTOP / SIGTERM against a live training run.

``tools/chaos_bringup.py`` executes the *bring-up* failure paths (late
coordinator, kill+restart, degrade-to-survivors).  This driver executes
the **in-run** failure model of ``flextree_tpu.runtime`` +
``parallel.loop.fit(supervision=...)`` against real OS processes — the
signals are genuine, the heartbeats cross a real process boundary, and
the recovery machinery is the production code path, not a mock:

- ``sigkill``: a 3-member supervised group (one training process + two
  heartbeating peers).  Mid-run, one peer is SIGKILL'd; the trainer's
  ``MembershipView`` sees its lease expire within ``FT_LEASE`` seconds,
  and ``fit`` performs **live shrink-to-survivors**: drain, restore the
  latest CRC-verified checkpoint, replan the collective topology for the
  survivor count (``planner.replan_for_survivors``), rebuild through
  ``on_shrink``, and finish every remaining step without a process
  restart.  Asserted: a recorded membership epoch transition 3 → 2 with
  a replanned topo, and the run completing.
- ``sigstop``: a 2-member group; the peer is SIGSTOP'd past the
  straggler threshold (its heartbeat thread freezes with it) and
  SIGCONT'd inside the lease budget.  Asserted: the trainer classifies
  it straggler (recorded in ``run_report.json``) *without* shrinking —
  a stall is not a death — and the run completes.
- ``sigterm``: a single training process is preempted mid-run.  The
  ``PreemptionGuard`` turns SIGTERM into the "checkpoint now" fast path:
  a checkpoint lands within one step of the signal and the process exits
  cleanly; a relaunch resumes from exactly that step and completes.

The training step itself is a deterministic host-side toy (the
supervision layer neither knows nor cares what the step computes — the
same wiring drives the jitted steps via ``flextree_tpu.trainer``'s
``--step-timeout``/``--heartbeat-dir`` flags); each scenario's evidence
is the committed ``CHAOS_RUNTIME.json`` artifact.  Exit status is
non-zero when ANY scenario fails to recover, so CI can gate on it.

Usage: python tools/chaos_runtime.py [--out CHAOS_RUNTIME.json]
       [--scenario sigkill|sigstop|sigterm] [--no-artifact]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCENARIOS = ("sigkill", "sigstop", "sigterm", "sigkill_sharded")

# supervision budgets (seconds) — every scenario derives its waits from
# these, so the asserts below are "within the lease budget" by construction
HB_INTERVAL = 0.2
STRAGGLER_S = 0.8
LEASE_S = 2.0
STEP_SLEEP = 0.1


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------


class _ToyData:
    def batch_at(self, step):
        import numpy as np

        tok = np.full((2, 4), float(step + 1))
        return tok, tok


def _obs_ctx(rank: int = 0):
    """Flight-recorder context for chaos children: armed by ``FT_OBS_DIR``
    (tools/obs_chaos.py's dedicated scenario is the committed proof; this
    knob lets ANY chaos run leave a mergeable forensic record)."""
    import contextlib

    obs_dir = os.environ.get("FT_OBS_DIR")
    if not obs_dir:
        return contextlib.nullcontext()
    from flextree_tpu.obs import flight_recorder

    return flight_recorder(obs_dir, rank=rank)


def child_train() -> int:
    """The supervised training process (rank 0 of the heartbeat group)."""
    import numpy as np

    from flextree_tpu.parallel.loop import FitConfig, Supervision, fit
    from flextree_tpu.runtime import (
        MembershipView,
        PreemptionGuard,
        Supervisor,
        SupervisorConfig,
    )

    hb_dir = os.environ["FT_HB_DIR"]
    world = int(os.environ["FT_WORLD"])
    steps = int(os.environ["FT_STEPS"])
    ckpt_dir = os.environ["FT_CKPT_DIR"]
    step_sleep = float(os.environ.get("FT_STEP_SLEEP", str(STEP_SLEEP)))

    cfg_hb = SupervisorConfig(
        rank=0, dir=hb_dir, interval_s=HB_INTERVAL,
        straggler_s=STRAGGLER_S, lease_s=LEASE_S,
    )
    supervisor = Supervisor(cfg_hb)
    if world > 1:
        # bring-up barrier: wait for every member's FIRST beat before
        # arming membership supervision (launch-layer liveness is PR 1's
        # domain — in-run supervision begins once the world has
        # assembled).  Without this, a peer still paying its multi-second
        # interpreter/jax import reads as roster-dead and triggers a
        # spurious shrink at step 0 (observed under pytest-load).
        supervisor.beat_now()
        barrier_view = MembershipView.for_config(cfg_hb, configured=world)
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if all(s.step >= 0 for s in barrier_view.poll().values()):
                break  # every roster rank has beat at least once
            time.sleep(0.05)
        else:
            print("FAIL: peers never assembled for supervision", flush=True)
            return 1
    shrl: list = []

    def on_shrink(n_alive, plan):
        shrl.append({"alive": n_alive, "topo": plan.to_ft_topo()})
        return None  # the toy step is world-size-agnostic; the replan is the point

    supervision = Supervision(
        supervisor=supervisor,
        membership=MembershipView.for_config(cfg_hb, configured=world)
        if world > 1
        else None,
        configured_world=world if world > 1 else None,
        step_timeout_s=30.0,  # armed (the real watchdog path), never hit here
        on_shrink=on_shrink,
        nbytes_hint=1 << 20,
        preemption=PreemptionGuard().install(),
    )

    def step_fn(state, tokens, targets):
        time.sleep(step_sleep)  # a step takes real wall-time to supervise
        s = int(np.asarray(state["step"]))
        return (
            {"step": np.int64(s + 1), "w": np.asarray(state["w"]) - 0.01 * float(tokens.mean())},
            {"loss": float(tokens.mean())},
        )

    state = {"step": np.int64(0), "w": np.zeros(4, dtype=np.float64)}
    with _obs_ctx(rank=0):
        result = fit(
            state, step_fn, _ToyData(),
            FitConfig(
                num_steps=steps, ckpt_dir=ckpt_dir,
                ckpt_every=int(os.environ.get("FT_CKPT_EVERY", "5")),
                log_every=0,
            ),
            supervision=supervision,
        )
    from flextree_tpu.utils.checkpoint import list_checkpoints

    payload = {
        "final_step": int(np.asarray(result.state["step"])),
        "steps_run": result.steps_run,
        "resumed_from": result.resumed_from,
        "report": result.report.to_payload(),
        "shrink_calls": shrl,
        "ckpt_steps": [s for s, _ in list_checkpoints(ckpt_dir)],
    }
    print("CHAOS_JSON: " + json.dumps(payload), flush=True)
    return 0


def child_train_sharded() -> int:
    """The supervised SHARDED training process (PR 7): a real jitted
    ZeRO-1 dense step over a dp-wide virtual-CPU mesh whose width mirrors
    the heartbeat world.  Checkpoints are CONSOLIDATED (world-size
    independent); on shrink the survivors rebuild the step on the
    narrower mesh and re-partition the full CRC-verified checkpoint into
    their new owned shards (``zero.make_reshard_fn``)."""
    import dataclasses

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    import numpy as np

    from flextree_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        param_specs,
    )
    from flextree_tpu.parallel.loop import FitConfig, Supervision, fit
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_state_specs,
        make_train_step,
        zero_layout_for,
    )
    from flextree_tpu.parallel.zero import make_consolidate_fn, make_reshard_fn
    from flextree_tpu.runtime import (
        MembershipView,
        PreemptionGuard,
        Supervisor,
        SupervisorConfig,
    )

    hb_dir = os.environ["FT_HB_DIR"]
    world = int(os.environ["FT_WORLD"])
    steps = int(os.environ["FT_STEPS"])
    ckpt_dir = os.environ["FT_CKPT_DIR"]
    step_sleep = float(os.environ.get("FT_STEP_SLEEP", str(STEP_SLEEP)))

    model_cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64
    )
    axes = ("dp", "sp", "tp")
    base_tc = TrainConfig(shard_optimizer=True)

    def build_world(ndev, grad_topo=None):
        tc = dataclasses.replace(base_tc, grad_topo=grad_topo)
        mesh = make_mesh_nd(ndev, (ndev, 1, 1), axes)
        jit_step = make_train_step(mesh, model_cfg, tc)

        def step_fn(state, tokens, targets):
            time.sleep(step_sleep)  # give the supervision layer wall-time
            return jit_step(state, tokens, targets)

        pspecs = param_specs(model_cfg, "tp")
        shapes = jax.eval_shape(
            lambda k: init_params(k, model_cfg), jax.random.PRNGKey(0)
        )
        layout = zero_layout_for(mesh, shapes, pspecs, axes)
        packed_specs = make_state_specs(
            pspecs, dataclasses.replace(tc, shard_optimizer=False)
        )
        pack = make_consolidate_fn(mesh, pspecs, layout, grad_topo, False)
        unpack = make_reshard_fn(mesh, pspecs, layout, grad_topo, False)
        return mesh, step_fn, packed_specs, pack, unpack

    mesh, step_fn, packed_specs, pack, unpack = build_world(world)
    cur = {"pack": pack, "unpack": unpack}

    class _LMData:
        def batch_at(self, step):
            tok = (np.arange(6 * 16, dtype=np.int32).reshape(6, 16) + step) % 64
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

    shrl: list = []

    def on_shrink(n_alive, plan):
        mesh2, step2, specs2, pack2, unpack2 = build_world(
            n_alive, grad_topo=plan.to_ft_topo()
        )
        cur["pack"], cur["unpack"] = pack2, unpack2
        shrl.append({"alive": n_alive, "topo": plan.to_ft_topo()})
        return step2, mesh2, specs2, pack2, unpack2

    supervision = Supervision(
        supervisor=supervisor,
        membership=MembershipView.for_config(cfg_hb, configured=world),
        configured_world=world,
        step_timeout_s=60.0,
        on_shrink=on_shrink,
        nbytes_hint=1 << 16,
        preemption=PreemptionGuard().install(),
    )

    state = init_train_state(
        jax.random.PRNGKey(0), model_cfg, base_tc, mesh=mesh
    )
    with _obs_ctx(rank=0):
        result = fit(
            state, step_fn, _LMData(),
            FitConfig(
                num_steps=steps, ckpt_dir=ckpt_dir,
                ckpt_every=int(os.environ.get("FT_CKPT_EVERY", "4")),
                log_every=10, prefetch=0,
            ),
            mesh=mesh, state_specs=packed_specs, supervision=supervision,
            state_pack=pack, state_unpack=unpack,
        )
    # the consistency proof: consolidate the final sharded state, then
    # re-shard and re-consolidate — a consistent re-shard is a bitwise
    # fixed point, and every leaf must be finite
    cons = cur["pack"](result.state)
    roundtrip = cur["pack"](cur["unpack"](cons))
    flat_a = jax.tree.leaves(cons)
    flat_b = jax.tree.leaves(roundtrip)
    consistent = all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(flat_a, flat_b)
    )
    finite = all(np.isfinite(np.asarray(l)).all() for l in flat_a)
    from flextree_tpu.utils.checkpoint import list_checkpoints

    payload = {
        "final_step": int(np.asarray(jax.device_get(result.state["step"]))),
        "steps_run": result.steps_run,
        "resumed_from": result.resumed_from,
        "report": result.report.to_payload(),
        "shrink_calls": shrl,
        "ckpt_steps": [s for s, _ in list_checkpoints(ckpt_dir)],
        "reshard_consistent": bool(consistent),
        "state_finite": bool(finite),
        "losses": [float(l) for _, l in result.losses],
    }
    print("CHAOS_JSON: " + json.dumps(payload), flush=True)
    return 0


def child_peer() -> int:
    """A heartbeating group member doing fake work (real process, real
    lease): the thing the scenarios stop or kill."""
    from flextree_tpu.runtime import Supervisor, SupervisorConfig

    rank = int(os.environ["FT_RANK"])
    seconds = float(os.environ.get("FT_PEER_SECONDS", "30"))
    sup = Supervisor(
        SupervisorConfig(
            rank=rank, dir=os.environ["FT_HB_DIR"], interval_s=HB_INTERVAL,
            straggler_s=STRAGGLER_S, lease_s=LEASE_S,
        )
    ).start()
    t0 = time.time()
    step = 0
    while time.time() - t0 < seconds:
        time.sleep(STEP_SLEEP)
        step += 1
        sup.record_step(step, STEP_SLEEP)
    sup.stop()
    return 0


# --------------------------------------------------------------------------
# parent: scenario drivers
# --------------------------------------------------------------------------


def _spawn(role: str, hb_dir: str, ckpt_dir: str, extra_env=None):
    env = {
        **os.environ,
        "FT_CHAOS_ROLE": role,
        "FT_HB_DIR": hb_dir,
        "FT_CKPT_DIR": ckpt_dir,
        **(extra_env or {}),
    }
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _wait_for_step(hb_dir: str, rank: int, step: int, timeout: float = 60.0) -> int:
    """Poll the heartbeat file — the parent is just another membership
    observer — until ``rank`` reports progress past ``step``."""
    from flextree_tpu.runtime import read_control_json

    path = os.path.join(hb_dir, f"hb_{rank:05d}.json")
    deadline = time.time() + timeout
    while time.time() < deadline:
        beat = read_control_json(path)  # beats are CRC-trailered now
        if beat is not None and beat.get("step", -1) >= step:
            return beat["step"]
        time.sleep(0.05)
    raise TimeoutError(f"rank {rank} never reached step {step} in {timeout}s")


def _finish(proc, timeout=120):
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        out += f"\n[parent] TIMEOUT after {timeout}s"
    return out, proc.returncode


def _chaos_payload(log: str) -> dict | None:
    for line in log.splitlines():
        if line.startswith("CHAOS_JSON: "):
            return json.loads(line[len("CHAOS_JSON: "):])
    return None


def run_sigkill(workdir: str) -> dict:
    """Mid-run SIGKILL of a peer → live shrink-to-survivors resume."""
    hb = os.path.join(workdir, "hb")
    ck = os.path.join(workdir, "ck")
    steps = 60
    trainer = _spawn("train", hb, ck, {"FT_WORLD": "3", "FT_STEPS": str(steps)})
    peers = [
        _spawn("peer", hb, ck, {"FT_RANK": str(r), "FT_PEER_SECONDS": "45"})
        for r in (1, 2)
    ]
    checks: dict = {}
    try:
        kill_at = _wait_for_step(hb, 0, 10)
        os.kill(peers[1].pid, signal.SIGKILL)
        checks["killed_at_trainer_step"] = kill_at
        log, rc = _finish(trainer, timeout=180)
    finally:
        for p in (trainer, *peers):  # never leak a child into tmp cleanup
            if p.poll() is None:
                p.kill()
                p.communicate()
        peer_rcs = [p.returncode for p in peers]
    payload = _chaos_payload(log) or {}
    report = payload.get("report", {})
    epochs = report.get("membership_epochs", [])
    checks.update(
        trainer_rc=rc,
        epochs=epochs,
        shrink_calls=payload.get("shrink_calls"),
        final_step=payload.get("final_step"),
        peer_rcs=peer_rcs,
    )
    recovered = (
        rc == 0
        and payload.get("final_step") == steps
        and len(epochs) == 2
        and epochs[0]["alive"] == 3
        and epochs[1]["alive"] == 2
        and epochs[1]["dead"] == [2]
        and epochs[1]["topo"] is not None
        and payload.get("shrink_calls") == [{"alive": 2, "topo": epochs[1]["topo"]}]
    )
    return {
        "scenario": "sigkill",
        "injection": "SIGKILL of peer rank 2 mid-run",
        "recovered": recovered,
        "checks": checks,
        "log": log.splitlines(),
    }


def run_sigkill_sharded(workdir: str) -> dict:
    """Mid-run SIGKILL of a peer under a REAL jitted ZeRO-1 sharded step
    (PR 7): the trainer holds sharded optimizer state over a dp-3 mesh
    and checkpoints CONSOLIDATED; the shrink rebuilds on a dp-2 mesh,
    restores the full checkpoint and re-partitions it into the survivor
    world's owned shards.  Asserted: the 3 → 2 epoch with a replanned
    topo, the run completing with finite losses, and the re-shard being a
    bitwise fixed point (consolidate ∘ reshard ∘ consolidate)."""
    hb = os.path.join(workdir, "hb")
    ck = os.path.join(workdir, "ck")
    steps = 40
    trainer = _spawn(
        "train_sharded", hb, ck,
        {"FT_WORLD": "3", "FT_STEPS": str(steps), "FT_CKPT_EVERY": "4"},
    )
    peers = [
        _spawn("peer", hb, ck, {"FT_RANK": str(r), "FT_PEER_SECONDS": "90"})
        for r in (1, 2)
    ]
    checks: dict = {}
    try:
        kill_at = _wait_for_step(hb, 0, 8, timeout=120.0)
        os.kill(peers[1].pid, signal.SIGKILL)
        checks["killed_at_trainer_step"] = kill_at
        log, rc = _finish(trainer, timeout=300)
    finally:
        for p in (trainer, *peers):
            if p.poll() is None:
                p.kill()
                p.communicate()
        peer_rcs = [p.returncode for p in peers]
    payload = _chaos_payload(log) or {}
    report = payload.get("report", {})
    epochs = report.get("membership_epochs", [])
    losses = payload.get("losses", [])
    checks.update(
        trainer_rc=rc,
        epochs=epochs,
        shrink_calls=payload.get("shrink_calls"),
        final_step=payload.get("final_step"),
        reshard_consistent=payload.get("reshard_consistent"),
        state_finite=payload.get("state_finite"),
        peer_rcs=peer_rcs,
    )
    recovered = (
        rc == 0
        and payload.get("final_step") == steps
        and len(epochs) == 2
        and epochs[0]["alive"] == 3
        and epochs[1]["alive"] == 2
        and epochs[1]["dead"] == [2]
        and epochs[1]["topo"] is not None
        and payload.get("reshard_consistent") is True
        and payload.get("state_finite") is True
        and bool(losses)
        and all(math.isfinite(l) for l in losses)
    )
    return {
        "scenario": "sigkill_sharded",
        "injection": "SIGKILL of peer rank 2 under a live ZeRO-1 sharded "
                     "jitted run (dp-3 mesh -> dp-2 re-shard from the "
                     "consolidated checkpoint)",
        "recovered": recovered,
        "checks": checks,
        "log": log.splitlines(),
    }


def run_sigstop(workdir: str) -> dict:
    """SIGSTOP a peer past the straggler threshold, SIGCONT inside the
    lease → flagged straggler, no shrink, run completes."""
    hb = os.path.join(workdir, "hb")
    ck = os.path.join(workdir, "ck")
    steps = 55
    trainer = _spawn("train", hb, ck, {"FT_WORLD": "2", "FT_STEPS": str(steps)})
    peer = _spawn("peer", hb, ck, {"FT_RANK": "1", "FT_PEER_SECONDS": "45"})
    checks: dict = {}
    try:
        stop_at = _wait_for_step(hb, 0, 10)
        os.kill(peer.pid, signal.SIGSTOP)
        # hold the stall past straggler_s but well inside the lease
        time.sleep((STRAGGLER_S + LEASE_S) / 2)
        os.kill(peer.pid, signal.SIGCONT)
        checks["stopped_at_trainer_step"] = stop_at
        log, rc = _finish(trainer, timeout=180)
    finally:
        if peer.poll() is None:
            try:
                os.kill(peer.pid, signal.SIGCONT)  # never leave it frozen
            except OSError:
                pass
            peer.terminate()
        checks["peer_rc"] = _finish(peer, timeout=10)[1]
        if trainer.poll() is None:  # never leak a child into tmp cleanup
            trainer.kill()
            trainer.communicate()
    payload = _chaos_payload(log) or {}
    report = payload.get("report", {})
    checks.update(
        trainer_rc=rc,
        stragglers=report.get("stragglers"),
        epochs=report.get("membership_epochs"),
        final_step=payload.get("final_step"),
    )
    recovered = (
        rc == 0
        and payload.get("final_step") == steps
        and any(s["rank"] == 1 for s in report.get("stragglers", []))
        and len(report.get("membership_epochs", [])) == 1  # stall != death
    )
    return {
        "scenario": "sigstop",
        "injection": f"SIGSTOP of peer rank 1 for "
                     f"{(STRAGGLER_S + LEASE_S) / 2:.1f}s (straggler budget "
                     f"{STRAGGLER_S}s, lease {LEASE_S}s), then SIGCONT",
        "recovered": recovered,
        "checks": checks,
        "log": log.splitlines(),
    }


def run_sigterm(workdir: str) -> dict:
    """SIGTERM mid-run → preemption checkpoint within one step; relaunch
    resumes from exactly that step."""
    hb = os.path.join(workdir, "hb")
    ck = os.path.join(workdir, "ck")
    steps = 50
    env = {
        "FT_WORLD": "1",
        "FT_STEPS": str(steps),
        "FT_CKPT_EVERY": "1000",  # no periodic saves: the SIGTERM path only
    }
    trainer = _spawn("train", hb, ck, env)
    try:
        term_at = _wait_for_step(hb, 0, 10)
        os.kill(trainer.pid, signal.SIGTERM)
        log, rc = _finish(trainer, timeout=60)
    finally:
        # never leak a live child into the tmpdir cleanup (a concurrent
        # checkpoint write during rmtree crashes the whole driver)
        if trainer.poll() is None:
            trainer.kill()
            trainer.communicate()
    payload = _chaos_payload(log) or {}
    preempted_at = payload.get("report", {}).get("preempted_at")
    ckpt_steps = payload.get("ckpt_steps", [])

    # the launcher's restart: same checkpoint dir, no signal this time
    resumed = _spawn("train", os.path.join(workdir, "hb2"), ck, env)
    try:
        log2, rc2 = _finish(resumed, timeout=180)
    finally:
        if resumed.poll() is None:
            resumed.kill()
            resumed.communicate()
    payload2 = _chaos_payload(log2) or {}

    checks = {
        "term_at_trainer_step": term_at,
        "trainer_rc": rc,
        "preempted_at": preempted_at,
        "ckpt_steps": ckpt_steps,
        "resume_rc": rc2,
        "resumed_from": payload2.get("resumed_from"),
        "resume_final_step": payload2.get("final_step"),
    }
    # "within one step": the checkpoint IS the final step — no work ran
    # past it and none before it was lost (final_step == preempted_at ==
    # the only checkpoint).  The bound vs term_at is looser because the
    # parent observes progress through the heartbeat, which lags true
    # progress by up to interval_s/step_sleep steps + the in-flight step.
    hb_lag = int(HB_INTERVAL / STEP_SLEEP) + 2
    recovered = (
        rc == 0
        and preempted_at is not None
        and payload.get("final_step") == preempted_at
        and 0 <= preempted_at - term_at <= hb_lag
        and ckpt_steps == [preempted_at]
        and rc2 == 0
        and payload2.get("resumed_from") == preempted_at
        and payload2.get("final_step") == steps
    )
    return {
        "scenario": "sigterm",
        "injection": "SIGTERM of the training process mid-run, then relaunch",
        "recovered": recovered,
        "checks": checks,
        "log": log.splitlines() + ["--- resumed run ---"] + log2.splitlines(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--scenario", choices=SCENARIOS, action="append")
    ap.add_argument("--out", default=os.path.join(REPO, "CHAOS_RUNTIME.json"))
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        role = os.environ.get("FT_CHAOS_ROLE", "train")
        if role == "train":
            return child_train()
        if role == "train_sharded":
            return child_train_sharded()
        return child_peer()

    which = tuple(args.scenario) if args.scenario else SCENARIOS
    runners = {
        "sigkill": run_sigkill, "sigstop": run_sigstop,
        "sigterm": run_sigterm, "sigkill_sharded": run_sigkill_sharded,
    }
    results = []
    for name in which:
        print(f"=== scenario {name} ===", flush=True)
        with tempfile.TemporaryDirectory(prefix=f"ft_chaos_{name}_") as wd:
            try:
                res = runners[name](wd)
            except Exception as e:  # a crashed driver is a failed scenario,
                res = {  # not a skipped one — CI must see it
                    "scenario": name,
                    "recovered": False,
                    "error": f"{type(e).__name__}: {e}",
                    "log": [],
                }
        results.append(res)
        print(
            f"scenario {name}: "
            f"{'RECOVERED' if res['recovered'] else 'FAILED'}",
            flush=True,
        )
    ok = all(r["recovered"] for r in results)

    if not args.no_artifact:
        from flextree_tpu.utils.buildstamp import artifact_meta
        from flextree_tpu.utils.logging import write_result_file

        write_result_file(
            args.out,
            {
                "description": "Executed runtime chaos on one host: mid-run "
                               "SIGKILL (live shrink-to-survivors with "
                               "replanned topology), SIGSTOP straggler "
                               "(flagged within the lease budget, no "
                               "shrink), and SIGTERM preemption (checkpoint "
                               "within one step + exact resume) — the in-run "
                               "failure paths of flextree_tpu.runtime + "
                               "fit(supervision=...), see "
                               "docs/FAILURE_MODEL.md §Runtime failures",
                "build": artifact_meta(),
                "ok": ok,
                "budgets": {
                    "heartbeat_interval_s": HB_INTERVAL,
                    "straggler_s": STRAGGLER_S,
                    "lease_s": LEASE_S,
                    "step_sleep_s": STEP_SLEEP,
                },
                "scenarios": results,
            },
        )
        print(f"wrote {args.out} (ok={ok})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
