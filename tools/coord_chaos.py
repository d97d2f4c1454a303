#!/usr/bin/env python
"""Executed consensus proof: the coordinated elastic control plane under
adversarial handshake chaos — real processes, real signals, a real gloo
wire (ISSUE 14, docs/COORDINATION.md).

Two process worlds, one scripted fault matrix:

**Matrix world** (3 real OS processes sharing a heartbeat dir, each
training the same deterministic model rank-locally under
``fit(supervision=Supervision(coordination=...))``):

- ``kill_coordinator_at_propose`` — rank 0 publishes the proposal and is
  SIGKILL'd before its self-ack lands (the child simulates the
  crash-between-atomic-writes interleaving; the parent kills on
  proposal-observed).  The successor must RE-PROPOSE for the survivors.
- ``kill_coordinator_at_ackwait`` — rank 0 collects every ack and is
  killed holding the commit.  The successor must COMPLETE the in-flight
  commit at the SAME epoch (idempotency, never a double-apply).
- ``kill_coordinator_at_commit`` — rank 0 is killed right after the
  commit publishes.  Survivors apply it with no successor action.
- ``stalled_follower_fenced`` — rank 2 is SIGSTOP'd past the ack
  deadline: the decision re-proposes without it, and on SIGCONT the
  resumed rank must exit loudly with ``EpochFenced`` (exit code 3 + a
  guaranteed ``coord_fence`` dump) instead of training on a stale plan.
- ``torn_ledger`` — an adversarial scribbler truncates the proposal/
  commit/ack files throughout the handshake; the CRC trailers
  (``runtime/ctrlfile.py``) must parse-refuse-and-reread, never crash or
  mis-apply.
- ``coordinated_resize`` — the parent plays arbiter on the lease ledger;
  the grant change must flow propose → commit → group apply, every rank
  proving ``bitwise_resume`` and the lease ack carrying the committed
  control epoch (the can't-ack-what-you-didn't-apply fence).

**Gloo world** (``gloo_group_replan``): 3 real processes on a real gloo
TCP wire (production ``init_distributed``), every step an actual
cross-process FlexTree allreduce.  Rank 0 proposes a replan
(chunk-pipelined twin of the same schedule — bitwise-neutral by the
PR 2 property) with an agreed ``apply_step`` boundary; every rank blocks
at the boundary until the commit and flips plans at the SAME step.  The
wire itself referees: ranks running different schedules for one step
would deadlock the collective — completion + bitwise output IS the
same-boundary proof.

Machine-checked floors (non-zero exit on any violation):

1. all survivors converge to the same final control epoch AND the same
   decision fingerprint;
2. training output bitwise vs an unfaulted twin run (per world);
3. zero double-applied control epochs across the whole matrix (counted
   from the flight records' ``coord_apply`` events);
4. every fault scenario leaves a guaranteed flight-recorder dump with
   the handshake phase attached (``coord_phase``);
5. coordinator-death recovery (kill → successor's commit) completes
   within ``RECOVERY_BOUND_WINDOWS`` lease windows, recorded in the
   artifact.

Usage: python tools/coord_chaos.py [--smoke] [--out COORD_CHAOS.json]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# supervision budgets (seconds) — the lease bounds every protocol window
HB_INTERVAL = 0.2
STRAGGLER_S = 0.8
LEASE_S = 2.0
STEP_SLEEP = 0.1
WORLD = 3
STEPS = 40
PROPOSE_AT = 8  # the scripted replan's trigger step
RECOVERY_BOUND_WINDOWS = 4.0  # kill -> successor commit, in lease windows

_FENCED_RC = 3  # the fenced child's distinct exit code


# --------------------------------------------------------------------------
# shared child pieces
# --------------------------------------------------------------------------


def _state_sha(state) -> str:
    import numpy as np

    h = hashlib.sha256()
    for leaf in _tree_leaves(state):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _tree_leaves(state):
    # stable order without importing jax in pure-host children
    if isinstance(state, dict):
        out = []
        for k in sorted(state):
            out.extend(_tree_leaves(state[k]))
        return out
    return [state]


class _ToyData:
    def batch_at(self, step):
        import numpy as np

        tok = np.full((2, 8), float(step + 1))
        return tok, tok


def _toy_step(step_sleep: float, chunked: bool = False, on_step=None):
    """Deterministic host train step.  The ``chunked`` twin updates the
    weight vector slice-by-slice — structurally a different program,
    BITWISE the same result (elementwise ops, no reassociation) — so a
    committed replan swaps real code without perturbing the output the
    twin comparison pins."""
    import numpy as np

    def step_fn(state, tokens, targets):
        if on_step is not None:
            on_step(int(np.asarray(state["step"])))
        time.sleep(step_sleep)
        s = int(np.asarray(state["step"]))
        g = 0.01 * float(tokens.mean())
        w = np.asarray(state["w"]).copy()
        if chunked:
            for lo in range(0, w.size, 2):
                w[lo:lo + 2] = w[lo:lo + 2] - g
        else:
            w = w - g
        return {"step": np.int64(s + 1), "w": w}, {"loss": float(tokens.mean())}

    return step_fn


def _w0():
    import numpy as np

    return {"step": np.int64(0), "w": np.zeros(8, dtype=np.float64)}


class ScriptedReplan:
    """The chaos stand-in for ``FeedbackController``'s coordinated mode:
    the SAME ``maybe_tick``/``apply_committed`` surface ``fit`` drives,
    with the drift decision scripted to one step so the parent can time
    its fault injections against the handshake phases."""

    refusals = 0

    def __init__(self, handle, proposer_rank: int, at_step: int):
        self.handle = handle
        self.proposer_rank = proposer_rank
        self.at_step = at_step
        self.proposed = False

    def maybe_tick(self, step):
        if (
            not self.proposed
            and self.handle.rank == self.proposer_rank
            and self.handle.is_coordinator
            and step >= self.at_step
        ):
            epoch = self.handle.propose(
                "replan", {"topo": "chunked", "chunked": True}
            )
            if epoch is not None:
                self.proposed = True
        return None

    def apply_committed(self, payload, step=None):
        import types

        rebuilt = (
            _toy_step(
                float(os.environ.get("FT_STEP_SLEEP", str(STEP_SLEEP))),
                chunked=bool(payload.get("chunked")),
            ),
            None,
            None,
        )
        return types.SimpleNamespace(
            rebuilt=rebuilt,
            plan=types.SimpleNamespace(
                to_ft_topo=lambda: str(payload.get("topo", "?"))
            ),
            invalidated=0,
            params=None,
        )


def _holdable_handle(hb_dir, rank, membership, cfg):
    """A CoordinationHandle with the chaos hold knobs: ``FT_COORD_HOLD``
    = ``selfack`` (skip the proposer's own ack — the crash interleaving
    between the proposal write and the ack write) or ``commit`` (collect
    acks but never publish — the kill-at-ack-wait window)."""
    from flextree_tpu.runtime.coordination import CoordinationHandle

    hold = os.environ.get("FT_COORD_HOLD", "")

    class HoldableHandle(CoordinationHandle):
        def _ack(self, decision):
            if hold == "selfack" and decision.coordinator == self.rank:
                # model SIGKILL landing between the two atomic writes
                self._acked_epoch = decision.epoch
                self._pending = (decision.epoch, decision.apply_step)
                return
            super()._ack(decision)

        def _drive(self, prop):
            if hold == "commit" and prop is not None:
                return  # collect acks forever: the parent kills us here
            super()._drive(prop)

    return HoldableHandle(hb_dir, rank, membership=membership, cfg=cfg)


def child_worker() -> int:
    """One rank of the matrix world: rank-local deterministic training
    under full supervision + the coordination handle; emits a COORD_JSON
    line with the final state hash and the applied control-epoch trail."""
    import numpy as np

    from flextree_tpu.obs import flight_recorder
    from flextree_tpu.parallel.loop import FitConfig, Supervision, fit
    from flextree_tpu.runtime import (
        EpochFenced,
        LeaseLedger,
        MembershipView,
        Supervisor,
        SupervisorConfig,
        TrainLeaseClient,
    )
    from flextree_tpu.runtime.coordination import CoordinationConfig

    rank = int(os.environ["FT_RANK"])
    world = int(os.environ["FT_WORLD"])
    steps = int(os.environ["FT_STEPS"])
    hb_dir = os.environ["FT_HB_DIR"]
    obs_dir = os.environ["FT_OBS_DIR"]
    ckpt_dir = os.environ["FT_CKPT_DIR"]
    step_sleep = float(os.environ.get("FT_STEP_SLEEP", str(STEP_SLEEP)))
    resize_mode = os.environ.get("FT_COORD_RESIZE") == "1"

    cfg_hb = SupervisorConfig(
        rank=rank, dir=hb_dir, interval_s=HB_INTERVAL,
        straggler_s=STRAGGLER_S, lease_s=LEASE_S,
    )
    supervisor = Supervisor(cfg_hb)
    supervisor.beat_now()
    barrier = MembershipView.for_config(cfg_hb, configured=world)
    deadline = time.time() + 60.0
    while time.time() < deadline:
        if all(s.step >= 0 for s in barrier.poll().values()):
            break
        time.sleep(0.05)
    else:
        print("FAIL: peers never assembled", flush=True)
        return 1

    membership = MembershipView.for_config(cfg_hb, configured=world)
    handle = _holdable_handle(
        hb_dir, rank, membership,
        CoordinationConfig.for_lease(LEASE_S),
    )
    scripted = None if resize_mode else ScriptedReplan(handle, 0, PROPOSE_AT)
    client = None
    if resize_mode:
        client = TrainLeaseClient(
            LeaseLedger(hb_dir),
            initial_chips=tuple(
                int(c) for c in os.environ["FT_CHIPS"].split(",")
            ),
            on_resize=lambda chips, plan: None,  # rank-local: keep the step
            coordination=handle,
            poll_interval_s=0.1,
        )

    supervision = Supervision(
        supervisor=supervisor,
        membership=membership,
        configured_world=world,
        step_timeout_s=60.0,
        on_shrink=lambda n, plan: None,  # rank-local world: keep the step
        nbytes_hint=1 << 16,
        coordination=handle,
        feedback=scripted,
    )
    payload: dict = {"rank": rank}
    rc = 0
    with flight_recorder(obs_dir, rank=rank) as rec:
        try:
            result = fit(
                _w0(), _toy_step(step_sleep), _ToyData(),
                FitConfig(
                    num_steps=steps, ckpt_dir=ckpt_dir, ckpt_every=5,
                    log_every=0, prefetch=0,
                ),
                supervision=supervision,
                arbiter=client,
            )
            payload.update(
                final_step=int(np.asarray(result.state["step"])),
                state_sha=_state_sha(result.state),
                control_epochs=result.report.control_epochs,
                membership_epochs=result.report.membership_epochs,
                lease_epochs=result.report.lease_epochs,
                feedback_replans=result.report.feedback_replans,
                fenced=False,
            )
        except EpochFenced as e:
            payload.update(fenced=True, fence_error=str(e)[:200])
            rc = _FENCED_RC
        payload["dumps"] = rec.dumps
        payload["dump_path"] = rec.dump_path
    if client is not None:
        payload["lease_acked"] = client.ledger.acked_epoch("train")
        payload["lease_control_epoch"] = client.ledger.acked_control_epoch(
            "train"
        )
    print("COORD_JSON: " + json.dumps(payload), flush=True)
    return rc


def child_gloo() -> int:
    """One rank of the gloo world: every step is a REAL cross-process
    FlexTree allreduce; the committed replan flips to the chunk-pipelined
    twin at the agreed boundary.  The wire referees the boundary: a rank
    on the wrong schedule for one step deadlocks the collective."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flextree_tpu.obs import flight_recorder
    from flextree_tpu.parallel.allreduce import allreduce
    from flextree_tpu.parallel.launch import (
        ClusterConfig,
        flatten_mesh,
        hybrid_mesh,
        init_distributed,
    )
    from flextree_tpu.runtime import (
        MembershipView,
        Supervisor,
        SupervisorConfig,
    )
    from flextree_tpu.runtime.coordination import (
        CoordinationConfig,
        CoordinationHandle,
    )

    init_distributed(ClusterConfig.from_env())
    rank = jax.process_index()
    n = jax.device_count()
    steps = int(os.environ["FT_STEPS"])
    hb_dir = os.environ["FT_HB_DIR"]
    obs_dir = os.environ["FT_OBS_DIR"]
    replan = os.environ.get("FT_GLOO_REPLAN") == "1"
    size = 4096

    mesh = flatten_mesh(hybrid_mesh(ici_shape=(1,), dcn_shape=(n,)))
    sharding = NamedSharding(mesh, P("ft"))

    def smap(chunks):
        def device_fn(row):
            return allreduce(row[0], "ft", topo=str(n), chunks=chunks)[None]

        return jax.jit(
            jax.shard_map(
                device_fn, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"),
                check_vma=False,
            )
        )

    def grad_rows(step):
        def row(r):
            return np.random.default_rng(1000 * step + r).standard_normal(
                size
            ).astype(np.float32)

        local = row(rank)[None].reshape(-1)
        return jax.make_array_from_process_local_data(
            sharding, local, (n * size,)
        )

    def local_row(global_out):
        # the result is a GLOBAL array over all processes: this rank may
        # only read its own addressable shard — which, post-allreduce,
        # IS the full sum
        return np.asarray(
            jax.block_until_ready(global_out).addressable_shards[0].data
        ).reshape(-1)

    fns = {1: smap(1), 2: smap(2)}
    out1 = local_row(fns[1](grad_rows(0)))
    out2 = local_row(fns[2](grad_rows(0)))
    chunk_twin_bitwise = out1.tobytes() == out2.tobytes()

    cfg_hb = SupervisorConfig(
        rank=rank, dir=hb_dir, interval_s=HB_INTERVAL,
        straggler_s=STRAGGLER_S, lease_s=LEASE_S,
    )
    with flight_recorder(obs_dir, rank=rank) as rec:
        with Supervisor(cfg_hb) as sup:
            membership = MembershipView.for_config(cfg_hb, configured=n)
            handle = CoordinationHandle(
                hb_dir, rank, membership=membership,
                cfg=CoordinationConfig.for_lease(LEASE_S, apply_margin_steps=6),
            )
            w = np.zeros(size, dtype=np.float32)
            chunks = 1
            proposed = False
            applied = []
            for step in range(steps):
                dec = handle.gate(step)  # blocks at the boundary for commit
                if dec is not None:
                    chunks = int(dec.payload["chunks"])
                    handle.mark_applied(dec)
                    applied.append(
                        {"step": step, "epoch": dec.epoch,
                         "fingerprint": dec.fingerprint}
                    )
                if (
                    replan and not proposed and rank == 0
                    and step >= PROPOSE_AT
                ):
                    epoch = handle.propose(
                        "replan", {"chunks": 2, "topo": str(n)},
                        apply_step=handle.suggest_apply_step(),
                    )
                    proposed = epoch is not None
                local = local_row(fns[chunks](grad_rows(step)))
                w = w - 0.01 * local[:size]
                sup.record_step(step, STEP_SLEEP)
                time.sleep(0.05)  # keep ranks loosely in step for the wire
    payload = {
        "rank": rank,
        "final_step": steps,
        "state_sha": hashlib.sha256(w.tobytes()).hexdigest(),
        "chunk_twin_bitwise": chunk_twin_bitwise,
        "applied": applied,
        "final_chunks": chunks,
    }
    print("COORD_JSON: " + json.dumps(payload), flush=True)
    return 0


def child_twin() -> int:
    """The unfaulted twin: the same model/data/steps with no supervision,
    no coordination, no faults — its state hash is floor #2's oracle."""
    import numpy as np

    from flextree_tpu.parallel.loop import FitConfig, fit

    steps = int(os.environ["FT_STEPS"])
    result = fit(
        _w0(), _toy_step(0.0), _ToyData(),
        FitConfig(num_steps=steps, log_every=0, prefetch=0),
    )
    print(
        "COORD_JSON: " + json.dumps(
            {
                "final_step": int(np.asarray(result.state["step"])),
                "state_sha": _state_sha(result.state),
            }
        ),
        flush=True,
    )
    return 0


# --------------------------------------------------------------------------
# parent orchestration
# --------------------------------------------------------------------------


def _spawn(role: str, env: dict) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env={**os.environ, "FT_COORD_ROLE": role, **env},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _payload(log: str) -> dict:
    for line in log.splitlines():
        if line.startswith("COORD_JSON: "):
            return json.loads(line[len("COORD_JSON: "):])
    return {}


def _read_ctrl(path):
    from flextree_tpu.runtime import read_control_json

    return read_control_json(path)


def _wait_for(pred, timeout: float, what: str):
    deadline = time.time() + timeout
    while time.time() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(0.03)
    raise TimeoutError(f"never observed: {what}")


def _harvest(procs, timeout=180.0):
    outs, rcs = [], []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n[parent] TIMEOUT"
        outs.append(out)
        rcs.append(p.returncode)
    return outs, rcs


def _double_applies(obs_dir: str) -> int:
    """coord_apply events per (rank, epoch) beyond the first — floor #3."""
    from flextree_tpu.obs import read_dir

    events, _ = read_dir(obs_dir)
    counts: dict = {}
    for ev in events:
        if ev.get("kind") == "coord_apply":
            key = (ev.get("rank"), ev.get("epoch"))
            counts[key] = counts.get(key, 0) + 1
    return sum(c - 1 for c in counts.values() if c > 1)


def _dump_with_phase(obs_dir: str) -> dict | None:
    """The newest dump whose fields carry the handshake phase."""
    from flextree_tpu.obs import read_dir

    _, dumps = read_dir(obs_dir)
    for rank in sorted(dumps):
        d = dumps[rank]
        if d.get("coord_phase") is not None:
            return {
                "rank": rank,
                "reason": d.get("reason"),
                "coord_phase": d.get("coord_phase"),
            }
    return None


def run_twin(workdir: str) -> dict:
    p = _spawn("twin", {"FT_STEPS": str(STEPS)})
    out, _ = p.communicate(timeout=120)
    if p.returncode != 0:
        raise RuntimeError(f"twin failed:\n{out[-1500:]}")
    return _payload(out)


def _matrix_env(workdir: str, rank: int, extra=None) -> dict:
    return {
        "FT_RANK": str(rank),
        "FT_WORLD": str(WORLD),
        "FT_STEPS": str(STEPS),
        "FT_HB_DIR": os.path.join(workdir, "hb"),
        "FT_OBS_DIR": os.path.join(workdir, "obs"),
        "FT_CKPT_DIR": os.path.join(workdir, f"ck{rank}"),
        **(extra or {}),
    }


def run_kill_scenario(workdir: str, phase: str, twin: dict) -> dict:
    """Kill the coordinator at ``phase`` ∈ propose|ackwait|commit."""
    hb = os.path.join(workdir, "hb")
    obs = os.path.join(workdir, "obs")
    os.makedirs(hb, exist_ok=True)
    os.makedirs(obs, exist_ok=True)
    hold = {"propose": "selfack", "ackwait": "commit", "commit": ""}[phase]
    procs = []
    for rank in range(WORLD):
        extra = {"FT_COORD_HOLD": hold} if rank == 0 and hold else {}
        procs.append(_spawn("worker", _matrix_env(workdir, rank, extra)))
    checks: dict = {}
    try:
        prop_path = os.path.join(hb, "coord_proposal.json")
        commit_path = os.path.join(hb, "coord_commit.json")
        if phase == "propose":
            _wait_for(lambda: _read_ctrl(prop_path), 60, "proposal")
        elif phase == "ackwait":
            def _all_acked():
                prop = _read_ctrl(prop_path)
                if not prop:
                    return False
                acks = {
                    r: _read_ctrl(
                        os.path.join(hb, f"coord_ack_{r:05d}.json")
                    )
                    for r in range(WORLD)
                }
                return all(
                    a is not None and a.get("epoch", -1) >= prop["epoch"]
                    for a in acks.values()
                )

            _wait_for(_all_acked, 60, "all acks")
        else:
            _wait_for(lambda: _read_ctrl(commit_path), 60, "commit")
        os.kill(procs[0].pid, signal.SIGKILL)
        kill_wall = time.time()
        checks["killed_phase"] = phase
    finally:
        outs, rcs = _harvest(procs)
        for p in procs:
            if p.poll() is None:
                p.kill()

    payloads = [_payload(o) for o in outs]
    survivors = [payloads[r] for r in (1, 2)]
    commit = _read_ctrl(os.path.join(hb, "coord_commit.json"))
    commit_wall = float(commit["wall"]) if commit else None
    recovery_windows = (
        round(max(0.0, commit_wall - kill_wall) / LEASE_S, 3)
        if commit_wall is not None and phase in ("propose", "ackwait")
        else None
    )
    trails = [
        [(e["epoch"], e["fingerprint"]) for e in s.get("control_epochs", ())]
        for s in survivors
    ]
    shas = {s.get("state_sha") for s in survivors}
    dump = _dump_with_phase(obs)
    floors = {
        "survivors_completed": all(
            rcs[r] == 0 and payloads[r].get("final_step") == STEPS
            for r in (1, 2)
        ),
        "same_control_trail": len(set(map(tuple, trails))) == 1 and trails[0],
        "replan_applied": any(
            e[1] == _replan_fingerprint() for e in (trails[0] or ())
        ),
        "bitwise_vs_twin": shas == {twin["state_sha"]},
        "zero_double_applies": _double_applies(obs) == 0,
        "fault_dump_with_phase": dump is not None,
        "recovery_within_bound": (
            recovery_windows is None
            or recovery_windows <= RECOVERY_BOUND_WINDOWS
        ),
    }
    floors["same_control_trail"] = bool(floors["same_control_trail"])
    return {
        "scenario": f"kill_coordinator_at_{phase}",
        "injection": f"SIGKILL of rank 0 at handshake phase {phase}",
        "ok": all(floors.values()),
        "floors": floors,
        "checks": {
            **checks,
            "rcs": rcs,
            "recovery_windows": recovery_windows,
            "control_trails": trails,
            "state_shas": sorted(shas),
            "twin_sha": twin["state_sha"],
            "dump": dump,
            "commit_epoch": commit["epoch"] if commit else None,
            "log_tail": outs[0].splitlines()[-8:],
        },
    }


def _replan_fingerprint() -> str:
    from flextree_tpu.runtime.coordination import decision_fingerprint

    return decision_fingerprint(
        "replan", {"topo": "chunked", "chunked": True}
    )


def run_stall_scenario(workdir: str, twin: dict) -> dict:
    """SIGSTOP rank 2 past the ack deadline; it must be excluded and,
    on resume, fenced (exit 3 + coord_fence dump)."""
    hb = os.path.join(workdir, "hb")
    obs = os.path.join(workdir, "obs")
    os.makedirs(hb, exist_ok=True)
    os.makedirs(obs, exist_ok=True)
    procs = [
        _spawn("worker", _matrix_env(workdir, rank)) for rank in range(WORLD)
    ]
    try:
        # freeze rank 2 BEFORE the scripted proposal fires
        from flextree_tpu.runtime import read_control_json

        def _rank2_step(at):
            beat = read_control_json(
                os.path.join(hb, "hb_00002.json")
            )
            return beat is not None and beat.get("step", -1) >= at

        _wait_for(lambda: _rank2_step(3), 60, "rank 2 at step 3")
        os.kill(procs[2].pid, signal.SIGSTOP)
        stop_wall = time.time()
        # wait for the re-proposal that excludes rank 2, then its commit
        def _excluding_commit():
            c = _read_ctrl(os.path.join(hb, "coord_commit.json"))
            return c if (c and 2 not in c["participants"]) else None

        commit = _wait_for(_excluding_commit, 60, "commit excluding rank 2")
        os.kill(procs[2].pid, signal.SIGCONT)
    finally:
        outs, rcs = _harvest(procs)
        for p in procs:
            if p.poll() is None:
                p.kill()
    payloads = [_payload(o) for o in outs]
    survivors = [payloads[0], payloads[1]]
    shas = {s.get("state_sha") for s in survivors}
    trails = [
        [(e["epoch"], e["fingerprint"]) for e in s.get("control_epochs", ())]
        for s in survivors
    ]
    fence_dump = None
    from flextree_tpu.obs import read_dir

    _, dumps = read_dir(obs)
    if 2 in dumps and dumps[2].get("reason") == "coord_fence":
        fence_dump = {
            "reason": dumps[2]["reason"],
            "coord_phase": dumps[2].get("coord_phase"),
        }
    floors = {
        "survivors_completed": all(
            rcs[r] == 0 and payloads[r].get("final_step") == STEPS
            for r in (0, 1)
        ),
        "stalled_rank_fenced": rcs[2] == _FENCED_RC
        and payloads[2].get("fenced") is True,
        "fence_dump_with_phase": fence_dump is not None
        and fence_dump.get("coord_phase") is not None,
        "same_control_trail": bool(
            len(set(map(tuple, trails))) == 1 and trails[0]
        ),
        "bitwise_vs_twin": shas == {twin["state_sha"]},
        "zero_double_applies": _double_applies(obs) == 0,
        "excluded_from_commit": 2 not in commit["participants"],
    }
    return {
        "scenario": "stalled_follower_fenced",
        "injection": "SIGSTOP of rank 2 past the ack deadline, then SIGCONT",
        "ok": all(floors.values()),
        "floors": floors,
        "checks": {
            "rcs": rcs,
            "stop_to_commit_s": round(float(commit["wall"]) - stop_wall, 3),
            "control_trails": trails,
            "fence_dump": fence_dump,
            "fence_error": payloads[2].get("fence_error"),
            "state_shas": sorted(shas),
            "log_tail": outs[2].splitlines()[-8:],
        },
    }


def run_torn_scenario(workdir: str, twin: dict) -> dict:
    """An adversarial scribbler tears the control files mid-handshake:
    truncate to a random prefix, hold the torn bytes visible for a beat,
    restore — readers must parse-refuse-and-reread, never crash."""
    hb = os.path.join(workdir, "hb")
    obs = os.path.join(workdir, "obs")
    os.makedirs(hb, exist_ok=True)
    os.makedirs(obs, exist_ok=True)
    stop = threading.Event()
    torn_count = {"n": 0}

    def scribbler():
        rng = random.Random(7)
        names = [
            "coord_proposal.json", "coord_commit.json",
            "coord_ack_00000.json", "coord_ack_00001.json",
            "coord_ack_00002.json", "lease_ledger.json",
            "hb_00001.json",  # beats are trailered control files too
        ]
        while not stop.is_set():
            name = rng.choice(names)
            path = os.path.join(hb, name)
            try:
                with open(path, "rb") as f:
                    raw = f.read()
                if len(raw) > 2:
                    with open(path, "wb") as f:
                        f.write(raw[: rng.randrange(1, len(raw))])
                    torn_count["n"] += 1
                    time.sleep(0.02)  # the torn window readers see
                    with open(path, "wb") as f:
                        f.write(raw)
            except OSError:
                pass
            time.sleep(0.03)

    procs = [
        _spawn("worker", _matrix_env(workdir, rank)) for rank in range(WORLD)
    ]
    thread = threading.Thread(target=scribbler, daemon=True)
    thread.start()
    try:
        outs, rcs = _harvest(procs)
    finally:
        stop.set()
        thread.join(timeout=5)
        for p in procs:
            if p.poll() is None:
                p.kill()
    payloads = [_payload(o) for o in outs]
    shas = {p.get("state_sha") for p in payloads}
    trails = [
        [(e["epoch"], e["fingerprint"]) for e in p.get("control_epochs", ())]
        for p in payloads
    ]
    from flextree_tpu.obs import read_dir

    events, _ = read_dir(obs)
    torn_events = sum(
        1 for e in events if e.get("kind") == "torn_control_file"
    )
    floors = {
        "all_completed": all(
            rcs[r] == 0 and payloads[r].get("final_step") == STEPS
            for r in range(WORLD)
        ),
        "same_control_trail": bool(
            len(set(map(tuple, trails))) == 1 and trails[0]
        ),
        "replan_applied": any(
            e[1] == _replan_fingerprint() for e in (trails[0] or ())
        ),
        "bitwise_vs_twin": shas == {twin["state_sha"]},
        "zero_double_applies": _double_applies(obs) == 0,
    }
    return {
        "scenario": "torn_ledger",
        "injection": f"{torn_count['n']} truncate-hold-restore tears across "
                     "the control files",
        "ok": all(floors.values()),
        "floors": floors,
        "checks": {
            "rcs": rcs,
            "tears_injected": torn_count["n"],
            "torn_events_observed": torn_events,
            "control_trails": trails,
            "state_shas": sorted(shas),
        },
    }


def run_resize_scenario(workdir: str, twin: dict) -> dict:
    """The parent plays arbiter: a lease grant change must flow through
    propose → commit → group apply with bitwise_resume on every rank and
    the lease ack fenced on the committed control epoch."""
    from flextree_tpu.runtime import LeaseLedger

    hb = os.path.join(workdir, "hb")
    obs = os.path.join(workdir, "obs")
    os.makedirs(hb, exist_ok=True)
    os.makedirs(obs, exist_ok=True)
    ledger = LeaseLedger(hb)
    ledger.publish(0, {"train": (0, 1, 2, 3)}, reason="initial")
    procs = [
        _spawn(
            "worker",
            _matrix_env(
                workdir, rank,
                {"FT_COORD_RESIZE": "1", "FT_CHIPS": "0,1,2,3"},
            ),
        )
        for rank in range(WORLD)
    ]
    try:
        from flextree_tpu.runtime import read_control_json

        def _rank0_step(at):
            beat = read_control_json(os.path.join(hb, "hb_00000.json"))
            return beat is not None and beat.get("step", -1) >= at

        _wait_for(lambda: _rank0_step(PROPOSE_AT), 60, "steady state")
        ledger.publish(
            1, {"train": (0, 1), "arbiter": (2, 3)}, reason="chaos revoke"
        )
        revoke_wall = time.time()
    finally:
        outs, rcs = _harvest(procs)
        for p in procs:
            if p.poll() is None:
                p.kill()
    payloads = [_payload(o) for o in outs]
    shas = {p.get("state_sha") for p in payloads}
    trails = [
        [(e["epoch"], e["fingerprint"]) for e in p.get("control_epochs", ())]
        for p in payloads
    ]
    resizes = [p.get("lease_epochs", []) for p in payloads]
    floors = {
        "all_completed": all(
            rcs[r] == 0 and payloads[r].get("final_step") == STEPS
            for r in range(WORLD)
        ),
        "same_control_trail": bool(
            len(set(map(tuple, trails))) == 1 and trails[0]
        ),
        "resize_applied_once_per_rank": all(
            len(r) == 1 and r[0]["epoch"] == 1 for r in resizes
        ),
        "bitwise_resume_everywhere": all(
            r and r[0]["bitwise_resume"] for r in resizes
        ),
        "ack_carries_control_epoch": all(
            p.get("lease_acked") == 1
            and p.get("lease_control_epoch") is not None
            for p in payloads
        ),
        "bitwise_vs_twin": shas == {twin["state_sha"]},
        "zero_double_applies": _double_applies(obs) == 0,
    }
    return {
        "scenario": "coordinated_resize",
        "injection": "arbiter revokes chips 2,3 mid-run (lease epoch 1)",
        "ok": all(floors.values()),
        "floors": floors,
        "checks": {
            "rcs": rcs,
            "control_trails": trails,
            "lease_epochs": resizes,
            "lease_control_epochs": [
                p.get("lease_control_epoch") for p in payloads
            ],
            "state_shas": sorted(shas),
        },
    }


def run_gloo_scenario(workdir: str) -> dict:
    """3 real processes on a real gloo wire: the committed replan flips
    every rank to the chunk-pipelined schedule at ONE agreed boundary —
    the collective itself referees (a split-brain step deadlocks)."""
    import socket

    def launch(tag: str, replan: bool):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        hb = os.path.join(workdir, f"hb_{tag}")
        obs = os.path.join(workdir, f"obs_{tag}")
        os.makedirs(hb, exist_ok=True)
        os.makedirs(obs, exist_ok=True)
        env_base = dict(
            FT_STEPS=str(24),
            FT_HB_DIR=hb,
            FT_OBS_DIR=obs,
            FT_GLOO_REPLAN="1" if replan else "0",
            FT_COORDINATOR=f"127.0.0.1:{port}",
            FT_NUM_PROCESSES=str(WORLD),
        )
        procs = []
        for rank in range(WORLD):
            env = dict(env_base, FT_PROCESS_ID=str(rank))
            env.pop("JAX_PLATFORMS", None)
            procs.append(_spawn("gloo", env))
        outs, rcs = _harvest(procs, timeout=420.0)
        return [_payload(o) for o in outs], rcs, outs

    payloads, rcs, outs = launch("replan", replan=True)
    twin_payloads, twin_rcs, _twin_outs = launch("twin", replan=False)
    shas = {p.get("state_sha") for p in payloads}
    twin_shas = {p.get("state_sha") for p in twin_payloads}
    applied = [p.get("applied", []) for p in payloads]
    floors = {
        "wire_completed": all(rc == 0 for rc in rcs),
        "twin_completed": all(rc == 0 for rc in twin_rcs),
        "chunk_twin_bitwise": all(
            p.get("chunk_twin_bitwise") for p in payloads
        ),
        "replan_applied_same_epoch_everywhere": (
            all(len(a) == 1 for a in applied)
            and len(
                {(a[0]["epoch"], a[0]["fingerprint"]) for a in applied if a}
            ) == 1
            and all(p.get("final_chunks") == 2 for p in payloads)
        ),
        "same_apply_boundary": len(
            {a[0]["step"] for a in applied if a}
        ) == 1,
        "ranks_bitwise_identical": len(shas) == 1,
        "bitwise_vs_unfaulted_twin": shas == twin_shas and len(shas) == 1,
    }
    return {
        "scenario": "gloo_group_replan",
        "injection": "coordinated replan (chunk-pipelined twin) on a live "
                     "3-process gloo wire, boundary-synchronized",
        "ok": all(floors.values()),
        "floors": floors,
        "checks": {
            "rcs": rcs,
            "twin_rcs": twin_rcs,
            "applied": applied,
            "state_shas": sorted(shas),
            "twin_shas": sorted(twin_shas),
            "log_tail": outs[0].splitlines()[-8:],
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: kill-at-ackwait + torn ledger + "
                    "coordinated resize (full matrix in the committed "
                    "artifact)")
    ap.add_argument("--out", default=os.path.join(REPO, "COORD_CHAOS.json"))
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        role = os.environ.get("FT_COORD_ROLE", "worker")
        if role == "gloo":
            return child_gloo()
        if role == "twin":
            return child_twin()
        return child_worker()

    scenarios = (
        ["kill_ackwait", "torn", "resize"]
        if args.smoke
        else [
            "kill_propose", "kill_ackwait", "kill_commit",
            "stall", "torn", "resize", "gloo",
        ]
    )
    results = []
    with tempfile.TemporaryDirectory(prefix="ft_coord_chaos_") as wd:
        twin = run_twin(wd)
        print(f"twin: step {twin['final_step']} sha {twin['state_sha'][:16]}",
              flush=True)
        for name in scenarios:
            sub = os.path.join(wd, name)
            os.makedirs(sub, exist_ok=True)
            print(f"=== scenario {name} ===", flush=True)
            try:
                if name.startswith("kill_"):
                    res = run_kill_scenario(sub, name[len("kill_"):], twin)
                elif name == "stall":
                    res = run_stall_scenario(sub, twin)
                elif name == "torn":
                    res = run_torn_scenario(sub, twin)
                elif name == "resize":
                    res = run_resize_scenario(sub, twin)
                else:
                    res = run_gloo_scenario(sub)
            except Exception as e:  # a crashed scenario is a failed floor
                res = {
                    "scenario": name, "ok": False,
                    "error": f"{type(e).__name__}: {e}", "floors": {},
                }
            print(
                f"scenario {res['scenario']}: "
                f"{'OK' if res['ok'] else 'FAILED'} "
                + json.dumps(res.get("floors", {})),
                flush=True,
            )
            results.append(res)

    ok = all(r["ok"] for r in results)
    recovery = {
        r["scenario"]: r["checks"].get("recovery_windows")
        for r in results
        if r.get("checks", {}).get("recovery_windows") is not None
    }
    if not args.no_artifact:
        from flextree_tpu.utils.buildstamp import artifact_meta
        from flextree_tpu.utils.logging import write_result_file

        write_result_file(
            args.out,
            {
                "description": "Executed consensus chaos: the coordinated "
                               "elastic control plane (epoch-numbered "
                               "propose→ack→commit on the heartbeat dir, "
                               "runtime/coordination.py) under coordinator "
                               "SIGKILL at every handshake phase, a "
                               "SIGSTOP'd follower past the ack deadline, "
                               "an adversarial torn-ledger scribbler, a "
                               "group-committed arbiter resize, and a "
                               "boundary-synchronized replan on a real "
                               "3-process gloo wire — all floors "
                               "machine-checked, non-zero exit on any "
                               "violation; see docs/COORDINATION.md",
                "build": artifact_meta(),
                "ok": ok,
                "smoke": args.smoke,
                "budgets": {
                    "heartbeat_interval_s": HB_INTERVAL,
                    "straggler_s": STRAGGLER_S,
                    "lease_s": LEASE_S,
                    "step_sleep_s": STEP_SLEEP,
                    "recovery_bound_lease_windows": RECOVERY_BOUND_WINDOWS,
                },
                "world": WORLD,
                "steps": STEPS,
                "recovery_windows": recovery,
                "scenarios": {r["scenario"]: r for r in results},
            },
        )
        print(f"wrote {args.out} (ok={ok})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
