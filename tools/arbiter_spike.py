#!/usr/bin/env python
"""Arbiter spike: one elastic device pool under an injected arrival burst.

The ISSUE-13 tentpole evidence (docs/ARBITER.md).  One process, one chip
inventory (4 virtual CPU devices), two tenants sharing it live:

- **training**: a real jitted ZeRO-1 sharded dense step over a dp-3 mesh
  (chips 0-2), run by ``fit(arbiter=TrainLeaseClient(...))`` on its own
  thread with consolidated checkpoints — the exact world the chaos
  drivers SIGKILL;
- **serving**: a :class:`ReplicaPool` with one baseline replica (chip 3)
  plus two pre-warmed burst engines, fed open-loop Poisson arrivals
  (requests land on the wall clock whether or not the pool keeps up);
- **the arbiter**: ticking between pool rounds, reading the pool's
  windowed TTFT p99 against the SLO, moving chips through the lease
  ledger on the heartbeat dir.

The injected load has three phases: baseline (one replica holds the SLO
comfortably), a Poisson burst at several times the baseline capacity
(TTFT p99 blows through the SLO), then baseline again until everything
drains.  The expected story, every step machine-checked from the
artifacts the run leaves (arbiter decisions, RunReport.lease_epochs,
pool report, merged flight-record timeline):

1. the burst breaches the windowed SLO → ``slo_breach`` + the arbiter
   revokes 2 chips; training checkpoints NOW, shrinks dp-3 → dp-1
   (bitwise resume, in-run-verified), acks; the chips go to serving and
   the 2 warmed replicas join the pool (``lease_preempt`` →
   ``lease_grant``);
2. pooled capacity drains the backlog; the windowed p99 recovers to
   within the SLO **within one lease window of the burst's end** — the
   recovery floor;
3. sustained low-water p99 + cooldown → the burst replicas drain
   (in-flight requests re-route exactly-once), chips return
   (``lease_return``), training re-expands dp-1 → dp-3 (bitwise resume
   again) and its post-reclaim step time matches the pre-spike one.

Non-zero exit on any floor violation.  ``--smoke`` shortens the phases
and waives the two TIMING floors (recovery window, step-time
restoration) that a timeshared CI minute cannot hold honestly — the
structural floors (arbiter acted, bitwise zero-loss resume, chips
reclaimed, every request served exactly once, schema-valid timeline)
are enforced in both modes.

Usage: python tools/arbiter_spike.py [--smoke] [--out ARBITER_SPIKE.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from collections import deque

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

jax.config.update("jax_num_cpu_devices", 4)

import numpy as np  # noqa: E402

from flextree_tpu.arbiter import (  # noqa: E402
    ArbiterConfig,
    DeviceInventory,
    PoolArbiter,
    pool_slo_reader,
)
from flextree_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_params,
    param_specs,
)
from flextree_tpu.obs import (  # noqa: E402
    flight_recorder,
    merge_dir,
    read_dir,
    validate_trace,
    write_trace,
)
from flextree_tpu.parallel.loop import FitConfig, Supervision, fit  # noqa: E402
from flextree_tpu.runtime import (  # noqa: E402
    LeaseLedger,
    PreemptionGuard,
    TrainLeaseClient,
)
from flextree_tpu.serving import (  # noqa: E402
    BatcherConfig,
    PagedCacheConfig,
    PoolConfig,
    ReplicaPool,
    Request,
    ServingEngine,
)

_now = time.monotonic

# ---------------------------------------------------------------------------
# configuration: one window constant shared by the engines' rolling TTFT
# histograms and the arbiter's breach check — "one lease window" in the
# recovery floor means exactly this many seconds
# ---------------------------------------------------------------------------

WINDOW_S = 6.0
TICK_S = 0.4
# TTFT target: baseline traffic (25% utilization, ~6 ms decode rounds,
# ~200 ms service times) sits comfortably under the 50% low-water, the
# burst (~1.7x single-replica capacity) queues seconds past it
SLO_P99_MS = 600.0

CHIPS = (0, 1, 2, 3)
TRAIN_CHIPS = (0, 1, 2)  # dp-3 by default; chip 3 is serving's baseline
BURST_CHIPS = 2

TRAIN_BATCH = 6  # rows; divisible by every training world size (3, 1)
TRAIN_SEQ = 32
# pacing between train steps (chaos_runtime's step_sleep pattern): on this
# host the virtual chips share 2 physical cores, and an unpaced jitted hot
# loop saturates them — serving capacity then swings with scheduler luck
# and no floor is stable.  The pace stands in for the host CPU a real
# accelerator trainer would not be stealing from serving (the
# virtual-chips honest limit in docs/ARBITER.md); it is constant across
# all phases, so the pre/post step-time comparison (compute-only, timed
# inside the step) is unaffected.
TRAIN_PACE_S = 0.03
# per-round chip budget for serving replicas: on real accelerators decode
# is CHIP-bound — a round's duration is the chip's, and rounds on separate
# chips overlap perfectly.  On this rig the rounds are CPU-bound on the
# SAME two cores, so pooled capacity (the recovery floor's whole premise)
# would be a function of scheduler luck: measured pooled/single swung
# 1.2-1.6x across runs, flipping the floor.  Each replica round therefore
# sleeps a fixed chip budget after its (real) compute — capacity then maps
# to chips (3 replicas = 3x, deterministic) while every token, admission
# decision, and TTFT stamp stays real.  Documented in docs/ARBITER.md's
# honest limits.
CHIP_ROUND_S = 0.008


def _arbiter_cfg() -> ArbiterConfig:
    return ArbiterConfig(
        slo_p99_ms=SLO_P99_MS,
        window_s=WINDOW_S,
        release_frac=0.5,
        breach_ticks=2,
        clear_ticks=4,
        cooldown_s=3.0,
        min_train_chips=1,
        burst_chips=BURST_CHIPS,
        min_samples=6,
    )


def _serve_model():
    # big enough that a decode round's compute (~6 ms measured beside the
    # training thread) dominates the host loop — at toy sizes the pool is
    # loop-bound and no arrival rate can honestly saturate a replica
    cfg = TransformerConfig(
        vocab_size=128, d_model=256, n_heads=8, n_layers=4, d_ff=1024
    )
    return cfg, init_params(jax.random.PRNGKey(7), cfg)


def _train_model():
    return TransformerConfig(
        vocab_size=128, d_model=128, n_heads=4, n_layers=2, d_ff=512
    )


def _pcfg() -> PagedCacheConfig:
    # max prompt 8 + max out 48 = 56 positions = 7 blocks/seq; 2 slots
    # per replica -> 14 blocks + null + slack
    return PagedCacheConfig(num_blocks=17, block_size=8, blocks_per_seq=8)


# ---------------------------------------------------------------------------
# workload: three-phase open-loop Poisson arrivals — the generator is
# shared with tools/serve_elastic_chaos.py (flextree_tpu.serving.workload)
# so the two elastic drivers cannot drift apart on what "a burst" means
# ---------------------------------------------------------------------------

from flextree_tpu.serving.workload import (  # noqa: E402
    OUT_LENS,
    OUT_PROBS,
    PROMPT_LENS,
    build_spike_workload as build_workload,
)


# ---------------------------------------------------------------------------
# training: the sharded world builder (the chaos drivers' shape) + thread
# ---------------------------------------------------------------------------


class _LMData:
    def batch_at(self, step):
        tok = (
            np.arange(TRAIN_BATCH * TRAIN_SEQ, dtype=np.int32).reshape(
                TRAIN_BATCH, TRAIN_SEQ
            )
            + step
        ) % 128
        return tok, tok


class TrainWorlds:
    """Build (and pre-warm) the sharded training world per chip count, so
    a mid-run lease resize swaps to an already-compiled step instead of
    paying XLA inside the handoff."""

    def __init__(self, model_cfg):
        import jax as _jax

        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_state_specs,
            make_train_step,
            zero_layout_for,
        )
        from flextree_tpu.parallel.zero import (
            make_consolidate_fn,
            make_reshard_fn,
        )

        self._jax = _jax
        self.model_cfg = model_cfg
        self.base_tc = TrainConfig(shard_optimizer=True)
        self._mods = (
            make_mesh_nd, make_train_step, make_state_specs,
            zero_layout_for, make_consolidate_fn, make_reshard_fn,
            init_train_state, TrainConfig,
        )
        self._cache: dict = {}
        self.step_trace: list = []  # (wall, duration_s, world)

    def build(self, ndev: int, grad_topo=None):
        key = (ndev, grad_topo)
        if key in self._cache:
            return self._cache[key]
        (make_mesh_nd, make_train_step, make_state_specs, zero_layout_for,
         make_consolidate_fn, make_reshard_fn, _, TrainConfig) = self._mods
        jax_ = self._jax
        tc = dataclasses.replace(self.base_tc, grad_topo=grad_topo)
        mesh = make_mesh_nd(ndev, (ndev, 1, 1), ("dp", "sp", "tp"))
        jit_step = make_train_step(mesh, self.model_cfg, tc)
        trace = self.step_trace
        world = ndev

        def step_fn(state, tokens, targets):
            t0 = _now()
            out = jax_.block_until_ready(jit_step(state, tokens, targets))
            trace.append((time.time(), _now() - t0, world))
            time.sleep(TRAIN_PACE_S)  # outside the timed section
            return out

        pspecs = param_specs(self.model_cfg, "tp")
        shapes = jax_.eval_shape(
            lambda k: init_params(k, self.model_cfg), jax_.random.PRNGKey(0)
        )
        layout = zero_layout_for(mesh, shapes, pspecs, ("dp", "sp", "tp"))
        packed_specs = make_state_specs(
            pspecs, dataclasses.replace(tc, shard_optimizer=False)
        )
        pack = make_consolidate_fn(mesh, pspecs, layout, grad_topo, False)
        unpack = make_reshard_fn(mesh, pspecs, layout, grad_topo, False)
        built = (step_fn, mesh, packed_specs, pack, unpack)
        self._cache[key] = built
        return built

    def warm(self, ndev: int, grad_topo=None) -> None:
        """Compile the world's step (and its pack/unpack) off the clock."""
        from flextree_tpu.parallel.train import init_train_state

        step_fn, mesh, _, pack, unpack = self.build(ndev, grad_topo)
        state = init_train_state(
            jax.random.PRNGKey(0), self.model_cfg, self.base_tc, mesh=mesh
        )
        tok, tgt = _LMData().batch_at(0)
        state, _ = step_fn(state, tok, tgt)  # the step donates its state
        unpack(jax.device_get(pack(state)))
        # warming appends to the step trace; the run's trace starts clean
        self.step_trace.clear()

    def initial_state(self, ndev: int, grad_topo=None):
        from flextree_tpu.parallel.train import init_train_state

        _, mesh, _, _, _ = self.build(ndev, grad_topo)
        return init_train_state(
            jax.random.PRNGKey(0), self.model_cfg, self.base_tc, mesh=mesh
        )


def start_trainer(worlds: TrainWorlds, client: TrainLeaseClient,
                  ckpt_dir: str, guard: PreemptionGuard,
                  plans: dict) -> tuple:
    """Run ``fit`` on a daemon thread; returns (thread, result_holder)."""
    holder: dict = {}

    def on_resize(chips, plan):
        # the arbiter handle's rebuild hook: the resize twin of on_shrink
        # — new mesh width, replanned grad topo, fresh ZeRO converters
        return worlds.build(len(chips), plan.to_ft_topo())

    client.on_resize = on_resize
    ndev0 = len(TRAIN_CHIPS)
    step0, mesh0, specs0, pack0, unpack0 = worlds.build(
        ndev0, plans[ndev0]
    )
    state0 = worlds.initial_state(ndev0, plans[ndev0])

    def run():
        try:
            holder["result"] = fit(
                state0, step0, _LMData(),
                FitConfig(
                    num_steps=1_000_000,  # stopped by the preemption guard
                    ckpt_dir=ckpt_dir, ckpt_every=1_000_000,
                    log_every=0, prefetch=0,
                ),
                mesh=mesh0, state_specs=specs0,
                supervision=Supervision(preemption=guard),
                arbiter=client,
                state_pack=pack0, state_unpack=unpack0,
            )
        except Exception as e:  # surfaced as a floor violation by main
            holder["error"] = f"{type(e).__name__}: {e}"

    thread = threading.Thread(target=run, daemon=True, name="ft-trainer")
    thread.start()
    return thread, holder


# ---------------------------------------------------------------------------
# the spike run
# ---------------------------------------------------------------------------


def run_spike(smoke: bool, workdir: str, obs_dir: str) -> dict:
    from flextree_tpu.planner.choose import replan_for_survivors

    hb_dir = os.path.join(workdir, "hb")  # heartbeats AND the lease ledger
    ckpt_dir = os.path.join(workdir, "ck")
    os.makedirs(hb_dir, exist_ok=True)

    # spike rate sits above one replica's chip-paced capacity (~7 rps:
    # 2 slots / ~mean 29 rounds x ~9.5 ms) but well under the 3-replica
    # pooled one (~20 rps — chip-paced rounds overlap), so the burst
    # both breaches the SLO AND drains mid-spike once the granted
    # replicas come online — the recovery floor's premise: the backlog
    # is gone BEFORE the spike ends
    if smoke:
        t_base, t_spike, t_tail = 4.0, 5.0, 3.0
        base_rate, spike_rate = 2.0, 9.0
        post_steps = 4
    else:
        # the spike outlasts detection (~2s) + handoff (~1s) + backlog
        # drain (~2s) with margin
        t_base, t_spike, t_tail = 10.0, 12.0, 4.0
        base_rate, spike_rate = 2.0, 9.0
        post_steps = 12

    acfg = _arbiter_cfg()
    requests, spike_start, spike_end = build_workload(
        seed=13, base_rate=base_rate, spike_rate=spike_rate,
        t_base=t_base, t_spike=t_spike, t_tail=t_tail,
    )

    # --- serving: baseline replica + pre-warmed burst engines -------------
    scfg, sparams = _serve_model()
    pcfg = _pcfg()
    prompt_lens = sorted({r.prompt_len for r in requests})
    block_counts = sorted(
        {pcfg.blocks_for(r.prompt_len + r.max_new_tokens) for r in requests}
    )

    def make_engine() -> ServingEngine:
        eng = ServingEngine(
            sparams, scfg, pcfg, BatcherConfig(slots=2),
            slo_window_s=WINDOW_S,
        )
        eng.warmup(prompt_lens, block_counts)
        orig_step = eng.step

        def chip_paced_step():
            out = orig_step()
            time.sleep(CHIP_ROUND_S)  # the chip's share of the round
            return out

        eng.step = chip_paced_step
        return eng

    pool = ReplicaPool(
        [make_engine()],
        # parallel rounds: the burst replicas must buy real pooled
        # throughput on this multi-core host, not just more queues
        PoolConfig(heartbeat_dir=hb_dir, interval_s=0.1,
                   parallel_rounds=True),
    )
    burst_engines = deque(make_engine() for _ in range(BURST_CHIPS))
    chip_to_replica: dict = {}

    def on_serve_grant(chips):
        for c in chips:
            chip_to_replica[c] = pool.add_replica(burst_engines.popleft())

    def on_serve_return(chips):
        for c in chips:
            pool.release_replica(chip_to_replica.pop(c))

    # --- training: pre-warmed sharded worlds + the lease client ----------
    worlds = TrainWorlds(_train_model())
    nbytes_hint = 1 << 20
    plans = {
        n: replan_for_survivors(
            n, nbytes_hint, configured=len(TRAIN_CHIPS)
        ).to_ft_topo()
        for n in (len(TRAIN_CHIPS), len(TRAIN_CHIPS) - BURST_CHIPS)
    }
    for n, topo in plans.items():
        worlds.warm(n, topo)

    # --- the arbiter ------------------------------------------------------
    inventory = DeviceInventory(CHIPS, train=TRAIN_CHIPS)
    ledger = LeaseLedger(hb_dir)
    arbiter = PoolArbiter(
        inventory, ledger, acfg,
        slo_reader=pool_slo_reader(pool, window_s=acfg.window_s),
        on_serve_grant=on_serve_grant,
        on_serve_return=on_serve_return,
    )
    client = TrainLeaseClient(
        ledger, initial_chips=TRAIN_CHIPS, configured=len(TRAIN_CHIPS),
        nbytes_hint=nbytes_hint, poll_interval_s=0.1,
    )
    guard = PreemptionGuard()  # triggered in-process to stop the trainer
    trainer, holder = start_trainer(worlds, client, ckpt_dir, guard, plans)

    # --- the run loop -----------------------------------------------------
    pending = deque(sorted(requests, key=lambda r: r.arrival_s))
    t0 = _now()
    wall0 = time.time()
    last_tick = t0
    served_done = False
    quiet_wall: float | None = None  # everything drained AND chips home
    deadline = t0 + (90.0 if smoke else 240.0)

    while _now() < deadline:
        now = _now()
        rel = now - t0
        while pending and pending[0].arrival_s <= rel:
            req = pending.popleft()
            pool.submit(dataclasses.replace(req, arrival_s=t0 + req.arrival_s))
        if now - last_tick >= TICK_S:
            arbiter.tick()
            last_tick = now
        if not pool.idle:
            pool.step()
        else:
            time.sleep(0.02)
        served_done = not pending and pool.idle
        if served_done and not arbiter.loaned and not arbiter.pending_handoff:
            # the burst came back and every request drained: NOW the host
            # is quiet — wait for the trainer to bank post_steps
            # full-world steps past this point (the step-time floor
            # compares quiet-host medians on both sides; steps taken
            # while the tail was still draining are contended, not
            # "reclaimed")
            if quiet_wall is None:
                quiet_wall = time.time()
            post = [d for w, d, n in worlds.step_trace
                    if n == len(TRAIN_CHIPS) and w > quiet_wall]
            if len(post) >= post_steps:
                break
        else:
            quiet_wall = None
    ran_out = _now() >= deadline

    guard.trigger()
    trainer.join(timeout=120.0)
    result = holder.get("result")

    # --- assemble the evidence -------------------------------------------
    decisions = arbiter.decisions
    report = result.report if result is not None else None
    lease_epochs = list(report.lease_epochs) if report is not None else []
    pool_report = pool.report()
    pool.shutdown()

    def wall_of(action):
        return [d["wall"] for d in decisions if d["action"] == action]

    preempts, grants, returns = (
        wall_of("preempt"), wall_of("grant"), wall_of("return")
    )
    spike_end_wall = wall0 + spike_end

    # recovery: the first arbiter evaluation at/after the serve grant
    # whose windowed p99 is back inside the SLO (an empty window counts:
    # every spike-era TTFT aged out) and never breaches again
    recovery_wall = None
    if grants:
        for d in decisions:
            if d["wall"] < grants[0]:
                continue
            p99 = d["reading"]["p99_ms"]
            if d["reading"]["samples"] == 0 or (
                p99 is not None and p99 <= acfg.slo_p99_ms
            ):
                recovery_wall = d["wall"]
                break
    recovery_ref = max(grants[0], spike_end_wall) if grants else None
    recovery_s = (
        None if recovery_wall is None or recovery_ref is None
        else max(0.0, recovery_wall - recovery_ref)
    )
    recovery_windows = (
        None if recovery_s is None else round(recovery_s / WINDOW_S, 3)
    )

    # step-time restoration: full-world steps before the first resize vs
    # after the pool went fully quiet post-reclaim (steps taken while the
    # serving tail was still draining are contended, not "reclaimed")
    trace = list(worlds.step_trace)
    first_resize_wall = (
        min(preempts) if preempts else float("inf")
    )
    post_ref = quiet_wall if quiet_wall is not None else float("inf")
    pre = [d for w, d, n in trace
           if n == len(TRAIN_CHIPS) and w < first_resize_wall]
    post = [d for w, d, n in trace
            if n == len(TRAIN_CHIPS) and w > post_ref]
    pre_ms = round(float(np.median(pre)) * 1e3, 2) if pre else None
    post_ms = round(float(np.median(post)) * 1e3, 2) if post else None
    step_ratio = (
        round(post_ms / pre_ms, 3) if pre_ms and post_ms else None
    )

    completed = pool_report["completed"]
    submitted = pool_report["submitted"]

    doc = {
        "smoke": smoke,
        "phases": {
            "baseline_s": t_base, "spike_s": t_spike, "tail_s": t_tail,
            "base_rate_rps": base_rate, "spike_rate_rps": spike_rate,
            "requests": len(requests),
        },
        "arbiter": {
            "slo_p99_ms": acfg.slo_p99_ms,
            "window_s": acfg.window_s,
            "release_frac": acfg.release_frac,
            "cooldown_s": acfg.cooldown_s,
            "ticks": len(decisions),
            "preempts": len(preempts),
            "grants": len(grants),
            "returns": len(returns),
            "final_train_chips": list(inventory.held_by("train")),
            "final_serve_chips": list(inventory.held_by("serve")),
            "loaned_at_end": list(arbiter.loaned),
        },
        "serving": {
            "submitted": submitted,
            "completed": completed,
            "rejected": pool_report["rejected"],
            "reroutes": pool_report["reroutes"],
            "replicas": pool_report["replicas"],
            "released": pool_report["released"],
            "degraded": pool_report["degraded"],
        },
        "training": {
            "error": holder.get("error"),
            "steps_run": result.steps_run if result else None,
            "final_step": (
                int(np.asarray(jax.device_get(result.state["step"])))
                if result else None
            ),
            "anomalies": report.anomalies if report else None,
            "skipped_steps": list(report.skipped_steps) if report else None,
            "lease_epochs": lease_epochs,
            "losses_finite": (
                bool(result and all(np.isfinite(l) for _, l in result.losses))
            ),
            "pre_spike_step_ms": pre_ms,
            "post_reclaim_step_ms": post_ms,
            "step_time_ratio": step_ratio,
            "steps_by_world": {
                str(n): sum(1 for _, _, w in trace if w == n)
                for n in sorted({w for _, _, w in trace})
            },
        },
        "recovery": {
            "spike_end_wall": spike_end_wall,
            "first_grant_wall": grants[0] if grants else None,
            "recovery_wall": recovery_wall,
            "recovery_s_past_ref": recovery_s,
            "recovery_windows": recovery_windows,
        },
        # the arbiter's audit trail, downsampled: every action tick plus
        # one reading per second — enough to replay the decision story
        "decisions": [
            {
                "t": round(d["wall"] - wall0, 2),
                "action": d["action"],
                "p99_ms": d["reading"]["p99_ms"],
                "samples": d["reading"]["samples"],
                "breached": d["breached"],
            }
            for i, d in enumerate(decisions)
            if d["action"] is not None or i % max(1, int(1.0 / TICK_S)) == 0
        ],
        "ran_out_of_time": ran_out,
    }

    # --- machine-checked floors ------------------------------------------
    violations: list[str] = []

    def floor(ok: bool, what: str) -> None:
        if not ok:
            violations.append(what)

    floor(holder.get("error") is None,
          f"trainer died: {holder.get('error')}")
    floor(not ran_out, "run hit its wall-clock deadline before draining")
    # 1. the arbiter acted, and the handoff completed in both directions
    floor(len(preempts) >= 1, "no lease_preempt: the spike never moved chips")
    floor(len(grants) >= 1, "no lease_grant: chips never reached serving")
    floor(len(returns) >= 1, "no lease_return: chips never came back")
    # 2. chips reclaimed: training holds its full grant again
    floor(
        tuple(inventory.held_by("train")) == TRAIN_CHIPS,
        f"training did not reclaim its chips: "
        f"{inventory.held_by('train')} != {TRAIN_CHIPS}",
    )
    floor(not arbiter.loaned, f"chips still loaned: {arbiter.loaned}")
    # 3. zero lost steps, bitwise: every lease resize round-tripped the
    # packed state exactly, and the run skipped nothing
    floor(
        len(lease_epochs) >= 2,
        f"expected >= 2 lease resizes (shrink + expand), got "
        f"{len(lease_epochs)}",
    )
    floor(
        all(e["bitwise_resume"] for e in lease_epochs),
        f"non-bitwise resume in lease epochs: {lease_epochs}",
    )
    floor(
        report is not None and report.anomalies == 0
        and not report.skipped_steps,
        "training skipped steps",
    )
    floor(bool(doc["training"]["losses_finite"]), "non-finite training loss")
    # 4. serving: every submitted request completed exactly once
    floor(
        completed == submitted == len(requests),
        f"served {completed}/{submitted} of {len(requests)} requests",
    )
    floor(not pool_report["rejected"],
          f"rejected requests: {pool_report['rejected']}")
    if not smoke:
        # 5. the recovery floor: p99 back inside the SLO within one lease
        # window of max(first grant, spike end)
        floor(
            recovery_s is not None and recovery_s <= WINDOW_S,
            f"p99 did not recover within one lease window: "
            f"{recovery_s}s > {WINDOW_S}s",
        )
        # 6. the reclaim floor: post-burst full-world step time within
        # 1.5x of the pre-spike one (generous: one timeshared host)
        floor(
            step_ratio is not None and step_ratio <= 1.5,
            f"post-reclaim step time not restored: ratio {step_ratio}",
        )
    doc["violations"] = violations
    doc["ok"] = not violations
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "ARBITER_SPIKE.json"))
    ap.add_argument("--timeline-out", default=None,
                    help="also write the merged Chrome-trace JSON here")
    ap.add_argument("--smoke", action="store_true",
                    help="short phases; waive the timing floors (recovery "
                         "window, step-time restoration)")
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args(argv)

    workdir = tempfile.mkdtemp(prefix="ft_arbiter_spike_")
    obs_dir = os.path.join(workdir, "obs")
    try:
        with flight_recorder(obs_dir, rank=0):
            doc = run_spike(args.smoke, workdir, obs_dir)
        # the merged timeline: train steps, serve lifecycle flows, and the
        # arbiter lane, all on one track — schema-checked, not assumed
        trace = merge_dir(obs_dir)
        trace_bad = validate_trace(trace)
        kinds = {e["kind"] for e in read_dir(obs_dir)[0]}
        need = {"slo_breach", "lease_preempt", "lease_grant", "lease_return",
                "lease_resize", "step_start", "serve_admit"}
        missing = sorted(need - kinds)
        doc["timeline"] = {
            "events": len(trace.get("traceEvents", ())),
            "schema_violations": trace_bad,
            "missing_kinds": missing,
        }
        if trace_bad:
            doc["violations"].append(f"timeline schema violations: {trace_bad}")
        if missing:
            doc["violations"].append(f"timeline missing kinds: {missing}")
        doc["ok"] = not doc["violations"]
        if args.timeline_out:
            write_trace(trace, args.timeline_out)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    if not args.no_artifact:
        from flextree_tpu.utils.buildstamp import artifact_meta
        from flextree_tpu.utils.logging import write_result_file

        payload = {
            "description": (
                "Executed elastic-pool spike: a Poisson arrival burst "
                "breaches the serving TTFT SLO; the pool arbiter preempts "
                "chips from a live ZeRO-1 sharded training run (checkpoint "
                "-> shrink dp-3 -> dp-1, bitwise resume verified in-run) "
                "to two warmed serving replicas, p99 recovers within one "
                "lease window, and after the burst drains the chips return "
                "and training re-expands with its step time restored — "
                "machine-checked floors, see docs/ARBITER.md"
            ),
            "build": artifact_meta(),
            **doc,
        }
        write_result_file(args.out, payload)
        print(f"wrote {args.out} (ok={doc['ok']})")
    if doc["violations"]:
        print("FLOOR VIOLATIONS:", file=sys.stderr)
        for v in doc["violations"]:
            print(f"  - {v}", file=sys.stderr)
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
