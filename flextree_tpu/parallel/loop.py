"""Training loop driver: steps + checkpointing + logging + resume + recovery.

Composes the pieces the rest of the package provides — any of the three
train steps (dense dp/sp/tp, pipeline, MoE), the ``LMDataset`` batch
addressing, and the checkpoint subsystem — into the run loop a framework
user actually calls.  Resume is exact: the loop reads ``state['step']``
after restoring and continues with ``dataset.batch_at(step)``, so a run
interrupted at any step and resumed produces the same parameters as a
straight-through run (pinned by tests).

Crash safety (docs/FAILURE_MODEL.md): a NaN/Inf guard on the step metrics
skips anomalous steps (the update is discarded, the batch is not retried
this run), rewinds to the last verified checkpoint after
``max_bad_steps`` *consecutive* anomalies, and gives up with
:class:`TrainingDiverged` once ``max_rewinds`` rewinds have not cured the
divergence.  Restores go through ``restore_train_state``'s integrity
fallback, so a truncated newest checkpoint silently falls back one.

Runtime supervision (the in-run half of the failure model): pass a
:class:`Supervision` and the loop gains a step watchdog (a hung step
raises a typed ``FT_STEP_TIMEOUT`` instead of blocking forever, with a
bounded retry for transient stalls), heartbeat-driven membership (this
rank beats through a ``runtime.Supervisor``; dead peers confirmed by the
``membership`` view trigger **live shrink-to-survivors**: drain in-flight
work, restore the latest CRC-verified checkpoint, replan the collective
topology via ``planner.replan_for_survivors``, optionally rebuild the
step through ``on_shrink``, and resume — no process restart), straggler
accounting from per-rank step-duration EWMAs, and preemption-aware
checkpointing (a :class:`~flextree_tpu.runtime.PreemptionGuard`'s SIGTERM
flag takes a synchronous "checkpoint now" fast path within one step; a
:class:`~flextree_tpu.runtime.BackgroundSaver` moves periodic saves off
the step path so the rewind window stays small).

The run's :class:`RunReport` (anomalies, skipped steps, rewinds,
checkpoint fallbacks, step timeouts, stragglers, membership epoch
transitions, preemption point) is returned on the :class:`FitResult`
and, when a checkpoint dir is configured, written there as
``run_report.json`` (via :meth:`RunReport.to_json`) — including when the
run dies with :class:`TrainingDiverged`, which is exactly when the
postmortem needs it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import time
from typing import Any, Callable

import jax
import numpy as np

from ..obs import dump_current, get_registry, record_event, span
from ..utils.checkpoint import (
    latest_checkpoint,
    restore_train_state,
    save_train_state,
)
from ..utils.logging import get_logger
from ..utils.profiling import plan_capture, step_scope

__all__ = [
    "FitConfig",
    "FitResult",
    "RunReport",
    "ShrinkExhausted",
    "Supervision",
    "TrainingDiverged",
    "fit",
]

log = get_logger("flextree.train")


class TrainingDiverged(RuntimeError):
    """The NaN/Inf guard exhausted its recovery budget: ``max_bad_steps``
    consecutive anomalies with no checkpoint to rewind to, or
    ``max_rewinds`` rewinds that did not cure the divergence."""


class ShrinkExhausted(RuntimeError):
    """Peers kept dying past the ``Supervision.max_shrinks`` budget — the
    run refuses to keep replanning around a collapsing world."""


@dataclasses.dataclass(frozen=True)
class FitConfig:
    num_steps: int = 100
    ckpt_dir: str | None = None
    ckpt_every: int = 100
    max_to_keep: int = 3
    log_every: int = 10
    resume: bool = True  # restore from ckpt_dir's latest checkpoint if any
    # background-prefetch depth (0 disables): batches are pulled this many
    # steps ahead on a daemon thread (``flextree_tpu.data.prefetch``) while
    # the current step runs on device
    prefetch: int = 2
    # NaN/Inf guard: skip steps whose loss (or grad_norm, when the step
    # reports one) is non-finite; after max_bad_steps CONSECUTIVE skips,
    # rewind to the last verified checkpoint; after max_rewinds rewinds
    # raise TrainingDiverged.  A built step (make_train_step and its two
    # siblings) makes the verdict itself (metrics["applied"]) and refuses
    # a bad update on the device, so the FIRST bad update never lands
    # and fit reads step n's verdict while step n+1 runs: the accounting
    # (skips, streak, rewind) comes one step late and the device never
    # waits for the host.  A step that gives no verdict is guarded here,
    # on the host: its metrics are fetched before the next dispatch and a
    # bad update is dropped by keeping the old state.  nan_guard=False
    # turns fit's part off: nothing is fetched but the logged losses,
    # nothing is counted and nothing rewinds; a built step still refuses
    # its bad updates, silently.
    nan_guard: bool = True
    max_bad_steps: int = 3
    max_rewinds: int = 2


@dataclasses.dataclass
class Supervision:
    """Runtime-supervision wiring for :func:`fit` (every field optional —
    a ``None`` field leaves that feature off, so ``Supervision()`` is the
    no-op and the unsupervised loop is byte-for-byte the historical one).

    ``supervisor``: a ``runtime.Supervisor`` — this rank's heartbeat
    emitter; started/stopped by ``fit`` and fed each step's duration (the
    straggler EWMA peers classify against).  ``membership``: the liveness
    view — a ``runtime.MembershipView`` (or any callable returning
    ``{rank: state_str}``) polled every ``check_every`` steps.
    ``configured_world``: the membership roster size at start (defaults
    to the first poll's).  ``step_timeout_s``: the per-step watchdog
    deadline (``None`` reads ``FT_STEP_TIMEOUT``; unset = watchdog off);
    a timed-out step is retried up to ``max_step_retries`` times when no
    death is confirmed, then the :class:`~flextree_tpu.runtime.StepTimeout`
    propagates.  ``on_shrink(n_alive, plan)``: rebuild hook for the
    shrink path — return ``None`` to keep the current step, a
    ``(step_fn, mesh, state_specs)`` triple for the survivor world (the
    plan carries the replanned widths), or a 5-tuple additionally
    carrying ``(state_pack, state_unpack)`` converters for the survivor
    world — the ZeRO-1 re-shard path: sharded runs checkpoint in the
    CONSOLIDATED layout (``fit``'s ``state_pack``), so after a shrink the
    survivors restore the full CRC-verified checkpoint and re-partition
    it into their new owned shards (``state_unpack`` =
    ``parallel.zero.make_reshard_fn`` for the new world).  ``nbytes_hint``
    prices that replan.  ``preemption``: a ``runtime.PreemptionGuard``
    polled every iteration for the checkpoint-now fast path.
    ``background_saver``: a ``runtime.BackgroundSaver`` — periodic saves
    go through it instead of blocking the step path (the final save
    stays synchronous, after a drain).

    ``feedback``: a ``planner.feedback.FeedbackController`` — the
    closed-loop planner hook (ISSUE 12, docs/FEEDBACK.md).  Every
    ``every_k`` steps *with the flight recorder on* it probes the live
    wire, pairs measured against predicted comm cost, and — past the
    drift band — refits the calibration constants, invalidates stale
    plan-cache entries, and hands back a replanned step that ``fit``
    swaps through the SAME rebuild path the shrink handler uses (its
    ``on_replan`` hook returns the same 3-/5-tuple ``on_shrink`` does,
    minus the restore: the world didn't change, only the plan).  With no
    recorder installed the per-step cost is one ``None`` check — the
    identical check ``record_event`` makes — so telemetry-off runs pay
    nothing.

    ``coordination``: a ``runtime.CoordinationHandle`` — arms the
    coordinated elastic control plane (docs/COORDINATION.md) for
    multi-process groups.  Elastic decisions then stop being rank-local:
    confirmed deaths make the group's *coordinator* (lowest-rank healthy
    member) PROPOSE a shrink whose survivor set and replanned topology
    every rank applies from the committed control epoch; the feedback
    controller's drift refits propose group-wide replans the same way
    (arm it with the same handle); and arbiter lease resizes ride the
    identical commit path via ``TrainLeaseClient(coordination=...)``.
    The loop calls ``gate(step)`` once per iteration; a rank excluded
    from a committed epoch exits loudly with ``runtime.EpochFenced``
    rather than training on a stale plan.
    """

    supervisor: Any = None
    membership: Any = None
    configured_world: int | None = None
    check_every: int = 1
    step_timeout_s: float | None = None
    max_step_retries: int = 1
    on_shrink: Callable | None = None
    nbytes_hint: int = 4 << 20
    max_shrinks: int = 2
    preemption: Any = None
    background_saver: Any = None
    feedback: Any = None
    coordination: Any = None


@dataclasses.dataclass
class RunReport:
    """End-of-run accounting of everything the recovery machinery did."""

    anomalies: int = 0  # non-finite steps skipped
    skipped_steps: list = dataclasses.field(default_factory=list)
    rewinds: int = 0  # checkpoint rewinds after consecutive anomalies
    ckpt_fallbacks: int = 0  # corrupt checkpoints skipped during restore
    resumed_from: int = 0
    init_retries: int = 0  # bring-up attempts beyond the first (launch layer)
    # --- runtime supervision (all zero/empty when fit ran unsupervised) ---
    step_timeouts: int = 0  # watchdog deadlines hit (FT_STEP_TIMEOUT)
    step_retries: int = 0  # timed-out steps retried (no death confirmed)
    stragglers: list = dataclasses.field(default_factory=list)
    # --- closed-loop planner feedback (zero/empty without a controller) ---
    feedback_refits: int = 0  # drift-triggered constant refits
    feedback_replans: int = 0  # refits whose on_replan hook swapped the step
    feedback_refusals: int = 0  # refits refused (starved/degenerate samples)
    # --- arbiter chip leases (empty when fit ran without an arbiter) ---
    # one entry per applied grant change — {"step", "epoch", "chips",
    # "topo", "bitwise_resume"}: the checkpoint→rebuild→restore cycle's
    # in-run proof that the resize lost nothing (docs/ARBITER.md)
    lease_epochs: list = dataclasses.field(default_factory=list)
    # membership epochs: entry 0 is the starting world, one more per live
    # shrink — {"step", "alive", "configured", "topo", "dead"}
    membership_epochs: list = dataclasses.field(default_factory=list)
    # --- coordinated control plane (empty without a coordination handle) ---
    # one entry per APPLIED committed control epoch — {"step", "epoch",
    # "kind", "fingerprint"}: the per-rank audit the chaos floors compare
    # (same final epoch + fingerprint on every survivor, no double-applies)
    control_epochs: list = dataclasses.field(default_factory=list)
    preempted_at: int | None = None  # step the SIGTERM checkpoint ran at
    background_saves: int = 0  # off-step-path checkpoint writes
    # the ambient obs registry's snapshot (None when the run carried no
    # telemetry): run_report.json is then a VIEW over the same counters /
    # histograms the flight recorder's metrics export carries
    metrics: dict | None = None

    def to_payload(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        """The machine-readable form ``fit`` persists as run_report.json
        (recovery events as stable keys, so tooling can gate on them)."""
        return json.dumps(self.to_payload(), indent=2, sort_keys=True)


@dataclasses.dataclass
class FitResult:
    state: Any
    losses: list  # (step, loss) pairs at log points
    steps_run: int
    resumed_from: int
    report: RunReport = dataclasses.field(default_factory=RunReport)


def _metrics_finite(metrics) -> bool:
    """Host-side finiteness of the guard metrics (loss + grad norm)."""
    for key in ("loss", "grad_norm"):
        if key in metrics:
            v = float(np.asarray(jax.device_get(metrics[key])))
            if not math.isfinite(v):
                return False
    return True


def _own_buffers(packed, state):
    """``packed`` with every leaf that IS one of ``state``'s device arrays
    copied on the device: what a background writer may keep while the
    next step consumes (donates) the state itself."""
    from .train import copy_leaf

    live = {id(x) for x in jax.tree.leaves(state) if isinstance(x, jax.Array)}
    return jax.tree.map(
        lambda x: copy_leaf(x) if id(x) in live else x, packed
    )


def _consumed(state) -> bool:
    """True when a step has taken (donated) ``state``'s buffers."""
    return any(
        isinstance(x, jax.Array) and x.is_deleted()
        for x in jax.tree.leaves(state)
    )


def _apply_rebuild(rebuilt, cur_pack, cur_unpack):
    """Normalize a rebuild-hook result to the full 5-tuple swap.

    Both step-swap seams — ``Supervision.on_shrink`` (world shrank) and
    ``FeedbackConfig.on_replan`` (plan changed) — return either a
    ``(step_fn, mesh, specs)`` 3-tuple or the re-shard path's 5-tuple
    with the checkpoint-layout converters.  A 3-tuple keeps the current
    converters; one helper owns the dispatch so the two swap paths
    cannot diverge."""
    if len(rebuilt) == 5:
        return rebuilt
    step_fn, mesh, specs = rebuilt
    return step_fn, mesh, specs, cur_pack, cur_unpack


def _stamp_step(state: dict, step: int) -> dict:
    """A copy of ``state`` with ``state['step']`` set to ``step`` (keeps
    the step leaf the single source of truth when a step is skipped)."""
    import jax.numpy as jnp

    old = state["step"]
    new = dict(state)
    new["step"] = jnp.asarray(step, np.asarray(jax.device_get(old)).dtype)
    return new


def fit(
    state,
    step_fn: Callable,
    dataset,
    cfg: FitConfig = FitConfig(),
    *,
    mesh=None,
    state_specs=None,
    supervision: Supervision | None = None,
    arbiter: Any = None,
    state_pack: Callable | None = None,
    state_unpack: Callable | None = None,
) -> FitResult:
    """Run ``step_fn(state, tokens, targets) -> (state, metrics)`` for
    ``cfg.num_steps`` total steps over ``dataset`` (an ``LMDataset``).

    ``state['step']`` is the single source of truth for progress: batches
    are addressed by it, checkpoints are named by it, and resume reads it
    back.  Pass ``mesh``/``state_specs`` to restore sharded.

    ``state_pack``/``state_unpack`` (optional) convert the live state to
    and from its on-disk checkpoint layout: every save writes
    ``state_pack(state)`` and every restore returns
    ``state_unpack(loaded)``.  The ZeRO-1 sharded trainer wires
    ``parallel.zero.make_consolidate_fn``/``make_reshard_fn`` here, so
    its checkpoints are the replicated (world-size-independent) layout —
    ``state_specs`` then describes the PACKED layout, since that is what
    the restore reads.  A live shrink may swap both hooks via
    ``Supervision.on_shrink``'s 5-tuple return.

    ``supervision`` (optional) arms the runtime-supervision layer — step
    watchdog, heartbeat membership with live shrink-to-survivors,
    straggler accounting, preemption checkpointing; see
    :class:`Supervision`.  Without it the loop is the historical one.

    ``arbiter`` (optional) is this run's chip-lease handle — a
    :class:`~flextree_tpu.runtime.TrainLeaseClient` (or anything with the
    same ``poll(step)`` / ``ack(directive)`` / ``on_resize`` surface).
    When the pool arbiter moves chips (docs/ARBITER.md), the loop rides
    the preemption-checkpoint machinery in place: drain pending saves,
    checkpoint NOW, rebuild for the new chip count through the handle's
    ``on_resize`` hook (the same 3-/5-tuple swap ``on_shrink`` uses),
    restore, verify the restored packed state is BITWISE the one just
    saved, and ack the lease epoch — only then may the arbiter hand the
    revoked chips to serving.  Each applied change is recorded in
    ``RunReport.lease_epochs``.
    """
    report = RunReport()
    sup = supervision
    # mutable current-epoch execution context: live shrink swaps these
    cur_step_fn, cur_mesh, cur_specs = step_fn, mesh, state_specs
    cur_pack, cur_unpack = state_pack, state_unpack

    def _fallback(bad_path, exc):
        report.ckpt_fallbacks += 1

    def _restore():
        loaded = restore_train_state(
            cfg.ckpt_dir, mesh=cur_mesh, specs=cur_specs, on_fallback=_fallback
        )
        return cur_unpack(loaded) if cur_unpack is not None else loaded

    def _packed(s):
        return cur_pack(s) if cur_pack is not None else s

    resumed_from = 0
    if cfg.resume and cfg.ckpt_dir and latest_checkpoint(cfg.ckpt_dir):
        state = _restore()
        resumed_from = int(np.asarray(jax.device_get(state["step"])))
        report.resumed_from = resumed_from
        log.info("resumed from step %d (%s)", resumed_from, cfg.ckpt_dir)

    losses: list = []
    start = int(np.asarray(jax.device_get(state["step"])))
    t0 = time.perf_counter()
    step = start
    bad_streak = 0

    def _batches(from_step):
        if cfg.prefetch and from_step < cfg.num_steps and hasattr(dataset, "iter_from"):
            from ..data import prefetch as _prefetch

            return _prefetch(dataset.iter_from(from_step), size=cfg.prefetch)
        return None

    batches = _batches(start)

    # a self-guarded step whose verdict the host has not read yet, as
    # (step index, metrics): the step in flight.  Depth one.
    pending = None

    def _log_loss(done, metrics):
        if cfg.log_every and (done % cfg.log_every == 0 or done == cfg.num_steps):
            loss = float(metrics["loss"])
            losses.append((done, loss))
            rate = (done - start) / (time.perf_counter() - t0)
            log.info("step %d loss %.4f (%.1f steps/s)", done, loss, rate)

    def _back_to_checkpoint():
        """``state``, ``step`` and the batch stream at the newest
        checkpoint that verifies; a step in flight is dropped with the
        state it ran from."""
        nonlocal state, step, batches, pending
        if sup is not None:
            # never race an in-flight background save's rotation
            # with the restore (the saver forbids two writers)
            _drained_saves(timeout=None)
        pending = None
        state = _restore()
        step = int(np.asarray(jax.device_get(state["step"])))
        batches = _batches(step)

    def _bad_step(at) -> bool:
        """The accounting of the non-finite step ``at``.  True when it
        rewound (:func:`_back_to_checkpoint`)."""
        nonlocal bad_streak
        report.anomalies += 1
        report.skipped_steps.append(at)
        bad_streak += 1
        record_event("nan_skip", step=at, streak=bad_streak)
        log.warning(
            "step %d: non-finite loss/grad (%d consecutive) — update skipped",
            at, bad_streak,
        )
        if bad_streak < cfg.max_bad_steps:
            return False
        if not (cfg.ckpt_dir and latest_checkpoint(cfg.ckpt_dir)):
            raise TrainingDiverged(
                f"{bad_streak} consecutive non-finite steps at step "
                f"{at} and no checkpoint to rewind to"
            )
        if report.rewinds >= cfg.max_rewinds:
            raise TrainingDiverged(
                f"still diverging after {report.rewinds} rewinds "
                f"(step {at})"
            )
        dump_current("nan_rewind", step=at)  # pre-rewind context
        _back_to_checkpoint()
        report.rewinds += 1
        bad_streak = 0
        record_event("nan_rewind", step=step)
        log.warning("rewound to checkpointed step %d", step)
        return True

    def _settle(entry, lagged) -> str:
        """Read a self-guarded step's verdict and do that step's
        accounting: "applied", "skipped" (the step refused its update and
        advanced past the batch) or "rewound".  ``lagged``: 1 when a later
        step is already on the device, 0 when the host waits for this one."""
        nonlocal bad_streak
        at, metrics = entry
        with span("ft.loop.guard_fetch", lagged=lagged, steps=1):
            applied = not cfg.nan_guard or bool(
                np.asarray(jax.device_get(metrics["applied"]))
            )
        if not applied:
            return "rewound" if _bad_step(at) else "skipped"
        bad_streak = 0
        _log_loss(at + 1, metrics)
        return "applied"

    def _drain() -> str:
        """Settle the step in flight, if there is one: before anything
        that needs the state (a save, a shrink, a resize) or ends the run."""
        nonlocal pending
        if pending is None:
            return "applied"
        entry, pending = pending, None
        return _settle(entry, 0)

    def _lease_resize(at_step, directive):
        """Apply an arbiter grant change: checkpoint now, rebuild for the
        new chip count, restore, prove the resume bitwise, ack.

        The cycle is the SIGTERM-preemption fast path composed with the
        shrink path's rebuild — but triggered by the lease ledger and
        resumed IN-PROCESS (the world changed size, the process did not).
        The bitwise proof compares the packed (world-independent) state
        on both sides of the cycle: what the preempt checkpoint saved
        must be exactly what the resized world runs from — zero steps
        lost, by construction and by check.
        """
        nonlocal state, step, batches
        nonlocal cur_step_fn, cur_mesh, cur_specs, cur_pack, cur_unpack
        from ..planner.choose import replan_for_survivors

        _drain()
        n = directive.n
        if n < 1:
            raise ValueError(
                f"lease epoch {directive.epoch} grants training zero chips "
                "— the arbiter's min_train_chips floor should forbid this"
            )
        configured = max(getattr(arbiter, "configured", None) or n, n)
        nbytes = getattr(arbiter, "nbytes_hint", 4 << 20)
        plan = replan_for_survivors(n, nbytes, configured=configured)
        if getattr(directive, "topo", None):
            # a coordinated resize broadcasts the coordinator's plan —
            # every rank must run IT, not its own chooser's winner
            from ..runtime.coordination import apply_spec_override

            plan = apply_spec_override(plan, directive.topo, n)
        log.warning(
            "lease resize at step %d: epoch %d grants chips %s (%d); "
            "replanned topo %s",
            at_step, directive.epoch, list(directive.chips), n,
            plan.to_ft_topo(),
        )
        if sup is not None and sup.background_saver is not None:
            # the restore below must never race an in-flight save's
            # rotation (the background saver forbids two writers)
            sup.background_saver.drain(None)
        old_pack = cur_pack
        packed = _packed(state)
        pre_host = jax.device_get(packed)
        if cfg.ckpt_dir:
            # checkpoint NOW — the preemption fast path's save, so the
            # revoked chips carry no un-persisted work when they leave
            save_train_state(cfg.ckpt_dir, packed, max_to_keep=cfg.max_to_keep)
        on_resize = getattr(arbiter, "on_resize", None)
        rebuilt = (
            on_resize(directive.chips, plan) if on_resize is not None else None
        )
        if rebuilt is not None:
            (cur_step_fn, cur_mesh, cur_specs,
             cur_pack, cur_unpack) = _apply_rebuild(
                 rebuilt, cur_pack, cur_unpack)
        if cfg.ckpt_dir and latest_checkpoint(cfg.ckpt_dir):
            state = _restore()
            step = int(np.asarray(jax.device_get(state["step"])))
        elif old_pack is not None or cur_unpack is not None:
            # no checkpoint dir: convert the live state through the
            # packed layout, exactly what the shrink path does
            state = (
                cur_unpack(pre_host) if cur_unpack is not None else pre_host
            )
        # the bitwise-resume proof: the new world's packed view of the
        # restored state vs the packed state the checkpoint saved
        post_host = jax.device_get(_packed(state))
        pre_leaves = jax.tree.leaves(pre_host)
        post_leaves = jax.tree.leaves(post_host)
        bitwise = len(pre_leaves) == len(post_leaves) and all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(pre_leaves, post_leaves)
        )
        if not bitwise:
            log.error(
                "lease resize at step %d is NOT a bitwise resume — the "
                "packed state changed across the preempt/restore cycle",
                at_step,
            )
        report.lease_epochs.append(
            {
                "step": at_step,
                "epoch": directive.epoch,
                "chips": list(directive.chips),
                "topo": plan.to_ft_topo(),
                "bitwise_resume": bitwise,
            }
        )
        record_event(
            "lease_resize", step=at_step, epoch=directive.epoch,
            chips=list(directive.chips), n=n, topo=plan.to_ft_topo(),
            bitwise_resume=bitwise,
        )
        arbiter.ack(directive)
        batches = _batches(step)

    # ---- runtime supervision wiring (sup=None leaves the historical loop)
    watchdog = None
    step_timeout = None
    world: int | None = None  # current epoch's alive count
    known_dead: set = set()
    pending_dead: set = set()  # observed deaths awaiting a group decision
    flagged_stragglers: set = set()
    shrinks = 0
    timeout_retries = 0
    feedback_dead = False  # a tick raised: feedback disarmed for the run
    coordn = sup.coordination if sup is not None else None
    if sup is not None:
        from ..runtime.watchdog import StepTimeout, StepWatchdog, step_timeout_from_env

        step_timeout = (
            sup.step_timeout_s
            if sup.step_timeout_s is not None
            else step_timeout_from_env()
        )
        if step_timeout is not None:
            watchdog = StepWatchdog()
        if sup.supervisor is not None:
            sup.supervisor.start()

        def _poll_membership() -> dict | None:
            """Normalize the liveness source to ``{rank: state_str}``."""
            m = sup.membership
            if m is None:
                return None
            if hasattr(m, "poll"):
                return {r: s.state for r, s in m.poll().items()}
            return dict(m())

        def _drained_saves(timeout=30.0) -> bool:
            """True when no background save is pending/in flight.  A False
            return means a slow save still owns the directory — the caller
            must NOT start a second writer (or a restore) against it."""
            if sup.background_saver is None:
                return True
            ok = sup.background_saver.drain(timeout)
            if not ok:
                log.warning(
                    "background save still in flight after %.0fs drain; "
                    "skipping the conflicting synchronous writer", timeout,
                )
            return ok

        def _feed_supervisor(dur_s):
            if sup.supervisor is not None:
                sup.supervisor.record_step(step, dur_s)
            reg = get_registry()
            if reg is not None:
                reg.histogram("train.step_ms").observe(dur_s * 1e3)

        def _materialized_step(st, tk, tg):
            # JAX dispatch is async: a jitted step returns unmaterialized
            # futures in milliseconds even when a dead peer has wedged the
            # collective — the block would then happen OUTSIDE the deadline
            # at the metrics fetch.  Materialize inside the watchdogged
            # call so FT_STEP_TIMEOUT covers device execution, not just
            # dispatch.  (The guard then reads this step's verdict at
            # once, not one step late: nothing is left in flight.)
            return jax.block_until_ready(cur_step_fn(st, tk, tg))

        def _shrink(at_step, new_dead, *, alive=None, plan=None):
            """Live shrink-to-survivors: drain, rebuild, restore, resume.

            ``alive``/``plan`` are the coordinated-broadcast overrides: a
            committed group shrink carries the coordinator's survivor
            count and replanned topology so every rank applies THE SAME
            decision instead of each computing its own."""
            nonlocal state, world, shrinks, step, batches
            nonlocal cur_step_fn, cur_mesh, cur_specs, cur_pack, cur_unpack
            from ..planner.choose import replan_for_survivors

            _drain()
            prev_world = world
            n_alive = (
                int(alive) if alive is not None
                else max(1, world - len(new_dead))
            )
            if plan is None:
                plan = replan_for_survivors(
                    n_alive, sup.nbytes_hint, configured=prev_world
                )
            log.warning(
                "membership shrink at step %d: ranks %s dead, %d/%d alive; "
                "replanned topo %s",
                at_step, new_dead, n_alive, prev_world, plan.to_ft_topo(),
            )
            # drain in-flight work: pending background saves first (the old
            # epoch's prefetcher is dropped below when batches reseek)
            _drained_saves(timeout=None)  # restore must never race a save
            old_pack = cur_pack  # the OLD world's consolidator, pre-swap
            rebuilt = (
                sup.on_shrink(n_alive, plan) if sup.on_shrink is not None else None
            )
            if rebuilt is not None:
                # 5-tuple = the re-shard path: the survivor world gets its
                # own checkpoint-layout converters (ZeRO state re-carved
                # from the consolidated checkpoint)
                (cur_step_fn, cur_mesh, cur_specs,
                 cur_pack, cur_unpack) = _apply_rebuild(
                     rebuilt, cur_pack, cur_unpack)
            if cfg.ckpt_dir and latest_checkpoint(cfg.ckpt_dir):
                state = _restore()
                step = int(np.asarray(jax.device_get(state["step"])))
                log.warning(
                    "restored checkpointed step %d for the survivor world", step
                )
            elif old_pack is not None or cur_unpack is not None:
                # no checkpoint yet, but the state layout is
                # world-size-dependent (ZeRO shards): convert the LIVE
                # state through the packed (world-independent) layout —
                # the old world consolidates, the new world re-shards.
                # The old mesh's devices are still alive in-process, so
                # the old consolidator can run one last time.
                packed = old_pack(state) if old_pack is not None else state
                # host round-trip: the packed state lives on the OLD
                # mesh's devices; the survivor world's converter places
                # it fresh (exactly what a checkpoint restore would do)
                packed = jax.device_get(packed)
                state = (
                    cur_unpack(packed) if cur_unpack is not None else packed
                )
                log.warning(
                    "no checkpoint to restore: re-sharded the live state "
                    "for the survivor world"
                )
            world = n_alive
            shrinks += 1
            report.membership_epochs.append(
                {
                    "step": at_step,
                    "alive": n_alive,
                    "configured": prev_world,
                    "topo": plan.to_ft_topo(),
                    "dead": list(new_dead),
                }
            )
            record_event(
                "shrink", step=at_step, dead=list(new_dead), alive=n_alive,
                configured=prev_world, topo=plan.to_ft_topo(),
            )
            # the forensic record of WHAT the survivor saw around the
            # death: ring context + the shrink decision, guaranteed —
            # with the handshake phase attached when the shrink was a
            # group decision (which phase the fault interrupted)
            dump_current(
                "peer_shrink", step=at_step, dead=list(new_dead),
                **({"coord_phase": coordn.phase} if coordn is not None else {}),
            )
            batches = _batches(step)

        def _membership_tick(at_step) -> str:
            """One liveness poll: record stragglers, shrink on new deaths.
            Returns "shrunk" | "ok" | "unknown" (no membership source)."""
            nonlocal world
            statuses = _poll_membership()
            if statuses is None:
                return "unknown"
            if world is None:
                world = sup.configured_world or len(statuses)
            for r, st in sorted(statuses.items()):
                if st == "straggler" and r not in flagged_stragglers:
                    flagged_stragglers.add(r)
                    report.stragglers.append({"rank": r, "step": at_step})
                    record_event("straggler", peer=r, step=at_step)
                    log.warning(
                        "rank %d classified straggler at step %d", r, at_step
                    )
            new_dead = sorted(
                r
                for r, st in statuses.items()
                if st == "dead" and r not in known_dead
            )
            if not new_dead:
                return "ok"
            known_dead.update(new_dead)
            if shrinks >= sup.max_shrinks:
                raise ShrinkExhausted(
                    f"ranks {new_dead} died at step {at_step} after "
                    f"{shrinks} shrink(s); max_shrinks={sup.max_shrinks}"
                )
            if coordn is not None:
                # coordinated group: a local death observation is not
                # authority.  Park it; the coordination gate below turns
                # it into a propose→ack→commit group decision (this rank
                # proposes only while it IS the coordinator), and the
                # shrink applies when the committed epoch arrives.
                pending_dead.update(new_dead)
                return "ok"
            _shrink(at_step, new_dead)
            return "shrunk"

        def _apply_committed(at_step, decision):
            """Apply one committed group decision (the coordination gate's
            output) and advance this rank's fence.  Every branch applies
            EXACTLY what the commit carries — the local machinery only
            executes, it never re-decides."""
            nonlocal cur_step_fn, cur_mesh, cur_specs, cur_pack, cur_unpack
            payload = decision.payload
            if decision.kind == "shrink":
                if shrinks >= sup.max_shrinks:
                    raise ShrinkExhausted(
                        f"committed shrink epoch {decision.epoch} at step "
                        f"{at_step} after {shrinks} shrink(s); "
                        f"max_shrinks={sup.max_shrinks}"
                    )
                from ..runtime.coordination import committed_shrink_plan

                dead = [int(r) for r in payload.get("dead", ())]
                known_dead.update(dead)
                pending_dead.difference_update(dead)
                plan = committed_shrink_plan(payload, sup.nbytes_hint)
                _shrink(
                    at_step, dead, alive=int(payload["alive"]), plan=plan
                )
            elif decision.kind == "replan":
                if sup.feedback is not None:
                    dec = sup.feedback.apply_committed(payload, step=at_step)
                    report.feedback_refits += 1
                    if dec.rebuilt is not None:
                        (cur_step_fn, cur_mesh, cur_specs,
                         cur_pack, cur_unpack) = _apply_rebuild(
                             dec.rebuilt, cur_pack, cur_unpack)
                        report.feedback_replans += 1
                    record_event(
                        "feedback_replan", step=at_step,
                        topo=dec.plan.to_ft_topo(),
                        invalidated=dec.invalidated,
                        swapped=dec.rebuilt is not None,
                        control_epoch=decision.epoch,
                    )
                else:
                    # a committed replan this rank CANNOT execute: the
                    # peers are swapping comm plans and we would keep the
                    # old one — the exact split-brain the protocol
                    # exists to prevent.  Loud exit, never silent
                    # divergence (the fencing ethos).
                    from ..runtime.coordination import ProtocolViolation

                    raise ProtocolViolation(
                        f"committed replan epoch {decision.epoch} but this "
                        "rank has no feedback controller to apply it — arm "
                        "Supervision.feedback with a coordinated "
                        "FeedbackController on every rank, or on none"
                    )
            elif decision.kind == "resize":
                if arbiter is not None:
                    from ..runtime.leases import ResizeDirective

                    _lease_resize(
                        at_step,
                        ResizeDirective(
                            epoch=int(payload["lease_epoch"]),
                            chips=tuple(payload.get("chips", ())),
                            reason=str(payload.get("reason", "")),
                            control_epoch=decision.epoch,
                            topo=payload.get("topo"),
                        ),
                    )
                else:
                    from ..runtime.coordination import ProtocolViolation

                    raise ProtocolViolation(
                        f"committed resize epoch {decision.epoch} but this "
                        "rank has no lease client — pass the coordinated "
                        "TrainLeaseClient as fit(arbiter=...) on every rank"
                    )
            else:
                from ..runtime.coordination import ProtocolViolation

                raise ProtocolViolation(
                    f"committed decision kind {decision.kind!r} (epoch "
                    f"{decision.epoch}) is unknown to this rank — version "
                    "skew across the group; refusing to train on a "
                    "possibly-stale plan"
                )
            coordn.mark_applied(decision)
            report.control_epochs.append(
                {
                    "step": at_step,
                    "epoch": decision.epoch,
                    "kind": decision.kind,
                    "fingerprint": decision.fingerprint,
                }
            )

        def _coordination_gate(at_step) -> bool:
            """One control-plane tick: apply at most one committed
            decision, else propose parked deaths (coordinator only).
            True when a decision was applied (the loop re-enters: the
            world/plan just changed under it).  Apply-before-propose +
            the handle's refusal to propose over an unapplied commit
            keep a parked death from double-proposing while its own
            shrink is mid-delivery."""
            decision = coordn.gate(at_step)
            if decision is not None:
                _apply_committed(at_step, decision)
                return True
            if (
                pending_dead
                and shrinks < sup.max_shrinks
                and coordn.is_coordinator
            ):
                from ..planner.choose import replan_for_survivors

                n_alive = max(1, (world or 1) - len(pending_dead))
                plan = replan_for_survivors(
                    n_alive, sup.nbytes_hint, configured=world
                )
                # None while another decision is mid-handshake — the
                # parked deaths re-propose on a later tick
                proposed = coordn.propose(
                    "shrink",
                    {
                        "dead": sorted(pending_dead),
                        "alive": n_alive,
                        "configured": world,
                        "topo": plan.to_ft_topo(),
                    },
                )
                if proposed is not None:
                    # the ledger now carries the survivor set (a dying
                    # proposer's successor re-proposes from THERE): the
                    # local parking is done; the apply path re-derives
                    # the dead list from the committed payload
                    pending_dead.clear()
            return False

        # epoch 0: the starting world
        if sup.membership is not None or sup.configured_world:
            statuses0 = _poll_membership() or {}
            world = sup.configured_world or (len(statuses0) or None)
            if world:
                report.membership_epochs.append(
                    {
                        "step": start,
                        "alive": world,
                        "configured": world,
                        "topo": None,
                        "dead": [],
                    }
                )

    # id pairs fit_start with fit_end in the merged timeline (their step
    # fields legitimately differ: the run starts at `start`, ends later)
    record_event(
        "fit_start", id=start, step=start, num_steps=cfg.num_steps,
        resumed_from=resumed_from,
    )
    try:
        while step < cfg.num_steps or pending is not None:
            if step >= cfg.num_steps:
                # the last step is still in flight: read its verdict (a
                # rewind re-enters the loop) before the run may end
                _drain()
                continue
            with span("ft.loop.step", step=step):
                if sup is not None or arbiter is not None:
                    # supervisor, coordination and lease ticks
                    with span("ft.loop.bookkeeping"):
                        if sup is not None:
                            if sup.preemption is not None and sup.preemption.preempted:
                                # the checkpoint-now fast path: at most one step lost
                                _drain()
                                if cfg.ckpt_dir and _drained_saves():
                                    # drain timed out -> the in-flight background save
                                    # IS a recent checkpoint; racing its rotation with
                                    # a second writer would be worse than one lost step
                                    save_train_state(
                                        cfg.ckpt_dir, _packed(state),
                                        max_to_keep=cfg.max_to_keep,
                                    )
                                report.preempted_at = step
                                record_event("preempt", step=step)
                                dump_current("preempted", step=step)
                                log.warning(
                                    "preemption: checkpointed at step %d, exiting", step
                                )
                                break
                            if (
                                sup.membership is not None
                                and step % max(1, sup.check_every) == 0
                                and _membership_tick(step) == "shrunk"
                            ):
                                continue
                            if coordn is not None and _coordination_gate(step):
                                # a committed group decision just applied (shrink /
                                # replan / resize): re-enter the loop on the new world
                                continue
                        if arbiter is not None:
                            # the arbiter moved chips: apply the grant before the next
                            # step (checkpoint → rebuild → restore → ack), then loop —
                            # the resized world re-reads its batch stream from `step`
                            directive = arbiter.poll(step)
                            if directive is not None:
                                _lease_resize(step, directive)
                                continue
                with span("ft.loop.data_wait"):
                    tokens, targets = (
                        next(batches) if batches is not None else dataset.batch_at(step)
                    )
                record_event("step_start", step=step)
                materialized = False
                if sup is None:
                    with span("ft.loop.dispatch"):
                        new_state, metrics = cur_step_fn(
                            state, tokens, targets
                        )
                else:
                    # probe-free feedback (docs/FEEDBACK.md): when the
                    # controller wants per-step spans (probe_free=True with
                    # the recorder on — recorder off costs one None check),
                    # capture the compile-time bucket plan while a fresh step
                    # traces, MATERIALIZE the step (async dispatch would time
                    # the enqueue, not the execution), and feed the host-timed
                    # duration to the span clock below.
                    fb = sup.feedback
                    fb_spans = (
                        fb is not None
                        and not feedback_dead
                        and hasattr(fb, "wants_step_spans")
                        and fb.wants_step_spans()
                    )
                    fb_cap = None
                    materialized = watchdog is not None or fb_spans
                    t_step0 = time.perf_counter()
                    try:
                        with contextlib.ExitStack() as _stack:
                            _stack.enter_context(
                                step_scope(on_duration=_feed_supervisor)
                            )
                            _stack.enter_context(span("ft.loop.dispatch"))
                            if fb_spans:
                                fb_cap = _stack.enter_context(plan_capture())
                            new_state, metrics = (
                                watchdog.run(
                                    _materialized_step, state, tokens, targets,
                                    timeout_s=step_timeout, step=step,
                                )
                                if watchdog is not None
                                else (
                                    _materialized_step(state, tokens, targets)
                                    if fb_spans
                                    else cur_step_fn(state, tokens, targets)
                                )
                            )
                    except StepTimeout as e:
                        report.step_timeouts += 1
                        log.warning("%s", e)
                        # the watchdog recorded the timeout event; the dump is
                        # fit's to guarantee — this is a failure path even when
                        # the retry below saves the run
                        dump_current("watchdog_timeout", step=step)
                        batches = _batches(step)  # reseek: the batch was consumed
                        if _membership_tick(step) == "shrunk":
                            timeout_retries = 0
                            continue
                        if timeout_retries < sup.max_step_retries:
                            if _consumed(state):
                                # the abandoned step owned (donated) its
                                # state: the retry starts from the last
                                # checkpoint, or there is nothing to retry
                                if not (cfg.ckpt_dir
                                        and latest_checkpoint(cfg.ckpt_dir)):
                                    raise
                                _back_to_checkpoint()
                            timeout_retries += 1
                            report.step_retries += 1
                            log.warning(
                                "retrying step %d after timeout (%d/%d)",
                                step, timeout_retries, sup.max_step_retries,
                            )
                            continue
                        raise
                    timeout_retries = 0
                    if fb_spans:
                        try:
                            if fb_cap:
                                fb.set_step_plan(fb_cap)
                            fb.observe_step(
                                step, time.perf_counter() - t_step0
                            )
                        except Exception as e:  # noqa: BLE001 — obs contract
                            # span bookkeeping must never kill the run: same
                            # disarm semantics as a raising tick below
                            feedback_dead = True
                            record_event(
                                "feedback_error", step=step,
                                reason=f"{type(e).__name__}: {e}"[:300],
                            )
                            log.exception(
                                "per-step span clock failed at step %d; "
                                "planner feedback disarmed for the run", step,
                            )
                record_event("step_end", step=step)
                ckpt_due = bool(
                    cfg.ckpt_dir and cfg.ckpt_every
                    and (step + 1) % cfg.ckpt_every == 0
                )
                self_guarded = "applied" in metrics
                if self_guarded:
                    # the step made its own verdict, refused a bad update
                    # on the device and took (donated) its state: the
                    # result IS the state, and the host reads the step
                    # BEFORE this one while this one runs
                    state = new_state
                    waiting, pending = pending, (step, metrics)
                    step += 1
                    if (waiting is not None
                            and _settle(waiting, 1) == "rewound"):
                        continue
                    # read this step's own verdict now where the step was
                    # materialised on purpose (watchdog, span clock), where
                    # a feedback controller ticks between steps, and where
                    # a checkpoint is due: never a refused step's state
                    if (
                        materialized or ckpt_due
                        or (sup is not None and sup.feedback is not None)
                    ) and _drain() != "applied":
                        continue
                else:
                    if _drain() == "rewound":  # a swapped-out step's last
                        continue
                    # where the host waits for the device: the guard
                    # fetches the step's loss
                    with span("ft.loop.guard_fetch", lagged=0, steps=1):
                        finite = not cfg.nan_guard or _metrics_finite(metrics)
                    if not finite:
                        if not _bad_step(step):
                            # skip: discard the poisoned update, advance
                            # past the batch
                            step += 1
                            state = _stamp_step(state, step)
                        continue
                with span("ft.loop.bookkeeping"):
                    if not self_guarded:
                        state = new_state
                        bad_streak = 0
                        step += 1
                    if (sup is not None and sup.feedback is not None
                            and not feedback_dead and step < cfg.num_steps):
                        # closed-loop planner feedback (docs/FEEDBACK.md): with no
                        # recorder installed maybe_tick is ONE None check — the
                        # same check record_event makes — so telemetry-off runs
                        # pay nothing; on the every_k cadence it probes the wire,
                        # and past the drift band hands back a refitted replan.
                        # Gated on step < num_steps: a tick after the FINAL step
                        # would spend a probe round (and possibly a refit + full
                        # step rebuild) on a plan no step will ever run.
                        try:
                            decision = sup.feedback.maybe_tick(step)
                            if decision is not None and getattr(
                                decision, "rotation", False
                            ):
                                # a probe-free plan-rotation swap: a bucket-size
                                # variant of the same plan (bitwise-invariant),
                                # applied through the replan swap path but NOT a
                                # refit — the controller recorded feedback_rotate
                                if decision.rebuilt is not None:
                                    (cur_step_fn, cur_mesh, cur_specs,
                                     cur_pack, cur_unpack) = _apply_rebuild(
                                         decision.rebuilt, cur_pack, cur_unpack)
                            elif decision is not None:
                                report.feedback_refits += 1
                                if decision.rebuilt is not None:
                                    # the same swap the shrink path runs, minus the
                                    # restore: the world didn't change, only the plan
                                    (cur_step_fn, cur_mesh, cur_specs,
                                     cur_pack, cur_unpack) = _apply_rebuild(
                                         decision.rebuilt, cur_pack, cur_unpack)
                                    report.feedback_replans += 1
                                record_event(
                                    "feedback_replan",
                                    step=step,
                                    topo=decision.plan.to_ft_topo(),
                                    invalidated=decision.invalidated,
                                    swapped=decision.rebuilt is not None,
                                )
                                log.warning(
                                    "feedback replan at step %d: topo %s, %d cache "
                                    "entr%s invalidated%s",
                                    step, decision.plan.to_ft_topo(),
                                    decision.invalidated,
                                    "y" if decision.invalidated == 1 else "ies",
                                    "" if decision.rebuilt is not None
                                    else " (no rebuild hook: plan recorded only)",
                                )
                        except Exception as e:
                            # telemetry never kills the run (the obs contract:
                            # spill errors drop, predicted_error spans skip) — an
                            # unwritable calibration path, a failed probe compile,
                            # or a broken rebuild hook disarms feedback for the
                            # rest of the run and training continues on the
                            # current plan.  A half-applied swap is impossible:
                            # _apply_rebuild returns before any of the five
                            # loop-state names is reassigned.
                            feedback_dead = True
                            # the reason must land in the FLIGHT record, not only
                            # the process log: a later SIGKILL takes the log with
                            # it while the spilled record survives (the same
                            # post-mortem parity feedback_refused already has)
                            record_event(
                                "feedback_error", step=step,
                                reason=f"{type(e).__name__}: {e}"[:300],
                            )
                            log.exception(
                                "feedback tick failed at step %d; planner feedback "
                                "disarmed for the rest of the run", step,
                            )
                    if not self_guarded:
                        _log_loss(step, metrics)
                    if ckpt_due:
                        if sup is not None and sup.background_saver is not None:
                            # off-step-path save: the step loop never blocks on
                            # serialization + fsync, so ckpt_every can be small
                            # (the pack conversion, when set, runs on-path — it
                            # is the consolidation collective, not the fsync).
                            # The writer keeps buffers of its own: the next
                            # step consumes the state's
                            sup.background_saver.submit(
                                _own_buffers(_packed(state), state)
                            )
                        else:
                            save_train_state(
                                cfg.ckpt_dir, _packed(state), max_to_keep=cfg.max_to_keep
                            )
        # the preemption fast path already saved this exact state — a second
        # serialize+fsync would double the cost inside the grace window
        if cfg.ckpt_dir and step > start and report.preempted_at is None:
            if sup is None or _drained_saves():
                save_train_state(
                    cfg.ckpt_dir, _packed(state), max_to_keep=cfg.max_to_keep
                )
    finally:
        if sup is not None:
            if sup.feedback is not None:
                # refusals happen inside the controller (a refused refit
                # returns no decision); mirror its count into the report
                report.feedback_refusals = getattr(
                    sup.feedback, "refusals", 0
                )
            if sup.background_saver is not None:
                sup.background_saver.drain()
                report.background_saves = sup.background_saver.saves
            if sup.supervisor is not None:
                sup.supervisor.stop()
            if watchdog is not None:
                watchdog.close()
        # mirror the recovery accounting into the ambient registry (when
        # telemetry is on) and embed its snapshot: run_report.json becomes
        # a view over the same counters the obs metrics export carries
        reg = get_registry()
        if reg is not None:
            reg.counter("train.steps").inc(max(step - start, 0))
            reg.counter("train.anomalies").inc(report.anomalies)
            reg.counter("train.rewinds").inc(report.rewinds)
            reg.counter("train.step_timeouts").inc(report.step_timeouts)
            reg.counter("train.shrinks").inc(
                max(len(report.membership_epochs) - 1, 0)
            )
            reg.counter("train.background_saves").inc(report.background_saves)
            reg.counter("train.feedback_refits").inc(report.feedback_refits)
            reg.counter("train.feedback_replans").inc(report.feedback_replans)
            reg.counter("train.feedback_refusals").inc(report.feedback_refusals)
            reg.counter("train.lease_resizes").inc(len(report.lease_epochs))
            reg.counter("train.control_applies").inc(
                len(report.control_epochs)
            )
            reg.gauge("train.last_step").set(step)
            report.metrics = reg.snapshot()
        record_event("fit_end", id=start, step=step)
        # the accounting matters MOST for runs that die (a TrainingDiverged
        # postmortem needs the anomaly/rewind trail) — write it regardless
        if cfg.ckpt_dir:
            os.makedirs(cfg.ckpt_dir, exist_ok=True)
            with open(os.path.join(cfg.ckpt_dir, "run_report.json"), "w") as f:
                f.write(report.to_json())
    return FitResult(state, losses, step - start, resumed_from, report)
