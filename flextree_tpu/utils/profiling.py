"""Profiling / tracing: named comm spans, span ledgers, step timing.

Host-side unless noted:

- :func:`comm_span` names each bucket's collectives (a
  ``jax.named_scope``, like the per-stage ``ft_rs_stage*`` /
  ``ft_ag_stage*`` scopes inside :mod:`flextree_tpu.parallel.allreduce`);
  at trace time it feeds every active :class:`SpanLedger` *and* the
  ambient flight recorder (:mod:`flextree_tpu.obs`), carrying plan
  provenance when the caller supplies it — the always-on telemetry
  layer's view of the comm plan.
- :func:`step_scope` times one host-level training step for the
  straggler classifier.

Host spans with a start, an end and a parent are
:func:`flextree_tpu.obs.span`; a profile is ``jax.profiler.start_trace``
(the benchmark's ``--trace 1``).

(The reference-lineage note — how the C++ ``SHOW_TIME`` / ``FT_DEBUG``
compile-time knobs map onto these runtime facilities — lives in
``docs/OBSERVABILITY.md``.)
"""

from __future__ import annotations

import contextlib
import os
import re
import time

from .logging import get_logger

__all__ = [
    "comm_span",
    "span_bytes",
    "SpanLedger",
    "span_ledger",
    "plan_capture",
    "Ewma",
    "step_scope",
    "debug_dump_schedule",
    "debug_enabled",
]


class Ewma:
    """Exponentially-weighted moving average — the per-rank step-duration
    signal the runtime supervision layer classifies stragglers from.

    Each rank folds its step wall-times into an EWMA (``alpha`` weights
    the newest sample) and publishes it in its heartbeat
    (``runtime.supervisor.Supervisor``); the coordinator's
    ``MembershipView`` flags ranks whose EWMA is an outlier against the
    peer median.  An EWMA rather than the last sample so one noisy step
    (GC pause, page fault) doesn't flap the classification.
    """

    def __init__(self, alpha: float = 0.2):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = alpha
        self.value: float | None = None
        self.count = 0

    def update(self, sample: float) -> float:
        self.value = (
            sample
            if self.value is None
            else self.alpha * sample + (1.0 - self.alpha) * self.value
        )
        self.count += 1
        return self.value


@contextlib.contextmanager
def step_scope(ewma: "Ewma | None" = None, on_duration=None):
    """Time one host-level training step; feed the duration to an
    :class:`Ewma` and/or ``on_duration(seconds)`` (e.g.
    ``Supervisor.record_step`` partial) on exit.  The host-side sibling
    of :func:`comm_span`: ``comm_span`` names device spans inside jitted
    code, ``step_scope`` accounts the wall-clock of the whole dispatched
    step — the quantity the straggler classifier compares across ranks.
    """
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        if ewma is not None:
            ewma.update(dt)
        if on_duration is not None:
            on_duration(dt)


class SpanLedger:
    """Trace-time accounting of :func:`comm_span` scopes.

    While active (``with span_ledger() as ledger``), every ``comm_span``
    entered — including inside a ``jax.jit`` trace — records its name
    into the ledger.  Bucket-sync span names carry their payload bytes as
    a ``_{nbytes}B`` suffix (``ft_bucket*`` / ``ft_overlap_bucket*``), so
    the ledger can attribute *planned wire bytes per bucket* for a traced
    step: which buckets actually fired and what they carried.  Host-side
    bookkeeping only — nothing enters the traced program.
    """

    def __init__(self):
        self.spans: list[str] = []

    def record(self, name: str) -> None:
        self.spans.append(name)

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(self.spans)

    def total_bytes(self, prefix: str = "") -> int:
        """Sum of the ``_{n}B`` suffixes of recorded spans with ``prefix``."""
        total = 0
        for name in self.spans:
            if not name.startswith(prefix):
                continue
            m = _BYTES_SUFFIX.search(name)
            if m:
                total += int(m.group(1))
        return total


#: The byte-attribution suffix contract: the LAST ``_``-separated token
#: must be exactly ``{digits}B``.  Anchored so a name whose final token
#: merely *ends* in ``B`` (``..._fooB``, ``..._0xB``) never miscounts.
_BYTES_SUFFIX = re.compile(r"_(\d+)B$")


def span_bytes(name: str) -> int | None:
    """The ``_{n}B`` payload suffix of a span name, or None."""
    m = _BYTES_SUFFIX.search(name)
    return int(m.group(1)) if m else None


_ACTIVE_LEDGERS: list[SpanLedger] = []

#: active plan captures: every ``comm_span`` entered with a provenance
#: payload appends ``(name, provenance)`` to each — the trace-time hook
#: the per-step span clock (``obs/stepclock.py``) uses to learn WHICH
#: buckets a freshly-compiled step will run, so per-step measured spans
#: can be keyed to the compile-time provenance without re-deriving it
_ACTIVE_PLAN_CAPTURES: list[list] = []


@contextlib.contextmanager
def span_ledger():
    """Collect every ``comm_span`` entered in this block into a
    :class:`SpanLedger` (trace-time; reentrant — nested ledgers all
    record)."""
    ledger = SpanLedger()
    _ACTIVE_LEDGERS.append(ledger)
    try:
        yield ledger
    finally:
        _ACTIVE_LEDGERS.remove(ledger)


@contextlib.contextmanager
def plan_capture():
    """Collect every provenance-carrying ``comm_span`` entered in this
    block as ``(name, provenance_dict)`` pairs — the compile-time bucket
    plan of whatever traced under it.  Like :func:`span_ledger` this is
    trace-time bookkeeping: under ``jit`` the spans fire while tracing,
    so wrapping a step's FIRST (compiling) call yields its full bucket
    plan and wrapping an already-compiled call yields nothing.  The list
    is shared module state (not thread-local) deliberately: the watchdog
    runs steps on a worker thread and the capture must still see them."""
    cap: list = []
    _ACTIVE_PLAN_CAPTURES.append(cap)
    try:
        yield cap
    finally:
        _ACTIVE_PLAN_CAPTURES.remove(cap)


@contextlib.contextmanager
def comm_span(name: str, provenance: dict | None = None):
    """Named communication span: a ``jax.named_scope``, so the span shows up
    as a named range over its collectives in profiler traces, exactly like
    the per-stage ``ft_rs_stage*`` scopes.

    This is the per-*bucket* observability layer the fused gradient sync
    uses (``parallel.bucketing``): each bucket's collectives trace under an
    ``ft_bucket{i}_{axis}_{k}leaves_{bytes}B`` range, so a profile (or a
    run_report built from one) can attribute comm time per bucket and
    separate comm from compute per step.

    Every span also feeds the active :class:`SpanLedger`\\ s and the
    ambient flight recorder (:func:`flextree_tpu.obs.record_event`, a
    no-op when none is installed): ``provenance`` — the comm plan behind
    the span (``obs.provenance.bucket_provenance``) — upgrades the
    recorded event from a bare ``collective`` to a ``bucket_planned``
    carrying widths/codec/sharded and the predicted cost breakdown.
    """
    import jax

    for ledger in _ACTIVE_LEDGERS:
        ledger.record(name)
    from ..obs import record_event

    if provenance is not None:
        for cap in _ACTIVE_PLAN_CAPTURES:
            cap.append((name, provenance))
        record_event("bucket_planned", name=name, **provenance)
    else:
        record_event("collective", name=name, bytes=span_bytes(name))
    with jax.named_scope(name):
        yield


def debug_enabled() -> bool:
    """True when the ``FT_DEBUG`` env var is set to a truthy value."""
    return os.environ.get("FT_DEBUG", "").strip().lower() not in (
        "",
        "0",
        "false",
        "no",
        "off",
    )


def debug_dump_schedule(topo, rank: int | None = None, force: bool = False) -> str | None:
    """Dump the per-rank schedule when ``FT_DEBUG`` is on (or ``force``).

    ``topo`` is a ``flextree_tpu.schedule.stages.Topology``.  Returns the
    dump string (also logged) or None when debug is off — mirrors the
    reference's ``FT_DEBUG``-gated ``print_ops`` topology dumps
    (``mpi_mod.hpp:105-131``, call sites under ``#ifdef FT_DEBUG``).
    """
    if not (force or debug_enabled()):
        return None
    from ..schedule.plan import format_plan

    ranks = range(topo.num_nodes) if rank is None else (rank,)
    out = "\n".join(format_plan(topo, r) for r in ranks)
    get_logger("flextree.debug").info("\n%s", out)
    return out
