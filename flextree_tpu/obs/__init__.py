"""Unified telemetry: flight recorder, metrics registry, trace timeline.

The rest of the package emits *fragments* of observability — ``comm_span``
named scopes, ``RunReport`` accounting, serving timestamps, bench JSON
artifacts.  This package is the single place they meet:

- :mod:`~flextree_tpu.obs.recorder` — a bounded, lock-cheap per-rank
  **flight recorder**: a ring buffer of structured events (step
  boundaries, bucket plans with provenance, heartbeats, lease verdicts,
  shrinks, serving request lifecycle) that spills to an append-only JSONL
  file and writes a **guaranteed dump** on every failure path, so a chaos
  scenario leaves a forensic record instead of only a pass/fail bit;
- :mod:`~flextree_tpu.obs.metrics` — a **metrics registry** of counters /
  gauges / fixed-bucket histograms with bounded memory and a stable JSON
  snapshot, replacing ad-hoc stamp lists;
- :mod:`~flextree_tpu.obs.timeline` — the **cross-rank merger**: fuse
  per-rank event files into one Chrome-trace/Perfetto-loadable JSON
  (ranks as tracks, requests and buckets as flows, every comm event
  carrying its plan provenance and predicted cost).

Instrumentation sites call :func:`record_event` for a point and
:func:`span` for a stretch of time (a ``jax.profiler.TraceAnnotation``
always, so a running profile shows it beside the device operations, plus
one ``span`` event with its start, end and parent when a recorder is
installed).  ``record_event`` is a module-global read
plus a ``None`` check when no recorder is installed, so the library pays
nothing until a run opts in (``with flight_recorder(dir, rank):`` or the
trainer's ``--obs-dir``/``--flight-recorder`` flags).  See
``docs/OBSERVABILITY.md`` for the event schema and how to open a merged
timeline in Perfetto.
"""

from .metrics import (
    Counter,
    FrozenWindow,
    Gauge,
    Histogram,
    MetricsRegistry,
    WindowedHistogram,
    load_window,
    merged_window_percentile,
    prometheus_exposition,
)
from .provenance import bucket_provenance, topo_spec
from .recorder import (
    FlightRecorder,
    current_recorder,
    dump_current,
    flight_recorder,
    get_registry,
    install_signal_dump,
    record_event,
    span,
)
from .stepclock import StepPlan, StepSample, StepSpanClock, plan_from_capture
from .timeline import (
    ResidualSample,
    merge_dir,
    merge_events,
    read_dir,
    read_events,
    residual_group_key,
    residual_pairs,
    residual_table,
    validate_trace,
    write_trace,
)

__all__ = [
    "bucket_provenance",
    "topo_spec",
    "Counter",
    "Gauge",
    "Histogram",
    "WindowedHistogram",
    "FrozenWindow",
    "load_window",
    "merged_window_percentile",
    "prometheus_exposition",
    "MetricsRegistry",
    "StepSpanClock",
    "StepPlan",
    "StepSample",
    "plan_from_capture",
    "FlightRecorder",
    "flight_recorder",
    "current_recorder",
    "record_event",
    "span",
    "dump_current",
    "get_registry",
    "install_signal_dump",
    "merge_dir",
    "merge_events",
    "read_dir",
    "read_events",
    "ResidualSample",
    "residual_group_key",
    "residual_pairs",
    "residual_table",
    "validate_trace",
    "write_trace",
]
