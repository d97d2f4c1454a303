"""What every cell shares: finding the cell's files by name, the device
gate, the traced window, the per-layer readers and the result line.

The harness is driven by data.  ``BENCHMARK.json`` names cells and
metrics; a cell ``<config>.<traffic>`` is ``configs/<config>.json`` under
``traffic/<traffic>.json``; the traffic file's ``kind`` names the driver
module ``benchmarks/lib/<kind>.py``; a per-layer metric ``<name>`` is
``metrics/<name>.json``, whose ``reader`` (``module:function``) is looked
up in ``benchmarks/readers/``.  Adding any of them is adding files and
one entry, never editing one.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import sys
import time

from . import traffic as traffic_lib

__all__ = [
    "ROOT", "REPO", "Cell", "Run", "ReaderContext", "say", "load_benchmark",
    "load_cell", "apply_rehearsal", "device_info", "require_device",
    "peak_memory_bytes", "CompileCounter", "traced", "span",
    "per_layer_metrics", "result_line", "seed32", "window_seconds",
    "say_setup",
]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/
REPO = os.path.dirname(ROOT)


def say(msg: str) -> None:
    """An earlier line of the output (the last line is the result)."""
    print(msg, flush=True)


def seed32(seed: int) -> int:
    """``--seed`` folded into what a 32-bit PRNG key takes (the driver's
    seeds are a little over 2**31)."""
    return int(seed) % (2**32)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


@dataclasses.dataclass
class Run:
    """What a driver hands back.  ``end_to_end``: metric name -> value.
    ``obs``: whatever the driver observed, for the per-layer readers.
    ``trace_dir``: where the traced window's profile is (traced runs)."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    obs: dict
    setup_s: float
    trace_dir: str | None = None


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(repo: str = REPO) -> dict:
    return _read_json(os.path.join(repo, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None, root: str = ROOT) -> Cell:
    bench = bench or load_benchmark(os.path.dirname(root))
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in bench['workloads']]})"
        )
    row = rows[0]
    cfg_row = next(c for c in bench["configs"] if c["name"] == row["config"])
    return Cell(
        name=name,
        chips=int(row["chips"]),
        config_name=row["config"],
        traffic_name=row["traffic"],
        config=_read_json(os.path.join(os.path.dirname(root), cfg_row["file"])),
        traffic=traffic_lib.load(row["traffic"], root),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def apply_rehearsal(cell: Cell, root: str = ROOT) -> None:
    """Shrink a cell to the tiny sizes of ``rehearsal/configs/<config>``
    and ``rehearsal/traffic/<traffic>`` (CPU only)."""
    tiny = os.path.join(root, "rehearsal")
    cell.config.update(
        _read_json(os.path.join(tiny, "configs", f"{cell.config_name}.json")))
    cell.traffic.update(
        _read_json(os.path.join(tiny, "traffic", f"{cell.traffic_name}.json")))


# ------------------------------------------------------------------ device


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_device(cell: Cell, rehearsal: bool) -> dict:
    """The device as JAX reports it; exit 2 with no result unless it is a
    TPU with the chips the cell asks for (or the CPU, in a rehearsal)."""
    info = device_info()
    say(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if rehearsal:
        if info["platform"] != "cpu":
            raise SystemExit("--rehearsal is for the CPU (JAX_PLATFORMS=cpu)")
    elif info["platform"] != "tpu":
        print("benchmark: JAX found no TPU; a speed from any other device "
              "is not a result (use --rehearsal to try the control flow on "
              "the CPU).", file=sys.stderr)
        raise SystemExit(2)
    if info["count"] < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, JAX found "
              f"{info['count']}.", file=sys.stderr)
        raise SystemExit(2)
    return info


def peak_memory_bytes() -> int:
    """The high-water mark of the fullest chip (0 where the backend does
    not report one, as on the CPU)."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    return int(max(peaks, default=0))


class CompileCounter:
    """Counts the programs JAX had to compile or fetch from its cache,
    by the time each was asked for (``jax.monitoring``): any inside the
    window means a shape was not warmed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.stamps: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **_):
        if name == self.EVENT:
            self.stamps.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.stamps if t0 <= t <= t1)


# ----------------------------------------------------------------- tracing


def span(name: str):
    """A host span in the profiler's own trace (nothing when no trace is
    running), named ``bench_<name>``."""
    import jax

    return jax.profiler.TraceAnnotation("bench_" + name)


@contextlib.contextmanager
def traced(trace_dir: str | None):
    """Profile the enclosed window to ``trace_dir`` (no Python call
    tracing: the host plane would outgrow the device's), wrapped in the
    ``bench_window`` span.  With ``None`` nothing is traced."""
    if trace_dir is None:
        yield
        return
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # the harness's own spans, no more
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with span("window"):
            yield
    finally:
        jax.profiler.stop_trace()


def window_seconds(cell: Cell, seconds: float, trace_dir) -> float:
    """How long the window is: ``--seconds``, or in a traced run the
    traffic file's shorter ``trace_seconds`` (traces are large)."""
    if trace_dir is None:
        return seconds
    return min(seconds, float(cell.traffic["trace_seconds"]))


def say_setup(setup: dict, total: float, extra: str = "") -> None:
    say("set-up breakdown (s): "
        + str({k: round(v, 2) for k, v in setup.items()})
        + f" total {total:.2f}{extra}")


# ----------------------------------------------------------------- readers


@dataclasses.dataclass
class ReaderContext:
    cell: Cell
    run: Run
    device: dict
    trace: object  # lib.xplane.Trace or None
    window: tuple | None  # (start_ns, end_ns) of the traced window
    # lib.peaks.Peaks of the device; None in a rehearsal (the CPU has no
    # row, and a reader that needs a peak then has nothing to read)
    peaks: object = None

    @property
    def obs(self) -> dict:
        return self.run.obs


def _reader(spec: str):
    module, _, fn = spec.partition(":")
    return getattr(importlib.import_module(f"benchmarks.readers.{module}"), fn)


def per_layer_metrics(ctx: ReaderContext, root: str = ROOT) -> dict:
    """Each per-layer metric of the cell, read by its own reader.  A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for entry in ctx.cell.per_layer:
        meta = _read_json(os.path.join(root, "metrics", f"{entry['name']}.json"))
        value = _reader(meta["reader"])(ctx, **meta.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def result_line(run: Run, metrics: dict, device: dict, breakdown=None,
                rehearsal: bool = False) -> str:
    line = {
        "correct": bool(run.correct),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if rehearsal:
        line["rehearsal"] = True
    return json.dumps(line)
