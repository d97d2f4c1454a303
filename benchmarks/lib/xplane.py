"""From a profiler trace to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
nothing but JAX) into a small neutral form; everything else works on that
form, so the tests can feed it a recorded trace kept as JSON.

What a TPU trace holds (looked at by hand on the v5e, PERF.md): one plane
per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per
executed HLO operation, NAMED BY ITS WHOLE HLO TEXT (``%fusion.609 =
s32[1,16,4,128]{...} fusion(...), kind=kLoop, calls=...``), with start and
duration in nanoseconds on one clock with the host plane; the core runs
them one at a time.  ``XLA Modules`` has one event per run of a compiled
program (``jit_device_step(<id>)``), ``Steps`` one per step, and ``Async
XLA Ops`` the in-flight spans of asynchronous copies and collectives,
which overlap the operation line and are NOT device busy time: what the
core pays for them is the ``-start`` and ``-done`` operations on its own
line.  The host plane holds the spans the harness put round its own calls
(``TraceAnnotation`` names starting ``bench_``) on the line ``python3``.

Busy time is the UNION of the operation intervals (nested events, such as
a loop and its body, are not counted twice); an operation's own time is
its duration less its children's.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

__all__ = [
    "Event", "Line", "Plane", "Trace", "load", "find_xplane", "to_json",
    "from_json", "device_planes", "op_events", "module_events",
    "host_spans", "window_of", "union_seconds", "busy_seconds",
    "self_times", "group_key", "top_ops", "idle_gaps", "matching_seconds",
    "module_seconds", "SPAN_PREFIX",
]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench_"
WINDOW_SPAN = "bench_window"
# ``%name.12 = (f32[4,2048]{...}`` -> name, first result shape
_INSTR_RE = re.compile(r"^%?([^\s=]+?)(?:\.\d+)? = \(*([a-z]+\d*\[[\d,]*\])")
_KEEP_STR = 160  # characters of a string stat kept (HLO text can be long)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def text(self) -> str:
        """The name and every string stat, for matching by pattern."""
        return " ".join(
            [self.name] + [v for v in self.stats.values() if isinstance(v, str)]
        )


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list

    def line(self, name: str):
        for ln in self.lines:
            if ln.name == name:
                return ln
        return None


@dataclasses.dataclass
class Trace:
    planes: list


# ------------------------------------------------------------------ loading


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stat_value(v):
    if isinstance(v, (bool, int, float)):
        return v
    if isinstance(v, bytes):
        v = v.decode("utf-8", "replace")
    return str(v)[:_KEEP_STR]


def load(path: str) -> Trace:
    """Device planes whole; of host planes only the harness's own spans
    (a host plane holds every Python call otherwise)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for ln in plane.lines:
            events = []
            for ev in ln.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                stats = {}
                if device:
                    for k, v in ev.stats:
                        stats[str(k)] = _stat_value(v)
                events.append(Event(
                    ev.name, float(ev.start_ns), float(ev.duration_ns), stats
                ))
            if events:
                lines.append(Line(ln.name, events))
        if lines:
            planes.append(Plane(plane.name, lines))
    return Trace(planes)


def to_json(trace: Trace) -> str:
    return json.dumps(dataclasses.asdict(trace))


def from_json(text: str) -> Trace:
    raw = json.loads(text)
    return Trace([
        Plane(p["name"], [
            Line(ln["name"], [Event(**e) for e in ln["events"]])
            for ln in p["lines"]
        ])
        for p in raw["planes"]
    ])


# --------------------------------------------------------------- selection


def device_planes(trace: Trace) -> list:
    """Planes of chips that ran operations, in device order."""
    planes = [
        p for p in trace.planes
        if re.fullmatch(r"/device:TPU:\d+", p.name) and p.line(OPS_LINE)
    ]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def op_events(plane: Plane) -> list:
    """The plane's operations by start (an enclosing event before what it
    holds); sorted once and kept, a serving trace has millions."""
    cached = plane.__dict__.get("_ops_by_start")
    if cached is None:
        ln = plane.line(OPS_LINE)
        cached = sorted(
            ln.events, key=lambda e: (e.start_ns, -e.dur_ns)
        ) if ln else []
        plane.__dict__["_ops_by_start"] = cached
    return cached


def module_events(plane: Plane) -> list:
    ln = plane.line(MODULES_LINE)
    return sorted(ln.events, key=lambda e: e.start_ns) if ln else []


def host_spans(trace: Trace) -> list:
    """The harness's own spans, from every host line, by start."""
    spans = [
        e for p in trace.planes if not p.name.startswith("/device:")
        for ln in p.lines for e in ln.events
        if e.name.startswith(SPAN_PREFIX)
    ]
    return sorted(spans, key=lambda e: e.start_ns)


def window_of(trace: Trace) -> tuple:
    """(start_ns, end_ns) of the traced window: the harness's
    ``bench_window`` span where the trace has it, else the span from the
    first device operation to the end of the last."""
    for span in host_spans(trace):
        if span.name == WINDOW_SPAN:
            return span.start_ns, span.end_ns
    ops = [e for p in device_planes(trace) for e in op_events(p)]
    if not ops:
        raise ValueError("trace holds neither a window span nor a device op")
    return min(e.start_ns for e in ops), max(e.end_ns for e in ops)


# -------------------------------------------------------------- reductions


def _clipped(events, window):
    lo, hi = window
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            yield a, b


def _merged(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union_seconds(events, window) -> float:
    return sum(b - a for a, b in _merged(_clipped(events, window))) / 1e9


def busy_seconds(trace: Trace, window=None) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    window = window or window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return 0.0
    return sum(
        union_seconds(op_events(p), window) for p in planes
    ) / len(planes)


def self_times(events) -> list:
    """``(event, own_ns)`` for events sorted by start: an event's own
    time is its duration less that of the events nested in it."""
    out, stack = [], []  # stack of [event, child_ns]
    for e in events:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            done, child = stack.pop()
            out.append((done, max(done.dur_ns - child, 0.0)))
        if stack:
            stack[-1][1] += min(e.dur_ns, stack[-1][0].end_ns - e.start_ns)
        stack.append([e, 0.0])
    while stack:
        done, child = stack.pop()
        out.append((done, max(done.dur_ns - child, 0.0)))
    return out


def group_key(event: Event) -> str:
    """What operations are grouped by in a breakdown: the instruction's
    name without its instance number, and its (first) result shape, as
    ``fusion f32[4,2048,8192]``; the bare name where the event is not HLO
    text."""
    m = _INSTR_RE.match(event.name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return re.sub(r"\.\d+$", "", event.name.lstrip("%"))


def _in_window(events, window):
    lo, hi = window
    return [e for e in events if e.end_ns > lo and e.start_ns < hi]


def top_ops(trace: Trace, window=None, n: int = 10) -> list:
    """``[[group, seconds], ...]``: own time by group, averaged over the
    chips, the ``n`` largest."""
    window = window or window_of(trace)
    planes = device_planes(trace)
    totals: dict = {}
    for p in planes:
        for e, own in self_times(_in_window(op_events(p), window)):
            key = group_key(e)
            totals[key] = totals.get(key, 0.0) + own
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(len(planes), 1)] for k, v in ranked]


def matching_seconds(trace: Trace, pattern: str, window=None) -> float:
    """Own seconds of the operations whose name or string stats match
    ``pattern``, averaged over the chips."""
    window = window or window_of(trace)
    rx = re.compile(pattern)
    planes = device_planes(trace)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        for e, own in self_times(_in_window(op_events(p), window)):
            if rx.search(e.text):
                total += own
    return total / 1e9 / len(planes)


def module_seconds(trace: Trace, pattern, window=None) -> tuple:
    """(seconds, runs) of the compiled programs whose name matches
    ``pattern``, on chip 0, inside the window."""
    window = window or window_of(trace)
    rx = re.compile(pattern)
    planes = device_planes(trace)
    if not planes:
        return 0.0, 0
    runs = [
        e for e in _in_window(module_events(planes[0]), window)
        if rx.search(e.name)
    ]
    return union_seconds(runs, window), len(runs)


def idle_gaps(trace: Trace, window=None, n: int = 10,
              small_ns: float = 2000.0) -> list:
    """``[[host span, seconds], ...]``: chip 0's idle time inside the
    window, by the harness span that covers each gap's middle.  Gaps under
    ``small_ns`` between operations are one entry of their own; gaps no
    span covers are ``outside_spans``."""
    window = window or window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    busy = _merged(_clipped(op_events(planes[0]), window))
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
    spans = [s for s in host_spans(trace) if s.name != WINDOW_SPAN]
    starts = [s.start_ns for s in spans]
    totals: dict = {}
    for a, b in gaps:
        if b <= a:
            continue
        if b - a < small_ns:
            key = "gaps_under_2_us_between_operations"
        else:
            mid = (a + b) / 2
            # the innermost (latest-starting) span that covers the middle;
            # the harness nests its spans a few deep at most
            key = "outside_spans"
            i = bisect.bisect_right(starts, mid)
            for s in reversed(spans[max(i - 8, 0) : i]):
                if mid < s.end_ns:
                    key = s.name[len(SPAN_PREFIX):]
                    break
        totals[key] = totals.get(key, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
