"""Readers of what the program itself put into the profile: its host
spans (``flextree_tpu.obs.span``: ``ft.<layer>.<phase>``, the ids and
counts each carries as its stats) and the ``ft_<phase>`` named scopes on
the path of each device operation.

``lib.xplane.load`` keeps only the harness's ``bench_`` spans of the host
planes, so the program's are read here from the run's ``.xplane.pb``
(``ctx.run.trace_dir``, still there while the readers run), once a run.
A trace handed over in the neutral form with ``ft.`` events on a host
plane (the tests' hand-made and recorded ones) is read as it is.  All
times are on the profile's one clock, the device operations' and the
harness window's too.

Where the scope path sits on the v5e (looked at by hand, PERF.md §6,
PR 26): an ``XLA Ops`` event carries no string of its own but its HLO
text; XLA's ``op_name`` (``jit(device_step)/transpose(jvp(ft_mlp))/
dot_general:``) is the stat ``tf_op`` of the event's METADATA record in
the plane, which ``jax.profiler.ProfileData`` does not hand out.  So the
metadata table is read from the file's protobuf wire format directly
(``op_paths``: three nested messages, no dependency), and joined to the
events by their HLO text.  Operations the compiler put in itself (layout
copies, slices) have no ``tf_op``.

Every reader takes the span or scope names as arguments, so a metric is a
file of names.  Where the program has no such span or scope (a parent
commit), or the trace no device plane (a rehearsal), a reader returns
None and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import os
import re
import statistics

from benchmarks.lib import xplane

PROGRAM_PREFIX = "ft."  # the program's spans (the harness's are bench_)
PATH_STAT = "tf_op"  # the stat that holds XLA's op_name path
_SCOPE = re.compile(r"\bft_[A-Za-z0-9_]+")
OUTSIDE = None  # the key of idle time that no program span covers


# ------------------------------------------------------------ the spans


def _spans_of_file(path: str) -> list:
    import jax

    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    spans.append(xplane.Event(
                        ev.name, float(ev.start_ns), float(ev.duration_ns),
                        {str(k): v for k, v in ev.stats},
                    ))
    return spans


def _once(ctx, key: str, make):
    """``make()``, kept on the context: several metrics read one table."""
    if key not in ctx.__dict__:
        ctx.__dict__[key] = make()
    return ctx.__dict__[key]


def _xplane_file(ctx):
    """The run's ``.xplane.pb``, or None (no traced run, or a trace that
    was handed over in the neutral form)."""
    trace_dir = ctx.run.trace_dir
    if trace_dir and os.path.isdir(trace_dir):
        return xplane.find_xplane(trace_dir)
    return None


def program_spans(ctx) -> list:
    """The program's ``ft.`` spans by start (an enclosing span before
    what it holds), read once a run."""

    def read():
        spans = [
            e for p in (ctx.trace.planes if ctx.trace else [])
            if not p.name.startswith("/device:")
            for ln in p.lines for e in ln.events
            if e.name.startswith(PROGRAM_PREFIX)
        ]
        path = _xplane_file(ctx)
        if not spans and path:
            spans = _spans_of_file(path)
        return sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns))

    return _once(ctx, "_program_spans", read)


def _named(ctx, name: str) -> list:
    """Spans of that name that opened inside the window."""
    lo, hi = ctx.window
    return [
        s for s in program_spans(ctx)
        if s.name == name and lo <= s.start_ns < hi
    ]


def innermost_segments(spans) -> list:
    """``[(start, end, name), ...]``, disjoint and in order: each stretch
    of time under the innermost span open in it.  ``spans`` by start, an
    enclosing span first; a span that outlasts the one it opened in is cut
    where that one ends."""
    segs, stack = [], []  # stack of [end, name]
    cursor = 0.0

    def emit(end, name):
        nonlocal cursor
        if end > cursor:
            segs.append((cursor, end, name))
        cursor = max(cursor, end)

    for s in spans:
        while stack and stack[-1][0] <= s.start_ns:
            emit(*stack.pop())
        if stack:
            emit(s.start_ns, stack[-1][1])
        cursor = max(cursor, s.start_ns)
        stack.append([min(s.end_ns, stack[-1][0]) if stack else s.end_ns,
                      s.name])
    while stack:
        emit(*stack.pop())
    return segs


# ------------------------------------------- the operations' scope paths


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in an xplane file")
            value, i = buf[i : i + size], i + size
        yield key >> 3, value


def _map_value(entry):
    """The value of a ``map<int64, message>`` entry."""
    return next(v for num, v in _fields(entry) if num == 2)


def op_paths(path: str) -> dict:
    """``{HLO text: op_name path}`` of the device planes' operations, from
    the metadata tables of an ``.xplane.pb`` (XSpace.planes=1; XPlane
    name=2, event_metadata=4, stat_metadata=5; XEventMetadata name=2,
    stats=5; XStat metadata_id=1, str_value=5, ref_value=7, the latter the
    id of a stat record whose name is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    paths: dict = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v) for n, v in fields if n == 2), b"")
        if not name.startswith(b"/device:"):
            continue
        stat_names = {}
        for n, entry in fields:
            if n == 5:
                meta = dict(_fields(_map_value(entry)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for n, entry in fields:
            if n != 4:
                continue
            text, found = "", None
            for m, value in _fields(_map_value(entry)):
                if m == 2:
                    text = bytes(value).decode("utf-8", "replace")
                elif m == 5:
                    st = dict(_fields(value))
                    if stat_names.get(st.get(1)) == PATH_STAT:
                        found = (
                            bytes(st[5]).decode("utf-8", "replace")
                            if 5 in st else stat_names.get(st.get(7), "")
                        )
            if found is not None:
                paths[text] = found
    return paths


# ------------------------------------------------------------- idle time


def chip0_idle_gaps(trace, window) -> list:
    """``[(start, end), ...]``: where chip 0 ran no operation, inside the
    window (the gaps ``xplane.idle_gaps`` hands to the harness's spans)."""
    ops = xplane.op_events(xplane.device_planes(trace)[0])
    busy = xplane._merged(xplane._clipped(ops, window))
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    return [
        (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]


def idle_by_span(segments, gaps) -> dict:
    """Nanoseconds of the gaps under each span name; a gap that crosses
    several segments is split among them by overlap, and what no segment
    covers goes to ``OUTSIDE``."""
    starts = [s[0] for s in segments]
    totals: dict = {}
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segments) and segments[i][0] < b:
            lo, hi, name = segments[i]
            part = min(hi, b) - max(lo, a)
            if part > 0:
                totals[name] = totals.get(name, 0.0) + part
                covered += part
            i += 1
        if b - a > covered:
            totals[OUTSIDE] = totals.get(OUTSIDE, 0.0) + (b - a) - covered
    return totals


def _idle_totals(ctx):
    return _once(ctx, "_idle_by_span", lambda: idle_by_span(
        innermost_segments(program_spans(ctx)),
        chip0_idle_gaps(ctx.trace, ctx.window),
    ))


def _has_device(ctx) -> bool:
    return ctx.trace is not None and bool(xplane.device_planes(ctx.trace))


# -------------------------------------------------------------- readers


def span_ms_p50(ctx, name: str, within: str | None = None,
                having: str | None = None):
    """Median duration, in ms, of the spans ``name`` that opened in the
    window.  With ``within`` and ``having``: only those inside a
    ``within`` span that also holds a ``having`` span (admissions, of the
    rounds that admitted)."""
    spans = _named(ctx, name)
    if within is not None:
        marks = sorted(s.start_ns for s in _named(ctx, having))
        kept = [
            (w.start_ns, w.end_ns) for w in _named(ctx, within)
            if bisect.bisect_left(marks, w.start_ns)
            < bisect.bisect_left(marks, w.end_ns)
        ]
        spans = [
            s for s in spans
            if any(a <= s.start_ns < b for a, b in kept)
        ]
    if not spans:
        return None
    return statistics.median(s.dur_ns for s in spans) / 1e6


def idle_ms_per(ctx, names: list, per: str):
    """Chip 0's idle time in the window that falls under the spans
    ``names`` (under the innermost span open at each instant; a gap is
    split by overlap among the spans it crosses), over the count of
    ``per`` spans, in ms.  ``names: []``: under no program span."""
    if not _has_device(ctx):
        return None
    count = len(_named(ctx, per))
    if count == 0:
        return None
    totals = _idle_totals(ctx)
    keys = names or [OUTSIDE]
    return sum(totals.get(k, 0.0) for k in keys) / count / 1e6


def count_ratio_p50(ctx, name: str, num: str, den: str):
    """Median over the spans ``name`` of the window of one of their
    counts over another, in %."""
    ratios = [
        100.0 * float(s.stats[num]) / float(s.stats[den])
        for s in _named(ctx, name)
        if num in s.stats and float(s.stats.get(den) or 0) > 0
    ]
    return statistics.median(ratios) if ratios else None


def _scoped_own_times(ctx) -> list:
    """``[(own_ns, scopes on the operation's path), ...]`` of every
    operation in the window, all chips; None where no operation names a
    scope at all (the program has none, or the profile no path).  The
    path is the event's own ``tf_op`` stat where it has one (the tests'
    traces), else the file's metadata record of the same HLO text."""

    def read():
        lo, hi = ctx.window
        path = _xplane_file(ctx)
        in_file = op_paths(path) if path else {}
        rows = []
        for plane in xplane.device_planes(ctx.trace):
            ops = [e for e in xplane.op_events(plane)
                   if e.end_ns > lo and e.start_ns < hi]
            for e, own in xplane.self_times(ops):
                op_name = e.stats.get(PATH_STAT) or in_file.get(e.name, "")
                rows.append((own, frozenset(_SCOPE.findall(op_name))))
        return rows if any(scopes for _, scopes in rows) else None

    return _once(ctx, "_scoped_own_times", read)


def scope_share(ctx, scopes: list):
    """Own time of the device operations whose path (``op_name``: forward
    ``.../ft_mlp/...`` or transposed ``transpose(jvp(ft_mlp))``) holds one
    of ``scopes``, over busy time, in %.  ``scopes: []``: operations under
    no ``ft_`` scope at all."""
    if not _has_device(ctx):
        return None
    rows = _scoped_own_times(ctx)
    busy = xplane.busy_seconds(ctx.trace, ctx.window)
    if rows is None or busy <= 0:
        return None
    wanted = set(scopes)
    own = sum(
        ns for ns, found in rows
        if (found & wanted if wanted else not found)
    )
    return 100.0 * own / 1e9 / len(xplane.device_planes(ctx.trace)) / busy
