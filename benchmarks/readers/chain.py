"""The gap between two decode rounds, read as a causal chain and never as
an overlap of two clocks.

The rule of this file, which its tests hold: **every number is a
difference of two stamps of the same plane; a host stamp is never
subtracted from a device stamp or laid over one**, but for the two
``trace.*`` readings of :func:`crossing_min_ms`, which exist to show what
the profile's clocks did.  (``spans.idle_by_span`` lays chip 0's idle gaps
over the host's spans; the profiler joins the two planes' clocks once a
session, to about a millisecond, and the pieces of a 3 ms gap move
between spans with that offset.)

Between two rounds the device waits for one chain: round n's pick program
ends -> its ids reach the host and ``ft.engine.decode_fetch`` closes -> the
host samples, retires, keeps its books, leaves ``step()``, the caller runs,
``step()`` is entered, the batcher admits and builds its arrays ->
``ft.engine.decode_dispatch`` opens -> round n+1's decode program starts.
Its length is a difference of two DEVICE stamps; the host's part of it a
difference of two HOST stamps; what is left is the two crossings together
(the ids' way back and the program's way out), a difference of two
differences in which the offset between the clocks cancels.

The two sides are joined without a stamp.  The host's rounds are the
``ft.engine.decode_dispatch`` spans that opened in the window (a host
span, on the host's clock), each with the ``decode_fetch`` and ``round``
span of the same ``round`` id.  The device's are the runs of the decode
program on chip 0's ``XLA Modules`` line, in order, the whole profile's
(to clip them by the window would lay a host stamp over them), each with
the first run of the pick program after it.  Equal counts join by order.
A dispatch comes before its program, so a round that an edge of the
profile cut is the device's FIRST (its dispatch opened before the profile
did) or the host's LAST (its program never ran inside it): a difference of
one is dropped there.  Anything else returns None: the reader never
guesses.  A program without the ``round`` id on its dispatch (a parent
commit) has nothing to read, and every reader returns None.

A PAIR is two rounds n, n+1 whose ids follow each other, with no
``ft.engine.prefill`` span between fetch n and dispatch n+1 and no other
program between pick n and decode n+1 on the device.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import statistics

from benchmarks.lib import xplane
from benchmarks.readers import spans

DISPATCH = "ft.engine.decode_dispatch"
FETCH = "ft.engine.decode_fetch"
ROUND = "ft.engine.round"
PREFILL = "ft.engine.prefill"
ROUND_ID = "round"  # the id that joins a round's host spans
DECODE_PROGRAM = re.compile(r"paged_decode")
PICK_PROGRAM = re.compile(r"greedy_ids")


@dataclasses.dataclass(frozen=True)
class HostRound:
    """One round's stamps on the host's clock (ns)."""

    round_id: int
    round_open: float
    round_close: float
    dispatch_open: float
    fetch_close: float


@dataclasses.dataclass(frozen=True)
class DeviceRound:
    """One round's stamps on chip 0's clock (ns): the decode program's
    first operation, the pick program's last.  ``then_idle``: no other
    program ran between this pick and the next decode program."""

    decode_start: float
    pick_end: float
    then_idle: bool


def host_rounds(ctx) -> list:
    """One entry for each ``decode_dispatch`` span that opened in the
    window, in order: the round's stamps, or None where the ``decode_fetch``
    or ``round`` span of its id is missing (the profile closed inside the
    round).  [] where a dispatch carries no id."""
    by_id = {
        name: {int(s.stats[ROUND_ID]): s for s in spans._named(ctx, name)
               if ROUND_ID in s.stats}
        for name in (FETCH, ROUND)
    }
    rounds = []
    for d in spans._named(ctx, DISPATCH):
        if ROUND_ID not in d.stats:
            return []
        rid = int(d.stats[ROUND_ID])
        fetch, whole = by_id[FETCH].get(rid), by_id[ROUND].get(rid)
        rounds.append(None if fetch is None or whole is None else HostRound(
            rid, whole.start_ns, whole.end_ns, d.start_ns, fetch.end_ns,
        ))
    return rounds


def _op_edges(ops, module):
    """(start of the first, end of the last) operation that began inside
    one run of a program; None where the run holds none."""
    i = bisect.bisect_left(ops, module.start_ns, key=lambda e: e.start_ns)
    inside = []
    while i < len(ops) and ops[i].start_ns <= module.end_ns:
        inside.append(ops[i])
        i += 1
    if not inside:
        return None
    return inside[0].start_ns, max(e.end_ns for e in inside)


def device_rounds(trace) -> list:
    """One entry for each run of the decode program on chip 0, in the
    order they ran, the whole profile's: its stamps with those of the
    first run of the pick program after it, or None where it has no pick
    before the next decode run or no operation (the profile closed inside
    the round)."""
    planes = xplane.device_planes(trace) if trace is not None else []
    if not planes:
        return []
    ops = xplane.op_events(planes[0])
    modules = xplane.module_events(planes[0])
    decodes = [i for i, m in enumerate(modules)
               if DECODE_PROGRAM.search(m.name)]
    rounds = []
    for n, i in enumerate(decodes):
        until = decodes[n + 1] if n + 1 < len(decodes) else len(modules)
        pick = next((j for j in range(i + 1, until)
                     if PICK_PROGRAM.search(modules[j].name)), None)
        first = _op_edges(ops, modules[i])
        last = None if pick is None else _op_edges(ops, modules[pick])
        rounds.append(None if first is None or last is None else DeviceRound(
            first[0], last[1],
            then_idle=n + 1 < len(decodes) and until == pick + 1,
        ))
    return rounds


def joined(ctx):
    """``[(HostRound, DeviceRound), ...]`` in order, the rounds whole on
    both sides; None where the two sides cannot be joined without a
    guess."""

    def read():
        host, device = host_rounds(ctx), device_rounds(ctx.trace)
        if len(device) == len(host) + 1:
            device = device[1:]
        elif len(host) == len(device) + 1:
            host = host[:-1]
        if len(host) != len(device):
            return None
        both = [(h, d) for h, d in zip(host, device)
                if h is not None and d is not None]
        return both or None

    return spans._once(ctx, "_chain_joined", read)


def pairs(ctx):
    """``[((host n, device n), (host n+1, device n+1)), ...]``: the
    consecutive rounds with nothing but the chain between them."""
    rounds = joined(ctx)
    if rounds is None:
        return None
    prefills = sorted(s.start_ns for s in spans._named(ctx, PREFILL))
    out = []
    for a, b in zip(rounds, rounds[1:]):
        (ha, da), (hb, _) = a, b
        admitted = bisect.bisect_left(prefills, ha.fetch_close) \
            < bisect.bisect_left(prefills, hb.dispatch_open)
        if hb.round_id == ha.round_id + 1 and da.then_idle and not admitted:
            out.append((a, b))
    return out


def _gap_ns(part: str, a, b) -> float:
    (ha, da), (hb, db) = a, b
    device = db.decode_start - da.pick_end
    host = hb.dispatch_open - ha.fetch_close
    return {
        "device": device,
        "host": host,
        "crossing": device - host,
        "outside": hb.round_open - ha.round_close,
    }[part]


# -------------------------------------------------------------- readers


def gap_ms_p50(ctx, part: str):
    """Median over the window's pairs, in ms, of one reading of the gap
    between round n's pick program and round n+1's decode program:

    ``device``    last operation of pick n -> first operation of decode
                  n+1, on chip 0's clock;
    ``host``      ``decode_fetch`` n closing -> ``decode_dispatch`` n+1
                  opening, on the host's: what the host does while the
                  device waits for it;
    ``crossing``  device less host, pair by pair: the ids' way to the host
                  plus the program's way to the device;
    ``outside``   ``ft.engine.round`` n closing -> n+1 opening: the caller
                  of ``step()``; a part of ``host``.
    """
    found = pairs(ctx)
    if not found:
        return None
    return statistics.median(_gap_ns(part, a, b) for a, b in found) / 1e6


def crossing_min_ms(ctx, side: str):
    """The only readings that cross the clocks, and say so: the LEAST over
    the window's joined rounds, in ms, of

    ``launch``  decode program's first operation - ``decode_dispatch``
                opening,
    ``return``  ``decode_fetch`` closing - pick program's last operation,

    as the profile recorded both planes.  Either is at least 0 on true
    clocks; one that reads negative shows the profile's clocks apart by at
    least that much, and the two sum to a floor under the crossing
    whatever the offset."""
    rounds = joined(ctx)
    if rounds is None:
        return None
    return min({
        "launch": lambda h, d: d.decode_start - h.dispatch_open,
        "return": lambda h, d: h.fetch_close - d.pick_end,
    }[side](h, d) for h, d in rounds) / 1e6
