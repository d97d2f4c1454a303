"""Readers for cells whose model holds a recurrent state a slot beside a
latent row a position (kind ``serve_closed_state``)."""

from __future__ import annotations

import statistics

from benchmarks.lib import counts_kimi, xplane
from benchmarks.readers import spans
from benchmarks.readers.mla import _device_and_peaks


def decode_roofline(ctx, match: str, span: str, hit: str, local: str):
    """Least time a decode round could take over the decode program's
    traced time a round, in %: the larger of the bytes it must move over
    the HBM peak and the FLOPs it must do over the bf16 peak
    (``lib.counts_kimi``), from the window's means of the routed experts
    HIT and the LOCAL picks a round (counts the decode program hands out,
    on the ``span`` spans), the slots that decoded (each reads and writes
    its state) and the live cached rows.  None where the program records
    no such counts (a parent commit) or the trace no device."""
    if not _device_and_peaks(ctx):
        return None
    counted = [
        (float(s.stats[hit]), float(s.stats[local]))
        for s in spans._named(ctx, span)
        if hit in s.stats and local in s.stats
    ]
    decoded = [(r[3], r[4]) for r in ctx.obs["rounds"] if r[3] > 0]
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    if not counted or not decoded or runs == 0 or secs <= 0:
        return None
    c = ctx.cell.config
    active = statistics.fmean(d[0] for d in decoded)
    live = statistics.fmean(d[1] for d in decoded)
    least = max(
        counts_kimi.decode_round_bytes(
            c, statistics.fmean(h for h, _ in counted), live, active
        ) / ctx.peaks.hbm_bytes_per_s,
        counts_kimi.decode_round_flops(
            c, active, statistics.fmean(p for _, p in counted), live
        ) / ctx.peaks.bf16_flops,
    )
    return 100.0 * least / (secs / runs)


def prefill_roofline(ctx, match: str, span: str, length: str):
    """Least time the window's prefills could take over the prefill
    programs' traced time, in %: the FLOPs of every prompt whose ``span``
    opened in the window (its ``length`` stat; ``lib.counts_kimi.
    prefill_flops``, the chunked scan's own among them) over the bf16
    peak.  None where the window holds no such span or the trace no
    device."""
    if not _device_and_peaks(ctx):
        return None
    prompts = [
        int(s.stats[length]) for s in spans._named(ctx, span)
        if length in s.stats
    ]
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    if not prompts or runs == 0 or secs <= 0:
        return None
    flops = sum(counts_kimi.prefill_flops(ctx.cell.config, t) for t in prompts)
    return 100.0 * flops / ctx.peaks.bf16_flops / secs


def state_bytes_share(ctx, span: str, per_slot: str, per_position: str):
    """Median over the window's decode rounds of the live state's bytes
    over the live state's plus the live cached rows' bytes, in %: the
    slots that decoded times what a slot holds, against the live
    positions times what a position takes (both as the program states
    them on the ``span`` spans).  None where the program states neither
    (a parent commit, a block that holds no state)."""
    stated = [
        (float(s.stats[per_slot]), float(s.stats[per_position]))
        for s in spans._named(ctx, span)
        if per_slot in s.stats and per_position in s.stats
    ]
    if not stated or stated[0][0] <= 0:
        return None
    slot_bytes, position_bytes = stated[0]
    shares = [
        100.0 * r[3] * slot_bytes
        / (r[3] * slot_bytes + r[4] * position_bytes)
        for r in ctx.obs["rounds"] if r[3] > 0
    ]
    return statistics.median(shares) if shares else None
