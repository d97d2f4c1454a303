"""Readers for cells whose model generates by diffusion over blocks (kind
``serve_closed_block``)."""

from __future__ import annotations

import statistics

from benchmarks.lib import counts_sdar, xplane
from benchmarks.readers import spans
from benchmarks.readers.mla import _device_and_peaks


def sum_ratio(ctx, name: str, num: str, den: str):
    """The sum over the window's ``name`` spans of one count over the sum
    of another (the tokens revealed over the slot-passes that ran).  None
    where no span carries both (a parent commit)."""
    counted = [
        (float(s.stats[num]), float(s.stats[den]))
        for s in spans._named(ctx, name) if num in s.stats and den in s.stats
    ]
    total = sum(d for _, d in counted)
    return sum(n for n, _ in counted) / total if total > 0 else None


def decode_roofline(ctx, match: str, span: str, hit: str, local: str):
    """Least time a pass could take over the pass program's traced time a
    round, in %: the larger of the bytes it must move over the HBM peak and
    the FLOPs it must do over the bf16 peak (``lib.counts_sdar``), from the
    window's means of the experts HIT and the LOCAL picks a round (counts
    the pass program hands out, on the ``span`` spans), the slots in a pass
    and their committed positions.  None where the program records no such
    counts (a parent commit) or the trace no device."""
    if not _device_and_peaks(ctx):
        return None
    counted = [
        (float(s.stats[hit]), float(s.stats[local]))
        for s in spans._named(ctx, span)
        if hit in s.stats and local in s.stats
    ]
    passes = [(r[3], r[4]) for r in ctx.obs["rounds"] if r[3] > 0]
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    if not counted or not passes or runs == 0 or secs <= 0:
        return None
    c = ctx.cell.config
    active = statistics.fmean(p[0] for p in passes)
    live = statistics.fmean(p[1] for p in passes)
    least = max(
        counts_sdar.pass_bytes(
            c, statistics.fmean(h for h, _ in counted), live, active
        ) / ctx.peaks.hbm_bytes_per_s,
        counts_sdar.pass_flops(
            c, active, statistics.fmean(p for _, p in counted), live
        ) / ctx.peaks.bf16_flops,
    )
    return 100.0 * least / (secs / runs)


def prefill_roofline(ctx, match: str, span: str, length: str):
    """Least time the window's prefills could take over the prefill
    programs' traced time, in %: the FLOPs of every prompt whose ``span``
    opened in the window (its ``length`` stat; ``lib.counts_sdar.
    prefill_flops``, the prompt's whole blocks) over the bf16 peak.  None
    where the window holds no such span or the trace no device."""
    if not _device_and_peaks(ctx):
        return None
    prompts = [
        int(s.stats[length]) for s in spans._named(ctx, span)
        if length in s.stats
    ]
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    if not prompts or runs == 0 or secs <= 0:
        return None
    flops = sum(counts_sdar.prefill_flops(ctx.cell.config, t) for t in prompts)
    return 100.0 * flops / ctx.peaks.bf16_flops / secs
