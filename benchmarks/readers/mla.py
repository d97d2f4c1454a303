"""Readers for cells whose model caches one latent row a position (kind
``serve_closed_latent``)."""

from __future__ import annotations

import statistics

from benchmarks.lib import counts_pangu, xplane
from benchmarks.readers import spans


def _device_and_peaks(ctx) -> bool:
    return ctx.peaks is not None and ctx.trace is not None \
        and bool(xplane.device_planes(ctx.trace))


def decode_roofline(ctx, match: str, span: str, hit: str, local: str):
    """Least time a decode round could take over the decode program's
    traced time a round, in %: the larger of the bytes it must read over
    the HBM peak and the FLOPs it must do over the bf16 peak
    (``lib.counts_pangu``), from the window's means of the routed experts
    HIT and the LOCAL picks a round (counts the decode program hands out,
    on the ``span`` spans), the slots that decoded and the live cached
    rows.  None where the program records no such counts (a parent
    commit) or the trace no device."""
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
    live = statistics.fmean(d[1] for d in decoded)
    least = max(
        counts_pangu.decode_round_bytes(
            c, statistics.fmean(h for h, _ in counted), live
        ) / ctx.peaks.hbm_bytes_per_s,
        counts_pangu.decode_round_flops(
            c, statistics.fmean(d[0] for d in decoded),
            statistics.fmean(p for _, p in counted), live,
        ) / ctx.peaks.bf16_flops,
    )
    return 100.0 * least / (secs / runs)


def prefill_roofline(ctx, match: str, span: str, length: str):
    """Least time the window's prefills could take over the prefill
    programs' traced time, in %: the FLOPs of every prompt whose ``span``
    opened in the window (its ``length`` stat; ``lib.counts_pangu.
    prefill_flops``) over the bf16 peak.  A prefill is bound by compute:
    a prompt of thousands of positions does thousands of FLOPs a weight
    byte.  None where the window holds no such span or the trace no
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
    flops = sum(counts_pangu.prefill_flops(ctx.cell.config, t) for t in prompts)
    return 100.0 * flops / ctx.peaks.bf16_flops / secs


def span_time_share(ctx, name: str):
    """Time under the spans ``name`` that opened in the window, over the
    window's, in %.  None where the window holds no such span."""
    found = spans._named(ctx, name)
    lo, hi = ctx.window
    if not found or hi <= lo:
        return None
    return 100.0 * sum(min(s.end_ns, hi) - s.start_ns for s in found) / (hi - lo)
