"""Readers for cells whose model holds a recurrent state a slot beside
plain K and V rows a position (kind ``serve_closed_hybrid``)."""

from __future__ import annotations

import statistics

from benchmarks.lib import counts_olmo, xplane
from benchmarks.readers import spans
from benchmarks.readers.mla import _device_and_peaks


def decode_roofline(ctx, match: str):
    """Least time a decode round could take over the decode program's
    traced time a round, in %: the larger of the bytes it must move over
    the HBM peak and the FLOPs it must do over the bf16 peak
    (``lib.counts_olmo``), from the window's means of the slots that
    decoded (each reads and writes its state) and the live cached
    positions.  None where the window holds no decode round or the trace
    no device."""
    if not _device_and_peaks(ctx):
        return None
    decoded = [(r[3], r[4]) for r in ctx.obs["rounds"] if r[3] > 0]
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    if not decoded or runs == 0 or secs <= 0:
        return None
    c = ctx.cell.config
    active = statistics.fmean(d[0] for d in decoded)
    live = statistics.fmean(d[1] for d in decoded)
    least = max(
        counts_olmo.decode_round_bytes(c, live, active)
        / ctx.peaks.hbm_bytes_per_s,
        counts_olmo.decode_round_flops(c, active, live)
        / ctx.peaks.bf16_flops,
    )
    return 100.0 * least / (secs / runs)


def prefill_roofline(ctx, match: str, span: str, length: str):
    """Least time the window's prefills could take over the prefill
    programs' traced time, in %: the FLOPs of every prompt whose ``span``
    opened in the window (its ``length`` stat; ``lib.counts_olmo.
    prefill_flops``, the scan's and the attention's own among them) over
    the bf16 peak.  None where the window holds no such span or the trace
    no device."""
    if not _device_and_peaks(ctx):
        return None
    prompts = [
        int(s.stats[length]) for s in spans._named(ctx, span)
        if length in s.stats
    ]
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    if not prompts or runs == 0 or secs <= 0:
        return None
    flops = sum(counts_olmo.prefill_flops(ctx.cell.config, t) for t in prompts)
    return 100.0 * flops / ctx.peaks.bf16_flops / secs
